"""Continuous-batching GPT serving demo (hetu_tpu.serving).

Trains a tiny GPT on the synthetic next-token task next = (x+1) % V —
a few hundred steps make greedy decoding reproduce the arithmetic
chain — then serves a mixed-length request burst through the
ServingEngine: short requests retire and free their slots while a long
straggler keeps decoding, tokens stream per-iteration, and the engine's
metrics (TTFT, tok/s, batch occupancy) print at the end.

    python examples/nlp/serve_gpt.py --requests 6 --slots 2

``--spec K`` turns on speculative decoding: a truncated-layer draft
(the trained model's first layer) proposes K tokens per wave, the
target verifies them in one batched step, and the +1-chain outputs
stay token-identical — on the well-trained chain the draft predicts
the arithmetic too, so most waves emit several tokens:

    python examples/nlp/serve_gpt.py --requests 6 --slots 2 --spec 3
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), '..', '..'))

import argparse
import logging

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTForCausalLM
from hetu_tpu.serving import Request, ServingEngine

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("serve_gpt")


def train_tiny(cfg, steps, lr):
    m = GPTForCausalLM(cfg, name="sg")
    ids = ht.placeholder_op("sg_ids")
    labels = ht.placeholder_op("sg_labels")
    loss, _ = m(ids, labels=labels)
    train = ht.optim.AdamOptimizer(learning_rate=lr).minimize(loss)
    ex = ht.Executor({"train": [loss, train]})
    rng = np.random.RandomState(1)
    lv = None
    for step in range(steps):
        iv = rng.randint(0, cfg.vocab_size,
                         (cfg.batch_size, cfg.seq_len)).astype(np.int32)
        tv = ((iv + 1) % cfg.vocab_size).astype(np.int32)
        lv = ex.run("train", feed_dict={ids: iv, labels: tv})[0]
        if step % 100 == 0:
            logger.info("train step %d loss %.4f", step, float(lv))
    return ex


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab-size", type=int, default=61)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--train-steps", type=int, default=250)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative decoding: a truncated-layer "
                         "draft proposes up to this many tokens per "
                         "wave (0 = off); outputs stay token-identical")
    ap.add_argument("--spec-draft-layers", type=int, default=1)
    args = ap.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = GPTConfig(vocab_size=args.vocab_size, hidden_size=args.hidden,
                    num_hidden_layers=args.num_layers,
                    num_attention_heads=args.heads,
                    max_position_embeddings=args.seq_len, batch_size=4,
                    seq_len=args.seq_len, dropout_rate=0.0)
    ex = train_tiny(cfg, args.train_steps, args.lr)

    def stream(req, tok):
        logger.info("  %s += %d", req.request_id, tok)

    eng = ServingEngine(ex.var_values, cfg, slots=args.slots,
                        queue_limit=args.requests,
                        spec=args.spec or None,
                        spec_draft_layers=args.spec_draft_layers)
    rng = np.random.RandomState(7)
    reqs = []
    for i in range(args.requests):
        start = int(rng.randint(0, args.vocab_size - 1))
        # one long straggler, the rest short: the shorts cycle through
        # the straggler's slot-mates while it keeps decoding
        n = args.seq_len - 2 if i == 0 else int(rng.randint(2, 6))
        reqs.append(Request(prompt=[start], max_new_tokens=n,
                            stream_cb=stream))
    results = eng.run(reqs)

    ok = 0
    for r in reqs:
        res = results[r.request_id]
        want = [(r.prompt[0] + k) % args.vocab_size
                for k in range(len(res.tokens))]
        good = res.tokens.tolist() == want
        ok += good
        logger.info("%s (%s, %d tokens, ttft %.1f ms): %s%s",
                    r.request_id, res.finish_reason, res.n_generated,
                    res.ttft_s * 1e3, res.tokens.tolist(),
                    "" if good else f"  EXPECTED {want}")
    snap = eng.metrics.snapshot()
    logger.info("served %d requests, %s tokens @ %s tok/s, "
                "mean occupancy %.2f, %d fused steps",
                snap["requests_finished"], snap["tokens_generated"],
                snap["tokens_per_sec"], snap["mean_batch_occupancy"],
                snap["steps"])
    if args.spec:
        logger.info("speculative: %d waves, accepted %d/%d drafts "
                    "(rate %s), %.2f tokens/step",
                    eng.spec_waves, eng.spec_accepted,
                    eng.spec_proposed,
                    round(eng.spec_acceptance, 3)
                    if eng.spec_acceptance is not None else "-",
                    snap["tokens_per_step_mean"] or 0.0)
    return ok / len(reqs)


if __name__ == "__main__":
    main()
