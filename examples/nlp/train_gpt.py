"""Decoder-only causal LM pretraining (GPT-2 topology).

The reference zoo is BERT-centric; this example covers the decoder-only
family with the framework's measured-fast defaults (fused QKV, flash
attention from seq 1024, fused chunked tied head).  Trains on a local
token file when --data-path points at one (uint16/uint32 flat token
stream, nanoGPT-style), otherwise on a synthetic next-token task.
DP via --comm-mode AllReduce over all visible devices.
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), '..', '..'))

import argparse
import logging
import time

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTForCausalLM

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("gpt")


def load_tokens(path, vocab_size):
    """Flat binary token stream (nanoGPT data format: np.uint16)."""
    dtype = np.uint16 if vocab_size < (1 << 16) else np.uint32
    return np.fromfile(path, dtype=dtype).astype(np.int32)


def load_text_corpus(path, vocab_path):
    """Raw text corpus -> flat token stream through the pretraining
    pipeline (hetu_tpu.pretraining_data); builds a wordpiece vocab from
    the corpus when none is given.  Returns (tokens, vocab_size).  The
    FULL stream feeds batches()'s random windows — no fixed-block
    packing, so no tail tokens are lost."""
    from hetu_tpu.pretraining_data import (
        corpus_token_stream, load_or_build_tokenizer)
    tok = load_or_build_tokenizer(path, vocab_path)
    return corpus_token_stream(path, tok), len(tok.vocab)


def batches(tokens, cfg, rng):
    # valid starts: 0 .. len - seq_len - 1 inclusive (targets need one
    # extra token); randint's high bound is exclusive
    n = len(tokens) - cfg.seq_len
    if n < 1:
        raise SystemExit(
            f"--data-path holds {len(tokens)} tokens; need at least "
            f"seq_len+1 = {cfg.seq_len + 1} for one training window")
    while True:
        starts = rng.randint(0, n, cfg.batch_size)
        x = np.stack([tokens[s:s + cfg.seq_len] for s in starts])
        y = np.stack([tokens[s + 1:s + cfg.seq_len + 1] for s in starts])
        yield x.astype(np.int32), y.astype(np.int32)


def synthetic(cfg, rng):
    """Next token = (3 * token + 7) % vocab — learnable, non-trivial."""
    while True:
        x = rng.randint(0, cfg.vocab_size,
                        (cfg.batch_size, cfg.seq_len)).astype(np.int32)
        y = ((3 * x + 7) % cfg.vocab_size).astype(np.int32)
        yield x, y


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="small",
                        choices=["small", "medium"])
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--num-layers", type=int, default=None)
    parser.add_argument("--vocab-size", type=int, default=50257)
    parser.add_argument("--learning-rate", type=float, default=3e-4)
    parser.add_argument("--clip-grad-norm", type=float, default=1.0,
                        help="global gradient-norm bound (<=0 disables)")
    parser.add_argument("--num-steps", type=int, default=30)
    parser.add_argument("--comm-mode", default=None)
    parser.add_argument("--data-path", default=None,
                        help="flat uint16/uint32 token file (nanoGPT "
                             "format) or a raw .txt corpus; synthetic "
                             "task when absent")
    parser.add_argument("--vocab-path", default=None,
                        help="wordpiece vocab.txt for .txt corpora; "
                             "built from the corpus when absent")
    parser.add_argument("--use-flash", action=argparse.BooleanOptionalAction,
                        default=None,
                        help="pin flash on/off; default: auto (flash "
                             "from seq 1024, dropout permitting)")
    parser.add_argument("--demo-generate", type=int, default=0,
                        help="after training, greedy-decode this many "
                             "tokens from a short prompt")
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    make = GPTConfig.medium if args.config == "medium" else GPTConfig.small
    kw = dict(batch_size=args.batch_size, seq_len=args.seq_len,
              max_position_embeddings=args.seq_len,
              vocab_size=args.vocab_size, dropout_rate=0.0,
              use_flash=args.use_flash)
    if args.num_layers:
        kw["num_hidden_layers"] = args.num_layers

    corpus_tokens = None
    if args.data_path and args.data_path.endswith(".txt"):
        corpus_tokens, vocab_size = load_text_corpus(
            args.data_path, args.vocab_path)
        kw["vocab_size"] = max(vocab_size, 128)
        logger.info("tokenized %s: %d tokens, vocab %d", args.data_path,
                    len(corpus_tokens), vocab_size)
    cfg = make(**kw)

    model = GPTForCausalLM(cfg)
    ids = ht.placeholder_op("input_ids")
    labels = ht.placeholder_op("labels")
    loss, _logits = model(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=args.learning_rate,
                                  weight_decay=0.01)
    if args.clip_grad_norm > 0:
        opt.clip_grad_norm = args.clip_grad_norm
    train_op = opt.minimize(loss)
    subgraphs = {"train": [loss, train_op]}
    gen_ids = None
    if args.demo_generate > 0:
        gen_ids = ht.placeholder_op("gen_input_ids")
        # eval subgraph: no optimizer -> tc.training is False -> every
        # DropoutOp is identity (ops_conv.py DropoutOp), regardless of
        # the config's dropout_rate
        subgraphs["gen"] = [model(gen_ids)]
    executor = ht.Executor(subgraphs, comm_mode=args.comm_mode)

    rng = np.random.RandomState(0)
    if corpus_tokens is not None:
        stream = batches(corpus_tokens, cfg, rng)
        logger.info("training on text corpus %s", args.data_path)
    elif args.data_path and os.path.exists(args.data_path):
        stream = batches(load_tokens(args.data_path, cfg.vocab_size),
                         cfg, rng)
        logger.info("training on %s", args.data_path)
    else:
        stream = synthetic(cfg, rng)
        logger.info("no --data-path: synthetic next-token task")

    t0 = time.time()
    for step in range(args.num_steps):
        x, y = next(stream)
        out = executor.run("train", feed_dict={ids: x, labels: y})
        if step % 10 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            toks = (step + 1) * cfg.batch_size * cfg.seq_len / dt
            logger.info("step %d loss=%.4f (%.0f tokens/s)", step,
                        float(np.asarray(out[0]).reshape(-1)[0]), toks)

    if args.demo_generate > 0:
        from hetu_tpu.models.gpt import greedy_generate
        prompt = [int(t) % cfg.vocab_size for t in (1, 2, 3)]
        n = min(args.demo_generate, cfg.seq_len - len(prompt))
        seq = greedy_generate(executor, "gen", gen_ids, 0, prompt, n,
                              cfg.seq_len)
        logger.info("greedy continuation of %s: %s", prompt,
                    seq[len(prompt):])
        # same weights through the KV-cached scan (the serving path):
        # O(S) attention per token instead of a full forward per token
        from hetu_tpu.models.gpt_decode import generate_fast
        fast = generate_fast(executor.var_values, cfg, prompt,
                             num_tokens=n)
        logger.info("kv-cached continuation: %s (match=%s)",
                    fast[0, len(prompt):].tolist(),
                    fast[0].tolist() == seq)


if __name__ == "__main__":
    main()
