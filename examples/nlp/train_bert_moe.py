"""BERT-MoE pretraining (reference examples/nlp/bert/
train_hetu_bert_dp_moe.py driving hetu_bert_moe.py): the flagship LM
with MoE FFN blocks, trained over a dp x ep device mesh.

The expert stacks shard over 'ep' (GSPMD emits the token all-to-all at
the alltoall markers); everything else data-parallels over 'dp'.
Synthetic MLM/NSP batches — point --data-path at a corpus file for the
real pipeline (same flag surface as train_bert.py).
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, '..', '..'))
sys.path.insert(0, _HERE)   # for the shared `common` helpers

import argparse
import logging
import time

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import BertMoEConfig, BertMoEForPreTraining

from common import corpus_mlm_stream, synthetic_mlm_batch

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("bert_moe")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--num-layers", type=int, default=12)
    parser.add_argument("--hidden", type=int, default=768)
    parser.add_argument("--heads", type=int, default=12)
    parser.add_argument("--vocab-size", type=int, default=30522)
    parser.add_argument("--num-experts", type=int, default=8)
    parser.add_argument("--top-k", type=int, default=1)
    parser.add_argument("--moe-every", type=int, default=2,
                        help="every Nth block gets the MoE FFN "
                             "(1 = all blocks, the reference placement)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel mesh extent")
    parser.add_argument("--dp", type=int, default=1,
                        help="data-parallel mesh extent")
    parser.add_argument("--learning-rate", type=float, default=1e-4)
    parser.add_argument("--num-steps", type=int, default=30)
    parser.add_argument("--data-path", default=None,
                        help="raw text corpus (one sentence per line, "
                             "blank line between documents); synthetic "
                             "batches when absent")
    parser.add_argument("--vocab-path", default=None)
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    stream = None
    if args.data_path:
        stream, vocab_size = corpus_mlm_stream(
            args.data_path, args.vocab_path, args.batch_size,
            args.seq_len)
        args.vocab_size = max(vocab_size, 128)
        logger.info("pretraining on %s (vocab %d)", args.data_path,
                    vocab_size)

    cfg = BertMoEConfig(
        vocab_size=args.vocab_size, hidden_size=args.hidden,
        num_hidden_layers=args.num_layers, num_attention_heads=args.heads,
        intermediate_size=4 * args.hidden,
        max_position_embeddings=max(512, args.seq_len),
        batch_size=args.batch_size, seq_len=args.seq_len,
        num_experts=args.num_experts, top_k=args.top_k,
        moe_every=args.moe_every)

    model = BertMoEForPreTraining(cfg)
    ids = ht.placeholder_op("input_ids")
    tok = ht.placeholder_op("token_type_ids")
    mask = ht.placeholder_op("attention_mask")
    mlm = ht.placeholder_op("masked_lm_labels")
    nsp = ht.placeholder_op("next_sentence_label")
    loss, _, _ = model(ids, tok, attention_mask=mask,
                       masked_lm_labels=mlm, next_sentence_label=nsp)
    opt = ht.optim.AdamWOptimizer(learning_rate=args.learning_rate,
                                  weight_decay=0.01)
    train_op = opt.minimize(loss)
    strategy = None
    if args.ep > 1 or args.dp > 1:
        strategy = ht.dist.ExpertParallel(ep=args.ep, dp=args.dp)
    executor = ht.Executor({"train": [loss, train_op]},
                           dist_strategy=strategy)

    rng = np.random.RandomState(0)
    t0 = time.time()
    last = None
    for step in range(args.num_steps):
        if stream is not None:
            b_ids, b_tok, b_mask, b_mlm, b_nsp = next(stream)
        else:
            b_ids, b_tok, b_mask, b_mlm, b_nsp = synthetic_mlm_batch(
                rng, cfg)
        out = executor.run("train", feed_dict={
            ids: b_ids, tok: b_tok, mask: b_mask, mlm: b_mlm,
            nsp: b_nsp})
        last = float(np.asarray(out[0]).reshape(-1)[0])
        if step % 10 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            sps = (step + 1) * cfg.batch_size / dt
            logger.info("step %d loss=%.4f (%.1f samples/s)", step,
                        last, sps)
    return last


if __name__ == "__main__":
    main()
