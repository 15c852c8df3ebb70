"""BERT pretraining (reference examples/nlp/bert/train_hetu_bert.py).

MLM + NSP on tokenized corpus batches; falls back to synthetic token
streams when no corpus is present.  DP over all visible devices via
--comm-mode AllReduce (mesh sharding, not graph rewrite).
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
from hetu_tpu.models import BertConfig, BertForPreTraining

from common import corpus_mlm_stream, synthetic_mlm_batch

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("bert")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="base", choices=["base", "large"])
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--num-layers", type=int, default=None)
    parser.add_argument("--learning-rate", type=float, default=1e-4)
    parser.add_argument("--num-steps", type=int, default=30)
    parser.add_argument("--comm-mode", default=None)
    parser.add_argument("--use-flash", action="store_true")
    parser.add_argument("--data-path", default=None,
                        help="raw text corpus (one sentence per line, "
                             "blank line between documents); synthetic "
                             "batches when absent")
    parser.add_argument("--vocab-path", default=None,
                        help="wordpiece vocab.txt; built from the "
                             "corpus when absent")
    parser.add_argument("--dupe-factor", type=int, default=5)
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    make = BertConfig.large if args.config == "large" else BertConfig.base
    kw = dict(batch_size=args.batch_size, seq_len=args.seq_len,
              use_flash_attention=args.use_flash)
    if args.num_layers:
        kw["num_hidden_layers"] = args.num_layers

    stream = None
    if args.data_path:
        stream, vocab_size = corpus_mlm_stream(
            args.data_path, args.vocab_path, args.batch_size,
            args.seq_len, dupe_factor=args.dupe_factor)
        kw["vocab_size"] = max(vocab_size, 128)
        logger.info("pretraining on %s (vocab %d)", args.data_path,
                    vocab_size)
    cfg = make(**kw)

    model = BertForPreTraining(cfg)
    ids = ht.placeholder_op("input_ids")
    tok = ht.placeholder_op("token_type_ids")
    mask = ht.placeholder_op("attention_mask")
    mlm = ht.placeholder_op("masked_lm_labels")
    nsp = ht.placeholder_op("next_sentence_label")
    loss, _, _ = model(ids, tok, mask, mlm, nsp)
    opt = ht.optim.AdamWOptimizer(learning_rate=args.learning_rate,
                                  weight_decay=0.01)
    train_op = opt.minimize(loss)
    executor = ht.Executor({"train": [loss, train_op]},
                           comm_mode=args.comm_mode)

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
            ids: b_ids, tok: b_tok, mask: b_mask, mlm: b_mlm, nsp: b_nsp})
        last = float(np.asarray(out[0]).reshape(-1)[0])
        if step % 10 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            sps = (step + 1) * cfg.batch_size / dt
            logger.info("step %d loss=%.4f (%.1f samples/s)", step,
                        last, sps)
    return last


if __name__ == "__main__":
    main()
