"""BERT fine-tuning for SQuAD-style span extraction.

Reference: the BERT example suite's SQuAD stage
(examples/nlp/bert/data/SquadDownloader.py:1, data/bertPrep.py:1 stage
the official JSON) — load weights into BertForQuestionAnswering, train
start/end span prediction over doc-stride windows, report exact-match
and F1 with the official normalization.

Offline environment: --data points at an official-format SQuAD JSON
(tests/fixtures/squad/train-tiny.json is format-faithful); the vocab
comes from --vocab-path or is built hermetically from the contexts via
the shared bootstrap.

Distribution: --comm-mode AllReduce shards the batch over all visible
devices ('dp' mesh axis; XLA inserts the gradient psum).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/nlp/finetune_bert_squad.py \
          --data tests/fixtures/squad/train-tiny.json --num-steps 60
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, '..', '..'))
sys.path.insert(0, _HERE)   # for the shared `common` helpers

import argparse
import logging

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import BertConfig, BertForQuestionAnswering
from hetu_tpu.squad import (convert_examples_to_features,
                            extract_predictions, features_to_arrays,
                            read_squad_examples, squad_evaluate)
from common import hermetic_tokenizer

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("squad")


def build_tokenizer(examples, vocab_path):
    def lines():
        for ex in examples:
            yield " ".join(ex.doc_tokens)
            yield ex.question_text
    return hermetic_tokenizer(lines(), vocab_path)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data", required=True,
                   help="official-format SQuAD JSON (v1.1 or v2.0)")
    p.add_argument("--vocab-path", default=None)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--doc-stride", type=int, default=32)
    p.add_argument("--num-steps", type=int, default=60)
    p.add_argument("--learning-rate", type=float, default=2e-3)
    p.add_argument("--comm-mode", default=None,
                   choices=[None, "AllReduce"])
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    examples = read_squad_examples(args.data, is_training=True)
    tok = build_tokenizer(examples, args.vocab_path)
    features = convert_examples_to_features(
        examples, tok, max_seq_length=args.seq_len,
        doc_stride=args.doc_stride, max_query_length=16)
    arrays = features_to_arrays(features)
    n = len(features)
    logger.info("examples=%d features=%d vocab=%d",
                len(examples), n, len(tok.vocab))

    cfg = BertConfig(
        vocab_size=len(tok.vocab), hidden_size=args.hidden,
        num_hidden_layers=args.num_layers,
        num_attention_heads=args.heads,
        intermediate_size=4 * args.hidden,
        max_position_embeddings=max(args.seq_len, 64),
        batch_size=args.batch_size, seq_len=args.seq_len,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = BertForQuestionAnswering(cfg, name="bert_squad")

    ids = ht.placeholder_op("input_ids")
    mask = ht.placeholder_op("input_mask")
    segs = ht.placeholder_op("segment_ids")
    sp = ht.placeholder_op("start_positions")
    ep = ht.placeholder_op("end_positions")
    loss, start_logits, end_logits = model(
        ids, token_type_ids=segs, attention_mask=mask,
        start_positions=sp, end_positions=ep)
    opt = ht.optim.AdamOptimizer(learning_rate=args.learning_rate)
    train = opt.minimize(loss)
    kw = {}
    if args.comm_mode:
        kw.update(comm_mode=args.comm_mode,
                  dist_strategy=ht.dist.DataParallel())
    ex = ht.Executor({"train": [loss, train],
                      "eval": [start_logits, end_logits]}, **kw)

    rng = np.random.RandomState(0)
    for step in range(args.num_steps):
        take = rng.randint(0, n, args.batch_size)
        out = ex.run("train", feed_dict={
            ids: arrays["input_ids"][take],
            mask: arrays["input_mask"][take],
            segs: arrays["segment_ids"][take],
            sp: arrays["start_positions"][take],
            ep: arrays["end_positions"][take]})
        if step % 20 == 0 or step == args.num_steps - 1:
            logger.info("step %d loss %.4f", step,
                        float(np.asarray(out[0])))

    # eval: run every window through the trained head, extract spans
    all_start, all_end = [], []
    pad_to = (-n) % args.batch_size
    order = list(range(n)) + [0] * pad_to
    for i in range(0, len(order), args.batch_size):
        take = order[i:i + args.batch_size]
        s_l, e_l = ex.run("eval", feed_dict={
            ids: arrays["input_ids"][take],
            mask: arrays["input_mask"][take],
            segs: arrays["segment_ids"][take],
            sp: arrays["start_positions"][take],
            ep: arrays["end_positions"][take]})
        all_start.append(np.asarray(s_l))
        all_end.append(np.asarray(e_l))
    start_logits = np.concatenate(all_start)[:n]
    end_logits = np.concatenate(all_end)[:n]
    preds = extract_predictions(examples, features, start_logits,
                                end_logits)
    metrics = squad_evaluate(examples, preds)
    logger.info("exact_match %.2f f1 %.2f", metrics["exact_match"],
                metrics["f1"])
    return metrics


if __name__ == "__main__":
    main()
