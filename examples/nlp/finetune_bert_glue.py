"""BERT fine-tuning for GLUE-style sentence classification.

Reference: examples/nlp/bert GLUE fine-tune scripts (SST-2/MRPC etc.) —
load pretrained weights into BertForSequenceClassification, train the
classifier (+ backbone) on labeled pairs, report accuracy.

Offline environment: with --data pointing at a TSV of `label<TAB>text`
the wordpiece tokenizer encodes it; otherwise a synthetic, *learnable*
task stands in (label = whether the count of tokens from the first half
of the vocab exceeds half the sequence), so accuracy measurably rises.

Distribution: --comm-mode AllReduce shards the batch over all visible
devices ('dp' mesh axis; XLA inserts the gradient psum).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/nlp/finetune_bert_glue.py --num-steps 30
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
from hetu_tpu.glue import (PROCESSORS, compute_metrics,
                           convert_examples_to_arrays)
from hetu_tpu.models import BertConfig, BertForSequenceClassification
from common import hermetic_tokenizer

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("glue")


def load_tsv(path, tokenizer_dir, seq_len, vocab_size):
    """label<TAB>text TSV through the offline wordpiece tokenizer."""
    from hetu_tpu.tokenizers import BertWordPieceTokenizer
    tok = BertWordPieceTokenizer.from_pretrained(tokenizer_dir)
    ids, labels = [], []
    with open(path) as f:
        for line in f:
            lab, text = line.rstrip("\n").split("\t", 1)
            enc = tok.encode(text)[:seq_len]
            enc = enc + [0] * (seq_len - len(enc))
            ids.append(enc)
            labels.append(int(lab))
    return (np.asarray(ids, np.int32) % vocab_size,
            np.asarray(labels, np.int32))


def load_glue_task(task, data_dir, vocab_path, seq_len):
    """Official-format GLUE TSVs through the task processor suite
    (reference glue_processor/glue.py).  Returns (train arrays, dev
    arrays, num_labels, vocab_size); each arrays tuple is
    (input_ids, attention_mask, token_type_ids, labels)."""
    proc = PROCESSORS[task.lower()]()
    train_ex = proc.get_train_examples(data_dir)
    dev_ex = proc.get_dev_examples(data_dir)
    if not vocab_path:
        cand = os.path.join(data_dir, "vocab.txt")
        if os.path.exists(cand):
            vocab_path = cand

    def lines():
        for ex in train_ex + dev_ex:
            yield ex.text_a
            if ex.text_b:
                yield ex.text_b
    tok = hermetic_tokenizer(lines(), vocab_path)
    lab = proc.get_labels()
    return (convert_examples_to_arrays(train_ex, lab, seq_len, tok),
            convert_examples_to_arrays(dev_ex, lab, seq_len, tok),
            len(lab), len(tok.vocab))


def synthetic(rng, n, seq_len, vocab_size):
    """Learnable stand-in: label = [more than half the tokens come from
    the first half of the vocabulary]."""
    ids = rng.randint(0, vocab_size, (n, seq_len)).astype(np.int32)
    labels = ((ids < vocab_size // 2).mean(axis=1) > 0.5).astype(np.int32)
    return ids, labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="base", choices=["base", "large"])
    p.add_argument("--num-layers", type=int, default=2,
                   help="encoder depth override (small default: the "
                        "synthetic task needs no 12 layers)")
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=1000)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--num-labels", type=int, default=2)
    p.add_argument("--learning-rate", type=float, default=5e-4)
    p.add_argument("--num-steps", type=int, default=40)
    p.add_argument("--eval-every", type=int, default=10)
    p.add_argument("--data", default=None, help="label<TAB>text TSV")
    p.add_argument("--task", default=None,
                   choices=sorted(PROCESSORS),
                   help="GLUE task name; reads official TSVs from "
                        "--data-dir via the processor suite")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--vocab-path", default=None)
    p.add_argument("--tokenizer-dir", default=None)
    p.add_argument("--init-checkpoint", default=None,
                   help="directory saved by a pretraining Executor; "
                        "backbone weights load by name, heads stay fresh")
    p.add_argument("--comm-mode", default=None,
                   choices=[None, "AllReduce"])
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    glue_train = glue_dev = None
    if args.task:
        assert args.data_dir, "--task needs --data-dir"
        glue_train, glue_dev, args.num_labels, args.vocab = \
            load_glue_task(args.task, args.data_dir, args.vocab_path,
                           args.seq_len)
        logger.info("task %s: %d train / %d dev examples, vocab %d",
                    args.task, len(glue_train[0]), len(glue_dev[0]),
                    args.vocab)

    import jax
    mesh = None
    if args.comm_mode == "AllReduce" and jax.device_count() > 1:
        from hetu_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"dp": jax.device_count()})
        assert args.batch_size % jax.device_count() == 0

    cfg = BertConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                     num_hidden_layers=args.num_layers,
                     num_attention_heads=args.heads,
                     intermediate_size=4 * args.hidden,
                     seq_len=args.seq_len, batch_size=args.batch_size,
                     hidden_dropout_prob=0.1,
                     attention_probs_dropout_prob=0.1)
    ids = ht.placeholder_op("input_ids")
    tok_ids = ht.placeholder_op("token_type_ids")
    mask = ht.placeholder_op("attention_mask")
    labels = ht.placeholder_op("labels")
    model = BertForSequenceClassification(cfg, num_labels=args.num_labels)
    loss, logits = model(ids, tok_ids, mask, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=args.learning_rate,
                                  weight_decay=0.01)
    train = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train], "eval": [loss, logits]},
                     mesh=mesh)

    if args.init_checkpoint:
        import pickle
        with open(os.path.join(args.init_checkpoint,
                               "checkpoint.pkl"), "rb") as f:
            ckpt = pickle.load(f)
        pre = {k: v for k, v in ckpt["params"].items()
               if k in ex.variables and "classifier" not in k}
        ex.load_dict(pre)
        logger.info("loaded %d backbone tensors from %s",
                    len(pre), args.init_checkpoint)

    rng = np.random.RandomState(0)
    if glue_train is not None:
        tr_ids, tr_m, tr_t, tr_y = glue_train
        ev_ids, ev_m, ev_t, ev_y = glue_dev
        n_dev = len(ev_ids)
        # pad dev to a batch multiple by WRAPPING, and remember each
        # row's example index so metrics count every example exactly
        # once (plain repetition would double-weight an arbitrary
        # prefix and drop tails)
        pad_to = max(args.batch_size,
                     -(-n_dev // args.batch_size) * args.batch_size)
        ev_index = np.arange(pad_to) % n_dev
        ev_ids, ev_m, ev_t, ev_y = (a[ev_index]
                                    for a in (ev_ids, ev_m, ev_t, ev_y))
        reps_t = max(1, -(-2 * args.batch_size // max(len(tr_ids), 1)))
        tr_ids, tr_m, tr_t, tr_y = (np.concatenate([a] * reps_t)
                                    for a in (tr_ids, tr_m, tr_t, tr_y))
    else:
        if args.data:
            all_ids, all_labels = load_tsv(args.data, args.tokenizer_dir,
                                           args.seq_len, args.vocab)
        else:
            all_ids, all_labels = synthetic(rng, 4096, args.seq_len,
                                            args.vocab)
        split = int(0.9 * len(all_ids))
        tr_ids, tr_y = all_ids[:split], all_labels[:split]
        ev_ids, ev_y = all_ids[split:], all_labels[split:]
        tr_m = np.ones(tr_ids.shape, np.float32)
        ev_m = np.ones(ev_ids.shape, np.float32)
        tr_t = np.zeros(tr_ids.shape, np.int32)
        ev_t = np.zeros(ev_ids.shape, np.int32)

    def evaluate():
        preds, gold, idxs = [], [], []
        for i in range(0, len(ev_ids) - args.batch_size + 1,
                       args.batch_size):
            sl = slice(i, i + args.batch_size)
            _, lg = ex.run("eval", feed_dict={
                ids: ev_ids[sl], tok_ids: ev_t[sl], mask: ev_m[sl],
                labels: ev_y[sl]}, convert_to_numpy_ret_vals=True)
            preds.append(lg.argmax(-1))
            gold.append(ev_y[sl])
            if args.task:
                idxs.append(ev_index[sl])
        if not preds:
            return 0.0
        preds = np.concatenate(preds)
        gold = np.concatenate(gold)
        if args.task:
            # deduplicate the wrap-padding: one vote per dev example
            uniq = {}
            for j, pr, gl in zip(np.concatenate(idxs), preds, gold):
                uniq[int(j)] = (pr, gl)
            preds = np.array([v[0] for v in uniq.values()])
            gold = np.array([v[1] for v in uniq.values()])
            m = compute_metrics(args.task, preds, gold)
            logger.info("eval metrics %s (%d examples)", m, len(preds))
            return m["accuracy"]
        return float((preds == gold).mean())

    logger.info("initial eval accuracy %.3f", evaluate())
    t0 = time.time()
    for step in range(args.num_steps):
        j = rng.randint(0, len(tr_ids) - args.batch_size)
        sl = slice(j, j + args.batch_size)
        out = ex.run("train", feed_dict={
            ids: tr_ids[sl], tok_ids: tr_t[sl], mask: tr_m[sl],
            labels: tr_y[sl]})
        if (step + 1) % args.eval_every == 0:
            acc = evaluate()
            logger.info("step %d loss %.4f eval acc %.3f (%.1f s)",
                        step + 1, float(np.asarray(out[0])), acc,
                        time.time() - t0)
    final = evaluate()
    logger.info("final eval accuracy %.3f", final)
    return final


if __name__ == "__main__":
    main()
