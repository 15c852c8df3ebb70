"""Transformer machine translation (reference
examples/nlp/hetu_transformer.py / train_hetu_transformer.py).

Offline environment: a synthetic, *learnable* translation task stands in
for WMT — the "translation" of a source sequence is its reversal with a
fixed vocabulary permutation applied, so the encoder-decoder attention
has real structure to learn and token accuracy measurably rises.
Teacher forcing: decoder input is the shifted target.

DP over all visible devices via --comm-mode AllReduce.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/nlp/train_transformer.py --num-steps 60
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
from hetu_tpu.models.transformer import Transformer, TransformerConfig

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("mt")


def synthetic_pairs(rng, n, vocab, src_len, tgt_len, pad_id=0, bos_id=1):
    """tgt = reverse(permute(src)); ids 2..vocab-1 are 'words'."""
    perm = np.arange(vocab)
    perm[2:] = 2 + rng.permutation(vocab - 2)
    src = rng.randint(2, vocab, (n, src_len)).astype(np.int32)
    tgt_core = perm[src[:, ::-1]][:, :tgt_len - 1]

    def pad_to(a, width):
        return np.concatenate(
            [a, np.full((n, width - a.shape[1]), pad_id, np.int32)],
            axis=1) if a.shape[1] < width else a[:, :width]

    dec_in = np.concatenate(
        [np.full((n, 1), bos_id, np.int32), tgt_core[:, :-1]], axis=1)
    labels = np.concatenate(
        [tgt_core, np.full((n, 1), pad_id, np.int32)], axis=1)
    return (src, pad_to(dec_in, tgt_len).astype(np.int32),
            pad_to(labels, tgt_len).astype(np.int32))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--ffn", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--src-len", type=int, default=12)
    p.add_argument("--tgt-len", type=int, default=12)
    p.add_argument("--learning-rate", type=float, default=1e-3)
    p.add_argument("--num-steps", type=int, default=80)
    p.add_argument("--log-every", type=int, default=20)
    p.add_argument("--comm-mode", default=None, choices=[None, "AllReduce"])
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    mesh = None
    if args.comm_mode == "AllReduce" and jax.device_count() > 1:
        from hetu_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"dp": jax.device_count()})
        assert args.batch_size % jax.device_count() == 0

    cfg = TransformerConfig(
        src_vocab_size=args.vocab, tgt_vocab_size=args.vocab,
        hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, ffn_size=args.ffn, dropout_rate=0.0,
        batch_size=args.batch_size, src_len=args.src_len,
        tgt_len=args.tgt_len)
    src = ht.placeholder_op("src_ids")
    tgt = ht.placeholder_op("tgt_ids")
    labels = ht.placeholder_op("labels")
    model = Transformer(cfg)
    loss, logits = model(src, tgt, labels=labels)
    train = ht.optim.AdamOptimizer(
        learning_rate=args.learning_rate).minimize(loss)
    ex = ht.Executor({"train": [loss, train], "eval": [logits]},
                     mesh=mesh)

    rng = np.random.RandomState(0)
    S, D, L = synthetic_pairs(rng, 4096, args.vocab, args.src_len,
                              args.tgt_len)

    def token_acc():
        lg = np.asarray(ex.run("eval", feed_dict={
            src: S[:args.batch_size], tgt: D[:args.batch_size],
            labels: L[:args.batch_size]})[0])
        lg = lg.reshape(args.batch_size, args.tgt_len, -1)
        pred = lg.argmax(-1)
        mask = L[:args.batch_size] != 0
        return (pred == L[:args.batch_size])[mask].mean()

    t0 = time.time()
    for step in range(args.num_steps):
        j = rng.randint(0, len(S) - args.batch_size)
        out = ex.run("train", feed_dict={
            src: S[j:j + args.batch_size],
            tgt: D[j:j + args.batch_size],
            labels: L[j:j + args.batch_size]})
        if (step + 1) % args.log_every == 0:
            logger.info("step %d loss %.4f token_acc %.3f (%.1f s)",
                        step + 1, float(np.asarray(out[0])),
                        token_acc(), time.time() - t0)
    acc = token_acc()
    logger.info("final token accuracy %.3f", acc)
    return acc


if __name__ == "__main__":
    main()
