"""Long-context causal LM training (capability the reference lacks —
SURVEY.md §5.7: no sequence parallelism, BERT capped at seq 512).

Single chip: Pallas flash attention (O(S) memory, fused backward) makes
seq 4k-8k trainable where the unfused softmax(QK^T)V chain would
materialize the S x S score matrix per head.  Sequences beyond one
chip shard over a 'cp' mesh axis (ring attention / Ulysses in
parallel/context_parallel.py; see tests/test_context_parallel.py for the
multi-device drive — this example is the single-chip path).

  python examples/nlp/train_long_context.py --seq-len 4096   # one TPU
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/nlp/train_long_context.py --seq-len 256 --tiny
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

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("longctx")


def build_causal_lm(batch, seq, hidden, heads, layers_n, vocab,
                    use_flash=True, block_q=512, block_k=1024):
    ids = ht.placeholder_op("input_ids")
    emb = ht.layers.Embedding(vocab, hidden, name="lc_tok_emb")
    pos = ht.init.random_normal((seq, hidden), stddev=0.02, name="lc_pos")
    h = ht.embedding_lookup_op(emb.embedding_table, ids)
    h = h + ht.broadcast_shape_op(pos, (batch, seq, hidden), add_axes=[0])
    h = ht.array_reshape_op(h, [batch * seq, hidden])
    for i in range(layers_n):
        attn = ht.layers.MultiHeadAttention(
            hidden, heads, seq, batch, use_flash=use_flash, causal=True,
            block_q=block_q, block_k=block_k, name=f"lc{i}_attn")
        h = ht.layers.LayerNorm(hidden, name=f"lc{i}_ln1")(h + attn(h))
        wi = ht.layers.Linear(hidden, 4 * hidden, name=f"lc{i}_ffn_wi")
        wo = ht.layers.Linear(4 * hidden, hidden, name=f"lc{i}_ffn_wo")
        h = ht.layers.LayerNorm(hidden, name=f"lc{i}_ln2")(
            h + wo(ht.gelu_op(wi(h))))
    logits = ht.layers.Linear(hidden, vocab, name="lc_head")(h)
    # next-token prediction: labels = ids shifted left
    labels = ht.placeholder_op("labels")
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_sparse_op(
            logits, ht.array_reshape_op(labels, [batch * seq])), axes=0)
    return ids, labels, loss


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--hidden", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--num-steps", type=int, default=10)
    p.add_argument("--no-flash", action="store_true")
    p.add_argument("--tiny", action="store_true",
                   help="CPU-smoke scale")
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.tiny:
        args.hidden, args.heads, args.layers, args.vocab = 64, 2, 2, 200
        args.batch_size = max(args.batch_size, 2)
        args.num_steps = min(args.num_steps, 5)

    B, S = args.batch_size, args.seq_len
    ids, labels, loss = build_causal_lm(
        B, S, args.hidden, args.heads, args.layers, args.vocab,
        use_flash=not args.no_flash)
    train = ht.optim.AdamOptimizer(learning_rate=3e-4).minimize(loss)
    ex = ht.Executor({"train": [loss, train]}, mixed_precision="bf16")

    rng = np.random.RandomState(0)
    stream = rng.randint(0, args.vocab, (B, S + 1)).astype(np.int32)
    feed = {ids: stream[:, :-1], labels: stream[:, 1:]}

    l0 = float(np.asarray(ex.run("train", feed_dict=feed)[0]))  # compile
    t0 = time.perf_counter()
    for _ in range(args.num_steps):
        out = ex.run("train", feed_dict=feed)
    lN = float(np.asarray(out[0]))
    dt = (time.perf_counter() - t0) / args.num_steps
    toks = B * S / dt
    logger.info("seq %d: step %.1f ms, %.0f tokens/sec, loss %.4f -> %.4f",
                S, dt * 1e3, toks, l0, lN)
    assert np.isfinite(lN)
    return toks


if __name__ == "__main__":
    main()
