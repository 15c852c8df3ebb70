"""CTR training (reference examples/ctr/run_hetu.py).

Models: wdl_adult, wdl_criteo, dcn_criteo, deepfm_criteo, dc_criteo.
--comm-mode Hybrid routes embedding grads through the PS with the HET
cache while dense grads ride psum over the mesh (reference
optimizer.py:157-162 semantics).  Synthetic data stands in for Criteo
when raw files are absent.
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
from hetu_tpu import models

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("ctr")


def zipf_ids(rng, dim, size, a):
    """Zipf-skewed categorical ids (CTR id frequencies are power-law —
    the skew the HET cache exploits); a<=0 falls back to uniform."""
    if a <= 0:
        return rng.randint(0, dim, size).astype(np.int32)
    ranks = np.arange(1, dim + 1, dtype=np.float64)
    p = ranks ** -a
    p /= p.sum()
    ids = rng.choice(dim, size=size, p=p)
    # hotness should not imply row locality: scatter hot ids over the table
    perm = rng.permutation(dim)
    return perm[ids].astype(np.int32)


def synthetic_criteo(rng, n, feature_dimension, zipf=1.05):
    dense = rng.randn(n, 13).astype(np.float32)
    sparse = zipf_ids(rng, feature_dimension, (n, 26), zipf)
    y = rng.randint(0, 2, (n, 1)).astype(np.float32)
    return dense, sparse, y


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="wdl_criteo",
                        choices=["wdl_adult", "wdl_criteo", "dcn_criteo",
                                 "deepfm_criteo", "dc_criteo"])
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--data-path", default=None,
                        help="dir with reference-format criteo files "
                             "(train_*.npy / train.txt / train.csv); "
                             "synthetic data when unset")
    parser.add_argument("--num-steps", type=int, default=100)
    parser.add_argument("--feature-dim", type=int, default=100000,
                        help="embedding rows (Criteo full: 33762577)")
    parser.add_argument("--embedding-size", type=int, default=128)
    parser.add_argument("--comm-mode", default=None,
                        help="None / AllReduce / PS / Hybrid")
    parser.add_argument("--cache", default=None,
                        help="cstable policy: lru / lfu / lfuopt")
    parser.add_argument("--cache-bound", type=int, default=None,
                        help="cache capacity in rows (default: 10%% of "
                             "--feature-dim)")
    parser.add_argument("--zipf", type=float, default=1.05,
                        help="id skew exponent for synthetic data "
                             "(0 = uniform)")
    parser.add_argument("--bf16", action="store_true",
                        help="bf16 compute + bf16 embedding-row "
                             "transfers; fp32 masters on the PS")
    parser.add_argument("--all", action="store_true",
                        help="eval AUC each 10 steps")
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.cache_bound is None:
        args.cache_bound = max(args.feature_dim // 10, 1024)

    rng = np.random.RandomState(0)
    if args.model == "wdl_adult":
        X_deep = [ht.placeholder_op(f"xd{i}") for i in range(12)]
        X_wide = ht.placeholder_op("x_wide")
        y_ = ht.placeholder_op("y_")
        loss, pred, label, train_op = models.wdl_adult(X_deep, X_wide, y_)

        def batch():
            feeds = {X_wide: rng.randn(args.batch_size, 809)
                     .astype(np.float32),
                     y_: np.eye(2, dtype=np.float32)[
                         rng.randint(0, 2, args.batch_size)]}
            for i in range(8):
                feeds[X_deep[i]] = rng.randint(
                    0, 50, (args.batch_size,)).astype(np.int32)
            for i in range(8, 12):
                feeds[X_deep[i]] = rng.randn(args.batch_size)\
                    .astype(np.float32)
            return feeds
    else:
        builder = getattr(models, args.model)
        # feed through dataloaders: the ring prefetches batches and the
        # executor overlaps the NEXT batch's PS/cache embedding lookup
        # with the current step (placeholder feeds cannot be peeked)
        n_pool = 32
        if args.data_path:
            # reference-format local criteo (train_*.npy / train.txt /
            # train.csv — hetu_tpu.data.load_criteo)
            from hetu_tpu.data import load_criteo
            d, s, y = load_criteo(args.data_path)
            args.feature_dim = max(args.feature_dim, int(s.max()) + 1)
            logger.info("loaded criteo from %s: %d rows, %d features",
                        args.data_path, len(y), args.feature_dim)
        else:
            d, s, y = synthetic_criteo(rng, n_pool * args.batch_size,
                                       args.feature_dim, args.zipf)
        dense = ht.dataloader_op([ht.Dataloader(d, args.batch_size,
                                                "train")])
        sparse = ht.dataloader_op([ht.Dataloader(s, args.batch_size,
                                                 "train")])
        y_ = ht.dataloader_op([ht.Dataloader(y, args.batch_size,
                                             "train")])
        loss, pred, label, train_op = builder(
            dense, sparse, y_, feature_dimension=args.feature_dim,
            embedding_size=args.embedding_size)

        def batch():
            return None

    executor = ht.Executor({"train": [loss, pred, label, train_op]},
                           comm_mode=args.comm_mode,
                           cstable_policy=args.cache,
                           cache_bound=args.cache_bound,
                           mixed_precision="bf16" if args.bf16 else None)
    out = executor.run("train", feed_dict=batch())  # compile + warmup
    logger.info("step 0 loss=%.4f (compile)",
                float(np.asarray(out[0]).reshape(-1)[0]))
    t0 = time.time()
    for step in range(1, args.num_steps):
        out = executor.run("train", feed_dict=batch())
        if step % 10 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            msg = ""
            if args.all:
                y_score = np.asarray(out[1])
                y_true = np.asarray(out[2])
                if y_score.ndim == 2 and y_score.shape[-1] == 2:
                    y_score = y_score[:, 1]
                if y_true.ndim == 2 and y_true.shape[-1] == 2:
                    y_true = y_true[:, 1]
                msg = " auc=%.4f" % ht.metrics.auc_score(
                    y_score.reshape(-1), y_true.reshape(-1))
            if executor.cstables:
                perf = executor.ps_perf_summary()
                hr = np.mean([p["hit_rate"] for p in perf.values()])
                msg += " cache_hit=%.3f" % hr
            logger.info("step %d loss=%.4f (%.1f samples/s)%s", step,
                        float(np.asarray(out[0]).reshape(-1)[0]),
                        step * args.batch_size / dt, msg)


if __name__ == "__main__":
    main()
