"""GCN node classification (reference: GNN examples on GraphMix/DistGCN;
tests/test_DistGCN drives the 1.5-D partitioned GCN).

Two stacked graph-convolution layers built from `distgcn_15d_op`
(Z = (A @ H) @ W): on a single device it is a dense fused matmul chain;
with --mesh it runs the 1.5-D partition over (dp x tp) mesh axes — rows
of A/H over 'dp', columns of W over 'tp' — the TPU-native equivalent of
the reference's process-grid partitioning (DistGCN_15d.py).

Data: a synthetic two-community stochastic block model (dense intra-block
edges), labels = community — learnable from structure alone, no egress.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/gnn/train_gcn.py --mesh dp4xtp2
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, '..', '..'))
sys.path.insert(0, _HERE)   # for the shared `gnn_common` helpers

import argparse
import logging

import numpy as np

import hetu_tpu as ht
from gnn_common import parse_mesh, sbm_graph

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("gcn")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=256)
    p.add_argument("--classes", type=int, default=4)
    p.add_argument("--feat-dim", type=int, default=16)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--epochs", type=int, default=60)
    p.add_argument("--learning-rate", type=float, default=0.2)
    p.add_argument("--mesh", default=None,
                   help="e.g. dp4xtp2 — 1.5-D partition axes")
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    mesh = parse_mesh(args.mesh, logger)
    adj, feat, labels = sbm_graph(args.nodes, args.classes, 0.2, 0.01,
                                  args.feat_dim)
    train_mask = np.zeros(args.nodes, bool)
    train_mask[np.random.RandomState(1).choice(
        args.nodes, args.nodes // 2, replace=False)] = True

    a = ht.placeholder_op("adj")
    x = ht.placeholder_op("feat")
    y = ht.placeholder_op("labels")
    m = ht.placeholder_op("mask")
    w1 = ht.init.xavier_uniform((args.feat_dim, args.hidden), name="gcn_w1")
    w2 = ht.init.xavier_uniform((args.hidden, args.classes), name="gcn_w2")
    h = ht.relu_op(ht.distgcn_15d_op(a, x, w1))
    logits = ht.distgcn_15d_op(a, h, w2)
    per_node = ht.softmaxcrossentropy_sparse_op(logits, y)
    # semi-supervised: only train-mask nodes contribute to the loss;
    # held-out nodes are classified purely through graph propagation
    masked = ht.mul_op(per_node, m)
    loss = ht.div_op(ht.reduce_sum_op(masked, [0]),
                     ht.reduce_sum_op(m, [0]))
    train = ht.optim.AdamOptimizer(
        learning_rate=args.learning_rate).minimize(loss)
    ex = ht.Executor({"train": [loss, train], "eval": [logits]}, mesh=mesh)

    feed = {a: adj, x: feat, y: labels,
            m: train_mask.astype(np.float32)}
    for epoch in range(args.epochs):
        out = ex.run("train", feed_dict=feed)
        if (epoch + 1) % 20 == 0:
            lg = np.asarray(ex.run("eval", feed_dict=feed)[0])
            acc = (lg.argmax(-1) == labels)[~train_mask].mean()
            logger.info("epoch %d loss %.4f held-out acc %.3f",
                        epoch + 1, float(np.asarray(out[0])), acc)
    lg = np.asarray(ex.run("eval", feed_dict=feed)[0])
    acc = (lg.argmax(-1) == labels)[~train_mask].mean()
    logger.info("final held-out accuracy %.3f", acc)
    return acc


if __name__ == "__main__":
    main()
