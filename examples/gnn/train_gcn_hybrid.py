"""Hybrid-PS GCN: learnable node embeddings served by the parameter
server, graph convolutions on the device mesh.

Reference: examples/gnn/run_dist_hybrid.py:1 — the GraphMix/PS hybrid
deployment where node embeddings live server-side and each worker runs
GCN compute; here the embedding table is an ``is_embed`` variable the
Executor's Hybrid phases A/B pull/push through the PS (and through the
native C++ van when HETU_PS_VAN autoserve is on), while the 1.5-D
``distgcn_15d_op`` layers run on the mesh (examples/gnn/run_dist.py's
partitioning, SURVEY tests/test_DistGCN).

Data: the same synthetic stochastic block model as train_gcn.py —
labels recoverable from structure, no egress.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/gnn/train_gcn_hybrid.py --mesh dp4xtp2
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
logger = logging.getLogger("gcn-hybrid")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=256)
    p.add_argument("--classes", type=int, default=4)
    p.add_argument("--embed-dim", type=int, default=16)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--epochs", type=int, default=80)
    p.add_argument("--learning-rate", type=float, default=0.2)
    p.add_argument("--mesh", default=None,
                   help="e.g. dp4xtp2 — 1.5-D partition axes")
    p.add_argument("--cache-policy", default=None,
                   choices=[None, "LRU", "LFU", "LFUOpt"],
                   help="HET embedding cache between worker and PS")
    p.add_argument("--cache-bound", type=int, default=64)
    args = p.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    mesh = parse_mesh(args.mesh, logger)
    adj, _, labels = sbm_graph(args.nodes, args.classes, 0.2, 0.01)
    node_ids = np.arange(args.nodes).astype(np.int32)
    train_mask = np.zeros(args.nodes, bool)
    train_mask[np.random.RandomState(1).choice(
        args.nodes, args.nodes // 2, replace=False)] = True

    a = ht.placeholder_op("adj")
    ids = ht.placeholder_op("node_ids")
    y = ht.placeholder_op("labels")
    m = ht.placeholder_op("mask")
    # the PS-served table: structure is the only signal, so the
    # embeddings must LEARN community-separating features
    emb = ht.init.random_normal((args.nodes, args.embed_dim), stddev=0.3,
                                name="gcn_node_emb")
    emb.is_embed = True
    x = ht.embedding_lookup_op(emb, ids)
    w1 = ht.init.xavier_uniform((args.embed_dim, args.hidden),
                                name="gcn_w1")
    w2 = ht.init.xavier_uniform((args.hidden, args.classes),
                                name="gcn_w2")
    h = ht.relu_op(ht.distgcn_15d_op(a, x, w1))
    logits = ht.distgcn_15d_op(a, h, w2)
    per_node = ht.softmaxcrossentropy_sparse_op(logits, y)
    masked = ht.mul_op(per_node, m)
    loss = ht.div_op(ht.reduce_sum_op(masked, [0]),
                     ht.reduce_sum_op(m, [0]))
    train = ht.optim.SGDOptimizer(
        learning_rate=args.learning_rate).minimize(loss)
    kw = dict(comm_mode="Hybrid", mesh=mesh)
    if args.cache_policy:
        kw.update(cstable_policy=args.cache_policy,
                  cache_bound=args.cache_bound)
    ex = ht.Executor({"train": [loss, train], "eval": [logits]}, **kw)

    feed = {a: adj, ids: node_ids, y: labels,
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
