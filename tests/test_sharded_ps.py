"""Sharded PS client (reference: ps-lite key-range partitioning across a
server group — storage and push/pull traffic scale with server count)."""

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.ps.server import PSServer
from hetu_tpu.ps.sharded import ShardedPSClient

# smoke tier: this module is part of the <3-min verification
# battery (`pytest -m smoke`; ROADMAP tier-1 note)
pytestmark = pytest.mark.smoke


def _group(n=2):
    servers = [PSServer() for _ in range(n)]
    return servers, ShardedPSClient(servers=servers)


class TestRowSharding:
    def test_round_robin_rows(self):
        servers, c = _group(2)
        table = np.arange(24, dtype=np.float32).reshape(8, 3)
        c.param_set("t", table)
        # each server holds only its residue class
        np.testing.assert_array_equal(
            np.asarray(servers[0].pull("t")), table[0::2])
        np.testing.assert_array_equal(
            np.asarray(servers[1].pull("t")), table[1::2])
        np.testing.assert_array_equal(c.pull("t"), table)

    def test_sparse_pull_push_routes_by_id(self):
        servers, c = _group(3)
        table = np.random.RandomState(0).randn(9, 4).astype(np.float32)
        c.param_set("t", table)      # no server optimizer: push adds
        ids = np.array([2, 7, 7, 0, 5], np.int64)
        got = c.sparse_pull("t", ids)
        np.testing.assert_allclose(got, table[ids])
        rows = np.ones((5, 4), np.float32)
        c.sparse_push("t", ids, rows)
        out = c.pull("t")
        want = table.copy()
        # duplicate id 7 accumulates twice
        np.add.at(want, ids, rows)
        np.testing.assert_allclose(out, want, rtol=1e-6)

    def test_1d_param_routes_whole(self):
        servers, c = _group(2)
        v = np.arange(5, dtype=np.float32)
        c.param_set("bias", v)
        held = [s for s in servers if "bias" in s.params]
        assert len(held) == 1
        np.testing.assert_array_equal(c.pull("bias"), v)

    def test_fresh_client_discovers_sharding(self):
        servers, c = _group(2)
        table = np.random.RandomState(1).randn(6, 2).astype(np.float32)
        c.param_set("t2", table)
        c2 = ShardedPSClient(servers=servers)   # did not create the table
        np.testing.assert_allclose(c2.pull("t2"), table)
        np.testing.assert_allclose(
            c2.sparse_pull("t2", np.array([1, 4], np.int64)),
            table[[1, 4]])

    def test_dense_push_through_server_opt(self):
        servers, c = _group(2)
        table = np.zeros((4, 2), np.float32)
        c.param_set("t3", table, opt="sgd",
                    opt_args={"learning_rate": 1.0})
        c.push("t3", -np.ones((4, 2), np.float32))   # sgd: p -= lr*g
        np.testing.assert_allclose(c.pull("t3"), np.ones((4, 2)))


class TestExecutorHybridSharded:
    def _build(self, prefix):
        ids = ht.placeholder_op("ids")
        y = ht.placeholder_op("y")
        emb = ht.layers.Embedding(32, 8, name=f"{prefix}_emb")
        h = ht.embedding_lookup_op(emb.embedding_table, ids)
        h = ht.reduce_mean_op(h, [1])
        logits = ht.matmul_op(h, ht.init.xavier_uniform(
            (8, 2), name=f"{prefix}_head"))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, y), axes=0)
        train = ht.optim.SGDOptimizer(learning_rate=0.2).minimize(loss)
        return ids, y, loss, train

    def _batches(self, n=6):
        rng = np.random.RandomState(5)
        return [(rng.randint(0, 32, (8, 4)).astype(np.int32),
                 np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)])
                for _ in range(n)]

    def test_sharded_trajectory_matches_single_server(self):
        bs = self._batches()
        ids, y, loss, train = self._build("shA")
        ex1 = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid",
                          ps_comm=ShardedPSClient(servers=[PSServer()]))
        w0 = ex1.return_tensor_values()
        base = [float(np.asarray(ex1.run(
            "train", feed_dict={ids: a, y: b})[0])) for a, b in bs]

        ids, y, loss, train = self._build("shA")   # same names/shapes
        _, c = _group(3)
        ex2 = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid",
                          ps_comm=c)
        ex2.load_dict(w0)
        tr = [float(np.asarray(ex2.run(
            "train", feed_dict={ids: a, y: b})[0])) for a, b in bs]
        np.testing.assert_allclose(tr, base, atol=1e-5)

    def test_cache_path_uses_home_server(self):
        bs = self._batches()
        ids, y, loss, train = self._build("shC")
        servers, c = _group(2)
        ex = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid",
                         ps_comm=c, cstable_policy="LRU",
                         cache_bound=16)
        for a, b in bs:
            out = ex.run("train", feed_dict={ids: a, y: b})
            assert np.isfinite(float(np.asarray(out[0])))
        # the cached table lives WHOLE on exactly one server of the group
        held = [s for s in servers if "shC_emb_table" in s.params]
        assert len(held) == 1
        assert held[0].params["shC_emb_table"].value.shape[0] == 32


class TestReviewRegressions:
    def test_async_lookup_does_not_deadlock_fan_pool(self):
        """External async submissions (executor ps_lookup_async duck-types
        _pool) must not starve the internal per-shard fan-out pool."""
        servers, c = _group(2)
        for t in ("tA", "tB", "tC"):
            c.param_set(t, np.random.RandomState(0).randn(
                8, 4).astype(np.float32))
        ids = np.arange(8, dtype=np.int64)
        # saturate the external pool with tasks that each fan out
        futs = [c._pool.submit(c.sparse_pull, t, ids)
                for t in ("tA", "tB", "tC", "tA", "tB", "tC")]
        import concurrent.futures
        done, not_done = concurrent.futures.wait(futs, timeout=30)
        assert not not_done, "fan-out deadlocked behind external tasks"
        for f in done:
            assert f.result().shape == (8, 4)

    def test_load_preserves_server_optimizer(self, tmp_path):
        servers, c = _group(2)
        c.param_set("lp", np.zeros((4, 2), np.float32), opt="sgd",
                    opt_args={"learning_rate": 1.0})
        c.save("lp", str(tmp_path))
        c.push("lp", np.ones((4, 2), np.float32))    # sgd: -= lr*g
        c.load("lp", str(tmp_path))                  # back to zeros...
        np.testing.assert_allclose(c.pull("lp"), 0.0)
        c.push("lp", np.ones((4, 2), np.float32))
        # ...and the optimizer survived the load: SGD applied, not raw add
        np.testing.assert_allclose(c.pull("lp"), -1.0)

    def test_empty_ids_sparse_pull(self):
        servers, c = _group(2)
        c.param_set("ei", np.ones((6, 3), np.float32))
        out = c.sparse_pull("ei", np.array([], np.int64))
        assert out.shape == (0, 3)

    def test_fused_sd_pushpull_single_round_trip(self):
        servers, c = _group(2)
        table = np.zeros((8, 2), np.float32)
        c.param_set("fp", table, opt="sgd", opt_args={"learning_rate": 1.0})
        ids = np.array([0, 3, 5], np.int64)
        rows = np.ones((3, 2), np.float32)
        out = c.sd_pushpull("fp", ids, rows, pull_ids=np.array(
            [1, 5, 0], np.int64))
        # pushes applied (sgd lr=1: -=1), pulls see post-push values
        np.testing.assert_allclose(out, [[0, 0], [-1, -1], [-1, -1]])


class TestShardedVan:
    """r5: van routing composes with row sharding — each home PSClient
    discovers ITS server's van and routes that shard's traffic through
    it; results must equal the python-tier sharded run."""

    def test_sharded_group_with_vans_matches_python_tier(self):
        rng = np.random.RandomState(3)
        table = rng.randn(12, 4).astype(np.float32)
        ids = np.array([2, 7, 7, 0, 5, 11], np.int64)
        rows = rng.randn(6, 4).astype(np.float32)

        # python-tier reference result
        servers_py, c_py = _group(2)
        c_py.param_set("t", table, opt="sgd",
                       opt_args={"learning_rate": 0.5})
        want = c_py.sd_pushpull("t", ids, rows)

        # van-enabled group: every shard's table autoserves (inside
        # the try: a failing second enable must still shut down the
        # first server's bound van)
        servers_v, c_v = _group(2)
        try:
            for s in servers_v:
                s.enable_van_autoserve()
            c_v.param_set("t", table, opt="sgd",
                          opt_args={"learning_rate": 0.5})
            got = c_v.sd_pushpull("t", ids, rows)
            np.testing.assert_allclose(got, np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
            # both shards really serve their half from the van, and
            # EVERY home client opened a fast-tier socket (the ids
            # route traffic to both shards — a single silent python-
            # tier fallback is exactly the regression under test)
            assert all(s._van_keys for s in servers_v)
            assert all(cl._van_clients for cl in c_v.clients)
            np.testing.assert_allclose(c_v.pull("t"),
                                       np.asarray(c_py.pull("t")),
                                       rtol=1e-6, atol=1e-6)
        finally:
            for s in servers_v:
                s.shutdown()
