"""Native PS core tests: the C++ fused optimizer loops must match the
numpy fallback bit-for-bit-ish on every optimizer, dense and sparse
(reference equivalent: server optimizers in ps-lite server/optimizer.h,
exercised by tests/pstests)."""

import os

import numpy as np
import pytest

from hetu_tpu.ps import server as S

OPTS = [
    ("sgd", {"learning_rate": 0.1}),
    ("momentum", {"learning_rate": 0.1, "momentum": 0.9}),
    ("nesterov", {"learning_rate": 0.1}),
    ("adagrad", {"learning_rate": 0.1}),
    ("adam", {"learning_rate": 0.01}),
]


def _mk(opt, kw, shape=(32, 8), seed=0):
    rng = np.random.RandomState(seed)
    o = S.SERVER_OPTIMIZERS[opt](**kw)
    value = rng.randn(*shape).astype(np.float32)
    state = o.init_state(shape)
    return o, value, state, rng


@pytest.mark.parametrize("opt,kw", OPTS)
def test_dense_native_matches_numpy(opt, kw, monkeypatch):
    o, v_nat, s_nat, rng = _mk(opt, kw)
    _, v_np, s_np, _ = _mk(opt, kw)
    grads = [rng.randn(*v_nat.shape).astype(np.float32)
             for _ in range(5)]
    for g in grads:
        o.apply_dense(v_nat, g, s_nat)
    monkeypatch.setattr(S, "_f32_ready", lambda *a: False)
    for g in grads:
        o.apply_dense(v_np, g, s_np)
    np.testing.assert_allclose(v_nat, v_np, rtol=1e-5, atol=1e-6)
    for k in s_nat:
        np.testing.assert_allclose(np.asarray(s_nat[k]),
                                   np.asarray(s_np[k]), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("opt,kw", OPTS)
def test_sparse_native_matches_numpy(opt, kw, monkeypatch):
    o, v_nat, s_nat, rng = _mk(opt, kw)
    _, v_np, s_np, _ = _mk(opt, kw)
    pushes = []
    for _ in range(4):
        ids = rng.randint(0, 32, 12).astype(np.int64)  # with duplicates
        rows = rng.randn(12, 8).astype(np.float32)
        pushes.append((ids, rows))
    for ids, rows in pushes:
        o.apply_sparse(v_nat, ids, rows, s_nat)
    monkeypatch.setattr(S, "_f32_ready", lambda *a: False)
    for ids, rows in pushes:
        o.apply_sparse(v_np, ids, rows, s_np)
    np.testing.assert_allclose(v_nat, v_np, rtol=1e-4, atol=1e-5)


def test_native_build_is_keyed_by_the_sources(tmp_path, monkeypatch):
    """What is loaded is decided by the committed sources alone (ISSUE
    22): the library lands in ``_build/<hash of source+headers+flags>/``,
    a changed source builds elsewhere, a stray ``.so`` next to the source
    is never looked at, and a build that fails raises."""
    import shutil
    from hetu_tpu import native
    for f in ("cache.cpp",):
        shutil.copy(os.path.join(native._DIR, f), tmp_path / f)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_BUILD", str(tmp_path / "_build"))
    (tmp_path / "libhetu_cache.so").write_bytes(b"not a library")
    native.build_and_load("cache.cpp", "libhetu_cache.so")
    first = native.loaded["libhetu_cache.so"]
    assert os.path.dirname(os.path.dirname(first)) == str(tmp_path / "_build")
    with open(tmp_path / "cache.cpp", "a") as f:
        f.write("\n// changed\n")
    native.build_and_load("cache.cpp", "libhetu_cache.so")
    assert native.loaded["libhetu_cache.so"] != first
    with open(tmp_path / "cache.cpp", "a") as f:
        f.write("\nthis is not C++\n")
    with pytest.raises(RuntimeError, match="g\\+\\+ failed building"):
        native.build_and_load("cache.cpp", "libhetu_cache.so")


def test_import_builds_no_native_library():
    """The PS core is built on the first PS update or gather, not at
    ``import hetu_tpu``: a host without g++ fails a PS run only, never a
    GPT trainer or server that imports the package."""
    import subprocess
    import sys
    code = ("import hetu_tpu, hetu_tpu.native as n; "
            "assert not n.loaded, n.loaded; "
            "import numpy as np; from hetu_tpu.ps import server as S; "
            "v = np.ones((2, 2), np.float32); "
            "S.ServerSGD(0.5).apply_dense(v, v.copy(), {}); "
            "assert list(n.loaded) == ['libps_core.so'], n.loaded; "
            "assert v[0, 0] == 0.5")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_duplicate_ids_update_stateful_row_once():
    """Stateful optimizers must merge duplicate ids (reference dedups via
    IndexedSlices): two pushes of the same row in one call != two calls."""
    o, value, state, rng = _mk("adagrad", {"learning_rate": 0.1})
    v2 = value.copy()
    s2 = o.init_state(value.shape)
    g = rng.randn(8).astype(np.float32)
    ids = np.array([3, 3], np.int64)
    rows = np.stack([g, g])
    o.apply_sparse(value, ids, rows, state)       # one merged update of 2g
    o.apply_sparse(v2, np.array([3], np.int64), (2 * g)[None], s2)
    np.testing.assert_allclose(value[3], v2[3], rtol=1e-5)


def test_server_sparse_roundtrip_native():
    srv = S.PSServer()
    srv.param_init("t", (16, 4), init_type="constant", arg1=0.0,
                   opt="sgd", opt_args={"learning_rate": 1.0})
    ids = np.array([1, 5, 5], np.int64)
    rows = np.ones((3, 4), np.float32)
    srv.sparse_push("t", ids, rows)
    out = srv.sparse_pull("t", np.array([1, 5], np.int64))
    np.testing.assert_allclose(out[0], -1.0)
    np.testing.assert_allclose(out[1], -2.0)
    # versions bumped once per unique id
    assert srv.params["t"].versions[5] == 1
    assert srv.params["t"].versions[1] == 1
    assert srv.params["t"].versions[0] == 0


class TestNativeVan:
    """C++ PS van (native/ps_van.cpp + ps/van.py): the sparse hot path
    served entirely from C++ threads (reference ps-lite zmq_van tier)."""

    @pytest.fixture()
    def van_pair(self):
        from hetu_tpu.ps.van import NativeVan, VanClient
        van = NativeVan()
        port = van.listen()
        value = van.register_sgd_table(
            7, np.zeros((64, 4), np.float32), lr=0.5)
        cli = VanClient("127.0.0.1", port, dim=4)
        yield van, cli, value
        cli.close()
        van.stop()

    def test_push_pull_sgd_semantics(self, van_pair):
        van, cli, value = van_pair
        ids = np.array([3, 9, 3])          # duplicate id
        grads = np.ones((3, 4), np.float32)
        cli.push(7, ids, grads)
        # sequential scatter: id 3 stepped twice
        got = cli.pull(7, np.array([3, 9, 0]))
        np.testing.assert_allclose(got[0], -1.0)   # 2 * -0.5
        np.testing.assert_allclose(got[1], -0.5)
        np.testing.assert_allclose(got[2], 0.0)
        # the registered buffer IS the served table (zero copy)
        np.testing.assert_allclose(value[3], -1.0)

    def test_pushpull_roundtrip(self, van_pair):
        van, cli, _ = van_pair
        ids = np.arange(8)
        grads = np.full((8, 4), 2.0, np.float32)
        rows = cli.sd_pushpull(7, ids, grads)
        np.testing.assert_allclose(rows, -1.0)     # post-update rows

    def test_out_of_range_id_rejected(self, van_pair):
        van, cli, value = van_pair
        before = value.copy()
        with pytest.raises(RuntimeError):
            cli.push(7, np.array([64]), np.ones((1, 4), np.float32))
        np.testing.assert_allclose(value, before)  # nothing applied

    def test_unknown_key_rejected(self, van_pair):
        van, cli, _ = van_pair
        with pytest.raises(RuntimeError):
            cli.pull(99, np.array([0]))

    def test_version_counters_bump(self):
        from hetu_tpu.ps.van import NativeVan, VanClient
        van = NativeVan()
        port = van.listen()
        versions = np.zeros(16, np.int64)
        van.register_sgd_table(1, np.zeros((16, 2), np.float32),
                               lr=0.1, versions=versions)
        cli = VanClient("127.0.0.1", port, dim=2)
        cli.push(1, np.array([2, 2, 5]), np.ones((3, 2), np.float32))
        # one bump per UNIQUE id per request (python-tier parity)
        assert versions[2] == 1 and versions[5] == 1
        assert versions[0] == 0
        cli.close()
        van.stop()

    def test_concurrent_clients_serialize_on_table_mutex(self):
        from hetu_tpu.ps.van import NativeVan, VanClient
        import threading
        van = NativeVan()
        port = van.listen()
        value = van.register_sgd_table(
            0, np.zeros((128, 4), np.float32), lr=1.0)
        N, per = 4, 50
        ids = np.arange(128)

        def hammer(seed):
            c = VanClient("127.0.0.1", port, dim=4)
            g = np.ones((128, 4), np.float32)
            for _ in range(per):
                c.push(0, ids, g)
            c.close()

        ts = [threading.Thread(target=hammer, args=(i,))
              for i in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # every update applied exactly once: value = -N*per
        np.testing.assert_allclose(value, -float(N * per))
        van.stop()


class TestVanServerIntegration:
    """PSServer.serve_van: one table served by BOTH tiers — the python
    PSFunc surface and the C++ van — consistently on the same buffer."""

    def test_both_tiers_update_one_buffer(self):
        from hetu_tpu.ps.server import PSServer
        from hetu_tpu.ps.van import VanClient
        PSServer._instance = None
        srv = PSServer.get()
        srv.param_init("emb", (32, 4), "constant", 0.0, opt="sgd",
                       opt_args={"learning_rate": 1.0})
        port, keymap = srv.serve_van(["emb"])
        try:
            cli = VanClient("127.0.0.1", port, dim=4)
            ids = np.arange(8)
            g = np.ones((8, 4), np.float32)
            cli.push(keymap["emb"], ids, g)          # via the van
            srv.sparse_push("emb", ids, g)           # via python PSFunc
            # both updates landed on the SAME buffer
            got = srv.sparse_pull("emb", ids)
            np.testing.assert_allclose(got, -2.0)
            got_van = cli.pull(keymap["emb"], ids)
            np.testing.assert_allclose(got_van, -2.0)
            # versions bumped by both tiers (HET sync sees van pushes)
            s_ids, _, vers = srv.sync_embedding(
                "emb", ids, np.zeros(8, np.int64), 0)
            assert len(s_ids) == 8 and (vers == 2).all()
            cli.close()
        finally:
            srv.shutdown()
            PSServer._instance = None

    def test_concurrent_tiers_serialize(self):
        from hetu_tpu.ps.server import PSServer
        from hetu_tpu.ps.van import VanClient
        import threading
        PSServer._instance = None
        srv = PSServer.get()
        srv.param_init("t", (64, 4), "constant", 0.0, opt="sgd",
                       opt_args={"learning_rate": 1.0})
        port, keymap = srv.serve_van(["t"])
        try:
            ids = np.arange(64)
            g = np.ones((64, 4), np.float32)
            per = 40

            def via_van():
                c = VanClient("127.0.0.1", port, dim=4)
                for _ in range(per):
                    c.push(keymap["t"], ids, g)
                c.close()

            def via_python():
                for _ in range(per):
                    srv.sparse_push("t", ids, g)

            ts = [threading.Thread(target=via_van),
                  threading.Thread(target=via_python),
                  threading.Thread(target=via_van)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            np.testing.assert_allclose(srv.sparse_pull("t", ids),
                                       -float(3 * per))
        finally:
            srv.shutdown()
            PSServer._instance = None

    def test_unservable_table_rejected(self):
        """Tables the van cannot serve (non-2-D) stay python-tier;
        r5 widened the family to include optimizer-less (accumulate)
        2-D tables, so the non-qualifying example is a 1-D vector."""
        from hetu_tpu.ps.server import PSServer
        PSServer._instance = None
        srv = PSServer.get()
        srv.param_init("vec", (8,), "constant", 0.0, opt="sgd",
                       opt_args={"learning_rate": 0.1})
        try:
            with pytest.raises(ValueError):
                srv.serve_van(["vec"])
            # auto-selection simply skips non-qualifying tables
            port, keymap = srv.serve_van()
            assert "vec" not in keymap
        finally:
            srv.shutdown()
            PSServer._instance = None

    def test_adam_table_served_with_shared_step(self):
        """r5: the van applies the FULL server-optimizer family
        (reference server/optimizer.h via zmq_van); an adam table's
        slot state and step counter are SHARED with the python tier."""
        from hetu_tpu.ps.server import PSServer
        from hetu_tpu.ps.van import VanClient
        PSServer._instance = None
        srv = PSServer.get()
        srv.param_init("ad", (8, 2), "constant", 0.0, opt="adam",
                       opt_args={"learning_rate": 0.1})
        try:
            port, keymap = srv.serve_van(["ad"])
            assert "ad" in keymap
            cli = VanClient("127.0.0.1", port, dim=2)
            ids = np.array([1, 3], np.int64)
            cli.push(keymap["ad"], ids, np.ones((2, 2), np.float32))
            p = srv.params["ad"]
            assert int(p.state["t"]) == 1          # van bumped the
            assert float(p.state["m"][1, 0]) != 0  # python-side state
            # python tier continues the SAME trajectory (t -> 2)
            srv.sparse_push("ad", ids, np.ones((2, 2), np.float32))
            assert int(p.state["t"]) == 2
            cli.close()
        finally:
            srv.shutdown()
            PSServer._instance = None


@pytest.mark.parametrize("optname,kw", [
    ("momentum", {"learning_rate": 0.2, "momentum": 0.9}),
    ("nesterov", {"learning_rate": 0.2, "momentum": 0.8}),
    ("adagrad", {"learning_rate": 0.3}),
    ("adam", {"learning_rate": 0.05}),
])
def test_van_optimizer_matches_python_tier(optname, kw):
    """Van-served pushes (dup ids included) must land EXACTLY where the
    python tier's apply_sparse would: same value trajectory, same slot
    state, advanced in the registered (shared) buffers."""
    from hetu_tpu.ps.server import SERVER_OPTIMIZERS
    from hetu_tpu.ps.van import NativeVan, VanClient
    rng = np.random.RandomState(7)
    opt_py = SERVER_OPTIMIZERS[optname](**kw)
    opt_van = SERVER_OPTIMIZERS[optname](**kw)
    value_py = rng.randn(32, 4).astype(np.float32)
    state_py = opt_py.init_state(value_py.shape)
    value_van = value_py.copy()
    state_van = opt_van.init_state(value_van.shape)
    van = NativeVan()
    port = van.listen()
    served = van.register_table(3, value_van, opt_van, state_van)
    cli = VanClient("127.0.0.1", port, dim=4)
    try:
        for _ in range(3):
            ids = np.array([5, 9, 5, 20], np.int64)   # duplicate id
            rows = rng.randn(4, 4).astype(np.float32)
            opt_py.apply_sparse(value_py, ids, rows, state_py)
            cli.push(3, ids, rows)
        np.testing.assert_allclose(served, value_py, rtol=2e-6,
                                   atol=1e-6)
        for k in state_py:          # slot state advanced identically,
            np.testing.assert_allclose(                 # in the shared
                np.asarray(state_van[k]), np.asarray(state_py[k]),
                rtol=2e-6, atol=1e-6)                   # registered arrays
    finally:
        cli.close()
        van.stop()


def test_van_served_keys_refuse_buffer_replacement():
    from hetu_tpu.ps.server import PSServer
    PSServer._instance = None
    srv = PSServer.get()
    srv.param_init("k", (8, 2), "constant", 0.0, opt="sgd",
                   opt_args={"learning_rate": 0.1})
    srv.serve_van(["k"])
    try:
        # r5: a qualifying re-set RE-REGISTERS the van table in place
        # (the executor bridge param_sets on load_dict); the served
        # buffer follows the new value
        srv.param_set("k", np.full((8, 2), 7.0, np.float32), opt="sgd",
                      opt_args={"learning_rate": 0.1})
        np.testing.assert_allclose(
            srv.sparse_pull("k", np.arange(8)), 7.0)
        assert "k" in srv._van_keys
        # a respec the van cannot serve (1-D) stays refused — it would
        # silently detach the fast tier
        with pytest.raises(ValueError):
            srv.param_set("k", np.ones(8, np.float32))
        with pytest.raises(ValueError):
            srv.param_clear("k")
        # the in-place path stays open (checkpoint restore)
        srv.param_assign("k", np.full((8, 2), 3.0, np.float32))
        np.testing.assert_allclose(
            srv.sparse_pull("k", np.arange(8)), 3.0)
    finally:
        srv.shutdown()
        PSServer._instance = None


def test_van_version_dedup_matches_python_tier():
    """[5,5,5] in one push bumps versions[5] ONCE on both tiers (HET
    staleness counters must not diverge by tier)."""
    from hetu_tpu.ps.server import PSServer
    from hetu_tpu.ps.van import VanClient
    PSServer._instance = None
    srv = PSServer.get()
    srv.param_init("vd", (16, 2), "constant", 0.0, opt="sgd",
                   opt_args={"learning_rate": 0.1})
    port, keymap = srv.serve_van(["vd"])
    try:
        cli = VanClient("127.0.0.1", port, dim=2)
        dup = np.array([5, 5, 5, 2])
        cli.push(keymap["vd"], dup, np.ones((4, 2), np.float32))
        srv.sparse_push("vd", dup, np.ones((4, 2), np.float32))
        _, _, vers = srv.sync_embedding("vd", np.array([5, 2]),
                                        np.zeros(2, np.int64), 0)
        assert list(vers) == [2, 2], vers   # one bump per tier each
        cli.close()
    finally:
        srv.shutdown()
        PSServer._instance = None


def test_shutdown_restores_python_locks():
    """PSFunc ops on a formerly-van-served key keep working after
    shutdown (the composite lock is unwound, no dead C++ handle)."""
    from hetu_tpu.ps.server import PSServer
    PSServer._instance = None
    srv = PSServer.get()
    srv.param_init("s", (8, 2), "constant", 1.0, opt="sgd",
                   opt_args={"learning_rate": 0.5})
    srv.serve_van(["s"])
    srv.shutdown()
    # van gone: the python surface still serves the key...
    np.testing.assert_allclose(srv.sparse_pull("s", np.arange(8)), 1.0)
    srv.sparse_push("s", np.array([0]), np.ones((1, 2), np.float32))
    np.testing.assert_allclose(srv.sparse_pull("s", np.array([0])), 0.5)
    # ...and the replace/clear guards lift
    srv.param_set("s", np.zeros((8, 2), np.float32))
    srv.param_clear("s")
    PSServer._instance = None


def test_van_autoserve_and_discovery_over_tcp():
    """The heturun deployment shape: a TCP PSServer with autoserve on —
    tables created by clients over RPC register with the van as they
    appear; workers discover the fast tier via the van_info RPC and
    push through it consistently with the python surface."""
    from hetu_tpu.ps.server import PSServer
    from hetu_tpu.ps.client import PSClient, _TCPTransport
    from hetu_tpu.ps.van import VanClient
    PSServer._instance = None
    PSClient._instance = None
    srv = PSServer.get()
    srv.serve_tcp(23993, block=False)
    vport = srv.enable_van_autoserve()
    try:
        c = PSClient(transport=_TCPTransport("127.0.0.1", 23993))
        # created AFTER autoserve was enabled -> auto-registered
        c.parameter_init("auto", (16, 4), "constant", 0.0, opt="sgd",
                         opt_args={"learning_rate": 1.0})
        # r5: the full optimizer family + accumulate tables autoserve;
        # only shapes the van cannot serve (1-D) stay python-tier
        c.parameter_init("adam_t", (8, 2), "constant", 0.0, opt="adam",
                         opt_args={"learning_rate": 0.1})
        c.parameter_init("vec_t", (8,), "constant", 0.0, opt="sgd",
                         opt_args={"learning_rate": 0.1})
        got_port, keymap = c.t.call("van_info")
        assert got_port == vport
        assert "auto" in keymap and "adam_t" in keymap
        assert "vec_t" not in keymap
        vc = VanClient("127.0.0.1", got_port, dim=4)
        ids = np.arange(8)
        vc.push(keymap["auto"], ids, np.ones((8, 4), np.float32))
        np.testing.assert_allclose(c.sparse_pull("auto", ids), -1.0)
        vc.close()
        c.finalize()
    finally:
        srv.shutdown()
        PSServer._instance = None
        PSClient._instance = None


class TestVanCacheSync:
    """r5: the HET cache verbs ride the C++ tier — sync_embedding is
    van op 4, push_embedding is a push on an accumulate-mode table
    (reference: the hetu_cache protocol served by the C++ PS)."""

    def _server(self):
        from hetu_tpu.ps.server import PSServer
        PSServer._instance = None
        srv = PSServer.get()
        srv.param_init("ct", (16, 4), "constant", 1.0, opt=None)
        return srv

    def test_sync_embedding_parity_with_python_tier(self):
        from hetu_tpu.ps.van import VanClient
        srv = self._server()
        try:
            port, keymap = srv.serve_van(["ct"])
            cli = VanClient("127.0.0.1", port)
            # advance versions on rows 2 and 5 through the van
            cli.push(keymap["ct"], np.array([2, 5, 5]),
                     np.full((3, 4), 0.5, np.float32))
            ids = np.arange(8)
            stored = np.zeros(8, np.int64)
            want = srv.sync_embedding("ct", ids, stored, 0)
            got = cli.sync_embedding(keymap["ct"], ids, stored, 0)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(np.asarray(g),
                                              np.asarray(w))
            # accumulate semantics: duplicate push rows SUMMED onto 1.0
            np.testing.assert_allclose(got[1][got[0] == 5], 2.0)
            np.testing.assert_allclose(got[1][got[0] == 2], 1.5)
            # bound filters rows within staleness tolerance: versions
            # bump once per unique id per REQUEST, so a second push
            # takes row 5 to version 2 while row 2 stays at 1
            cli.push(keymap["ct"], np.array([5]),
                     np.full((1, 4), 0.5, np.float32))
            s_ids, _, _ = cli.sync_embedding(keymap["ct"], ids, stored,
                                             bound=1)
            assert list(s_ids) == [5]
            cli.close()
        finally:
            srv.shutdown()
            from hetu_tpu.ps.server import PSServer
            PSServer._instance = None

    def test_client_routes_cache_verbs_through_van(self):
        """PSClient.sync_embedding/push_embedding reach the C++ tier
        when the table is van-served (cstable's hot verbs)."""
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc
        srv = self._server()
        psc.PSClient._instance = None
        try:
            srv.serve_van(["ct"])
            c = psc.PSClient()
            c.push_embedding("ct", np.array([3, 3]),
                             np.ones((2, 4), np.float32))
            st = c._van_local.state
            assert st["cli"] is not None    # the fast tier was used
            s_ids, rows, vers = c.sync_embedding(
                "ct", np.arange(16), np.zeros(16, np.int64), 0)
            assert list(s_ids) == [3]
            np.testing.assert_allclose(rows, 3.0)   # 1 + 2x1 summed
            assert list(vers) == [1]        # one bump per unique push
            c.finalize()
        finally:
            srv.shutdown()
            PSServer._instance = None
            psc.PSClient._instance = None

    def test_cstable_training_over_van_matches_dense(self):
        """Full hybrid+cache training with the table van-autoserved:
        the cstable sync protocol rides the C++ tier and the trajectory
        still equals the dense run."""
        import hetu_tpu as ht
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc

        def build():
            ids = ht.placeholder_op("ids")
            y = ht.placeholder_op("y")
            emb = ht.init.random_normal((50, 8), stddev=0.1,
                                        name="emb_vc")
            emb.is_embed = True
            e = ht.array_reshape_op(ht.embedding_lookup_op(emb, ids),
                                    [-1, 16])
            w = ht.init.xavier_uniform((16, 2), name="w_vc")
            loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
                ht.matmul_op(e, w), y), axes=0)
            train = ht.optim.SGDOptimizer(
                learning_rate=0.1).minimize(loss)
            return ids, y, loss, train

        rng = np.random.RandomState(0)
        batches = [(rng.randint(0, 50, (16, 2)).astype(np.int32),
                    np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)])
                   for _ in range(8)]

        PSServer._instance = None
        psc.PSClient._instance = None
        ids, y, loss, train = build()
        ex1 = ht.Executor({"train": [loss, train]})
        w0 = ex1.return_tensor_values()
        base = [float(np.asarray(ex1.run(
            "train", feed_dict={ids: a, y: c})[0])) for a, c in batches]

        PSServer._instance = None
        psc.PSClient._instance = None
        srv = PSServer.get()
        srv.enable_van_autoserve()
        try:
            ids, y, loss, train = build()
            ex2 = ht.Executor({"train": [loss, train]},
                              comm_mode="Hybrid", cstable_policy="LRU",
                              cache_bound=8)
            ex2.load_dict(w0)
            tr = [float(np.asarray(ex2.run(
                "train", feed_dict={ids: a, y: c})[0]))
                for a, c in batches]
            np.testing.assert_allclose(tr, base, atol=1e-5)
            assert "emb_vc" in srv._van_keys
        finally:
            srv.shutdown()
            PSServer._instance = None
            psc.PSClient._instance = None


class TestVanFallbackContract:
    """The client's van fallback rules: reads retry anywhere, pushes
    retry ONLY when the frame never fully left (double-apply safety),
    and late serve_van is discovered within the refresh window."""

    def _pair(self):
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc
        self._reset()
        srv = PSServer.get()
        srv.param_init("fb", (8, 2), "constant", 0.0, opt="sgd",
                       opt_args={"learning_rate": 1.0})
        return srv, psc.PSClient()

    def test_send_side_failure_falls_back_without_double_apply(self):
        from hetu_tpu.ps.van import VanTransportError
        srv, c = self._pair()
        try:
            srv.serve_van(["fb"])
            ids = np.array([1], np.int64)
            c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            st = c._van_local.state
            assert st["cli"] is not None

            # send-side failure: NOT applied -> python tier retries,
            # so the table advances exactly one more step, and the
            # broken van socket is dropped for this thread
            def boom(*a, **kw):
                raise VanTransportError("sim send fail",
                                        maybe_applied=False)
            st["cli"].push = boom
            c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            np.testing.assert_allclose(
                srv.params["fb"].value[1], -2.0)   # exactly 2 steps
            assert st["cli"] is None and st["dead"]
        finally:
            c.finalize()
            srv.shutdown()
            self._reset()

    def test_response_side_failure_raises_instead_of_double_apply(self):
        from hetu_tpu.ps.van import VanTransportError
        from hetu_tpu.ps.client import PSConnectionError
        srv, c = self._pair()
        try:
            srv.serve_van(["fb"])
            ids = np.array([2], np.int64)
            c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            st = c._van_local.state
            def boom(*a, **kw):
                raise VanTransportError("sim recv fail",
                                        maybe_applied=True)
            st["cli"].push = boom
            with pytest.raises(PSConnectionError):
                c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            # the update was NOT silently re-applied python-side
            np.testing.assert_allclose(srv.params["fb"].value[2], -1.0)
        finally:
            c.finalize()
            srv.shutdown()
            self._reset()

    def test_late_serve_van_discovered_after_refresh_window(self):
        """Traffic starts python-tier; serve_van afterwards is picked
        up once the per-thread refresh window elapses."""
        srv, c = self._pair()
        try:
            ids = np.array([0], np.int64)
            c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            st = c._van_local.state
            assert st["cli"] is None          # python tier so far
            srv.serve_van(["fb"])
            st["checked_at"] = 0.0            # window elapsed
            c.sparse_push("fb", ids, np.ones((1, 2), np.float32))
            assert st["cli"] is not None      # fast tier picked up
            np.testing.assert_allclose(srv.params["fb"].value[0], -2.0)
        finally:
            c.finalize()
            srv.shutdown()
            self._reset()

    @staticmethod
    def _reset():
        from hetu_tpu.ps.server import PSServer
        import hetu_tpu.ps.client as psc
        PSServer._instance = None
        psc.PSClient._instance = None
