"""ISSUE 9: quantized bytes everywhere — one int8 layer, three seams.

The load-bearing claims, each pinned separately:

- codec: symmetric per-chunk int8 round-trips within the documented
  ``amax / 254`` per-element bound (numpy and jax halves agree), and
  the wire codec carries the (int8 payload, scales) pair natively —
  property-tested alongside the pre-existing edge dtypes, because the
  codec is now load-bearing for quantized payloads;
- PS transport: ``HETU_PS_QUANT=int8`` push/pull parity within the
  bound, >= 3.5x wire-byte reduction on the PR 5 counters, replication
  and resync move the quantized form (under ``HETU_CHAOS`` too), and
  training through the PS stays on the exact loss curve within a bound;
- collectives: the quantize→all_gather→dequantize trio sums correctly
  under real shard_map execution, shard_check REJECTS a quantize
  without its paired dequantize across the collective, and
  collective_check sees int8 legs as first-class signatures;
- serving KV: the int8 kernels match their dequantize oracles, the
  engine with ``kv_quant="int8"`` is greedy-identical to offline f32
  on the parity model (contiguous, paged, fast path, chunked prefill,
  shared prefixes), and the teacher-forced margin gate holds;
- defaults: with every knob unset, nothing changes a byte.

Everything runs on the CPU harness (kernels interpret-mode) — smoke.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu import quant, telemetry
from hetu_tpu.ps import wire
from hetu_tpu.ps.client import PSClient, _LocalTransport, _TCPTransport
from hetu_tpu.ps.server import PSServer

pytestmark = pytest.mark.smoke


def fresh_ps():
    PSServer._instance = None
    PSClient._instance = None


def _err_bound(x):
    """The documented per-element bound for one flat-chunk encode of
    ``x``: half a quantization step of the worst chunk."""
    m = float(np.abs(x).max()) if np.asarray(x).size else 0.0
    return m / 254.0 + 1e-7


# --------------------------------------------------------------------- #
# codec
# --------------------------------------------------------------------- #

class TestCodec:
    def test_roundtrip_error_bound(self):
        rng = np.random.RandomState(0)
        for shape in [(1000,), (7, 13), (4, 256), (1,), (3, 1, 5)]:
            x = (rng.randn(*shape) * rng.uniform(0.01, 30)).astype(
                np.float32)
            qa = quant.QuantArray.encode(x)
            back = qa.decode()
            assert back.shape == x.shape and back.dtype == np.float32
            assert np.abs(back - x).max() <= _err_bound(x)

    def test_outlier_poisons_only_its_chunk(self):
        # per-CHUNK scales: a 1e3 outlier in chunk 0 must not blow up
        # chunk 1's precision
        x = np.full(512, 0.01, np.float32)
        x[3] = 1000.0
        back = quant.QuantArray.encode(x, chunk=256).decode()
        assert np.abs(back[256:] - 0.01).max() <= 0.01 / 200

    def test_zero_and_empty_and_0d(self):
        for x in [np.zeros((4, 8), np.float32),
                  np.zeros((0,), np.float32),
                  np.asarray(2.5, np.float32)]:
            back = quant.QuantArray.encode(x).decode()
            np.testing.assert_allclose(back, x, atol=_err_bound(x))
        # all-zero chunks decode to exact zero (scale 1.0, q 0)
        np.testing.assert_array_equal(
            quant.QuantArray.encode(np.zeros(300, np.float32)).decode(),
            0.0)

    def test_jax_and_np_halves_agree(self):
        rng = np.random.RandomState(1)
        x = rng.randn(4, 512).astype(np.float32)
        qn, sn = quant.quantize_np(x, 256)
        qj, sj = quant.quantize_jax(jnp.asarray(x), 256)
        np.testing.assert_array_equal(qn.reshape(4, 512), np.asarray(qj))
        np.testing.assert_allclose(sn, np.asarray(sj).reshape(-1),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(quant.dequantize_jax(qj, sj, 256)),
            quant.dequantize_np(qn, sn, 256).reshape(4, 512), rtol=1e-6)

    def test_kv_encode_per_position_head_scales(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(3, 5, 2, 8).astype(np.float32))
        q, s = quant.kv_encode(x)
        assert q.dtype == jnp.int8 and q.shape == x.shape
        assert s.shape == x.shape[:-1]
        back = quant.kv_decode(q, s)
        # bound per (position, head) row
        amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
        assert np.all(np.abs(np.asarray(back) - np.asarray(x))
                      <= amax / 254 + 1e-7)

    def test_mode_grammar(self, monkeypatch):
        assert quant.resolve_quant("int8", "HETU_PS_QUANT") == "int8"
        assert quant.resolve_quant("0", "HETU_PS_QUANT") is None
        assert quant.resolve_quant(None, "HETU_PS_QUANT") is None
        monkeypatch.setenv("HETU_PS_QUANT", "int8")
        assert quant.ps_quant() == "int8"
        with pytest.raises(ValueError):
            quant.resolve_quant("int3", "HETU_PS_QUANT")


# --------------------------------------------------------------------- #
# wire codec: the scales-bearing pair + edge dtypes (satellite)
# --------------------------------------------------------------------- #

class TestWireQuant:
    def test_quant_pair_property_roundtrip(self):
        """Seeded property test: arbitrary float arrays survive the
        encode → dumps → loads → decode trip with q/scales/shape/chunk
        preserved EXACTLY (the pair is the payload of record; decode
        happens at the far end)."""
        rng = np.random.RandomState(3)
        for _ in range(25):
            nd = rng.randint(0, 4)
            shape = tuple(int(rng.randint(0, 9)) for _ in range(nd))
            x = np.asarray(rng.randn(*shape) * rng.uniform(0.001, 100),
                           np.float32)
            chunk = int(rng.choice([16, 64, 256]))
            qa = quant.QuantArray.encode(x, chunk)
            back = wire.loads(wire.dumps(qa))
            assert isinstance(back, quant.QuantArray)
            assert back.shape == x.shape and back.chunk == chunk
            np.testing.assert_array_equal(np.asarray(back.q),
                                          np.asarray(qa.q))
            np.testing.assert_array_equal(np.asarray(back.scales),
                                          np.asarray(qa.scales))
            np.testing.assert_allclose(back.decode(), qa.decode(),
                                       rtol=1e-6, atol=1e-7)

    def test_quant_pair_composes_in_envelope(self):
        qa = quant.QuantArray.encode(np.ones(2000, np.float32) * 3)
        msg = ("__req2__", "cid", 7, "push", ("key", qa),
               {"async_": False})
        back = wire.loads(wire.dumps(msg))
        assert back[3] == "push"
        assert isinstance(back[4][1], quant.QuantArray)
        np.testing.assert_allclose(back[4][1].decode(), 3.0,
                                   atol=3 / 200)

    def test_edge_dtypes_roundtrip(self):
        """int8/uint8/0-d/empty arrays — the raw-array tags the quant
        payloads lean on — keep exact dtype + contents."""
        cases = [np.arange(-5, 5, dtype=np.int8),
                 np.arange(9, dtype=np.uint8).reshape(3, 3),
                 np.asarray(7, np.int8),                  # 0-d int8
                 np.zeros((0, 4), np.float32),            # empty
                 np.zeros((), np.float64),                # 0-d f64
                 np.asarray([], np.int64)]
        for x in cases:
            back = wire.loads(wire.dumps(x))
            assert back.dtype == x.dtype and back.shape == x.shape
            np.testing.assert_array_equal(back, x)

    def test_wire_bytes_reduction(self):
        x = np.random.RandomState(4).randn(4096).astype(np.float32)
        plain = len(wire.dumps(x))
        packed = len(wire.dumps(quant.QuantArray.encode(x)))
        assert plain / packed >= 3.5


# --------------------------------------------------------------------- #
# PS transport
# --------------------------------------------------------------------- #

class TestPSQuant:
    def _sgd_client(self, key="w", shape=(64, 64), lr=0.1):
        fresh_ps()
        c = PSClient(transport=_LocalTransport())
        c.param_set(key, np.zeros(shape, np.float32), opt="sgd",
                    opt_args={"learning_rate": lr})
        return c

    def test_push_pull_parity_within_bound(self, monkeypatch):
        g = np.random.RandomState(5).randn(64, 64).astype(np.float32)
        c = self._sgd_client()
        monkeypatch.setenv("HETU_PS_QUANT", "int8")
        c.push("w", g)
        out = c.pull("w")
        ref = -0.1 * g
        # push quantizes g once; pull quantizes the value once
        assert np.abs(out - ref).max() <= 2 * 0.1 * _err_bound(g) \
            + _err_bound(ref)
        fresh_ps()

    def test_default_off_is_exact(self):
        g = np.random.RandomState(6).randn(64, 64).astype(np.float32)
        c = self._sgd_client()
        c.push("w", g)
        np.testing.assert_array_equal(c.pull("w"), -0.1 * g)
        fresh_ps()

    def test_small_payloads_stay_exact(self, monkeypatch):
        """Control-plane arrays under the WIRE_MIN_SIZE floor must
        round-trip bit-perfectly even with quantization on (row-shard
        metadata would misroute otherwise)."""
        monkeypatch.setenv("HETU_PS_QUANT", "int8")
        c = self._sgd_client("tiny", shape=(4, 3))
        g = np.random.RandomState(7).randn(4, 3).astype(np.float32)
        c.push("tiny", g)
        np.testing.assert_array_equal(c.pull("tiny"), -0.1 * g)
        fresh_ps()

    def test_tcp_wire_reduction_on_counters(self, monkeypatch):
        """The acceptance measurement: per push/pull wire bytes via the
        PR 5 ps.rpc.bytes_sent/recv counters drop >= 3.5x with int8 on,
        and ps.rpc.bytes_saved accounts the delta."""
        import socket
        fresh_ps()
        server = PSServer.get()
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        server.serve_tcp(port, block=False)
        try:
            g = np.random.RandomState(8).randn(128, 128).astype(
                np.float32)
            t = _TCPTransport("127.0.0.1", port)
            c = PSClient(transport=t)
            c.param_set("big", np.zeros((128, 128), np.float32),
                        opt="sgd", opt_args={"learning_rate": 0.1})
            c.push("big", g)                      # warm

            def bytes_for(n):
                telemetry.reset()
                for _ in range(n):
                    c.push("big", g)
                    c.pull("big")
                snap = telemetry.snapshot()["counters"]
                return (snap["ps.rpc.bytes_sent"]
                        + snap["ps.rpc.bytes_recv"],
                        snap.get("ps.rpc.bytes_saved", 0))

            exact, saved0 = bytes_for(3)
            assert saved0 == 0
            monkeypatch.setenv("HETU_PS_QUANT", "int8")
            packed, saved = bytes_for(3)
            assert exact / packed >= 3.5
            assert saved > 0
            c.finalize()
        finally:
            server.shutdown()
            fresh_ps()

    def test_sparse_verbs_quantized_parity(self, monkeypatch):
        fresh_ps()
        c = PSClient(transport=_LocalTransport())
        rows, dim = 64, 32
        c.param_set("emb", np.zeros((rows, dim), np.float32),
                    opt="sgd", opt_args={"learning_rate": 0.5})
        rng = np.random.RandomState(9)
        ids = rng.randint(0, rows, 48).astype(np.int64)
        grads = rng.randn(48, dim).astype(np.float32)
        ref = np.zeros((rows, dim), np.float32)
        uniq, inv = np.unique(ids, return_inverse=True)
        merged = np.zeros((len(uniq), dim), np.float32)
        np.add.at(merged, inv, grads)
        ref[uniq] -= 0.5 * merged
        monkeypatch.setenv("HETU_PS_QUANT", "int8")
        out = c.sd_pushpull("emb", ids, grads,
                            pull_ids=np.arange(rows))
        assert np.abs(out - ref).max() <= \
            0.5 * 3 * _err_bound(grads) + _err_bound(ref) + 1e-5
        fresh_ps()

    def test_replication_resync_under_chaos_moves_quantized(
            self, monkeypatch):
        """Satellite + tentpole: with int8 wire AND seeded chaos drops
        active, a replicated group's failover + resync walks the exact
        same trajectory as a fault-free quantized run — both sides
        dequantize the identical frames, and resync ships the table
        back through the quantized pull/param_set pair."""
        from hetu_tpu.ps.client import PSConnectionError
        from hetu_tpu.ps.sharded import (REPLICA_PREFIX, ShardedPSClient,
                                         _LocalServerTransport)
        monkeypatch.setenv("HETU_PS_QUANT", "int8")

        def steps(client, n, skip=0):
            rng = np.random.RandomState(10)
            for i in range(n):
                ids = rng.randint(0, 8, 5).astype(np.int64)
                grads = rng.randn(5, 3).astype(np.float32)
                if i >= skip:
                    client.sd_pushpull("t", ids, grads)

        def mk(replicate):
            servers = [PSServer(), PSServer()]
            c = ShardedPSClient(servers=servers, replicate=replicate)
            c.param_set("t", np.zeros((8, 3), np.float32), opt="sgd",
                        opt_args={"learning_rate": 0.5})
            return servers, c

        _, base = mk(False)
        steps(base, 12)
        want = base.pull("t")

        monkeypatch.setenv("HETU_CHAOS", "seed=5,drop=0.15")
        try:
            servers, c = mk(True)
            steps(c, 6)
            c.drain_replication()
            np.testing.assert_allclose(
                np.asarray(servers[1].pull(REPLICA_PREFIX + "t")),
                np.asarray(servers[0].pull("t")))

            class _Dead:
                def call(self, method, *a, **kw):
                    raise PSConnectionError("server gone (test)")

                def close(self):
                    pass

            c.clients[0].t = _Dead()
            steps(c, 12, skip=6)
            assert c.failed_shards() == [0]
            np.testing.assert_allclose(c.pull("t"), want, atol=1e-5)
            fresh = PSServer()
            c.clients[0].t = _LocalServerTransport(fresh)
            restored = c.resync_shard(0)
            assert "t" in restored and c.failed_shards() == []
            # the resynced primary's shard came back through the
            # quantized wire: equal within one encode/decode of the
            # table values
            np.testing.assert_allclose(
                np.asarray(fresh.pull("t")), np.asarray(want)[0::2],
                atol=float(np.abs(np.asarray(want)).max()) / 100)
        finally:
            monkeypatch.delenv("HETU_CHAOS", raising=False)
            fresh_ps()

    def test_ps_training_loss_curve_within_bound(self, monkeypatch):
        """Training parity gate: the SAME model trained through
        comm_mode='PS' (dense params server-optimized, every grad and
        pull crossing the wire) with int8 on tracks the exact run's
        loss curve within a small absolute band."""
        def train(quant_on):
            fresh_ps()
            if quant_on:
                monkeypatch.setenv("HETU_PS_QUANT", "int8")
            else:
                monkeypatch.delenv("HETU_PS_QUANT", raising=False)
            x = ht.placeholder_op("x")
            y = ht.placeholder_op("y")
            # SAME names in both runs: init_value seeds per name, so
            # distinct names would compare different models
            w = ht.init.xavier_uniform((64, 64), name="qw")
            w2 = ht.init.xavier_uniform((64, 2), name="qw2")
            h = ht.relu_op(ht.matmul_op(x, w))
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y),
                axes=0)
            train_op = ht.optim.SGDOptimizer(
                learning_rate=0.1).minimize(loss)
            ex = ht.Executor({"train": [loss, train_op]},
                             comm_mode="PS", seed=11)
            rng = np.random.RandomState(12)
            losses = []
            for _ in range(15):
                a = rng.randn(16, 64).astype(np.float32)
                lab = (a[:, 0] > 0).astype(np.int64)
                c = np.eye(2, dtype=np.float32)[lab]
                losses.append(float(np.asarray(
                    ex.run("train", feed_dict={x: a, y: c})[0])))
            return np.asarray(losses)

        exact = train(False)
        q = train(True)
        fresh_ps()
        assert exact[-1] < exact[0]          # it actually trains
        assert q[-1] < q[0]
        assert np.abs(q - exact).max() < 0.05, (exact, q)


# --------------------------------------------------------------------- #
# quantized collective pair
# --------------------------------------------------------------------- #

class TestCommQuantPair:
    def _trio(self, shape=(8, 32)):
        g = ht.placeholder_op("qgrad")
        return ht.quantized_allreduce_op(g, shape=shape)

    def test_shard_map_numerics_and_int8_on_wire(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from hetu_tpu.graph.node import TraceContext
        from hetu_tpu.parallel.collective_check import (
            check_collective_order, quantized_collectives)
        from hetu_tpu.parallel.mesh import make_mesh
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices (host platform count)")
        n = jax.device_count()
        mesh = make_mesh({"dp": n})
        trio = self._trio()

        def body(x):
            tc = TraceContext(axis_env=("dp",))
            gth = trio.inputs[0]
            q = gth.inputs[0]
            return trio.compute(
                [gth.compute([q.compute([x], tc)], tc)], tc)

        seq = check_collective_order(body, mesh, P(), P("dp"),
                                     [jnp.ones((8, 32))])
        assert quantized_collectives(seq), \
            "no int8 collective in the traced program"
        f = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
        x = np.random.RandomState(13).randn(8, 32).astype(np.float32)
        out = np.asarray(jax.jit(f)(x))
        ref = n * x
        assert np.abs(out - ref).max() <= n * _err_bound(x) * 1.5

    def test_pjit_mode_is_fake_quant(self):
        from hetu_tpu.graph.node import TraceContext
        trio = self._trio()
        tc = TraceContext()                    # no axis env: pjit mode
        gth = trio.inputs[0]
        q = gth.inputs[0]
        x = jnp.asarray(
            np.random.RandomState(14).randn(8, 32).astype(np.float32))
        out = trio.compute([gth.compute([q.compute([x], tc)], tc)], tc)
        assert out.shape == (8, 32)
        assert np.abs(np.asarray(out) - np.asarray(x)).max() \
            <= _err_bound(np.asarray(x))

    def test_shard_check_accepts_paired_rejects_unpaired(self):
        from hetu_tpu.analysis.shard_check import (
            ShardCheckError, check_quantized_collectives)
        from hetu_tpu.graph.ops_comm import (
            DequantizeCommOp, QuantAllReduceCommunicateOp,
            QuantizeCommOp)
        trio = self._trio()
        assert len(check_quantized_collectives([trio])) == 1
        # quantize whose pair never crosses a collective
        q = QuantizeCommOp(ht.placeholder_op("g1"))
        d = DequantizeCommOp(q, (4, 4))
        with pytest.raises(ShardCheckError, match="quant"):
            check_quantized_collectives([d])
        # collective with no dequantize consumer
        gth = QuantAllReduceCommunicateOp(
            QuantizeCommOp(ht.placeholder_op("g2")))
        with pytest.raises(ShardCheckError, match="paired"):
            check_quantized_collectives([gth])
        # collective over a raw (unquantized) input
        gth2 = QuantAllReduceCommunicateOp(ht.placeholder_op("g3"))
        d2 = DequantizeCommOp(gth2, (4, 4))
        with pytest.raises(ShardCheckError, match="QuantizeCommOp"):
            check_quantized_collectives([d2])
        # axis disagreement inside one trio
        q3 = QuantizeCommOp(ht.placeholder_op("g4"), axis="dp")
        g3 = QuantAllReduceCommunicateOp(q3, axis="dp")
        d3 = DequantizeCommOp(g3, (4, 4), axis="tp")
        with pytest.raises(ShardCheckError, match="axis"):
            check_quantized_collectives([d3])

    def test_check_parallelism_wires_the_pairing(self):
        from hetu_tpu.analysis.shard_check import (ShardCheckError,
                                                   check_parallelism)
        from hetu_tpu.graph.ops_comm import (
            QuantAllReduceCommunicateOp, QuantizeCommOp)
        gth = QuantAllReduceCommunicateOp(
            QuantizeCommOp(ht.placeholder_op("g5")))
        with pytest.raises(ShardCheckError):
            check_parallelism([gth], None)

    def test_strategy_splices_and_trains(self, monkeypatch):
        from hetu_tpu.graph.ops_comm import DequantizeCommOp
        from hetu_tpu.parallel.distributed_strategies import DataParallel

        def build_and_train(aggregate):
            x = ht.placeholder_op("x")
            # same name across runs: same seeded init, comparable curves
            w = ht.init.xavier_uniform((32, 32), name="dpq_w")
            h = ht.relu_op(ht.matmul_op(x, w))
            loss = ht.reduce_mean_op(
                ht.reduce_mean_op(h, axes=1), axes=0)
            train = ht.optim.SGDOptimizer(
                learning_rate=0.1).minimize(loss)
            ex = ht.Executor(
                {"train": [loss, train]}, seed=3,
                dist_strategy=DataParallel(aggregate=aggregate,
                                           num_devices=1))
            feed = np.ones((8, 32), np.float32)
            losses = [float(np.asarray(
                ex.run("train", feed_dict={x: feed})[0]))
                for _ in range(6)]
            return ex, losses

        ex_q, lq = build_and_train("quant_allreduce")
        opt = next(n for nodes in ex_q.eval_node_dict.values()
                   for n in nodes
                   if type(n).__name__ == "OptimizerOp")
        assert all(isinstance(g, DequantizeCommOp) for g in opt.inputs)
        _, le = build_and_train(None)
        assert lq[-1] < lq[0]
        assert abs(lq[-1] - le[-1]) < 0.05

    def test_env_knob_activates_splice(self, monkeypatch):
        from hetu_tpu.parallel.distributed_strategies import DataParallel
        monkeypatch.setenv("HETU_COMM_QUANT", "int8")
        assert DataParallel()._quantized()
        monkeypatch.delenv("HETU_COMM_QUANT")
        assert not DataParallel()._quantized()
        assert DataParallel(aggregate="allreduce")._quantized() is False


# --------------------------------------------------------------------- #
# int8 KV cache
# --------------------------------------------------------------------- #

def _rand_gpt(name="qg", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    rng = np.random.RandomState(seed)
    hd = H * Dh
    p = {f"{name}_wte_table": rng.randn(V, hd) * 0.05,
         f"{name}_wpe": rng.randn(S, hd) * 0.05,
         f"{name}_ln_f_scale": np.ones(hd),
         f"{name}_ln_f_bias": np.zeros(hd)}
    for i in range(L):
        us = f"{name}_h{i}"
        for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                       ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                       ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
            p[f"{us}_{w}_weight"] = rng.randn(*shp) * 0.05
            p[f"{us}_{w}_bias"] = np.zeros(shp[1])
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = np.ones(hd)
            p[f"{us}_{ln}_bias"] = np.zeros(hd)
    from hetu_tpu.models import GPTConfig
    cfg = GPTConfig(vocab_size=V, hidden_size=hd, num_hidden_layers=L,
                    num_attention_heads=H, max_position_embeddings=S,
                    batch_size=1, seq_len=S, dropout_rate=0.0)
    return p, cfg


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


class TestKVQuantEngine:
    def _offline(self, model, prompts, n=6):
        from hetu_tpu.models.gpt_decode import generate_fast
        p, cfg = model
        return sorted(
            generate_fast(p, cfg, np.asarray([pr], np.int32),
                          num_tokens=n)[0].tolist()
            for pr in prompts)

    def _engine(self, model, prompts, n=6, **kw):
        from hetu_tpu.serving import Request, ServingEngine
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=4, **kw)
        res = eng.run([Request(prompt=pr, max_new_tokens=n, seed=i)
                       for i, pr in enumerate(prompts)])
        return eng, sorted(r.tokens.tolist() for r in res.values())

    PROMPTS = [[7, 8, 9, 10], [3, 1, 4], [11, 12, 13, 14, 15]]

    def test_engine_int8_greedy_identical_to_offline(self, model):
        ref = self._offline(model, self.PROMPTS)
        for kw in [dict(kv_block=8, fast_path=False),
                   dict(kv_block=8, fast_path=True)]:
            eng, out = self._engine(model, self.PROMPTS,
                                    kv_quant="int8", **kw)
            assert out == ref, kw
            assert eng.kv.quant == "int8"
            assert isinstance(eng.kv.cache_k, tuple)
            assert eng.kv.cache_k[0].dtype == jnp.int8

    def test_env_knob_and_stats(self, model, monkeypatch):
        monkeypatch.setenv("HETU_KV_QUANT", "int8")
        eng, out = self._engine(model, self.PROMPTS, kv_block=8,
                                fast_path=False)
        assert eng.kv.quant == "int8"
        assert eng.kv.stats()["quant"] == "int8"
        assert out == self._offline(model, self.PROMPTS)

    def test_chunked_prefill_shared_prefix_cow_int8(self, model):
        pre = [5, 6, 7, 8, 9, 10, 11, 12, 13]   # straddles block 4
        prompts = [pre + [20 + i] for i in range(3)]
        _, a = self._engine(model, prompts, kv_quant="int8",
                            kv_block=4, fast_path=False,
                            prefix_share=True, prefill_chunk=4)
        eng_b, b = self._engine(model, prompts, kv_block=4,
                                fast_path=False, prefix_share=False)
        assert a == b

    def test_cache_bytes_reduced(self, model):
        from hetu_tpu.serving import ServingEngine
        p, cfg = model
        exact = ServingEngine(p, cfg, slots=4).kv.cache_bytes
        int8 = ServingEngine(p, cfg, slots=4,
                             kv_quant="int8").kv.cache_bytes
        # Dh=8 here: (8 + 4) / 32 per value — bigger heads do better
        assert int8 < exact / 2

    def test_manager_accepts_dtype_int8(self):
        from hetu_tpu.serving import PagedKVManager
        for dtype in ("int8", jnp.int8):
            pm = PagedKVManager(layers=1, heads=2, head_dim=8, slots=2,
                                max_seq_len=32, block=8, dtype=dtype)
            assert pm.quant == "int8" and isinstance(pm.cache_k, tuple)
            assert pm.cache_k[1].dtype == jnp.float32

    def test_teacher_forced_margin_gate(self, model):
        from hetu_tpu.models.gpt_decode import teacher_forced_logits
        p, cfg = model
        seq = np.asarray([7, 8, 9, 10, 11, 3, 1, 4, 2], np.int32)
        le = np.asarray(teacher_forced_logits(p, cfg, seq))
        lq = np.asarray(teacher_forced_logits(p, cfg, seq,
                                              kv_fake_quant=True))
        delta = float(np.abs(lq - le).max())
        assert delta < 0.1
        top2 = np.sort(le, axis=-1)
        margin = top2[:, -1] - top2[:, -2]
        confident = margin > 2 * delta
        assert confident.any()
        assert (le.argmax(-1) == lq.argmax(-1))[confident].all()

    def test_bf16_params_follow_into_cache(self, model):
        """Satellite regression: no dtype argument + bf16 params must
        give a bf16 cache (the docstring's 'follow the weights'), not a
        silent f32 upcast."""
        from hetu_tpu.serving import ServingEngine
        p, cfg = model
        pbf = {k: jnp.asarray(np.asarray(v), jnp.bfloat16)
               for k, v in p.items()}
        eng = ServingEngine(pbf, cfg, slots=2, fast_path=False)
        assert eng.kv.cache_k.dtype == jnp.bfloat16
        assert eng.params[f"qg_wte_table"].dtype == jnp.bfloat16

    def test_default_off_cache_is_plain_f32(self, model):
        from hetu_tpu.serving import ServingEngine
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2)
        assert not isinstance(eng.kv.cache_k, tuple)
        assert eng.kv.cache_k.dtype == jnp.float32
        assert eng.kv.quant is None
