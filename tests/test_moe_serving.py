"""MoE expert-parallel serving (ISSUE 20): the flagship MoE GPT decodes
through the ONE compiled core.

The load-bearing contracts:

- **Identity**: an ``MoEDecodeConfig`` model decodes TOKEN-IDENTICALLY
  through ServingEngine and offline ``generate_fast`` across every
  cache configuration — the block-table pool (ref + fast),
  int8-quantized KV, speculative (draft skips routing), ragged mixed
  wave, and chunked prefill.
- **Dense oracle**: ``top_k == num_experts`` at non-binding capacity
  with replicated experts (``convert_dense_to_moe``) reproduces the
  dense model's greedy stream exactly — raw softmax combine weights
  sum to 1, so any gate renormalization bug breaks this test.
- **Attribution**: routed + dropped == wave tokens x top_k x MoE
  layers, per serve_step record — enforced live by the engine counters
  and offline by ``hetu_trace --check``.
- **Static rejection**: a malformed expert mesh (axis missing, E not
  divisible) and a broken dispatch/combine a2a pairing fail in
  ``analysis.shard_check`` before any compile.
- **EP parity**: the explicit shard_map + lax.all_to_all reference
  formulation matches the local ``moe_ffn`` at non-binding capacity,
  with and without the int8 wire (``HETU_MOE_QUANT``).
"""

import json

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.models.moe_decode import (
    MoEDecodeConfig, MoESpec, convert_dense_to_moe, ep_shard_params,
    init_moe_params, moe_capacity, moe_ffn, moe_ffn_ep_reference,
    moe_spec_of,
)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.analysis.shard_check import (
    ShardCheckError, check_expert_alltoall, check_expert_mesh,
)


PROMPTS = [[5, 9, 2], [7, 1, 4, 3, 8], [11, 6]]
MAX_NEW = 8


def _moe_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=32, num_hidden_layers=4,
                num_attention_heads=2, ffn_mult=2, seq_len=48,
                dropout_rate=0.0, max_position_embeddings=48,
                num_experts=4, top_k=2, capacity_factor=2.0, moe_every=2)
    base.update(kw)
    return MoEDecodeConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _moe_cfg()
    params = init_moe_params(cfg, name="moe", seed=0)
    return params, cfg


@pytest.fixture(scope="module")
def offline_ref(model):
    params, cfg = model
    ref = {}
    for i, p in enumerate(PROMPTS):
        toks = generate_fast(params, cfg, [p], MAX_NEW,
                             temperature=0.0, seed=0, name="moe")
        ref[i] = [int(t) for t in np.asarray(toks)[0][len(p):]]
    return ref


def _dense_params(rng, name, L, D, F, V, S):
    p = {f"{name}_wte_table": rng.randn(V, D).astype(np.float32) * 0.05,
         f"{name}_wpe": rng.randn(S, D).astype(np.float32) * 0.05,
         f"{name}_ln_f_scale": np.ones(D, np.float32),
         f"{name}_ln_f_bias": np.zeros(D, np.float32)}
    for i in range(L):
        us = f"{name}_h{i}"
        for w, shp in [("attn_q", (D, D)), ("attn_k", (D, D)),
                       ("attn_v", (D, D)), ("attn_proj", (D, D)),
                       ("ffn_wi", (D, F)), ("ffn_wo", (F, D))]:
            p[f"{us}_{w}_weight"] = \
                rng.randn(*shp).astype(np.float32) * 0.05
            p[f"{us}_{w}_bias"] = np.zeros(shp[1], np.float32)
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = np.ones(D, np.float32)
            p[f"{us}_{ln}_bias"] = np.zeros(D, np.float32)
    return p


def _mk(n=len(PROMPTS)):
    return [Request(request_id=str(i), prompt=PROMPTS[i],
                    max_new_tokens=MAX_NEW, temperature=0.0, seed=0)
            for i in range(n)]


def _run_engine(params, cfg, **kw):
    eng = ServingEngine(params, cfg, slots=4, name="moe", **kw)
    out = eng.run(_mk())
    got = {int(i): [int(t) for t in
                    np.asarray(r.tokens)[r.prompt_len:]]
           for i, r in out.items()}
    return eng, got


ENGINE_MATRIX = [
    ("paged", dict(fast_path=True)),
    ("paged_int8", dict(fast_path=True, kv_quant="int8")),
    ("spec", dict(fast_path=True, spec=2)),
    ("paged_ref", dict(fast_path=False)),
    ("paged_chunked", dict(fast_path=True, prefill_chunk=2)),
]


class TestEngineIdentity:
    @pytest.mark.parametrize("label,kw", ENGINE_MATRIX,
                             ids=[m[0] for m in ENGINE_MATRIX])
    def test_engine_matches_offline(self, model, offline_ref, label, kw):
        params, cfg = model
        eng, got = _run_engine(params, cfg, **kw)
        assert got == offline_ref, label
        # MoE accounting closed THE invariant: every valid token was
        # either granted an expert slot or dropped, k slots per token
        # per MoE layer (draft proposals route nothing)
        n_moe = moe_spec_of(cfg).moe_layers(cfg.num_hidden_layers)
        total = int(eng.expert_load.sum() + eng.expert_drops.sum())
        assert total == eng.moe_tokens * cfg.top_k * n_moe
        assert eng.moe_tokens > 0
        assert eng.expert_imbalance is not None
        assert eng.expert_drop_rate is not None

    def test_dense_engine_has_no_moe_counters(self):
        cfg = GPTConfig(vocab_size=61, hidden_size=16,
                        num_hidden_layers=2, num_attention_heads=2,
                        max_position_embeddings=32, batch_size=1,
                        seq_len=32, dropout_rate=0.0)
        params = _dense_params(np.random.RandomState(0), "dn", L=2,
                               D=16, F=64, V=61, S=32)
        eng = ServingEngine(params, cfg, slots=2, name="dn")
        assert eng.moe is None
        assert eng.expert_imbalance is None
        assert eng.expert_drop_rate is None


class TestDenseOracle:
    def test_k_equals_E_replicated_experts_reproduce_dense(self):
        """convert_dense_to_moe + top_k == num_experts at non-binding
        capacity is the dense model bit-for-bit (greedy)."""
        dense_cfg = GPTConfig(vocab_size=97, hidden_size=32,
                              num_hidden_layers=2,
                              num_attention_heads=2, ffn_mult=2,
                              max_position_embeddings=48, batch_size=1,
                              seq_len=48, dropout_rate=0.0)
        p = _dense_params(np.random.RandomState(1), "or", L=2, D=32,
                          F=64, V=97, S=48)
        moe_cfg = _moe_cfg(num_hidden_layers=2, num_experts=4, top_k=4,
                           capacity_factor=8.0, moe_every=1)
        mp = convert_dense_to_moe(p, dense_cfg, moe_cfg, name="or")

        for prompt in PROMPTS:
            want = generate_fast(p, dense_cfg, [prompt], MAX_NEW,
                                 temperature=0.0, seed=0, name="or")
            got = generate_fast(mp, moe_cfg, [prompt], MAX_NEW,
                                temperature=0.0, seed=0, name="or")
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got))

    def test_moe_ffn_dense_oracle_direct(self):
        """The FFN function itself: replicated experts + k=E at
        non-binding capacity == plain dense gelu FFN numerically."""
        rng = np.random.RandomState(2)
        D, F, E, T = 16, 32, 4, 12
        wi = rng.randn(D, F).astype(np.float32) * 0.1
        wo = rng.randn(F, D).astype(np.float32) * 0.1
        bi = rng.randn(F).astype(np.float32) * 0.1
        bo = rng.randn(D).astype(np.float32) * 0.1
        params = {
            "m_h0_moe_gate_weight": np.zeros((D, E), np.float32),
            "m_h0_moe_expert_stack_w1": np.broadcast_to(
                wi, (E, D, F)).copy(),
            "m_h0_moe_expert_stack_b1": np.broadcast_to(
                bi, (E, F)).copy(),
            "m_h0_moe_expert_stack_w2": np.broadcast_to(
                wo, (E, F, D)).copy(),
            "m_h0_moe_expert_stack_b2": np.broadcast_to(
                bo, (E, D)).copy(),
        }
        spec = MoESpec(num_experts=E, top_k=E, capacity_factor=8.0,
                       moe_every=1)
        x = rng.randn(T, D).astype(np.float32)
        y = moe_ffn(params, "m_h0", jnp.asarray(x), spec)
        from hetu_tpu.models.moe_decode import _gelu_tanh
        want = _gelu_tanh(x @ wi + bi) @ wo + bo
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=2e-5)

    def test_capacity_binding_drops_to_residual(self):
        """A token past capacity contributes EXACTLY zero (residual
        carries), and load+drop accounts for every (token, rank)."""
        rng = np.random.RandomState(3)
        D, F, E, T = 16, 32, 4, 16
        params = {
            "m_h0_moe_gate_weight":
                rng.randn(D, E).astype(np.float32) * 5.0,
            "m_h0_moe_expert_stack_w1":
                rng.randn(E, D, F).astype(np.float32) * 0.1,
            "m_h0_moe_expert_stack_w2":
                rng.randn(E, F, D).astype(np.float32) * 0.1,
        }
        x = rng.randn(T, D).astype(np.float32)
        # cap tiny: cf such that capacity binds hard
        spec = MoESpec(num_experts=E, top_k=1, capacity_factor=0.25,
                       moe_every=1)
        cap = moe_capacity(spec, T)
        stats = {}
        y = np.asarray(moe_ffn(params, "m_h0", jnp.asarray(x), spec,
                               stats=stats))
        load = np.asarray(stats["load"])
        drop = np.asarray(stats["drop"])
        assert int(load.sum() + drop.sum()) == T * spec.top_k
        assert np.all(load <= cap)
        assert int(drop.sum()) > 0  # the fixture actually binds
        # recompute who got dropped, assert their output rows are 0
        gates = np.asarray(jax.nn.softmax(
            x @ params["m_h0_moe_gate_weight"], axis=-1))
        top1 = gates.argmax(1)
        arrival = np.zeros(E, int)
        for t in range(T):
            e = top1[t]
            if arrival[e] >= cap:
                np.testing.assert_allclose(y[t], 0.0, atol=1e-7)
            arrival[e] += 1

    def test_valid_mask_excludes_rows_from_capacity(self):
        """An invalid row neither routes nor claims a slot a valid
        token needed (batch-company independence)."""
        rng = np.random.RandomState(4)
        D, F, E, T = 16, 32, 4, 8
        params = {
            "m_h0_moe_gate_weight":
                rng.randn(D, E).astype(np.float32),
            "m_h0_moe_expert_stack_w1":
                rng.randn(E, D, F).astype(np.float32) * 0.1,
            "m_h0_moe_expert_stack_w2":
                rng.randn(E, F, D).astype(np.float32) * 0.1,
        }
        spec = MoESpec(num_experts=E, top_k=2, capacity_factor=8.0,
                       moe_every=1)
        x = rng.randn(T, D).astype(np.float32)
        valid = np.ones(T, bool)
        valid[T // 2:] = False
        stats = {}
        y = np.asarray(moe_ffn(params, "m_h0", jnp.asarray(x), spec,
                               valid=jnp.asarray(valid), stats=stats))
        # invalid rows produce exactly zero and claim zero slots
        np.testing.assert_allclose(y[T // 2:], 0.0, atol=1e-7)
        assert int(np.asarray(stats["load"]).sum()
                   + np.asarray(stats["drop"]).sum()) == \
            (T // 2) * spec.top_k
        # valid rows equal the all-valid run's rows (no interference)
        y_full = np.asarray(moe_ffn(params, "m_h0",
                                    jnp.asarray(x[:T // 2]), spec))
        np.testing.assert_allclose(y[:T // 2], y_full, atol=1e-5)


class TestTraceAttribution:
    def _trace(self, model, tmp_path, **kw):
        params, cfg = model
        log = str(tmp_path / "moe.jsonl")
        eng = ServingEngine(params, cfg, slots=4, name="moe",
                            log_path=log, **kw)
        eng.run(_mk())
        with open(log) as f:
            return log, [json.loads(ln) for ln in f]

    def test_green_stream_passes_check(self, model, tmp_path):
        from hetu_tpu.telemetry import trace as trace_mod
        log, recs = self._trace(model, tmp_path, fast_path=True)
        steps = [r for r in recs if r.get("event") == "serve_step"
                 and "moe_routed" in r]
        assert steps, "serve_step records must carry MoE attribution"
        for r in steps:
            assert r["moe_routed"] + r["moe_dropped"] == \
                r["moe_tokens"] * r["moe_k"] * r["moe_layers"]
        assert trace_mod.main([log, "--check"]) == 0
        assert trace_mod.check_moe_attribution(recs) == []

    def test_spec_stream_passes_check(self, model, tmp_path):
        from hetu_tpu.telemetry import trace as trace_mod
        log, recs = self._trace(model, tmp_path, fast_path=True,
                                spec=2)
        assert trace_mod.main([log, "--check"]) == 0

    def test_tampered_step_flagged(self, model, tmp_path):
        from hetu_tpu.telemetry import trace as trace_mod
        _, recs = self._trace(model, tmp_path, fast_path=True)
        step = next(r for r in recs if r.get("event") == "serve_step"
                    and "moe_routed" in r)
        bad = dict(step)
        bad["moe_routed"] = bad["moe_routed"] + 7
        problems = trace_mod.check_moe_attribution(recs + [bad])
        assert len(problems) == 1

    def test_dense_steps_exempt(self):
        from hetu_tpu.telemetry import trace as trace_mod
        assert trace_mod.check_moe_attribution(
            [{"event": "serve_step", "t": 0.0, "batch": 2,
              "new_tokens": 2}]) == []

    def test_malformed_companions_flagged(self):
        from hetu_tpu.telemetry import trace as trace_mod
        rec = {"event": "serve_step", "t": 0.0, "batch": 1,
               "new_tokens": 1, "moe_routed": 4,
               "moe_dropped": "zero", "moe_tokens": 2, "moe_k": 2,
               "moe_layers": 1}
        assert len(trace_mod.check_moe_attribution([rec])) == 1


class TestShardCheckExpertMesh:
    def test_valid_mesh_accepted(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        assert check_expert_mesh(mesh, 4, "ep") == 4
        assert check_expert_mesh(mesh, 8, "ep") == 4

    def test_indivisible_experts_rejected(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        with pytest.raises(ShardCheckError) as e:
            check_expert_mesh(mesh, 3, "ep")
        assert e.value.kind == "expert_mesh"

    def test_missing_axis_rejected(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        with pytest.raises(ShardCheckError) as e:
            check_expert_mesh(mesh, 4, "ep")
        assert e.value.kind == "expert_mesh"

    def test_no_mesh_rejected(self):
        with pytest.raises(ShardCheckError) as e:
            check_expert_mesh(None, 4, "ep")
        assert e.value.kind == "expert_mesh"

    def test_ep_shard_params_rejects_bad_mesh_before_placement(self):
        cfg = _moe_cfg(num_experts=3)
        params = init_moe_params(cfg, name="moe", seed=0)
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        with pytest.raises(ShardCheckError):
            ep_shard_params(params, mesh, cfg, axis="ep", name="moe")


class TestShardCheckA2APairing:
    """Graph fixtures for check_expert_alltoall — the quant-pair
    analog: dispatch without combine / odd exchange chain / mixed axes
    all fail statically with kind='a2a_pair'."""

    E, CAP, T, D = 4, 4, 8, 8

    def _gate_feeds(self):
        idx = ht.graph.ops_misc.Variable(
            "a2a_idx", value=(np.arange(self.T) % self.E)
            .astype(np.float32).reshape(-1, 1), trainable=False)
        loc = ht.graph.ops_misc.Variable(
            "a2a_loc", value=(np.arange(self.T) // self.E)
            .astype(np.float32), trainable=False)
        gts = ht.graph.ops_misc.Variable(
            "a2a_gts", value=np.ones(self.T, np.float32),
            trainable=False)
        return idx, loc, gts

    def _dispatch(self, x):
        from hetu_tpu.graph.ops_moe import layout_transform_op
        idx, loc, _ = self._gate_feeds()
        return layout_transform_op(x, [idx], [loc], self.CAP, self.E)

    def test_green_full_span(self):
        from hetu_tpu.graph.ops_moe import (
            alltoall_op, reverse_layout_transform_op)
        x = ht.placeholder_op("x")
        d = self._dispatch(x)
        a1 = alltoall_op(d, axis="ep")
        a2 = alltoall_op(a1, axis="ep")
        idx, loc, gts = self._gate_feeds()
        c = reverse_layout_transform_op(a2, [idx], [loc], [gts],
                                        self.CAP, self.E)
        spans = check_expert_alltoall([c])
        assert len(spans) == 1
        assert len(spans[0][1]) == 2

    def test_green_layer_graph(self):
        """The real MoELayer graph (gate + dispatch + a2a + combine)
        is a green fixture end to end."""
        gate = ht.layers.TopKGate(self.D, self.T, self.E, k=1,
                                  capacity_factor=2.0)
        experts = ht.layers.StackedExperts(self.E, self.D, 16,
                                           activation="relu")
        moe = ht.layers.MoELayer(gate=gate, experts=experts,
                                 num_tokens=self.T, embed_dim=self.D)
        out, l_aux = moe(ht.placeholder_op("x"))
        check_expert_alltoall([out, l_aux])

    def test_uncombined_dispatch_rejected(self):
        x = ht.placeholder_op("x")
        d = self._dispatch(x)
        y = ht.reduce_mean_op(d, axes=0)
        with pytest.raises(ShardCheckError) as e:
            check_expert_alltoall([y])
        assert e.value.kind == "a2a_pair"

    def test_odd_exchange_chain_rejected(self):
        from hetu_tpu.graph.ops_moe import (
            alltoall_op, reverse_layout_transform_op)
        x = ht.placeholder_op("x")
        d = self._dispatch(x)
        a1 = alltoall_op(d, axis="ep")
        idx, loc, gts = self._gate_feeds()
        c = reverse_layout_transform_op(a1, [idx], [loc], [gts],
                                        self.CAP, self.E)
        with pytest.raises(ShardCheckError) as e:
            check_expert_alltoall([c])
        assert e.value.kind == "a2a_pair"

    def test_mixed_axes_rejected(self):
        from hetu_tpu.graph.ops_moe import (
            alltoall_op, reverse_layout_transform_op)
        x = ht.placeholder_op("x")
        d = self._dispatch(x)
        a1 = alltoall_op(d, axis="ep")
        a2 = alltoall_op(a1, axis="dp")
        idx, loc, gts = self._gate_feeds()
        c = reverse_layout_transform_op(a2, [idx], [loc], [gts],
                                        self.CAP, self.E)
        with pytest.raises(ShardCheckError) as e:
            check_expert_alltoall([c])
        assert e.value.kind == "a2a_pair"

    def test_orphan_combine_rejected(self):
        from hetu_tpu.graph.ops_moe import reverse_layout_transform_op
        x = ht.placeholder_op("x")
        idx, loc, gts = self._gate_feeds()
        c = reverse_layout_transform_op(x, [idx], [loc], [gts],
                                        self.CAP, self.E)
        with pytest.raises(ShardCheckError) as e:
            check_expert_alltoall([c])
        assert e.value.kind == "a2a_pair"


class TestTelemetryAndTop:
    def test_counters_and_top_sections(self, model, tmp_path):
        from hetu_tpu import telemetry
        from hetu_tpu.telemetry.top import (render, render_fleet,
                                            summarize, summarize_fleet)
        from hetu_tpu.telemetry.trace import read_events
        params, cfg = model
        telemetry.reset()
        log = str(tmp_path / "top.jsonl")
        eng = ServingEngine(params, cfg, slots=4, name="moe",
                            fast_path=True, log_path=log,
                            tags={"replica": 0})
        eng.run(_mk())
        snap = telemetry.snapshot()
        assert snap["counters"].get("serve.expert_load", 0) > 0
        assert "serve.expert_imbalance" in snap["gauges"]
        events, bad = read_events([log])
        assert bad == 0
        # the drop rate rides the wave's event (what hetu_top reads)
        steps = [e for e in events if e.get("event") == "serve_step"]
        assert steps and all("moe_drop_rate" in e for e in steps)
        s = summarize(events)
        assert s["moe"] is not None
        assert s["moe"]["routed"] == int(eng.expert_load.sum())
        assert s["moe"]["dropped"] == int(eng.expert_drops.sum())
        text = render(s)
        assert "experts" in text and "imbalance" in text
        fleet = summarize_fleet(events)
        row = fleet["replicas"][0]
        assert row["moe_routed"] == int(eng.expert_load.sum())
        assert row["moe_drop_rate"] is not None
        ftext = render_fleet(fleet)
        assert "imb" in ftext and "drop%" in ftext

    def test_dense_fleet_rows_render_dashes(self, tmp_path):
        from hetu_tpu.telemetry.top import render_fleet, summarize_fleet
        fleet = summarize_fleet([
            {"event": "serve_step", "t": 0.0, "batch": 1,
             "new_tokens": 1, "replica": 0}])
        assert "-" in render_fleet(fleet)

    def test_validate_serving_rejects_missing_expert_stack(self, model):
        from hetu_tpu.analysis import validate_serving
        from hetu_tpu.analysis.verify import GraphVerifyError
        params, cfg = model
        bad = dict(params)
        bad.pop("moe_h1_moe_expert_stack_w1")
        with pytest.raises(GraphVerifyError):
            validate_serving(bad, cfg, "moe")

    def test_validate_serving_rejects_wrong_expert_count(self, model):
        """The corrupt rolling-swap payload: a per-expert leaf whose
        leading dim disagrees with config.num_experts."""
        from hetu_tpu.analysis import validate_serving
        from hetu_tpu.analysis.verify import GraphVerifyError
        params, cfg = model
        bad = dict(params)
        bad["moe_h1_moe_expert_stack_w1"] = \
            bad["moe_h1_moe_expert_stack_w1"][:2]
        with pytest.raises(GraphVerifyError):
            validate_serving(bad, cfg, "moe")


class TestExpertParallel:
    CF_UNBINDING = 8.0

    def _fixture(self):
        cfg = _moe_cfg(num_hidden_layers=2, seq_len=32,
                       max_position_embeddings=32,
                       capacity_factor=self.CF_UNBINDING)
        params = init_moe_params(cfg, name="moe", seed=0)
        spec = moe_spec_of(cfg)
        mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
        x = np.random.RandomState(0).randn(16, 32).astype(np.float32)
        return cfg, params, spec, mesh, x

    def test_ep_reference_matches_local(self):
        cfg, params, spec, mesh, x = self._fixture()
        y_local = moe_ffn(params, "moe_h1", jnp.asarray(x), spec)
        placed = ep_shard_params(params, mesh, cfg, axis="ep",
                                 name="moe")
        y_ep = moe_ffn_ep_reference(placed, "moe_h1", jnp.asarray(x),
                                    spec, mesh)
        np.testing.assert_allclose(np.asarray(y_local),
                                   np.asarray(y_ep), atol=1e-4)

    def test_int8_wire_within_quant_tolerance(self):
        cfg, params, spec, mesh, x = self._fixture()
        y_local = moe_ffn(params, "moe_h1", jnp.asarray(x), spec)
        placed = ep_shard_params(params, mesh, cfg, axis="ep",
                                 name="moe")
        y_q = moe_ffn_ep_reference(placed, "moe_h1", jnp.asarray(x),
                                   spec, mesh, quant="int8")
        assert float(jnp.max(jnp.abs(y_local - y_q))) < 0.2

    def test_expert_stacks_actually_sharded(self):
        cfg, params, _, mesh, _ = self._fixture()
        placed = ep_shard_params(params, mesh, cfg, axis="ep",
                                 name="moe")
        w1 = placed["moe_h1_moe_expert_stack_w1"]
        shard_shapes = {s.data.shape for s in w1.addressable_shards}
        E, D, F = w1.shape
        assert shard_shapes == {(E // 4, D, F)}
        # gate replicates
        gw = placed["moe_h1_moe_gate_weight"]
        assert {s.data.shape for s in gw.addressable_shards} == \
            {tuple(gw.shape)}


class TestSwapAndSpec:
    def test_draft_spec_skips_routing(self, model):
        params, cfg = model
        spec = moe_spec_of(cfg, draft=True)
        assert spec.draft is True
        eng = ServingEngine(params, cfg, slots=4, name="moe",
                            fast_path=True, spec=2)
        assert eng.cfg_tuple_draft[-1].draft is True
        assert eng.cfg_tuple[-1].draft is False

    def test_capacity_env_override(self, model, monkeypatch):
        from hetu_tpu.models.moe_decode import resolve_moe_capacity
        monkeypatch.setenv("HETU_MOE_CAPACITY", "3.5")
        assert resolve_moe_capacity() == 3.5
        _, cfg = model
        assert moe_spec_of(cfg).capacity_factor == 3.5
        monkeypatch.setenv("HETU_MOE_CAPACITY", "")
        assert moe_spec_of(cfg).capacity_factor == \
            cfg.capacity_factor

    def test_version_stamped_swap_covers_expert_leaves(self, model,
                                                       offline_ref):
        """PR 15 rolling swap: a full-dict swap with identical values
        but bumped version keeps decoding identically, and the swap
        validates per-expert leaf shapes."""
        params, cfg = model
        eng = ServingEngine(params, cfg, slots=4, name="moe",
                            fast_path=True)
        if not hasattr(eng, "swap_params"):
            pytest.skip("engine has no swap_params")
        eng.swap_params({k: np.asarray(v) for k, v in params.items()})
        out = eng.run(_mk())
        got = {int(i): [int(t) for t in
                        np.asarray(r.tokens)[r.prompt_len:]]
               for i, r in out.items()}
        assert got == offline_ref
