"""Continuous-batching serving engine (hetu_tpu/serving): the
iteration-level scheduler, slot-structured KV cache, masking
correctness, and backpressure — each pinned separately.

The load-bearing contract: engine outputs are a pure function of each
Request (prompt, seed, settings) — token-identical to offline
``generate_fast`` for greedy, identical across arrival orders and slot
assignments for sampling — while short requests leave the batch early
and new ones take their slots between waves.

Weights are a deterministic random GPT parameter dict (the engine's
contract is numeric parity, not model quality), so the whole file runs
in seconds; it is part of the ``smoke`` battery.
"""

import json
import os

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast, tp_shard_params
from hetu_tpu.serving import (
    QueueFull, Request, ServingEngine, ServingMetrics,
)


def _rand_gpt(name="sv", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    """Deterministic random params in generate_fast's naming contract."""
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
    cfg = GPTConfig(vocab_size=V, hidden_size=hd, num_hidden_layers=L,
                    num_attention_heads=H, max_position_embeddings=S,
                    batch_size=1, seq_len=S, dropout_rate=0.0)
    return p, cfg


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


@pytest.mark.smoke
class TestEngineParity:
    def test_greedy_matches_generate_fast_any_order(self, model):
        """Acceptance: per-request engine output token-identical to the
        offline path, for mixed lengths, any arrival order, any slot."""
        p, cfg = model
        trace = [([7, 8, 9], 6), ([3, 4], 11), ([1, 2, 3, 4, 5], 4),
                 ([11], 7), ([20, 21, 22, 23], 9), ([40], 3)]
        want = {tuple(pr): generate_fast(p, cfg, [pr], num_tokens=n)[0]
                for pr, n in trace}
        for order, slots in [(trace, 2), (trace[::-1], 2), (trace, 4)]:
            eng = ServingEngine(p, cfg, slots=slots, queue_limit=16)
            reqs = [Request(prompt=pr, max_new_tokens=n)
                    for pr, n in order]
            res = eng.run(reqs)
            assert len(res) == len(reqs)
            for r in reqs:
                got = res[r.request_id]
                assert got.finish_reason == "length"
                assert got.tokens.tolist() == \
                    want[tuple(r.prompt)].tolist()

    def test_eos_stops_engine_and_matches_offline(self, model):
        """EOS retirement: the engine emits the EOS then frees the slot;
        tokens equal the offline eos_id run up to the EOS (offline pads
        the remainder of its fixed span)."""
        p, cfg = model
        prompt, n = [7, 8, 9], 8
        plain = generate_fast(p, cfg, [prompt], num_tokens=n)[0]
        eos = int(plain[len(prompt)])     # first generated token
        off = generate_fast(p, cfg, [prompt], num_tokens=n, eos_id=eos,
                            pad_id=0)[0]
        eng = ServingEngine(p, cfg, slots=2)
        res = eng.run([Request(prompt=prompt, max_new_tokens=n,
                               eos_id=eos)])
        got = next(iter(res.values()))
        assert got.finish_reason == "eos"
        assert got.tokens[-1] == eos
        k = len(got.tokens)
        assert got.tokens.tolist() == off[:k].tolist()
        assert (off[k:] == 0).all()       # offline padded the tail

    def test_sampling_deterministic_across_arrival_orders(self, model):
        """Per-request rng streams + traced per-slot settings: sampled
        outputs identical no matter the submission order or slot."""
        p, cfg = model
        spec = [([3, 4], 0.9, 5, 11), ([7, 8, 9], 0.7, 3, 22),
                ([11], 1.1, 0, 33), ([5, 6], 0.8, 4, 44)]

        def run(order, slots):
            eng = ServingEngine(p, cfg, slots=slots, queue_limit=16)
            reqs = [Request(prompt=pr, max_new_tokens=6, temperature=t,
                            top_k=k, seed=s) for pr, t, k, s in order]
            res = eng.run(reqs)
            return {tuple(r.prompt): res[r.request_id].tokens.tolist()
                    for r in reqs}

        a = run(spec, 2)
        b = run(spec[::-1], 2)
        c = run(spec[1:] + spec[:1], 4)
        assert a == b == c

    def test_streaming_callback_order(self, model):
        p, cfg = model
        seen = []
        eng = ServingEngine(p, cfg, slots=2)
        req = Request(prompt=[7, 8, 9], max_new_tokens=5,
                      stream_cb=lambda r, t: seen.append((r.request_id, t)))
        res = eng.run([req])
        got = res[req.request_id]
        assert [t for _, t in seen] == got.generated
        assert all(rid == req.request_id for rid, _ in seen)

    def test_bf16_cache_composes(self, model):
        """dtype=bfloat16 halves weights AND the slot cache; greedy
        outputs match the offline bf16 path token-for-token."""
        import jax.numpy as jnp
        p, cfg = model
        want = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=6,
                             dtype=jnp.bfloat16)[0]
        eng = ServingEngine(p, cfg, slots=2, dtype=jnp.bfloat16)
        assert eng.kv.cache_k.dtype == jnp.bfloat16
        res = eng.run([Request(prompt=[7, 8, 9], max_new_tokens=6)])
        got = next(iter(res.values()))
        assert got.tokens.tolist() == want.tolist()

    def test_tp_sharded_params_compose(self, model):
        """tp_shard_params placements survive into the fused serving
        step (GSPMD propagates the Megatron split through the per-slot
        scatter + attention); outputs identical to unsharded."""
        from hetu_tpu.parallel.mesh import make_mesh
        p, cfg = _rand_gpt(name="tps", H=4, Dh=8)
        base = ServingEngine(p, cfg, slots=2).run(
            [Request(prompt=[7, 8, 9], max_new_tokens=6),
             Request(prompt=[3, 4], max_new_tokens=8)])
        mesh = make_mesh({"tp": 4})
        sharded = tp_shard_params(p, mesh, cfg)
        res = ServingEngine(sharded, cfg, slots=2).run(
            [Request(prompt=[7, 8, 9], max_new_tokens=6),
             Request(prompt=[3, 4], max_new_tokens=8)])
        assert sorted(r.tokens.tolist() for r in base.values()) == \
            sorted(r.tokens.tolist() for r in res.values())


@pytest.mark.smoke
class TestSchedulerEdgeCases:
    def test_queue_full_backpressure(self, model):
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=1, queue_limit=2)
        a = eng.submit(Request(prompt=[1], max_new_tokens=2))
        b = eng.submit(Request(prompt=[2], max_new_tokens=2))
        with pytest.raises(QueueFull):
            eng.submit(Request(prompt=[3], max_new_tokens=2))
        assert eng.metrics.rejected == 1
        # draining re-opens admission; everything accepted completes
        while eng.pending:
            eng.step()
        c = eng.submit(Request(prompt=[3], max_new_tokens=2))
        out = eng.run()
        assert set(out) == {c.request_id}
        assert eng.metrics.finished == 3
        # an impossible request is rejected outright, not queued
        with pytest.raises(ValueError):
            eng.submit(Request(prompt=[1] * 30, max_new_tokens=10))

    def test_same_length_degenerates_to_static_batching(self, model):
        """All requests the same shape, submitted together: one
        admission, ONE wave for the four prompts, full batch every
        step, one retirement wave — exactly static batching."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=4, queue_limit=8)
        reqs = [Request(prompt=[i + 1, i + 2, i + 3], max_new_tokens=6)
                for i in range(4)]
        res = eng.run(reqs)
        assert len(res) == 4
        snap = eng.metrics.snapshot()
        assert snap["mean_batch_occupancy"] == 1.0
        # the burst's prompts are one wave, which emits token 1; the
        # remaining 5 come from 5 decode waves
        assert eng.steps == 6 and eng.prefill_dispatches == 1
        steps = [e for e in eng.metrics.events
                 if e["event"] == "serve_step"]
        assert [e["q_prefill"] for e in steps] == [12, 0, 0, 0, 0, 0]
        assert [e["q_decode"] for e in steps] == [0, 4, 4, 4, 4, 4]
        assert eng.kv.total_allocs == 4   # no slot ever recycled

    def test_long_straggler_slots_cycle(self, model):
        """One long request pins a slot while short ones cycle through
        the other: iteration-level retirement admits mid-flight."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, queue_limit=16)
        straggler = Request(prompt=[1], max_new_tokens=14)
        shorts = [Request(prompt=[7, 8], max_new_tokens=2)
                  for _ in range(5)]
        res = eng.run([straggler] + shorts)
        assert len(res) == 6
        assert res[straggler.request_id].n_generated == 14
        # every short rode the straggler's window through recycled slots
        assert eng.kv.total_allocs == 6
        snap = eng.metrics.snapshot()
        assert snap["mean_batch_occupancy"] > 0.6
        # engine outputs still match offline per-request
        want = generate_fast(p, cfg, [straggler.prompt],
                             num_tokens=14)[0]
        assert res[straggler.request_id].tokens.tolist() == want.tolist()

    def test_short_circuit_finish_at_prefill(self, model):
        """max_new_tokens=1 (or instant EOS) retires in the wave that
        wrote its prompt — the slot is free when the step that lands
        that wave returns (it launches nothing: the caller fills the
        slot first), and the next step admits the request behind it."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=1)
        a = eng.submit(Request(prompt=[7, 8, 9], max_new_tokens=1))
        b = eng.submit(Request(prompt=[3, 4], max_new_tokens=1))
        assert eng.step() == [] and eng.kv.live()    # a's wave flies
        first = eng.step()
        assert [r.request_id for r in first] == [a.request_id]
        assert not eng.kv.live() and eng.queue_depth == 1
        assert eng.step() == [] and eng.queue_depth == 0
        assert [r.request_id for r in eng.step()] == [b.request_id]
        assert all(r.n_generated == 1 for r in first)
        # two prompt waves and never a decode row
        assert eng.steps == 2 and eng.prefill_dispatches == 2
        assert not any(e["q_decode"] for e in eng.metrics.events
                       if e["event"] == "serve_step")
        assert eng.kv.total_allocs == 2


@pytest.mark.smoke
class TestServingMetrics:
    def test_jsonl_events_follow_launcher_convention(self, model,
                                                     tmp_path):
        p, cfg = model
        log = str(tmp_path / "serve.jsonl")
        eng = ServingEngine(p, cfg, slots=2, log_path=log)
        eng.run([Request(prompt=[7, 8], max_new_tokens=3),
                 Request(prompt=[9], max_new_tokens=4)])
        with open(log) as f:
            recs = [json.loads(line) for line in f]
        kinds = [r["event"] for r in recs]
        assert kinds.count("serve_submit") == 2
        assert kinds.count("serve_admit") == 2
        assert kinds.count("serve_finish") == 2
        # the launcher's record shape: numeric epoch "t" + "event"
        assert all(isinstance(r["t"], float) and "event" in r
                   for r in recs)
        fin = [r for r in recs if r["event"] == "serve_finish"]
        assert {r["reason"] for r in fin} == {"length"}

    def test_snapshot_aggregates(self, model):
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2)
        eng.run([Request(prompt=[7, 8], max_new_tokens=4),
                 Request(prompt=[9], max_new_tokens=6)])
        s = eng.metrics.snapshot()
        assert s["requests_finished"] == 2
        assert s["tokens_generated"] == 10
        assert s["tokens_per_sec"] > 0
        assert s["ttft_p50_s"] is not None \
            and s["ttft_p99_s"] >= s["ttft_p50_s"]
        assert 0 < s["mean_batch_occupancy"] <= 1.0
        assert s["steps"] == eng.steps

    def test_env_log_path(self, model, tmp_path, monkeypatch):
        log = str(tmp_path / "env.jsonl")
        monkeypatch.setenv("HETU_SERVE_LOG", log)
        m = ServingMetrics()
        m.record_submit("r", 1)
        assert os.path.exists(log)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_default_is_one_path(model, monkeypatch, backend):
    """``ServingEngine(params, cfg)`` is the same scheduler over the same
    manager on every backend: the mixed wave over the paged pool of
    block 16, chosen by no argument.  The one thing that follows the
    platform is which code scores the wave: the Pallas kernel on a TPU,
    the masked reference elsewhere."""
    import inspect

    import jax
    from hetu_tpu.models.gpt_decode import _resolve_fast
    from hetu_tpu.serving.kv_manager import (PagedKVManager,
                                             resolve_kv_block)
    for knob in ("HETU_KV_BLOCK", "HETU_SERVE_FAST"):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_kv_block() == 16
    assert _resolve_fast() is (backend == "tpu")
    assert "ragged" not in inspect.signature(
        ServingEngine.__init__).parameters
    p, cfg = model
    eng = ServingEngine(p, cfg)
    assert isinstance(eng.kv, PagedKVManager) and eng.kv.block == 16
    assert eng.paged and eng.fast_path is (backend == "tpu")
    assert eng._mixed.keywords["attn"] == (
        "ragged" if backend == "tpu" else "masked")
    waves = []
    monkeypatch.setattr(
        ServingEngine, "_launch",
        lambda self, root, ahead: waves.append(ahead))
    assert eng.step() == [] and waves == [False]
