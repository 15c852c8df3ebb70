"""Ragged KV serving fast path: flash prefill + the ragged kernel
(its own parity suite is tests/test_ragged_kernel.py), pinned against
the masked/scan reference in interpret mode.

The load-bearing contracts, each tested separately:
- prefill parity: one batched flash-prefill dispatch writes the same
  cache rows and samples the same first token as the teacher-forced
  per-request scan;
- end-to-end greedy parity: engine outputs with ``fast_path=True`` are
  token-identical to the masked reference path (and to offline
  ``generate_fast`` on both of ITS prefill modes) for mixed lengths,
  bf16, and tensor-parallel params;
- batched admission: a burst of k same-bucket arrivals costs ONE
  jitted prefill dispatch on the fast path (k on the reference).

Everything runs on the forced 8-device CPU mesh via interpret mode —
``smoke`` tier.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import (
    _resolve_fast, generate_fast, tp_shard_params,
)
from hetu_tpu.serving import Request, ServingEngine


def _rand_gpt(name="fp", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    """Deterministic random params in generate_fast's naming contract
    (mirrors test_serving's helper; kept local so the files stay
    independently runnable)."""
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
class TestFlashPrefillParity:
    """Batched flash prefill vs the teacher-forced scan prefill."""

    def test_cache_rows_and_first_token_match_scan(self, model):
        """The two prefill implementations must write numerically
        matching K/V into the slot rows and sample the same first
        token, across prompt lengths straddling bucket boundaries."""
        from hetu_tpu.models.gpt_decode import (
            _prep_param, serve_prefill_batch_fn, serve_prefill_fn,
        )
        from hetu_tpu.serving.kv_manager import _bucket_prompt
        p, cfg = model
        params = {k: _prep_param(v) for k, v in p.items()}
        Dh = cfg.hidden_size // cfg.num_attention_heads
        cfg_tuple = ("fp", cfg.num_hidden_layers,
                     cfg.num_attention_heads, Dh,
                     cfg.max_position_embeddings)
        scan = serve_prefill_fn(donate=False)
        flash = serve_prefill_batch_fn(donate=False)
        S = cfg.max_position_embeddings
        # the offline cores' contiguous cache pair, two slot rows
        cache = jnp.zeros((cfg.num_hidden_layers, 2, S,
                           cfg.num_attention_heads, Dh), jnp.float32)
        for P in (1, 3, 7, 8, 9, 16):
            pb = _bucket_prompt(P, S, S)
            prompt = np.arange(1, P + 1, dtype=np.int32) % 60
            padded = np.zeros(pb, np.int32)
            padded[:P] = prompt
            key = np.asarray(jax.random.PRNGKey(0), np.uint32)
            f_scan, ck_s, cv_s, _ = scan(
                params, cfg_tuple, cache, cache,
                np.int32(1), padded, np.int32(P),
                np.float32(0.0), np.int32(0), key)
            f_flash, ck_f, cv_f, _ = flash(
                params, cfg_tuple, cache, cache,
                np.asarray([1], np.int32), padded[None],
                np.asarray([P], np.int32),
                np.zeros(1, np.float32), np.zeros(1, np.int32),
                key[None])
            assert int(f_scan) == int(f_flash[0]), P
            # only the FILLED prefix of the slot row is contractual
            # (the scan skips pad positions, flash writes pad garbage
            # there — decode overwrites each before the mask admits it)
            np.testing.assert_allclose(
                np.asarray(ck_s[:, 1, :P]), np.asarray(ck_f[:, 1, :P]),
                rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(cv_s[:, 1, :P]), np.asarray(cv_f[:, 1, :P]),
                rtol=2e-5, atol=2e-5)

    def test_generate_fast_flash_equals_scan(self, model):
        """Offline unification: prefill="flash" greedy outputs are
        token-identical to the teacher-forced reference, eos included."""
        p, cfg = model
        for prompt, n in [([7, 8, 9], 6), ([3, 4], 11), ([11], 7),
                          ([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)]:
            a = generate_fast(p, cfg, [prompt], num_tokens=n,
                              prefill="scan")[0]
            b = generate_fast(p, cfg, [prompt], num_tokens=n,
                              prefill="flash")[0]
            assert a.tolist() == b.tolist(), prompt
        plain = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8,
                              prefill="scan")[0]
        eos = int(plain[3])
        a = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8, eos_id=eos,
                          prefill="scan")[0]
        b = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8, eos_id=eos,
                          prefill="flash")[0]
        assert a.tolist() == b.tolist()
        # num_tokens=1: the scan contributes nothing — prefill-only
        a = generate_fast(p, cfg, [[5, 6]], num_tokens=1,
                          prefill="flash")[0]
        b = generate_fast(p, cfg, [[5, 6]], num_tokens=1,
                          prefill="scan")[0]
        assert a.tolist() == b.tolist()

    def test_generate_fast_flash_bf16(self, model):
        p, cfg = model
        a = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=6,
                          dtype=jnp.bfloat16, prefill="scan")[0]
        b = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=6,
                          dtype=jnp.bfloat16, prefill="flash")[0]
        assert a.tolist() == b.tolist()


@pytest.mark.smoke
class TestEngineFastPathParity:
    """End-to-end: ragged fast-path engine vs masked reference engine."""

    TRACE = [([7, 8, 9], 6), ([3, 4], 11), ([1, 2, 3, 4, 5], 4),
             ([11], 7), ([20, 21, 22, 23], 9), ([40], 3),
             ([9, 8, 7, 6, 5, 4, 3, 2, 1], 5)]

    def _run(self, p, cfg, fast, slots=2, **kw):
        eng = ServingEngine(p, cfg, slots=slots, queue_limit=16,
                            fast_path=fast, **kw)
        reqs = [Request(prompt=pr, max_new_tokens=n)
                for pr, n in self.TRACE]
        res = eng.run(reqs)
        return eng, {tuple(r.prompt): res[r.request_id].tokens.tolist()
                     for r in reqs}

    def test_greedy_identical_to_masked_reference(self, model):
        """Acceptance: mixed-length greedy trace, fast == reference,
        token for token — at 2 slots (heavy recycling) and 4."""
        p, cfg = model
        _, ref = self._run(p, cfg, fast=False)
        for slots in (2, 4):
            _, fast = self._run(p, cfg, fast=True, slots=slots)
            assert fast == ref
        # and both match offline generate_fast on its reference path
        for pr, n in self.TRACE:
            want = generate_fast(p, cfg, [pr], num_tokens=n,
                                 prefill="scan")[0]
            assert ref[tuple(pr)] == want.tolist()

    def test_eos_and_sampling_on_fast_path(self, model):
        p, cfg = model
        plain = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8)[0]
        eos = int(plain[3])
        outs = []
        for fast in (False, True):
            eng = ServingEngine(p, cfg, slots=2, fast_path=fast)
            res = eng.run([Request(prompt=[7, 8, 9], max_new_tokens=8,
                                   eos_id=eos),
                           Request(prompt=[3, 4], max_new_tokens=6,
                                   temperature=0.9, top_k=5, seed=11)])
            outs.append(sorted(r.tokens.tolist() for r in res.values()))
            assert {r.finish_reason for r in res.values()} == \
                {"eos", "length"}
        assert outs[0] == outs[1]

    def test_bf16_fast_path(self, model):
        p, cfg = model
        _, ref = self._run(p, cfg, fast=False, dtype=jnp.bfloat16)
        _, fast = self._run(p, cfg, fast=True, dtype=jnp.bfloat16)
        assert fast == ref

    def test_tp_sharded_params_compose(self):
        """tp_shard_params + fast path: flash prefill and the ragged
        kernel run under GSPMD-placed weights (interpret mode) with
        outputs identical to the unsharded fast path."""
        from hetu_tpu.parallel.mesh import make_mesh
        p, cfg = _rand_gpt(name="fpt", H=4, Dh=8)
        reqs = lambda: [Request(prompt=[7, 8, 9], max_new_tokens=6),
                        Request(prompt=[3, 4], max_new_tokens=8)]
        base = ServingEngine(p, cfg, slots=2, fast_path=True).run(reqs())
        mesh = make_mesh({"tp": 4})
        sharded = tp_shard_params(p, mesh, cfg)
        res = ServingEngine(sharded, cfg, slots=2,
                            fast_path=True).run(reqs())
        assert sorted(r.tokens.tolist() for r in base.values()) == \
            sorted(r.tokens.tolist() for r in res.values())


@pytest.mark.smoke
class TestBatchedAdmission:
    def test_burst_costs_one_dispatch(self, model):
        """A burst of k arrivals is ONE wave — one dispatch for the k
        prompts, kernel and reference alike — with identical outputs."""
        p, cfg = model
        burst = [Request(prompt=[i + 1, i + 2, i + 3], max_new_tokens=4)
                 for i in range(4)]

        def run(fast):
            eng = ServingEngine(p, cfg, slots=4, queue_limit=8,
                                fast_path=fast)
            res = eng.run(burst if fast else [
                Request(prompt=r.prompt, max_new_tokens=4)
                for r in burst])
            return eng, sorted(r.tokens.tolist() for r in res.values())

        ref_eng, ref = run(False)
        fast_eng, fast = run(True)
        assert fast == ref
        for eng in (ref_eng, fast_eng):
            assert eng.prefill_dispatches == 1
            assert eng.metrics.prefill_batched == 1
            assert eng.steps == 1 + 3      # the prompts' wave, 3 decode

    def test_mixed_buckets_share_the_wave(self, model):
        """Arrivals spanning two prompt buckets are still one wave, as
        wide as the longest (bucket 16); non-pow2 group sizes pad
        safely."""
        p, cfg = model
        reqs = [Request(prompt=[1, 2], max_new_tokens=3),          # b8
                Request(prompt=[3, 4, 5], max_new_tokens=3),       # b8
                Request(prompt=[6, 7, 8], max_new_tokens=3),       # b8
                Request(prompt=list(range(1, 10)), max_new_tokens=3)]
        eng = ServingEngine(p, cfg, slots=4, queue_limit=8,
                            fast_path=True)
        res = eng.run(reqs)
        assert len(res) == 4
        assert eng.prefill_dispatches == 1
        pre = [e for e in eng.metrics.events
               if e["event"] == "serve_prefill"]
        assert [(e["n"], e["bucket"]) for e in pre] == [(4, 16)]
        ref = ServingEngine(p, cfg, slots=4, queue_limit=8,
                            fast_path=False)
        res_ref = ref.run([Request(prompt=r.prompt, max_new_tokens=3)
                           for r in reqs])
        assert sorted(r.tokens.tolist() for r in res.values()) == \
            sorted(r.tokens.tolist() for r in res_ref.values())

    def test_finish_at_prefill_frees_slot_same_step(self, model):
        """max_new_tokens=1 retires in the wave that wrote the prompt:
        the slot is free when the step() that lands that wave returns
        and the next step admits the next queued request."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=1, fast_path=True)
        for r in (Request(prompt=[7, 8, 9], max_new_tokens=1),
                  Request(prompt=[3, 4], max_new_tokens=1)):
            eng.submit(r)
        assert eng.step() == []                   # launched
        first = eng.step()
        assert len(first) == 1 and not eng.kv.live()
        assert eng.step() == [] and eng.kv.live()
        second = eng.step()
        assert len(second) == 1 and not eng.pending
        assert all(r.n_generated == 1 for r in first + second)
        assert eng.steps == 2 and eng.prefill_dispatches == 2


@pytest.mark.smoke
class TestSelectionAndMetrics:
    def test_resolve_fast_precedence(self, monkeypatch):
        assert _resolve_fast(True) is True
        assert _resolve_fast(False) is False
        assert _resolve_fast("ragged") is True
        assert _resolve_fast("masked") is False
        monkeypatch.setenv("HETU_SERVE_FAST", "1")
        assert _resolve_fast(None) is True
        assert _resolve_fast(False) is False      # explicit arg wins
        monkeypatch.setenv("HETU_SERVE_FAST", "0")
        assert _resolve_fast(None) is False
        monkeypatch.delenv("HETU_SERVE_FAST")
        # auto: reference off-TPU (this harness is CPU)
        assert _resolve_fast(None) is (jax.default_backend() == "tpu")

    def test_engine_honors_env(self, model, monkeypatch):
        p, cfg = model
        monkeypatch.setenv("HETU_SERVE_FAST", "1")
        assert ServingEngine(p, cfg, slots=2).fast_path is True
        monkeypatch.setenv("HETU_SERVE_FAST", "0")
        assert ServingEngine(p, cfg, slots=2).fast_path is False
        assert ServingEngine(p, cfg, slots=2,
                             fast_path=True).fast_path is True

    def test_per_step_phase_events(self, model, tmp_path):
        """serve_step events carry prefill_ms/decode_ms; serve_prefill
        events carry the dispatch batch size — the A/B's attribution."""
        import json
        p, cfg = model
        log = str(tmp_path / "fast.jsonl")
        eng = ServingEngine(p, cfg, slots=2, log_path=log,
                            fast_path=True)
        eng.run([Request(prompt=[7, 8], max_new_tokens=3),
                 Request(prompt=[9], max_new_tokens=4)])
        with open(log) as f:
            recs = [json.loads(line) for line in f]
        steps = [r for r in recs if r["event"] == "serve_step"]
        pre = [r for r in recs if r["event"] == "serve_prefill"]
        assert steps and pre
        assert all("prefill_ms" in r and "decode_ms" in r for r in steps)
        assert all(r["decode_ms"] >= 0 for r in steps)
        assert sum(r["n"] for r in pre) == 2
        assert all(r["batched"] for r in pre)
        snap = eng.metrics.snapshot()
        assert snap["prefill_dispatches"] == len(pre)
        assert snap["decode_ms_p50"] is not None
        assert snap["prefill_ms_p50"] is not None
