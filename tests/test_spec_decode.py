"""Speculative decoding (ISSUE 10): draft-propose / batched-verify on
the serving engine — the multi-token verify kernel, the one-dispatch
draft scan, longest-prefix acceptance + bonus token, and KV rollback.

The load-bearing contract: speculative outputs are TOKEN-IDENTICAL to
the non-speculative engine and to offline ``generate_fast`` — greedy
trivially, and SAMPLED too, because every emitted token is the target's
own sequential sample from the request's rng stream (the verify returns
the stream state after every split, so the host resumes at exactly the
accepted count).  Identity must hold across every cache
configuration: the block-table pool with prefix sharing and chunked
prefill, int8-quantized, and the ragged fast path.

Rollback property tests (the ISSUE's satellite): randomized
propose/accept/reject sequences must leave the cache's live bytes equal
to a never-speculated replay on the paged pool (including COW-shared
prefixes — rollback must never free a block another holder still
references), and int8 variants (scale planes truncated in lockstep).

Weights are deterministic random GPTs (the contract is numeric parity,
not model quality); everything here is ``smoke``-tier.
"""

import json

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax
import jax.numpy as jnp

from hetu_tpu.kv_layout import kv_heads
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import (
    _decode_step, _kv_scatter, _verify_step, generate_fast,
    resolve_draft_layers, resolve_spec_k,
)
from hetu_tpu.serving import (
    PagedKVManager, Request, ServingEngine, ServingMetrics,
)


def _rand_gpt(name="sp", L=2, H=2, Dh=8, V=61, S=32, seed=0):
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


def _zero_late_layers(p, name="sp", first=1, L=2):
    """Output-zero layers >= first: the truncated-layer draft's logits
    then equal the target's bitwise — greedy acceptance 1.0 while the
    target still pays full-depth compute (the high-acceptance fixture)."""
    hp = dict(p)
    for i in range(first, L):
        for wn in ("attn_proj_weight", "attn_proj_bias",
                   "ffn_wo_weight", "ffn_wo_bias"):
            hp[f"{name}_h{i}_{wn}"] = np.zeros_like(p[f"{name}_h{i}_{wn}"])
    return hp


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


TRACE = [([7, 8, 9], 6), ([3, 4], 8), ([1, 2, 3, 4, 5], 4), ([11], 7)]


def _mk(trace=TRACE, **kw):
    return [Request(prompt=pr, max_new_tokens=n, **kw)
            for pr, n in trace]


def _outs(res):
    return sorted(r.tokens.tolist() for r in res.values())


@pytest.mark.smoke
class TestVerifyStep:
    def test_matches_sequential_decode_steps(self, model):
        """``_verify_step`` over a Q-block == Q sequential
        ``_decode_step`` calls: the same argmax token at every live
        position (the property the engine's acceptance rule relies
        on), logits equal to f32 round-off (a batched [B, Q] matmul
        and Q [B] matmuls may accumulate in a different order), and so
        the cache rows they write."""
        p, cfg = model
        name, L, H = "sp", 2, 2
        Dh, S = 8, 32
        cfgt = (name, L, H, Dh, S)
        from hetu_tpu.models.gpt_decode import _prep_param
        params = {k: _prep_param(v) for k, v in p.items()}
        B = 3
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, 61, (B, 6)).astype(np.int32)
        ck = jnp.zeros((L, B, S, H, Dh))
        cv = jnp.zeros_like(ck)
        for t in range(6):
            _, ck, cv = _decode_step(params, cfgt, ck, cv,
                                     jnp.int32(t), prompt[:, t])
        tokens = rng.randint(0, 61, (B, 4)).astype(np.int32)
        pos = np.full(B, 6, np.int32)
        qlen = np.array([4, 2, 1], np.int32)
        lv, ckv, cvv = _verify_step(params, cfgt, ck, cv, pos,
                                    jnp.asarray(tokens),
                                    jnp.asarray(qlen))
        lv = np.asarray(lv)
        ck2, cv2 = ck, cv
        p2 = pos.copy()
        for j in range(4):
            l2, ck2, cv2 = _decode_step(params, cfgt, ck2, cv2, p2,
                                        tokens[:, j])
            l2 = np.asarray(l2)
            for b in range(B):
                if j < qlen[b]:
                    np.testing.assert_allclose(lv[b, j], l2[b],
                                               rtol=1e-5, atol=1e-6)
                    assert lv[b, j].argmax() == l2[b].argmax()
            p2 = p2 + 1
        # live cache region equal to the same round-off (dead verify
        # positions land beyond each slot's live length)
        for b in range(B):
            n = 6 + int(qlen[b])
            np.testing.assert_allclose(
                np.asarray(ckv)[:, b, :n], np.asarray(ck2)[:, b, :n],
                rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------- #
# engine identity across cache configurations
# ------------------------------------------------------------------- #


@pytest.mark.smoke
class TestEngineIdentity:
    # the default engine's spec-vs-plain is covered by
    # test_sampled_identity and spec-vs-offline below; these pin the
    # other pool configurations (int8/chunked/shared-prefix,
    # fast_path exercising the ragged KERNEL in interpret mode)
    CONFIGS = [
        ("paged_shared", {"kv_block": 4, "prefix_share": True}),
        ("paged_chunked", {"kv_block": 4, "prefill_chunk": 3}),
        ("int8", {"kv_quant": "int8"}),
        ("paged_fast", {"kv_block": 4, "fast_path": True}),
    ]

    @pytest.mark.parametrize("label,kw",
                             CONFIGS, ids=[c[0] for c in CONFIGS])
    def test_greedy_identity(self, model, label, kw):
        """Acceptance: speculative greedy outputs token-identical to
        the plain engine under every cache configuration."""
        p, cfg = model
        plain = ServingEngine(p, cfg, slots=2, queue_limit=16,
                              **kw).run(_mk())
        eng = ServingEngine(p, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1, **kw)
        res = eng.run(_mk())
        assert _outs(plain) == _outs(res)
        assert eng.spec_waves > 0 and eng.spec_proposed > 0

    def test_greedy_identity_vs_offline(self, model):
        """Engine speculative greedy == offline generate_fast — the
        cross-path acceptance criterion."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1)
        res = eng.run(_mk())
        for pr, n in TRACE:
            want = generate_fast(p, cfg, [pr], num_tokens=n)[0]
            got = [r for r in res.values()
                   if r.tokens[:len(pr)].tolist() == list(pr)
                   and r.n_generated == n]
            assert any(g.tokens.tolist() == want.tolist() for g in got)

    def test_sampled_identity(self, model):
        """Sampling identity, not just distributional correctness:
        accepted tokens ARE the target's own sequential samples, so
        temperature/top_k/seed mixes reproduce the plain engine's
        outputs token for token."""
        p, cfg = model
        spec = [([3, 4], 0.9, 5, 11), ([7, 8, 9], 0.7, 3, 22),
                ([11], 1.1, 0, 33), ([5, 6], 0.8, 4, 44)]

        def run(spec_on):
            kw = (dict(spec=3, spec_adapt=False, spec_draft_layers=1)
                  if spec_on else {})
            eng = ServingEngine(p, cfg, slots=2, queue_limit=16, **kw)
            reqs = [Request(prompt=pr, max_new_tokens=6, temperature=t,
                            top_k=k, seed=s) for pr, t, k, s in spec]
            res = eng.run(reqs)
            return {tuple(r.prompt): res[r.request_id].tokens.tolist()
                    for r in reqs}

        assert run(False) == run(True)

    def test_eos_mid_wave(self, model):
        """An EOS inside the accepted span cuts the emission there and
        rolls the cache back to the cut; finish_reason and tokens match
        the plain engine."""
        p, cfg = model
        plain0 = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8)[0]
        eos = int(plain0[5])   # a mid-generation token becomes the EOS
        req = lambda: [Request(prompt=[7, 8, 9], max_new_tokens=8,  # noqa: E731
                               eos_id=eos)]
        pl = next(iter(ServingEngine(p, cfg, slots=2).run(req()).values()))
        sp = next(iter(ServingEngine(
            p, cfg, slots=2, spec=3, spec_adapt=False,
            spec_draft_layers=1).run(req()).values()))
        assert sp.tokens.tolist() == pl.tokens.tolist()
        assert sp.finish_reason == pl.finish_reason

    def test_high_acceptance_waves_and_attribution(self, model):
        """With the post-draft layers output-zeroed (draft logits ==
        target logits), every draft is accepted and the engine emits
        multiple tokens per wave — fewer waves than tokens; each
        Result's accepted/proposed attribution accounts for every
        generated token."""
        p, cfg = model
        hp = _zero_late_layers(p)
        eng = ServingEngine(hp, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1)
        res = eng.run(_mk())
        total = sum(r.n_generated for r in res.values())
        assert eng.spec_accepted == eng.spec_proposed > 0
        assert eng.spec_acceptance == 1.0
        assert eng.spec_waves < total
        snap = eng.metrics.snapshot()
        assert snap["tokens_per_step_mean"] > 1.0
        saw_accept = False
        for r in res.values():
            assert r.spec_proposed >= r.spec_accepted >= 0
            assert r.spec_accepted <= r.n_generated - 1
            saw_accept |= r.spec_accepted > 0
        assert saw_accept

    def test_adaptive_k_ramps_and_backs_off(self, model):
        """The sliding-window controller grows k to the cap under
        sustained full acceptance and collapses it to 1 under
        near-zero acceptance."""
        p, cfg = model
        hp = _zero_late_layers(p)
        eng = ServingEngine(hp, cfg, slots=2, queue_limit=64, spec=4,
                            spec_adapt=True, spec_draft_layers=1)
        assert eng._spec_kcur == 2    # ramp-up start: spec_k // 2
        eng.run([Request(prompt=[i % 50 + 1], max_new_tokens=18,
                         seed=i) for i in range(6)])
        assert eng._spec_kcur == 4
        # near-zero acceptance: hot sampling vs a greedy draft
        eng2 = ServingEngine(p, cfg, slots=2, queue_limit=64, spec=4,
                             spec_adapt=True, spec_draft_layers=1)
        eng2.run([Request(prompt=[i % 50 + 1], max_new_tokens=12,
                          temperature=2.0, seed=i) for i in range(6)])
        assert eng2._spec_kcur == 1
        assert eng2.spec_mean_k < 4

    def test_spec_env_knobs(self, model, monkeypatch):
        """$HETU_SPEC_K / $HETU_SPEC_DRAFT_LAYERS drive the engine and
        resolvers; explicit arguments win."""
        monkeypatch.setenv("HETU_SPEC_K", "3")
        monkeypatch.setenv("HETU_SPEC_DRAFT_LAYERS", "1")
        assert resolve_spec_k(None) == 3
        assert resolve_spec_k(5) == 5
        assert resolve_draft_layers(None, 8) == 1
        monkeypatch.delenv("HETU_SPEC_DRAFT_LAYERS")
        assert resolve_draft_layers(None, 8) == 2     # auto: L // 4
        assert resolve_draft_layers(99, 8) == 8       # clamped
        p, cfg = model
        sub = TRACE[:2]
        eng = ServingEngine(p, cfg, slots=2)
        assert eng.spec_k == 3 and eng.spec_draft_layers == 1
        plain_env = eng.run(_mk(sub))
        monkeypatch.setenv("HETU_SPEC_K", "0")
        plain = ServingEngine(p, cfg, slots=2).run(_mk(sub))
        assert _outs(plain_env) == _outs(plain)


@pytest.mark.smoke
class TestOfflineSpec:
    def test_generate_fast_spec_identity(self, model):
        p, cfg = model
        prompts = [[7, 8, 9], [3, 4, 5]]
        want = generate_fast(p, cfg, prompts, num_tokens=8)
        got = generate_fast(p, cfg, prompts, num_tokens=8, spec=3,
                            spec_draft_layers=1)
        assert want.tolist() == got.tolist()

    def test_generate_fast_spec_eos(self, model):
        p, cfg = model
        plain0 = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8)[0]
        eos = int(plain0[5])
        want = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8,
                             eos_id=eos, pad_id=0)
        got = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=8,
                            eos_id=eos, pad_id=0, spec=3,
                            spec_draft_layers=1)
        assert want.tolist() == got.tolist()

    def test_generate_fast_spec_num_tokens_1(self, model):
        p, cfg = model
        want = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=1)
        got = generate_fast(p, cfg, [[7, 8, 9]], num_tokens=1, spec=3,
                            spec_draft_layers=1)
        assert want.tolist() == got.tolist()


# ------------------------------------------------------------------- #
# KV rollback (truncate) property tests
# ------------------------------------------------------------------- #


def _write_positions(m, slot, positions, values, L=1, H=1, Dh=4):
    """Write one [H, Dh] slab per position through the manager's
    block tables, mirroring the verify write."""
    for pos, val in zip(positions, values):
        v = jnp.asarray(np.full((1, H, Dh), val, np.float32))
        for i in range(L):
            b = int(m.tables[slot, pos // m.block])
            off = pos % m.block
            m.cache_k = _kv_scatter(m.cache_k,
                                    (i, np.array([b]),
                                     np.array([off])), v)
            m.cache_v = _kv_scatter(m.cache_v,
                                    (i, np.array([b]),
                                     np.array([off])), v)


def _live_bytes(m, slot):
    """The slot's live-region cache content (payload + scale planes for
    quantized layouts), gathered position by position."""
    out = []
    n = int(m.lengths[slot])
    quant = isinstance(m.cache_k, tuple)
    for pos in range(n):
        b = int(m.tables[slot, pos // m.block])
        off = pos % m.block
        idx = (slice(None), b, off)
        if quant:
            out.append((np.asarray(m.cache_k[0][idx]).tobytes(),
                        np.asarray(m.cache_k[1][idx]).tobytes(),
                        np.asarray(m.cache_v[0][idx]).tobytes(),
                        np.asarray(m.cache_v[1][idx]).tobytes()))
        else:                          # rows: the [L, H, Dh] view
            out.append(tuple(
                kv_heads(np.asarray(c[idx]), m.heads,
                         m.head_dim).tobytes()
                for c in (m.cache_k, m.cache_v)))
    return out


@pytest.mark.smoke
class TestKVRollback:
    def _mgr(self, dtype=jnp.float32):
        return PagedKVManager(layers=1, heads=1, head_dim=4,
                              slots=2, max_seq_len=64, block=4,
                              dtype=dtype, prefix_share=False)

    @pytest.mark.parametrize("dtype", [jnp.float32, "int8"],
                             ids=["f32-paged", "int8-paged"])
    def test_speculate_rollback_equals_replay(self, dtype):
        """Property: after randomized propose/accept/reject rounds,
        the live cache region equals a never-speculated replay byte
        for byte — the float pool and the int8 variant (whose scale
        planes must truncate in lockstep)."""
        rng = np.random.RandomState(7)
        spec = self._mgr(dtype)
        replay = self._mgr(dtype)
        slot_s, _ = spec.alloc("r", [1, 2, 3], 40)
        slot_r, _ = replay.alloc("r", [1, 2, 3], 40)
        canonical = lambda pos: float(np.sin(pos + 1))  # noqa: E731
        n = 0
        for rnd in range(10):
            q = int(rng.randint(1, 5))
            if n + q > 40:
                break
            keep = int(rng.randint(1, q + 1))
            vals = [canonical(n + j) if j < keep
                    else 1e3 + rnd * 10 + j          # rejected garbage
                    for j in range(q)]
            _write_positions(spec, slot_s, range(n, n + q), vals)
            spec.advance(slot_s, q)
            spec.truncate(slot_s, n + keep)
            _write_positions(replay, slot_r, range(n, n + keep),
                             [canonical(n + j) for j in range(keep)])
            replay.advance(slot_r, keep)
            n += keep
        assert int(spec.lengths[slot_s]) == n
        assert _live_bytes(spec, slot_s) == _live_bytes(replay, slot_r)
        assert spec.free_blocks == replay.free_blocks

    def test_truncate_errors(self):
        m = self._mgr()
        slot, _ = m.alloc("r", [1, 2, 3, 4, 5], 10)
        m.advance(slot, 5)
        with pytest.raises(ValueError):
            m.truncate(slot, 6)        # beyond filled
        with pytest.raises(ValueError):
            m.truncate(slot, -1)
        m.truncate(slot, 3)
        assert int(m.lengths[slot]) == 3
        m.release(slot)
        with pytest.raises(ValueError):
            m.truncate(slot, 0)        # free slot

    def test_paged_truncate_never_frees_shared_blocks(self):
        """COW discipline: truncating INTO a shared region detaches the
        shared blocks from the truncating slot (fork-on-boundary, fresh
        swap past it) and never frees a block the prefix cache or
        another request still references."""
        m = PagedKVManager(layers=1, heads=1, head_dim=4, slots=3,
                           max_seq_len=64, block=4, prefix_share=True)
        prompt = list(range(1, 11))                      # 10 tokens
        s0, cached = m.alloc("a", prompt, 16)
        assert cached == 0
        _write_positions(m, s0, range(10),
                         [float(t) for t in prompt])
        m.advance(s0, 10)
        m.register_prefix(np.asarray(prompt), s0)
        # a second request attaches the shared prefix
        s1, cached = m.alloc("b", prompt + [30, 31], 20)
        assert cached > 0
        shared = [int(b) for b in m.tables[s1, :cached // m.block]]
        assert all(m.ref[b] >= 2 for b in shared)
        m.advance(s1, 12 - cached)   # pretend the tail got written
        before = _live_bytes(m, s0)
        cow0 = m.cow_copies
        # roll s1 back INTO the shared region (mid-block: position 6)
        m.truncate(s1, 6)
        # every surviving table entry s1 will write is now private
        for j in range(6 // m.block, int(m.n_table[s1])):
            assert m.ref[int(m.tables[s1, j])] == 1
        # the boundary block (positions 4..7, live below 6) was FORKED
        assert m.cow_copies == cow0 + 1
        # the shared blocks survive for every other holder, unharmed
        for b in shared:
            assert m.ref[b] >= 1
            assert b not in m._free
        assert _live_bytes(m, s0) == before
        # s1's live content below the cut is intact too
        got = _live_bytes(m, s1)
        want = [np.full((1, 1, 4), float(t), np.float32).tobytes()
                for t in prompt[:6]]
        assert [g[0] for g in got] == want

    def test_truncate_on_a_full_pool_evicts_the_prefix_it_shares(self):
        """A slot's own registered prompt is the other holder of its
        partial tail block: on a pool with nothing free, rollback evicts
        that entry and goes on with the block, private again, in place
        (the default engine with ``spec`` on a just-large-enough pool)."""
        m = PagedKVManager(layers=1, heads=1, head_dim=4, slots=1,
                           max_seq_len=16, block=4, pool_blocks=5,
                           prefix_share=True)
        prompt = [1, 2, 3, 4, 5, 6]                 # tail block: 2 of 4
        s0, _ = m.alloc("a", prompt, 16)
        assert m.free_blocks == 0
        m.advance(s0, 6)
        m.register_prefix(np.asarray(prompt), s0)
        tail = int(m.tables[s0, 1])
        assert m.ref[tail] == 2
        m.advance(s0, 2)                            # a verify block
        cow0 = m.cow_copies
        m.truncate(s0, 7)                           # one token rejected
        assert int(m.tables[s0, 1]) == tail and m.ref[tail] == 1
        assert m.cow_copies == cow0 and int(m.lengths[s0]) == 7
        m.release(s0)
        assert m.free_blocks == m.n_blocks - 1

    def test_engine_rollback_leaves_pool_consistent(self, model):
        """End to end: a paged speculative run releases every block it
        reserved — refcounts return to zero, the free list to full."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1,
                            kv_block=4, prefix_share=False)
        eng.run(_mk())
        assert eng.kv.free_blocks == eng.kv.n_blocks - 1
        assert int(np.sum(eng.kv.ref[1:])) == 0


# ------------------------------------------------------------------- #
# TPOT accounting + observability
# ------------------------------------------------------------------- #


@pytest.mark.smoke
class TestTpotAccounting:
    def test_tpot_from_per_step_token_counts(self, tmp_path):
        """The satellite fix: TPOT percentiles come from real per-step
        emitted-token counts, not decode_ms / (n_generated - 1)."""
        from hetu_tpu import telemetry
        m = ServingMetrics(log_path=str(tmp_path / "s.jsonl"))
        m.record_step(live=2, slots=4, queue_depth=0, dt_s=0.2,
                      new_tokens=4)
        m.record_step(live=2, slots=4, queue_depth=0, dt_s=0.2,
                      new_tokens=1)
        snap = m.snapshot()
        # 5 tokens: four at 0.05 s/tok, one at 0.2 -> p50 is 0.05
        assert abs(snap["tpot_p50_s"] - 0.05) < 1e-9
        assert snap["tpot_p99_s"] > 0.05
        assert snap["tokens_per_step_mean"] == 2.5
        steps = [e for e in m.events if e["event"] == "serve_step"]
        assert [e["new_tokens"] for e in steps] == [4, 1]
        hist = telemetry.snapshot()["histograms"].get(
            "serve.tokens_per_step")
        assert hist is not None and hist["count"] >= 2

    def test_spec_fields_on_step_events(self, model, tmp_path):
        p, cfg = model
        hp = _zero_late_layers(p)
        log = str(tmp_path / "spec.jsonl")
        eng = ServingEngine(hp, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1,
                            log_path=log)
        eng.run(_mk())
        with open(log) as f:
            recs = [json.loads(ln) for ln in f]
        steps = [r for r in recs if r["event"] == "serve_step"]
        # a wave with a verify block carries the draft's fields; a wave
        # of prompts alone proposed nothing and carries none
        verify = [r for r in steps if r["q_verify"]]
        assert verify and len(verify) == eng.spec_waves
        assert all("spec_k" in r and "spec_proposed" in r
                   and "spec_accepted" in r and "new_tokens" in r
                   for r in verify)
        assert not any("spec_k" in r or r["new_tokens"]
                       for r in steps if not r["q_verify"])
        assert sum(r["spec_accepted"] for r in verify) == \
            eng.spec_accepted
        retires = [r for r in recs if r["event"] == "req_retire"]
        for r in retires:
            assert r["spec_accepted"] + r["spec_bonus"] + 1 == \
                r["n_generated"]

    def test_trace_check_spec_attribution_rule(self, model, tmp_path):
        """hetu_trace --check passes on a real speculative stream and
        flags a tampered req_retire whose accounting no longer sums."""
        from hetu_tpu.telemetry import trace as trace_mod
        p, cfg = model
        log = str(tmp_path / "spec.jsonl")
        eng = ServingEngine(p, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1,
                            log_path=log)
        eng.run(_mk())
        assert trace_mod.main([log, "--check"]) == 0
        with open(log) as f:
            recs = [json.loads(ln) for ln in f]
        bad = next(r for r in recs if r["event"] == "req_retire")
        bad = dict(bad)
        bad["spec_accepted"] = bad["spec_accepted"] + 5
        bad["request"] = "req-tampered"
        problems = trace_mod.check_spec_attribution(recs + [bad])
        assert len(problems) == 1 and "req-tampered" in problems[0]
        # non-speculative records are exempt
        assert trace_mod.check_spec_attribution(
            [{"event": "req_retire", "request": "r", "t": 0.0,
              "ttft_ms": 1.0, "n_generated": 4}]) == []

    def test_hetu_top_spec_columns(self, model, tmp_path):
        from hetu_tpu.telemetry.top import (render, render_fleet,
                                            summarize, summarize_fleet)
        from hetu_tpu.telemetry.trace import read_events
        p, cfg = model
        hp = _zero_late_layers(p)
        log = str(tmp_path / "top.jsonl")
        eng = ServingEngine(hp, cfg, slots=2, queue_limit=16, spec=3,
                            spec_adapt=False, spec_draft_layers=1,
                            log_path=log, tags={"replica": 0})
        eng.run(_mk())
        events, bad = read_events([log])
        assert bad == 0
        stats = summarize(events)
        sp = stats["spec"]
        assert sp["drafted"] == eng.spec_proposed
        assert sp["accepted"] == eng.spec_accepted
        assert sp["acceptance"] == 1.0
        assert sp["mean_k"] == 3.0
        assert stats["tpot_p50_ms"] is not None
        frame = render(stats)
        assert "acceptance" in frame and "mean_k" in frame
        fleet = summarize_fleet(events)
        row = fleet["replicas"][0]
        assert row["drafted"] == eng.spec_proposed
        assert row["acceptance"] == 1.0
        assert "drafted" in render_fleet(fleet)
