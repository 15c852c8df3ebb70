"""Mixed-mode ragged dispatch (ISSUE 18): ONE kernel and ONE engine
wave for the whole serving hot loop.

Kernel tier: ``ragged_attention`` / ``ragged_paged_attention`` (one
parameterized Pallas body across contiguous/block-table x f32/int8)
must match the ONE masked-gather oracle (``ragged_masked_reference``)
on decode-only, verify-only, prefill-only, and freely mixed ``q_len``
waves — including arbitrarily permuted pools and int8 scale planes —
and must degenerate exactly to the per-mode kernels the phase-split
engine still runs (those stay behind as parity oracles).

Engine tier: the load-bearing contract is TOKEN IDENTITY — a
``ragged=True`` engine (``$HETU_SERVE_RAGGED``) that packs admissions,
chunk continuations, spec-verify, and decode into one wave per step
must emit exactly the tokens the phase-split scheduler emits, greedy
AND sampled, across contiguous/paged/int8/chunked/prefix-shared/
speculative configurations, while the ``chunk_stall`` lifecycle
component collapses to exactly 0.

Everything runs on CPU via interpret mode; ``smoke``-tier.
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax.numpy as jnp

from hetu_tpu.kernels.decode_attention import (
    masked_decode_reference, masked_verify_reference,
    paged_block_decode_attention, paged_block_verify_attention,
    paged_decode_attention, paged_verify_attention,
)
from hetu_tpu.kernels.ragged_attention import (
    ragged_attention, ragged_masked_reference, ragged_paged_attention,
    ragged_paged_reference,
)
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import resolve_serve_ragged
from hetu_tpu.serving import Request, ServingEngine


# ------------------------------------------------------------------- #
# kernel parity
# ------------------------------------------------------------------- #


def _wave(B=4, Q=4, H=2, Dh=8, S=64, seed=0, qlens=(4, 1, 2, 0),
          lens=(17, 33, 5, 0)):
    rng = np.random.RandomState(seed)
    q = rng.randn(B, Q, H, Dh).astype(np.float32)
    k = rng.randn(B, S, H, Dh).astype(np.float32)
    v = rng.randn(B, S, H, Dh).astype(np.float32)
    return (q, k, v, np.asarray(lens, np.int32)[:B],
            np.asarray(qlens, np.int32)[:B])


def _to_pool(k, v, bs=16, seed=1):
    """Scatter [B, S] logical KV into a permuted [N, bs] pool."""
    B, S = k.shape[:2]
    T = S // bs
    rng = np.random.RandomState(seed)
    N = B * T + 3
    perm = rng.permutation(N)[:B * T]
    tables = perm.reshape(B, T).astype(np.int32)
    pk = np.zeros((N, bs) + k.shape[2:], k.dtype)
    pv = np.zeros((N, bs) + v.shape[2:], v.dtype)
    for b in range(B):
        for j in range(T):
            pk[tables[b, j]] = k[b, j * bs:(j + 1) * bs]
            pv[tables[b, j]] = v[b, j * bs:(j + 1) * bs]
    return pk, pv, tables


def _quantize(x, axis=-1):
    """Int8 payload + per-(..., head) f32 scale planes."""
    amax = np.abs(x).max(axis=axis) + 1e-6
    scale = (amax / 127.0).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale


@pytest.mark.smoke
class TestRaggedKernel:
    # decode-only, spec-verify-only, full-prompt prefill, and freely
    # mixed waves — all one kernel, selected purely by per-slot data
    @pytest.mark.parametrize("qlens", [
        (1, 1, 1, 1), (4, 4, 4, 4), (4, 1, 2, 0), (2, 0, 4, 1)])
    def test_contiguous_matches_reference(self, qlens):
        q, k, v, lens, ql = _wave(qlens=qlens)
        got = ragged_attention(q, k, v, lens, ql, block_k=16,
                               interpret=True)
        want = ragged_masked_reference(q, k, v, lens, ql)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("qlens", [
        (1, 1, 1, 1), (4, 1, 2, 0)])
    def test_permuted_pool_matches_reference(self, qlens):
        q, k, v, lens, ql = _wave(qlens=qlens)
        pk, pv, tables = _to_pool(k, v)
        got = ragged_paged_attention(q, pk, pv, lens, ql, tables,
                                     interpret=True)
        want = ragged_paged_reference(q, pk, pv, lens, ql, tables)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        # the pool gather is the only paged/contiguous difference
        contig = ragged_masked_reference(q, k, v, lens, ql)
        np.testing.assert_allclose(np.asarray(want), np.asarray(contig),
                                   atol=1e-6, rtol=1e-6)

    def test_int8_twin_contiguous(self):
        q, k, v, lens, ql = _wave()
        k8, ks = _quantize(k)
        v8, vs = _quantize(v)
        got = ragged_attention(q, k8, v8, lens, ql, block_k=16,
                               k_scale=ks, v_scale=vs, interpret=True)
        want = ragged_masked_reference(q, k8, v8, lens, ql,
                                       k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_int8_twin_paged(self):
        q, k, v, lens, ql = _wave()
        pk, pv, tables = _to_pool(k, v)
        pk8, pks = _quantize(pk)
        pv8, pvs = _quantize(pv)
        got = ragged_paged_attention(q, pk8, pv8, lens, ql, tables,
                                     k_scale=pks, v_scale=pvs,
                                     interpret=True)
        want = ragged_paged_reference(q, pk8, pv8, lens, ql, tables,
                                      k_scale=pks, v_scale=pvs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    # a q-block longer than one tile (ISSUE 22: VMEM is bounded by
    # _MAX_ROWS, so a long prompt is several q-tiles): every live row
    # matches the reference exactly as a one-tile block does, wholly
    # dead tiles are skipped and come back zero
    @pytest.mark.parametrize("layout", ["contiguous", "paged",
                                        "paged-int8"])
    def test_tiled_q_block_matches_reference(self, layout, monkeypatch):
        from hetu_tpu.kernels import ragged_attention as ra
        monkeypatch.setattr(ra, "_ONE_TILE_ROWS", 8)
        monkeypatch.setattr(ra, "_MAX_ROWS", 8)     # H=2 -> 4-query tiles
        assert ra._q_tile(16, 2) == 4
        q, k, v, lens, ql = _wave(Q=16, qlens=(16, 1, 6, 0),
                                  lens=(40, 33, 6, 0))
        if layout == "contiguous":
            got = ragged_attention(q, k, v, lens, ql, block_k=16,
                                   interpret=True)
            want = ragged_masked_reference(q, k, v, lens, ql)
        else:
            pk, pv, tables = _to_pool(k, v)
            kw = {}
            if layout == "paged-int8":
                pk, ks = _quantize(pk)
                pv, vs = _quantize(pv)
                kw = dict(k_scale=ks, v_scale=vs)
            got = ragged_paged_attention(q, pk, pv, lens, ql, tables,
                                         interpret=True, **kw)
            want = ragged_paged_reference(q, pk, pv, lens, ql, tables,
                                          **kw)
        got, want = np.asarray(got), np.asarray(want)
        for b, n in enumerate(ql):
            live = -(-max(int(n), 1) // 4) * 4   # rows of live tiles
            np.testing.assert_allclose(got[b, :live], want[b, :live],
                                       atol=2e-5, rtol=2e-5)
            assert not got[b, live:].any()

    def test_dead_q_tiles_fetch_no_kv(self):
        """The kv index map, walked in grid order (a changed block index
        is one DMA): a decode slot (q_len 1) in a wave of eight q-tiles
        fetches each of its live kv blocks ONCE — its seven dead tiles
        stay on the block the live tile ended on — a verify slot
        likewise, and a whole-prompt slot fetches one causal triangle."""
        from hetu_tpu.kernels.ragged_attention import _kv_step_block
        tq, bk, n_t, n_kv = 128, 16, 8, 64
        lens = np.array([900, 1024, 300, 0], np.int32)
        qlens = np.array([1, 1024, 5, 0], np.int32)

        def fetches(b):
            seen, n = None, 0
            for t in range(n_t):
                for j in range(n_kv):
                    blk = int(_kv_step_block(lens, qlens, b, t, j, tq, bk))
                    n += blk != seen
                    seen = blk
            return n

        assert fetches(0) == -(-900 // bk)          # 57 live blocks, once
        assert fetches(2) == -(-300 // bk)
        assert fetches(3) == 1                      # empty slot: block 0
        # tile t of the whole prompt sees (t + 1) * tq positions
        assert fetches(1) == sum((t + 1) * tq // bk for t in range(n_t))

    def test_zero_length_slot_returns_zeros(self):
        q, k, v, lens, ql = _wave(qlens=(4, 1, 2, 0), lens=(17, 33, 5, 0))
        got = np.asarray(ragged_attention(q, k, v, lens, ql, block_k=16,
                                          interpret=True))
        assert np.all(got[3] == 0.0)

    def test_bf16_accumulates_f32(self):
        q, k, v, lens, ql = _wave()
        got = ragged_attention(q.astype(jnp.bfloat16),
                               k.astype(jnp.bfloat16),
                               v.astype(jnp.bfloat16), lens, ql,
                               block_k=16, interpret=True)
        assert got.dtype == jnp.bfloat16
        want = ragged_masked_reference(q, k, v, lens, ql)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            atol=3e-2, rtol=3e-2)

    # q_len = 1 IS the decode kernel; q_lens = spec widths IS the
    # verify kernel — the phase-split kernels stay as parity oracles
    def test_degenerates_to_decode_kernel(self):
        q, k, v, lens, _ = _wave()
        ones = np.ones_like(lens)
        got = np.asarray(ragged_attention(
            q[:, :1], k, v, lens, ones, block_k=16, interpret=True))
        old = np.asarray(paged_decode_attention(
            q[:, 0], k, v, lens, block_k=16, interpret=True))
        np.testing.assert_allclose(got[:, 0], old, atol=2e-5, rtol=2e-5)
        pk, pv, tables = _to_pool(k, v)
        gotp = np.asarray(ragged_paged_attention(
            q[:, :1], pk, pv, lens, ones, tables, interpret=True))
        oldp = np.asarray(paged_block_decode_attention(
            q[:, 0], pk, pv, lens, tables, interpret=True))
        np.testing.assert_allclose(gotp[:, 0], oldp, atol=2e-5,
                                   rtol=2e-5)

    def test_degenerates_to_verify_kernel(self):
        q, k, v, lens, ql = _wave()
        got = np.asarray(ragged_attention(q, k, v, lens, ql, block_k=16,
                                          interpret=True))
        old = np.asarray(paged_verify_attention(q, k, v, lens, ql,
                                                block_k=16,
                                                interpret=True))
        np.testing.assert_allclose(got, old, atol=2e-5, rtol=2e-5)
        pk, pv, tables = _to_pool(k, v)
        gotp = np.asarray(ragged_paged_attention(
            q, pk, pv, lens, ql, tables, interpret=True))
        oldp = np.asarray(paged_block_verify_attention(
            q, pk, pv, lens, ql, tables, interpret=True))
        np.testing.assert_allclose(gotp, oldp, atol=2e-5, rtol=2e-5)

    # the four old per-mode references are now delegates of the ONE
    # parameterized oracle — pin the degenerate-mode equivalences
    def test_unified_reference_subsumes_old(self):
        q, k, v, lens, ql = _wave()
        np.testing.assert_allclose(
            np.asarray(masked_verify_reference(q, k, v, lens, ql)),
            np.asarray(ragged_masked_reference(q, k, v, lens, ql)),
            atol=0, rtol=0)
        np.testing.assert_allclose(
            np.asarray(masked_decode_reference(q[:, 0], k, v, lens)),
            np.asarray(ragged_masked_reference(
                q[:, :1], k, v, lens,
                np.ones_like(lens)))[:, 0],
            atol=0, rtol=0)


# ------------------------------------------------------------------- #
# engine: one ragged wave per step, token-identical to phase-split
# ------------------------------------------------------------------- #


def _rand_gpt(name="rg", L=2, H=2, Dh=8, V=61, S=64, seed=0):
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


# greedy and sampled, short and long prompts, a prompt longer than the
# chunk size, and more requests than slots (queue + requeue pressure)
TRACE = [([7, 8, 9], 6, 0.0, 0), ([3, 4], 8, 0.0, 0),
         ([1, 2, 3, 4, 5], 4, 0.0, 0), ([11], 7, 0.0, 0),
         ([7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17], 5, 0.0, 0),
         ([2, 3], 6, 0.9, 5), ([9, 9, 9], 5, 0.7, 3)]


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


def _run(params, cfg, **kw):
    reqs = [Request(prompt=pr, max_new_tokens=n, temperature=t,
                    top_k=k, seed=i)
            for i, (pr, n, t, k) in enumerate(TRACE)]
    eng = ServingEngine(params, cfg, slots=4, **kw)
    res = eng.run(reqs)
    return sorted(r.tokens.tolist() for r in res.values()), eng


@pytest.mark.smoke
class TestMixedModeEngine:
    @pytest.mark.parametrize("cfg_kw", [
        dict(paged=False),
        dict(paged=False, kv_quant="int8"),
        dict(paged=True, kv_block=8),
        dict(paged=True, kv_block=8, prefill_chunk=4, kv_quant="int8"),
        dict(paged=True, kv_block=8, prefix_share=True, prefill_chunk=4),
    ], ids=["contig", "contig-int8", "paged", "paged-chunk-int8",
            "paged-prefix-chunk"])
    def test_token_identity_vs_phase_split(self, model, cfg_kw):
        p, cfg = model
        base, _ = _run(p, cfg, ragged=False, **cfg_kw)
        mix, eng = _run(p, cfg, ragged=True, **cfg_kw)
        assert eng.ragged
        assert base == mix

    def test_spec_decode_composes(self, model):
        p, cfg = model
        kw = dict(paged=True, kv_block=8, kv_quant="int8",
                  prefill_chunk=4)
        plain, _ = _run(p, cfg, ragged=False, **kw)
        mix, eng = _run(p, cfg, ragged=True, spec=2, **kw)
        assert eng.spec_k == 2 and eng.spec_waves > 0
        assert plain == mix

    def test_chunk_stall_folds_to_zero(self, model):
        p, cfg = model
        _, eng = _run(p, cfg, ragged=True, paged=True, kv_block=8,
                      prefill_chunk=4)
        cs = eng.metrics.components["chunk_stall_ms"]
        assert cs and all(v == 0.0 for v in cs)
        # kept in the schema for back-compat dashboards
        snap = eng.metrics.snapshot()
        assert snap["components"]["chunk_stall_ms"]["p99_ms"] == 0.0
        rep = eng.metrics.explain_tail()
        assert rep["mixed_mode"] and "mixed-mode" in rep["summary"]

    def test_serve_step_carries_mode_split(self, model):
        p, cfg = model
        # prefix_share off: every prompt token is then COMPUTED in some
        # wave, so the q_prefill ledger must sum to the trace exactly
        # (shared prefixes would legitimately skip their cached tokens)
        _, eng = _run(p, cfg, ragged=True, paged=True, kv_block=8,
                      prefix_share=False)
        steps = [e for e in eng.metrics.events
                 if e["event"] == "serve_step"]
        assert steps
        assert all({"q_prefill", "q_verify", "q_decode"} <= set(e)
                   for e in steps)
        assert sum(e["q_prefill"] for e in steps) == \
            sum(len(pr) for pr, *_ in TRACE)
        assert sum(e["q_decode"] for e in steps) > 0

    def test_env_resolution(self, monkeypatch, model):
        for val, want in [("1", True), ("mixed", True), ("ragged", True),
                          ("0", False), ("phase", False), ("off", False)]:
            monkeypatch.setenv("HETU_SERVE_RAGGED", val)
            assert resolve_serve_ragged() is want, val
        monkeypatch.setenv("HETU_SERVE_RAGGED", "auto")
        assert resolve_serve_ragged() is False   # CPU backend
        assert resolve_serve_ragged(True) is True
        monkeypatch.setenv("HETU_SERVE_RAGGED", "1")
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=4, paged=True, kv_block=8)
        assert eng.ragged and eng.metrics.mixed_mode
