"""The mixed ragged wave (ISSUE 18): ONE kernel and ONE engine wave
for the whole serving hot loop.

Kernel tier: ``ragged_paged_attention`` (the block-table pool, float
rows and int8) must match the ONE masked-gather oracle
(``ragged_masked_reference`` behind ``ragged_paged_reference``) on
decode-only, verify-only, prefill-only, and freely mixed ``q_len``
waves — including arbitrarily permuted pools and int8 scale planes,
bf16 pools, dead slots and a call under ``jit`` and under ``scan``.

Engine tier: the load-bearing contract is TOKEN IDENTITY — the engine,
which packs admissions, chunk continuations, spec-verify, and decode
into one wave per step, must emit exactly the tokens offline
``generate_fast`` emits, greedy AND sampled, across
float/int8/chunked/prefix-shared/speculative
configurations, while the ``chunk_stall`` lifecycle component
collapses to exactly 0.

Everything runs on CPU via interpret mode; ``smoke``-tier.
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax
import jax.numpy as jnp

from hetu_tpu.kernels.ragged_attention import (
    ragged_masked_reference, ragged_paged_attention,
    ragged_paged_reference,
)
from hetu_tpu.kv_layout import kv_heads, kv_row_width, kv_rows
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
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


def _paged(q, pk, pv, lens, ql, tables, **kw):
    """``ragged_paged_attention`` over ONE layer's ``[N, bs, H, Dh]``
    pools as the engine holds them: a float pool as lane-dense rows
    ``[1, N, bs, W]`` read in place, an int8 pool (``k_scale`` given)
    with its head axes and scale planes, a layer axis in front."""
    if "k_scale" in kw:
        kw = {n: np.asarray(a)[None] for n, a in kw.items()}
        pk, pv = np.asarray(pk)[None], np.asarray(pv)[None]
    else:
        W = kv_row_width(*pk.shape[2:])
        pk, pv = (kv_rows(jnp.asarray(a), W)[None] for a in (pk, pv))
    return ragged_paged_attention(q, pk, pv, lens, ql, tables,
                                  interpret=True, **kw)


def _quantize(x, axis=-1):
    """Int8 payload + per-(..., head) f32 scale planes."""
    amax = np.abs(x).max(axis=axis) + 1e-6
    scale = (amax / 127.0).astype(np.float32)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale


@pytest.mark.smoke
class TestRaggedKernel:
    # decode-only, spec-verify-only, full-prompt prefill, and freely
    # mixed waves — all one kernel, selected purely by per-slot data;
    # under ``jit`` as the engine's step calls it, and inside a
    # ``lax.scan`` as an offline loop would
    @pytest.mark.parametrize("how", ["eager", "jit", "scan"])
    @pytest.mark.parametrize("qlens", [
        (1, 1, 1, 1), (4, 4, 4, 4), (4, 1, 2, 0), (2, 0, 4, 1)])
    def test_permuted_pool_matches_reference(self, qlens, how):
        q, k, v, lens, ql = _wave(qlens=qlens)
        pk, pv, tables = _to_pool(k, v)
        if how == "scan":
            got = jax.lax.scan(
                lambda c, _: (c, _paged(q, pk, pv, lens, ql, tables)),
                0, None, length=2)[1][1]
        else:
            got = (jax.jit(_paged) if how == "jit" else _paged)(
                q, pk, pv, lens, ql, tables)
        want = ragged_paged_reference(q, pk, pv, lens, ql, tables)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        # the pool gather is the only paged/contiguous difference
        contig = ragged_masked_reference(q, k, v, lens, ql)
        np.testing.assert_allclose(np.asarray(want), np.asarray(contig),
                                   atol=1e-6, rtol=1e-6)

    # (the last: a q-block ONE query wide, a decode-only program)
    @pytest.mark.parametrize("qlens", [
        (4, 1, 2, 0), (1, 1, 1, 1), (4, 4, 4, 4), (1,)],
        ids=["qlens0", "qlens1", "qlens2", "one-query-block"])
    def test_int8_twin_paged(self, qlens):
        if len(qlens) == 1:
            q, k, v, lens, ql = _wave(Q=1, qlens=(1, 1, 1, 0),
                                      lens=(17, 64, 5, 0))
        else:
            q, k, v, lens, ql = _wave(qlens=qlens)
        pk, pv, tables = _to_pool(k, v)
        pk8, pks = _quantize(pk)
        pv8, pvs = _quantize(pv)
        got = _paged(q, pk8, pv8, lens, ql, tables, k_scale=pks,
                     v_scale=pvs)
        want = ragged_paged_reference(q, pk8, pv8, lens, ql, tables,
                                      k_scale=pks, v_scale=pvs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    # a q-block longer than one tile (ISSUE 22: VMEM is bounded by
    # _MAX_ROWS, so a long prompt is several q-tiles): every live row
    # matches the reference exactly as a one-tile block does, wholly
    # dead tiles are skipped and come back zero
    @pytest.mark.parametrize("layout", ["paged", "paged-int8"])
    def test_tiled_q_block_matches_reference(self, layout, monkeypatch):
        from hetu_tpu.kernels import ragged_attention as ra
        monkeypatch.setattr(ra, "_ONE_TILE_ROWS", 8)
        monkeypatch.setattr(ra, "_MAX_ROWS", 8)     # H=2 -> 4-query tiles
        assert ra._q_tile(16, 2) == 4
        q, k, v, lens, ql = _wave(Q=16, qlens=(16, 1, 6, 0),
                                  lens=(40, 33, 6, 0))
        pk, pv, tables = _to_pool(k, v)
        kw = {}
        if layout == "paged-int8":
            pk, ks = _quantize(pk)
            pv, vs = _quantize(pv)
            kw = dict(k_scale=ks, v_scale=vs)
        got = _paged(q, pk, pv, lens, ql, tables, **kw)
        want = ragged_paged_reference(q, pk, pv, lens, ql, tables, **kw)
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
        pk, pv, tables = _to_pool(k, v)
        got = np.asarray(_paged(q, pk, pv, lens, ql, tables))
        assert np.all(got[3] == 0.0)

    def test_bf16_accumulates_f32(self):
        q, k, v, lens, ql = _wave()
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        pk, pv, tables = _to_pool(np.asarray(kb), np.asarray(vb))
        got = _paged(qb, pk, pv, lens, ql, tables)
        assert got.dtype == jnp.bfloat16
        want = ragged_masked_reference(q, k, v, lens, ql)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            atol=3e-2, rtol=3e-2)


# ------------------------------------------------------------------- #
# the paged pool as lane-dense rows, read in place (ISSUE 31)
# ------------------------------------------------------------------- #

# one wave a case: (heads, Q, lens, q_lens, then what differs from the
# defaults of ``_rows_wave``).  A page is 16 positions, the table 20
# pages wide (320 positions), a group of pages 16 x 16 = 256 positions.
ROW_CASES = {
    # the three GPT-2 widths: 768, 1024, 1600 -> 1664 lanes; a chunk, a
    # decode slot, a k+1 verify block and a dead slot in one wave
    "w768-mixed": (12, 8, (300, 257, 5, 0), (8, 1, 5, 0), {}),
    "w1024-mixed": (16, 8, (300, 257, 5, 0), (8, 1, 5, 0), {}),
    "w1664-mixed": (25, 8, (300, 257, 5, 0), (8, 1, 5, 0), {}),
    "w1664-bf16": (25, 8, (300, 257, 5, 0), (8, 1, 5, 0),
                   dict(dtype=jnp.bfloat16, tol=3e-2)),
    # q-blocks of 1, k+1 and a chunk alone
    "decode-only-short": (12, 1, (1, 15, 16, 17), (1, 1, 1, 1), {}),
    "decode-only-group-edge": (12, 1, (255, 256, 257, 320), (1, 1, 1, 1),
                               {}),
    "verify-k+1": (12, 8, (5, 20, 255, 260), (5, 5, 5, 5), {}),
    "chunk": (12, 32, (32, 100, 256, 320), (32, 32, 32, 32), {}),
    "chunk-dead-tail": (12, 32, (17, 272, 40, 9), (17, 23, 1, 9), {}),
    # several q-tiles (8 queries each): a decode and a verify slot have
    # dead tiles, which copy nothing and come back zero
    "tiled-dead-tiles": (12, 32, (288, 256, 41, 0), (32, 1, 9, 0),
                         dict(max_rows=96)),
    "tiled-w1664": (25, 16, (270, 33, 16, 1), (16, 1, 4, 1),
                    dict(max_rows=100)),
    # the layer is an index in the page copy
    "layer-0": (12, 8, (300, 257, 5, 0), (8, 1, 5, 0), dict(layer=0)),
    "layer-last": (12, 8, (300, 257, 5, 0), (8, 1, 5, 0), dict(layer=2)),
    # two slots whose first two pages are the same pool blocks
    "shared-prefix": (12, 8, (40, 37, 300, 290), (8, 5, 1, 1),
                      dict(share=((0, 1, 2), (2, 3, 16)))),
    # large finite values in the pad columns and in scratch block 0
    "pad-and-scratch-garbage": (25, 8, (300, 16, 5, 0), (8, 1, 5, 0),
                                dict(garbage=1e3)),
}

# chunk waves with DECODING slots beside the chunks (ISSUE 43), as the
# dense entry sees them where they are too small to pack: tiles of 32
# queries, each live tile scored whole; a slot's one row, a tile whose
# live rows are exactly one sublane tile (8 of f32, 16 of bf16) and one
# more, a later tile with a short tail (17 of 16 + 1, 33 of 32 + 1), a
# dead slot, and a slot with ``q_len`` 0 whose pages are filled (a chunk
# deferred for a wave).  2 K/V heads of 64 (rows of 128 lanes) under
# ``groups`` query heads each, with and without a window.  (The second
# height such waves were given is the PACKED entry's since ISSUE 54:
# ``PACKED_LAYOUTS`` below.)
_F32 = dict(q_lens=((64, 1, 1, 17, 0, 0), (128, 8, 9, 1, 33, 0)), tol=2e-5)
_BF16 = dict(q_lens=((64, 1, 16, 17, 0, 0), (128, 16, 17, 1, 33, 0)),
             tol=3e-2, dtype=jnp.bfloat16)
for _k, (_g, _win) in enumerate((g, w) for g in (1, 4, 8) for w in (0, 40)):
    # each (groups, window) with both dtypes, one at Q 64 and one at Q 128
    for _i, (_name, _d) in enumerate((("f32", _F32), ("bf16", _BF16))):
        _ql = _d["q_lens"][(_k + _i) % 2]
        _kw = dict(groups=_g, window=_win, max_rows=2 * _g * 32,
                   tol=_d["tol"])
        if "dtype" in _d:
            _kw["dtype"] = _d["dtype"]
        # every slot decodes from (or prefills onto) a prefix; the last
        # has q_len 0 over 40 filled positions, the one before it (where
        # its q_len is 0) is dead
        _lens = tuple(40 if i == len(_ql) - 1 else
                      0 if n == 0 else (300, 257, 100, 290, 310)[i]
                      for i, n in enumerate(_ql))
        ROW_CASES[f"decoding-beside-Q{_ql[0]}-g{_g}-w{_win}-{_name}"] = (
            2 * _g, _ql[0], _lens, _ql, _kw)

# rows of SEVERAL lane chunks in tiles of 32 queries: GPT-2 XL's 13
# chunks, the last with one head beside the pad, and 2 chunks of 2 K/V
# heads x 4 query heads under a window
_LENS = (300, 257, 100, 290, 310, 40)
ROW_CASES.update({
    "decoding-beside-w1664-f32": (
        25, 64, _LENS, (64, 1, 8, 9, 33, 0),
        dict(max_rows=25 * 32, garbage=1e3)),
    "decoding-beside-w1664-bf16": (
        25, 64, _LENS, (64, 1, 16, 17, 33, 0),
        dict(max_rows=25 * 32, dtype=jnp.bfloat16, tol=3e-2)),
    "decoding-beside-w256-g4-w40-f32": (
        16, 64, _LENS, (64, 1, 8, 9, 33, 0),
        dict(max_rows=16 * 32, groups=4, window=40)),
})


def _rows_wave(H, Q, lens, q_lens, *, Dh=64, bs=16, T=20, L=3, layer=1,
               share=(), garbage=None, dtype=np.float32, seed=0, groups=1):
    """A pool pair ``[L, N, bs, W]`` with values in EVERY layer, block
    (scratch block 0 too) and pad column, tables whose dead entries
    point at scratch block 0, and ``share`` = (slot a, slot b, pages)
    triples making b's first pages a's.  ``H`` query heads read ``H //
    groups`` K/V heads."""
    rng = np.random.RandomState(seed)
    B, W = len(lens), kv_row_width(H // groups, Dh)
    N = B * T + 1
    pk = rng.randn(L, N, bs, W).astype(np.float32)
    pv = rng.randn(L, N, bs, W).astype(np.float32)
    if garbage is not None:
        for pool in (pk, pv):
            pool[..., H * Dh:] = garbage
            pool[:, 0] = garbage
    tables = (1 + rng.permutation(N - 1)[:B * T]).reshape(B, T)
    for a, b, pages in share:
        tables[b, :pages] = tables[a, :pages]
    for b, n in enumerate(lens):
        tables[b, -(-n // bs):] = 0
    q = rng.randn(B, Q, H, Dh).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pk, dtype),
            jnp.asarray(pv, dtype), np.asarray(lens, np.int32),
            np.asarray(q_lens, np.int32), tables.astype(np.int32), layer)


def _scored_rows(n, Q, tq):
    """[(first row, rows scored)] of a q-block's tiles with ``n`` live
    rows, written out from the dense kernel's contract and not through
    its rule: every live tile is scored whole, and tile 0 always."""
    return [(t * tq, tq if n > t * tq or t == 0 else 0)
            for t in range(-(-Q // tq))]


@pytest.mark.smoke
class TestPoolRowsKernel:
    @pytest.mark.parametrize("case", list(ROW_CASES), ids=list(ROW_CASES))
    def test_matches_reference_over_the_heads_view(self, case,
                                                   monkeypatch):
        from hetu_tpu.kernels import ragged_attention as ra
        H, Q, lens, q_lens, kw = ROW_CASES[case]
        kw = dict(kw)
        tol = kw.pop("tol", 2e-5)
        max_rows = kw.pop("max_rows", None)
        window = kw.pop("window", 0)
        groups = kw.get("groups", 1)
        if max_rows:
            monkeypatch.setattr(ra, "_MAX_ROWS", max_rows)
        q, pk, pv, lens, q_lens, tables, layer = _rows_wave(
            H, Q, lens, q_lens, **kw)
        got = np.asarray(ragged_paged_attention(
            q, pk, pv, lens, q_lens, tables, layer=layer, groups=groups,
            window=window, interpret=True), np.float32)
        f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
        if groups > 1 or window:
            from test_window_moe import banded_reference
            want = np.asarray(banded_reference(
                jnp.asarray(f32(q)), jnp.asarray(f32(pk)),
                jnp.asarray(f32(pv)), lens, q_lens, tables, layer, groups,
                window))
        else:
            # the oracle sees the same layer as [N, bs, H, Dh], pad dropped
            want = np.asarray(ragged_paged_reference(
                f32(q), kv_heads(f32(pk)[layer], H, 64),
                kv_heads(f32(pv)[layer], H, 64), lens, q_lens, tables))
        assert got.shape == want.shape == q.shape
        sub = 8 if q.dtype == np.float32 else 16
        tq = ra._fit_block(max(ra._MAX_ROWS // H, 1), -(-Q // sub) * sub)
        assert ra.rows_tiling(Q, H, q.dtype) == (-(-Q // sub) * sub, tq)
        for b, n in enumerate(q_lens):
            if lens[b] == 0:
                assert not got[b].any()          # a dead slot: zeros
                continue
            # live rows, and a scored tile's dead rows (clipped to the
            # last live position), are the reference's; dead tiles are
            # zeros
            for at, h in _scored_rows(int(n), Q, tq):
                np.testing.assert_allclose(got[b, at:at + h],
                                           want[b, at:at + h],
                                           atol=tol, rtol=tol)
                assert not got[b, at + h:at + tq].any()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_the_rule_says_what_the_kernel_did(self, dtype, monkeypatch):
        """``tile_heights`` at one height (what the engine's counters ask
        of a dense wave of this kernel) against the kernel's output: a
        live tile is scored WHOLE, its dead rows too (clipped to the
        last live position), whatever its live rows; a dead tile comes
        back zero, but tile 0 of a slot that has filled pages."""
        from hetu_tpu.kernels import ragged_attention as ra
        monkeypatch.setattr(ra, "_MAX_ROWS", 4 * 32)
        sub = 8 if dtype == jnp.float32 else 16
        q_lens = (64, 1, sub, sub + 1, 33, 32 + sub, 0)
        q, pk, pv, lens, q_lens, tables, layer = _rows_wave(
            4, 64, (300, 257, 100, 290, 310, 200, 40), q_lens, groups=2,
            dtype=dtype)
        Qp, tq = ra.rows_tiling(64, 4, dtype)
        assert (Qp, tq) == (64, 32)
        got = np.asarray(ragged_paged_attention(
            q, pk, pv, lens, q_lens, tables, layer=layer, groups=2,
            interpret=True), np.float32)
        live, full = ra.tile_heights(q_lens[:, None], np.arange(2)[None, :],
                                     tq, 0)
        assert live.tolist() == full.tolist() == [
            [1, 1], [1, 0], [1, 0], [1, 0], [1, 1], [1, 1], [0, 0]]
        for b in range(len(q_lens)):
            for t in range(2):
                tile = got[b, t * tq:(t + 1) * tq]
                scored = bool(live[b, t]) or (t == 0 and lens[b] > 0)
                assert tile[:sub].any() == tile[sub:].any() == scored
        assert ra.rows_tiling(1, 4, dtype) == (sub, sub)
        assert ra.rows_tiling(5, 4, dtype) == (sub, sub)

    def test_two_heights_where_the_short_product_has_rows_enough(self):
        """The cells' packed chunk programs (1,024 rows, bf16): 4 to 8
        query heads a K/V head stack 80-128 rows a short product and
        have two heights; GPT-2's one query head a K/V head stacks 32,
        which the MXU's weight loads bound as they bound the full
        height's 128: one height.  The dense programs' tiles are the
        same queries, each scored whole."""
        from hetu_tpu.kernels import ragged_attention as ra
        bf16 = jnp.bfloat16
        for H, Dh, groups, tq, short in (
                (32, 64, 4, 64, 16),        # lfm2
                (32, 128, 8, 64, 16),       # mellum
                (20, 128, 5, 64, 16),       # falcon
                (25, 64, 1, 64, 0),         # XL
                (12, 64, 1, 128, 0)):
            assert ra.rows_packed_tiling(1024, H, Dh, groups, bf16) == (
                1024, tq, short)
            assert ra.rows_tiling(256, H, bf16) == (256, tq)

    def test_a_row_that_is_not_the_heads_width_is_refused(self):
        q, pk, pv, lens, q_lens, tables, _ = _rows_wave(
            12, 1, (5, 5), (1, 1))
        with pytest.raises(ValueError, match="rows of 768 lanes"):
            ragged_paged_attention(q, pk[..., :640], pv[..., :640], lens,
                                   q_lens, tables, interpret=True)

    def test_row_width_and_views(self):
        assert [kv_row_width(h, 64) for h in (12, 16, 25)] == [
            768, 1024, 1664]
        x = np.arange(2 * 3 * 25 * 64, dtype=np.float32).reshape(
            2, 3, 25, 64)
        rows = np.asarray(kv_rows(jnp.asarray(x), 1664))
        assert rows.shape == (2, 3, 1664) and not rows[..., 1600:].any()
        np.testing.assert_array_equal(kv_heads(rows, 25, 64), x)


# ------------------------------------------------------------------- #
# ISSUE 54: the rows kernel's PACKED entry (a chunk wave's rows as they
# lie: ``ragged_paged_attention_rows``)
# ------------------------------------------------------------------- #

# layout: (q_lens, lengths after the wave, what it is there for).  128
# packed float32 rows in row tiles of 32 queries, short windows of 8;
# a page group is 16 pages = 256 positions, the table 20 pages.
PACKED_LAYOUTS = {
    # tile 0 holds slots 0, 1 and 2 whole and the head of slot 3
    "a-tile-crossed-by-four-slots": ((5, 9, 6, 40), (37, 300, 41, 290)),
    # the chunk starts at packed row 3 and crosses one tile more than it
    # fills
    "a-chunk-at-an-unaligned-row": ((1, 1, 1, 64), (257, 18, 300, 310)),
    # slots 1 and 2 are rows 18 and 19: window 2 of tile 0, a traced start
    "a-decode-row-in-a-later-window": ((18, 1, 1), (140, 258, 17)),
    "an-empty-slot-between-two-live": ((9, 0, 12), (100, 77, 270)),
    # tile 1 holds one row, tiles 2 and 3 nobody's
    "a-dead-tail": ((33,), (301,)),
    # under a window of 40 the first group in sight is group 1
    "a-first-group-past-page-0": ((9, 3, 1), (310, 300, 320)),
}


@pytest.mark.smoke
@pytest.mark.parametrize("layout", list(PACKED_LAYOUTS))
@pytest.mark.parametrize("groups", [1, 4, 5, 8])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "window40"])
def test_packed_rows_match_the_reference_and_the_dense_entry(
        monkeypatch, layout, groups, window):
    """Interpret mode, float32: the packed entry against the banded
    oracle and, live row for live row and to the bit, against the dense
    entry over the same slots' q-blocks (the same tile height, the same
    order of page groups); a row nobody owns comes back zero whatever
    the query held.  (Every layout is four slots, the last ones dead, and
    the layouts of one width follow one another: they share its
    programs.)"""
    q_lens, lens = (v + (0,) * (4 - len(v)) for v in PACKED_LAYOUTS[layout])
    _check_packed_rows(monkeypatch, groups * (2 if groups == 1 else 1),
                       groups, window, q_lens, lens)


def _check_packed_rows(monkeypatch, H, groups, window, q_lens, lens):
    from hetu_tpu.kernels import ragged_attention as ra
    from test_window_moe import banded_reference
    Q, R = 64, 128
    monkeypatch.setattr(ra, "_MAX_ROWS", 32 * H)
    monkeypatch.setattr(ra, "_SHORT_MIN_ROWS", 8)
    assert ra.rows_packed_tiling(R, H, 64, groups, jnp.float32) == (R, 32, 8)
    assert ra.rows_tiling(Q, H, jnp.float32) == (Q, 32)
    q, pk, pv, lens, q_lens, tables, layer = _rows_wave(
        H, Q, lens, q_lens, groups=groups, garbage=3.0)
    start = np.cumsum(q_lens) - q_lens
    packed = np.full((R, H, 64), 5.0, np.float32)     # the dead tail too
    for b, n in enumerate(q_lens):
        packed[start[b]:start[b] + n] = np.asarray(q)[b, :n]
    got = np.asarray(ra.ragged_paged_attention_rows(
        jnp.asarray(packed), pk, pv, lens, q_lens, start, tables,
        layer=layer, groups=groups, window=window, interpret=True))
    assert got.shape == (R, H * 64)
    want = np.asarray(banded_reference(q, pk, pv, lens, q_lens, tables,
                                       layer, groups, window))
    dense = np.asarray(ragged_paged_attention(
        q, pk, pv, lens, q_lens, tables, layer=layer, groups=groups,
        window=window, interpret=True))
    for b, n in enumerate(q_lens):
        mine = got[start[b]:start[b] + n].reshape(n, H, 64)
        np.testing.assert_allclose(mine, want[b, :n], atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(mine, dense[b, :n])
    assert not got[q_lens.sum():].any()


@pytest.mark.parametrize("layout", ["a-tile-crossed-by-four-slots",
                                    "a-decode-row-in-a-later-window"])
@pytest.mark.parametrize("H,groups,window", [(25, 1, 0), (16, 4, 40)],
                         ids=["w1664", "w256-g4-window40"])
def test_packed_rows_of_several_lane_chunks(monkeypatch, layout, H, groups,
                                            window):
    """Rows of SEVERAL lane chunks in the packed entry (the short window
    loops over the chunks of whole heads, the full tile takes them one by
    one): GPT-2 XL's 13 chunks, the last with one head beside the pad
    (its second height lowered here: ``_SHORT_MIN_ROWS``), and 2 chunks
    of 2 K/V heads x 4 query heads under a window."""
    q_lens, lens = (v + (0,) * (4 - len(v)) for v in PACKED_LAYOUTS[layout])
    _check_packed_rows(monkeypatch, H, groups, window, q_lens, lens)


def test_packed_rows_tiling_is_the_dense_entrys_at_the_cells_widths():
    """1,024 packed rows at the five cells' widths: the tile is the Q 256
    dense program's, so a live row's products are the dense entry's, and
    the short window one sublane tile of queries (GPT-2 XL: one
    height)."""
    from hetu_tpu.kernels import ragged_attention as ra
    bf16 = jnp.bfloat16
    for H, Dh, groups in ((32, 64, 4), (32, 128, 8), (20, 128, 5),
                          (25, 64, 1), (32, 128, 16)):
        assert ra.rows_packed_tiling(1024, H, Dh, groups, bf16) == (
            1024, ra.rows_tiling(256, H, bf16)[1], 16 if groups > 1 else 0)
    # a tile that is no whole number of short windows has one height
    assert ra.rows_tiling(40, 64, jnp.float32) == (40, 20)
    assert ra.rows_packed_tiling(40, 64, 64, 4, jnp.float32) == (40, 20, 0)


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


@pytest.fixture(scope="module")
def offline(model):
    """TRACE through offline ``generate_fast``, a request at a time:
    greedy requests through the teacher-forced scan; sampled requests
    through offline speculation, which draws the engine's per-request
    stream (``PRNGKey(seed)``, one split a generated token) and emits
    the target's own sequential samples."""
    p, cfg = model
    return sorted(
        (generate_fast(p, cfg, [pr], n, prefill="scan") if t == 0.0 else
         generate_fast(p, cfg, [pr], n, temperature=t, top_k=k, seed=i,
                       spec=1))[0].tolist()
        for i, (pr, n, t, k) in enumerate(TRACE))


@pytest.mark.smoke
class TestMixedModeEngine:
    @pytest.mark.parametrize("cfg_kw", [
        dict(kv_block=8),
        dict(kv_block=8, prefill_chunk=4, kv_quant="int8"),
        dict(kv_block=8, prefix_share=True, prefill_chunk=4),
    ], ids=["paged", "paged-chunk-int8", "paged-prefix-chunk"])
    def test_token_identity_vs_offline(self, model, offline, cfg_kw):
        p, cfg = model
        mix, eng = _run(p, cfg, **cfg_kw)
        assert eng.steps > 0
        assert mix == offline

    # greedy tokens of TRACE served by the mixed ragged wave over the
    # paged pool (fast path, block 8, chunk 4, prefix sharing), in the
    # order of sorted token lists: what the tree before ISSUE 31 served
    # (pool ``[.., H, Dh]``, a page a grid step), f32 and int8 alike
    SERVED = [[1, 2, 3, 4, 5, 32, 32, 32, 32],
              [2, 3, 45, 59, 59, 59, 59, 59],
              [3, 4, 16, 16, 16, 45, 45, 45, 45, 45],
              [7, 8, 9, 9, 9, 9, 9, 9, 1],
              [7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 1, 1, 1, 1, 1],
              [9, 9, 9, 9, 1, 1, 1, 1], [11, 55, 1, 1, 1, 1, 1, 1]]

    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_rows_pool_serves_the_tokens_it_served_before(self, model,
                                                          kind):
        """The pool's rows read in place change no served token: the
        ragged kernel path equals the tree before the change (bf16
        parted from f32 in one token there, and still does) and, where
        the arithmetic is exact enough to compare, the masked path."""
        p, cfg = model
        kw = dict(kv_block=8, prefill_chunk=4,
                  prefix_share=True,
                  **{"f32": {}, "bf16": dict(dtype=jnp.bfloat16),
                     "int8": dict(kv_quant="int8")}[kind])
        reqs = [Request(prompt=pr, max_new_tokens=n, seed=i)
                for i, (pr, n, _, _) in enumerate(TRACE)]

        def served(fast):
            eng = ServingEngine(p, cfg, slots=4, fast_path=fast, **kw)
            assert eng.paged
            if kind != "int8":
                assert eng.kv.cache_k.shape[-1] == 128    # rows, padded
            out = eng.run([Request(prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens,
                                   seed=r.seed) for r in reqs])
            return sorted(r.tokens.tolist() for r in out.values())

        want = [list(t) for t in self.SERVED]
        if kind == "bf16":
            want[0] = [1, 2, 3, 4, 5, 50, 50, 50, 30]
        got = served(True)
        assert got == sorted(want)
        if kind != "bf16":
            assert served(False) == got                   # masked path

    def test_spec_decode_composes(self, model, offline):
        p, cfg = model
        mix, eng = _run(p, cfg, spec=2, kv_block=8,
                        kv_quant="int8", prefill_chunk=4)
        assert eng.spec_k == 2 and eng.spec_waves > 0
        assert mix == offline

    def test_chunk_stall_folds_to_zero(self, model):
        p, cfg = model
        _, eng = _run(p, cfg, kv_block=8, prefill_chunk=4)
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
        _, eng = _run(p, cfg, kv_block=8, prefix_share=False)
        steps = [e for e in eng.metrics.events
                 if e["event"] == "serve_step"]
        assert steps
        assert all({"q_prefill", "q_verify", "q_decode"} <= set(e)
                   for e in steps)
        assert sum(e["q_prefill"] for e in steps) == \
            sum(len(pr) for pr, *_ in TRACE)
        assert sum(e["q_decode"] for e in steps) > 0


# ------------------------------------------------------------------- #
# the sampling window (ISSUE 26): the wave gathers each slot's window
# before the head and samples only the window
# ------------------------------------------------------------------- #


def _all_rows_sample(logits, temperature, top_k, rng_keys, first_row,
                     q_len):
    """The plain reference: the scan the wave ran before ISSUE 26, over
    EVERY row of the padded q-block's logits [B, Q, V], splitting slot
    b's stream at rows ``first_row[b] <= j < q_len[b]``."""
    import jax
    from hetu_tpu.models.gpt_decode import _sample_slot

    def row(keys, j):
        splits = jax.vmap(jax.random.split)(keys)
        do = (j >= first_row) & (j < q_len)
        keys = jnp.where(do[:, None], splits[:, 0], keys)
        tok = jax.vmap(_sample_slot)(logits[:, j], temperature, top_k,
                                     splits[:, 1])
        return keys, (tok, keys)

    _, (toks, after) = jax.lax.scan(row, rng_keys,
                                    jnp.arange(logits.shape[1]))
    return np.asarray(toks).T, np.asarray(after).transpose(1, 0, 2)


# slot: (tokens in the q-block, cache position, first_row); Q = 8, W = 3
WINDOW_WAVE = {
    0: ([5], 6, 0),                            # decode
    1: ([3, 1, 4, 1, 5, 9], 12, 5),            # a prompt's FINAL chunk
    2: ([2, 7, 1, 8, 2, 8, 1, 8], 8, 8),       # a mid-prompt chunk
    3: ([6, 2, 8], 9, 0),                      # a verify block, k = 2
}                                              # slot 4: dead
SAMPLING = {
    "greedy": ([0.0] * 5, [0] * 5),
    "temperature": ([0.8, 1.1, 0.7, 0.9, 1.0], [0] * 5),
    "top_k": ([0.8, 1.1, 0.7, 0.9, 1.0], [5, 3, 7, 4, 2]),
    "mixed-settings": ([0.0, 0.9, 0.0, 1.2, 0.5], [0, 6, 3, 0, 0]),
}


@pytest.mark.smoke
class TestSamplingWindow:
    @pytest.mark.parametrize("sampling", list(SAMPLING))
    def test_window_equals_all_rows(self, model, sampling):
        """One wave holding a decode slot, a final chunk, a mid-prompt
        chunk, a verify block and a dead slot: every row the engine
        reads carries the token and the stream state that sampling ALL
        rows of the q-block gave; empty windows return their key."""
        import jax
        from hetu_tpu.models import gpt_decode as gd
        from hetu_tpu.serving.kv_manager import assemble_mixed_wave
        p, cfg = model
        params = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
        L, H, S = (cfg.num_hidden_layers, cfg.num_attention_heads,
                   cfg.max_position_embeddings)
        B, W, Dh = 5, 3, cfg.hidden_size // H
        cfg_tuple = ("rg", L, H, Dh, S)
        wave = assemble_mixed_wave(
            B, {s: (t, pos, fr, len(t) > 3)
                for s, (t, pos, fr) in WINDOW_WAVE.items()})
        Q = wave["q"]
        assert Q == 8
        rng = np.random.RandomState(3)
        bs, T = 8, S // 8
        shape = (L, B * T + 1, bs, kv_row_width(H, Dh))   # pool rows
        tables = (1 + np.arange(B * T, dtype=np.int32)).reshape(B, T)
        ck = jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
        cv = jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
        temp, topk = (np.asarray(SAMPLING[sampling][0], np.float32),
                      np.asarray(SAMPLING[sampling][1], np.int32))
        keys = np.asarray(jax.vmap(jax.random.PRNGKey)(
            jnp.arange(B) + 11), np.uint32)
        desc = (wave["pos"], wave["tokens"], wave["q_len"])
        fn = gd.serve_mixed_paged_fn(False, "masked", W)
        sampled, _, _, after = fn(
            params, cfg_tuple, ck, cv, tables, *desc,
            wave["first_row"], wave["self_fresh"], temp, topk, keys,
            has_fresh=True)
        sampled, after = np.asarray(sampled), np.asarray(after)
        assert sampled.shape == (B, W) and after.shape == (B, W, 2)

        # the reference: the same step's logits at EVERY row (a window
        # that starts at row 0 and is Q wide), then the old scan
        full, _, _, _ = gd._mixed_step(
            params, cfg_tuple, ck, cv, *desc, np.zeros(B, np.int32),
            wave["self_fresh"], window=Q, block_tables=tables,
            has_fresh=True)
        assert full.shape == (B, Q, cfg.vocab_size)
        want_tok, want_keys = _all_rows_sample(
            full, temp, topk, keys, wave["first_row"], wave["q_len"])

        read = 0
        for b in range(B):
            first, n = int(wave["first_row"][b]), int(wave["q_len"][b])
            for w, j in enumerate(range(first, n)):
                assert sampled[b, w] == want_tok[b, j], (b, j)
                assert np.array_equal(after[b, w], want_keys[b, j]), (b, j)
                read += 1
            if n - first <= 0:        # mid-prompt chunk, dead slot
                assert np.array_equal(after[b], np.tile(keys[b], (W, 1)))
                assert np.array_equal(want_keys[b, Q - 1], keys[b])
        assert read == 1 + 1 + 3      # decode, final chunk, verify block

    @pytest.mark.parametrize("spec", [0, 2], ids=["W1", "W3"])
    def test_chunk_wave_program_never_meets_the_vocabulary(self, model,
                                                           spec):
        """The chunk wave's program at bucket 128, lowered from an
        engine's own state as ``chip_smoke.lowered_mixed_step`` lowers
        the decode wave: no [B, Q, V] tensor, and the loop that sorts
        the vocabulary runs W times, not Q."""
        import jax
        from hetu_tpu.serving.kv_manager import assemble_mixed_wave
        p, cfg = _rand_gpt(S=256)
        eng = ServingEngine(p, cfg, slots=4, kv_block=8, spec=spec or None)
        B, Q, V, W = 4, 128, cfg.vocab_size, spec + 1
        wave = assemble_mixed_wave(B, {0: (list(range(1, 101)), 0, 99, True),
                                       1: ([3], 7, 0, False)})
        assert wave["q"] == Q
        args = [eng.params, eng.cfg_tuple, eng.kv.cache_k, eng.kv.cache_v,
                eng.kv.tables.copy(), wave["pos"], wave["tokens"],
                wave["q_len"], wave["first_row"], wave["self_fresh"],
                eng._temp, eng._topk, eng._keys]
        kw = dict(eng._mixed.keywords, has_fresh=True)
        assert kw["window"] == W
        text = eng._mixed.func.lower(*args, **kw).as_text()
        assert f"{B}x{Q}x{V}x" not in text
        assert f"{B}x{W}x{V}xf32" in text

        def inner_jaxprs(eqn):
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        yield getattr(sub, "jaxpr", sub)

        def eqns_of(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for inner in inner_jaxprs(eqn):
                    yield from eqns_of(inner)

        def sorts(eqn):
            return any(e.primitive.name == "sort"
                       for inner in inner_jaxprs(eqn)
                       for e in eqns_of(inner))

        eqns = list(eqns_of(jax.make_jaxpr(
            lambda *a: eng._mixed.func(*a, **kw),
            static_argnums=(1,))(*args).jaxpr))
        shapes = {getattr(v.aval, "shape", None)
                  for e in eqns for v in e.outvars}
        assert (B, W, V) in shapes and (B, Q, V) not in shapes
        loops = [e.params["length"] for e in eqns
                 if e.primitive.name == "scan" and sorts(e)]
        assert loops == [W]

    def test_same_programs_as_before(self):
        """Warm-up as the benchmark's runner warms up (one request a
        bucket, alone, two tokens), then a run that mixes final chunks,
        mid-prompt chunks and decode slots: the engine holds one
        program for each (Q bucket, has_fresh) it did before ISSUE 26
        and the run builds nothing (the program's own compile watch,
        ``compile.programs``: what the runner counts)."""
        from hetu_tpu import telemetry
        built = telemetry.counter("compile.programs")
        p, cfg = _rand_gpt(name="wnd", V=67)     # programs nobody built
        eng = ServingEngine(p, cfg, slots=4, kv_block=8, prefill_chunk=16)
        programs = eng._mixed.func._cache_size   # one jit, every engine
        before = programs()
        for n in (8, 16):
            eng.run([Request(prompt=((np.arange(n) + n) % 67).tolist(),
                             max_new_tokens=2)])
        # (Q = 1, decode), (Q = 8, chunk), (Q = 16, chunk)
        assert programs() - before == 3
        rng = np.random.RandomState(5)
        reqs = [Request(prompt=rng.randint(1, 67, n).tolist(),
                        max_new_tokens=m, temperature=t, seed=i)
                for i, (n, m, t) in enumerate(
                    [(8, 5, 0.0), (40, 3, 0.0), (16, 6, 0.9),
                     (24, 2, 0.0), (8, 7, 0.0), (32, 4, 0.7)])]
        built_before = built.get()
        res = eng.run(reqs)
        assert len(res) == 6 and eng.prefill_chunks > 6
        assert programs() - before == 3
        assert built.get() == built_before
