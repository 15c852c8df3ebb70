"""ONE ragged mixed-mode attention kernel for the whole serving hot
loop (ISSUE 18, Ragged Paged Attention lineage).

A scheduler split by phase needs three kernel families an iteration —
flash prefill for admissions, a decode kernel for continuing streams, a
verify kernel for speculative waves — with a scheduling barrier between
the phases.  This module is all three:
every slot in a wave carries its OWN ``q_len`` (1 for decode, k+1 for
spec-verify, a chunk of prompt for prefill/chunked-prefill), and one
kernel call scores the whole mixed wave.  Mechanically:

  - grid (slot, q-tile, kv-block), kv innermost, so the online-softmax
    accumulators (one f32 (m, l, acc) row per (head, query)) persist in
    VMEM scratch across a q-tile's kv steps; the q-block is tiled so
    that VMEM is bounded by ``_MAX_ROWS`` (head, query) rows whatever
    the prompt length — a 1024-token prompt in one q-block is eight
    128-query tiles at 12 heads, not one 18 MB allocation;
  - per-slot ``q_len``/``kv_len``/block-table rows ride in as SCALAR
    PREFETCH so the kv block-index maps can see them;
  - blocks wholly past a slot's filled length REVISIT its last live
    block (a repeated index skips the DMA — flash_attention's
    ``_causal_kv_index`` trick) and their compute is skipped with
    ``@pl.when``; q-tiles wholly past a slot's ``q_len`` stay on that
    block too, so a wave's KV traffic is O(sum(kv_len)) per live
    q-tile, not O(B * S_max);
  - scores and the output accumulate in f32 over bf16 pools;
  - the int8 twin takes per-(position, head) scale planes on the same
    revisit index maps and dequantizes INSIDE the online-softmax loop
    (no f32 pool is ever materialized);
  - ``q_len = 1`` degenerates exactly to a one-query decode mask, so
    a decode-only wave pays no mixed-mode tax.

The bullets above are the BLOCKED body (``_ragged_kernel``), the int8
block-table pool's.  The float block-table pool — the engine's
production layout, the one a benchmark cell serves from — is read in
place by ``_kv_rows_kernel``
under the same wrapper and the same HLO name: pool rows of whole lane
tiles left in HBM, grid (slot, q-tile), pages copied by hand a group at
a time with the layer in the copy, the same per-slot data (q_len,
kv_len, tables), mask, dead-tile rule and f32 online softmax (see the
comment block over it), every live (slot, q-tile) step scored whole.
That is the DENSE entry (``ragged_paged_attention``), for a wave whose
rows lie as q-blocks ``[B, Q]``: every decode and verify wave, and a
chunk wave too small to pack.  A PACKED chunk wave's rows
(``gpt_decode._Rows``) go to ``ragged_paged_attention_rows`` as they
lie (ISSUE 54: ``_kv_rows_packed_kernel``, the same page pipeline,
mask and softmax step over a grid of the packed rows' tiles, each
visiting the slots whose rows cross it, under the same HLO names; a
visit is scored at the height its rows need, ``row_tile_visits``: a
decoding slot's one row beside a prompt chunk costs one sublane tile of
queries, not the tile's 64): which entry a wave takes is the caller's
``rows is not None``, nothing else.
ONE masked-gather reference (``ragged_masked_reference``) serves them
all for off-TPU interpret-mode parity.

The kernel reads the q-block's own K/V back from the pool (the
engine's mixed step writes before it attends), so a lossy cache dtype
(bf16/int8) round-trips prefill chunks as it round-trips decode/verify
positions; the masked engine path (``_mixed_step``'s ``has_fresh``)
scores a chunk's own rows before their round-trip instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kv_layout import kv_heads, kv_row_width, kv_rows
from .flash_attention import NEG_INF, _fit_block, _prec
from ._shared import _LANES, _use_interpret


# (head, query) accumulator rows one q-tile may hold: the (m, l, acc)
# scratch, the score block and the q/o tiles all scale with it.  The
# BLOCKED body (the int8 pool: K/V tiles of ``[bs, H, Dh]``): a
# q-block of up to 3200 rows (25 heads x 128 queries; 12 x 256 is
# 3072) compiled for v5e's 16 MiB scoped VMEM as
# ONE tile before there were tiles, and still is one; longer q-blocks,
# which the compiler refused, are cut into tiles of at most 2048 rows
# (3072-row tiles of an f32 cache are refused once there is more than
# one of them).  The ROWS kernel over the paged float pool
# (``[L, N, bs, W]``, below) always cuts at ``_MAX_ROWS``: its (m, l,
# acc) rows are 128 lanes wide for two heads of 64, and beside them it
# holds two buffers each of a group's K and V pages (16 pages x 16 x
# 1664 x 2 B = 0.85 MB a buffer at 25 heads, 3.4 MB in all); 25 heads
# give tiles of 64 queries, 12 and 16 heads of 128, every q-block from
# 1 to 1024 compiled for v5e at those widths (tests/test_chip_compile).
_ONE_TILE_ROWS = 3200
_MAX_ROWS = 2048


def _q_tile(Q, H):
    """Queries per q-tile: all of ``Q`` while its (head, query) rows fit
    ``_ONE_TILE_ROWS``, else the largest divisor of ``Q`` whose rows fit
    ``_MAX_ROWS``."""
    if H * Q <= _ONE_TILE_ROWS:
        return Q
    return _fit_block(max(_MAX_ROWS // H, 1), Q)


def _visible_end(lens_ref, qlens_ref, b, t, tq):
    """One past the last kv position q-tile ``t`` of slot ``b`` can
    see.  A tile that reaches the q-block's dead tail sees the whole
    filled prefix (dead rows clip to the last live position); a wholly
    live tile stops at its own last query (causality), so its
    above-diagonal kv blocks are never fetched or scored."""
    filled = lens_ref[b]
    return jnp.minimum(filled, filled - qlens_ref[b] + (t + 1) * tq)


def _live_tile(qlens_ref, b, t, tq):
    """Whether q-tile ``t`` of slot ``b`` holds a live query.  Tile 0
    always does (an empty slot still finalizes to zeros through it)."""
    return t * tq < jnp.maximum(qlens_ref[b], 1)


def _kv_step_block(lens_ref, qlens_ref, b, t, j, tq, bk):
    """The slot-local kv block that grid step (b, t, j) has resident.  A
    live tile walks its visible blocks and then REVISITS the last one
    (a repeated index skips the DMA).  A tile wholly in the q-block's
    dead tail scores nothing, so it stays on the block the last live
    tile ended on — the slot's final live block — for every ``j``: a
    decode slot (q_len 1) riding in a 1024-wide wave fetches its KV
    once, not once per q-tile."""
    end = _visible_end(lens_ref, qlens_ref, b, t, tq)
    last = jnp.maximum(end - 1, 0) // bk
    return jnp.where(_live_tile(qlens_ref, b, t, tq),
                     jnp.minimum(j, last), last)


def _query_positions(filled, qlen, nq, q0=0):
    """Absolute position of each query in a slot's q-block:
    query ``jq`` sits at ``filled - qlen + jq`` (``q0`` is the index of
    this tile's first query when the q-block is tiled); dead queries
    (``jq >= qlen``) clip to the last live position so their (discarded)
    softmax rows stay finite, and a fully-inert slot (filled 0) clips
    to 0 — the ``l == 0`` finalize guard zeroes its output anyway."""
    qidx = jax.lax.broadcasted_iota(jnp.int32, (1, nq, 1), 1)
    return jnp.clip(filled - qlen + q0 + qidx, 0,
                    jnp.maximum(filled - 1, 0))


def _online_softmax_multi(q, k, v, filled, qlen, j, bk, scale, m_ref,
                          l_ref, acc_ref, q0=0):
    """One KV block's contribution to a q-block's online softmax:
    ``q`` [Q, H, Dh] against ``k``/``v`` [bk, H, Dh], one accumulator
    row per (head, query).  The causal mask inside the q-block falls out
    of the per-query absolute positions — query jq admits kv positions
    up to ``filled - qlen + jq``, which for qlen=1 degenerates to the
    single-query kernel's ``< filled`` mask."""
    Q, H, Dh = q.shape
    R = H * Q
    # s[h, qj, s] = q[qj, h] . k[s, h] — batched over heads
    qt = jnp.swapaxes(q, 0, 1)                            # [H, Q, Dh]
    s = jax.lax.dot_general(
        qt, k, (((2,), (2,)), ((0,), (1,))),
        precision=_prec(q.dtype),
        preferred_element_type=jnp.float32) * scale       # [H, Q, bk]
    kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (H, Q, bk), 2)
    posq = _query_positions(filled, qlen, Q, q0)          # [1, Q, 1]
    s = jnp.where(kv_pos <= posq, s, NEG_INF)
    s = s.reshape(R, bk)
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - safe_m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(jnp.clip(m_prev - m_new, max=0.0))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(H, Q, bk).astype(v.dtype), v,
        (((2,), (0,)), ((0,), (1,))),
        precision=_prec(v.dtype),
        preferred_element_type=jnp.float32)               # [H, Q, Dh]
    acc_ref[:] = acc_ref[:] * alpha + pv.reshape(R, Dh)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _verify_finalize(o_ref, m_ref, l_ref, acc_ref, nq, heads, dh):
    l = l_ref[:, 0:1]
    denom = jnp.where(l == 0.0, 1.0, l)
    o = (acc_ref[:] / denom).reshape(heads, nq, dh)
    o_ref[0] = jnp.swapaxes(o, 0, 1).astype(o_ref.dtype)


def _ragged_kernel(lens_ref, qlens_ref, bt_ref, q_ref, k_ref, ks_ref,
                   v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
                   bk, n_kv, tq):
    """The blocked mixed-mode body, over int8 pages with their scale
    planes (``bt_ref``, the block tables, is read by the index maps
    alone).  Everything mode-specific is per-slot DATA (q_len, kv_len),
    never a code path: a decode slot is q_len=1, a spec-verify slot
    k+1, a prefill chunk its chunk width, all in the same wave."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qlen = qlens_ref[b]
    # blocks past what this tile can see are dead: their DMA was
    # already skipped by the revisit index map; skip the compute too.
    # Tiles wholly inside the dead tail (past q_len) fetch and score
    # nothing and finalize to zeros.
    end = _visible_end(lens_ref, qlens_ref, b, t, tq)

    @pl.when(_live_tile(qlens_ref, b, t, tq) & (j * bk < end))
    def _compute():
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][..., None]
        _online_softmax_multi(q_ref[0].astype(jnp.float32), k, v,
                              lens_ref[b], qlen, j, bk, scale, m_ref,
                              l_ref, acc_ref, q0=t * tq)

    @pl.when(j == n_kv - 1)
    def _finalize():
        _verify_finalize(o_ref, m_ref, l_ref, acc_ref, tq,
                         q_ref.shape[2], q_ref.shape[3])


def _ragged_paged_blocked(q, pool_k, pool_v, lengths, q_lens,
                          block_tables, k_scale, v_scale, interpret):
    """The int8 pool's path (ONE layer, ``[N_blocks, bs, H, Dh]`` int8
    with ``[N_blocks, bs, H]`` f32 scales): a page is a grid block,
    grid (slot, q-tile, page), ``_ragged_kernel`` dequantizing inside
    the online-softmax loop.  In no benchmark cell; see
    :func:`ragged_paged_attention` for why it did not move."""
    B, Q, H, Dh = q.shape
    bs = pool_k.shape[1]
    T = block_tables.shape[1]
    tq = _q_tile(Q, H)

    def block(b, t, j, lens_ref, qlens_ref, bt_ref):
        return bt_ref[b, _kv_step_block(lens_ref, qlens_ref, b, t, j,
                                        tq, bs)]

    def kv_idx(b, t, j, *refs):
        return (block(b, t, j, *refs), 0, 0, 0)

    def sc_idx(b, t, j, *refs):
        return (block(b, t, j, *refs), 0, 0)

    q_spec = pl.BlockSpec((1, tq, H, Dh), lambda b, t, j, *_: (b, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Q // tq, T),
        in_specs=[q_spec,
                  pl.BlockSpec((1, bs, H, Dh), kv_idx),
                  pl.BlockSpec((1, bs, H), sc_idx),
                  pl.BlockSpec((1, bs, H, Dh), kv_idx),
                  pl.BlockSpec((1, bs, H), sc_idx)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H * tq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((H * tq, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((H * tq, Dh), jnp.float32),      # output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, scale=Dh ** -0.5, bk=bs,
                          n_kv=T, tq=tq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, H, Dh), q.dtype),
        name="ragged_paged_mixed",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_lens.astype(jnp.int32),
      block_tables.astype(jnp.int32), q, pool_k, k_scale, pool_v, v_scale)


# ------------------- the paged K/V pool, read in place ------------------- #
#
# The pool pair is ``[L, N_blocks, bs, W]``: one ROW a position, head h in
# lanes ``[h * Dh, (h + 1) * Dh)``, ``W = kv_row_width(H, Dh)`` the next
# multiple of the 128 lanes (GPT-2 XL: 25 x 64 = 1600 -> 1664, zeros in
# the pad; 768 and 1024 need none).  Three things follow from the row
# (ledger, PR 30, ``serve-gpt2-xl-batch-closed``: of a 6 s trace 1.995 s
# were ``copy``, 0.912 s ``slice_bitcast_fusion`` and 1.666 s the kernel):
#
#   - ``[.., 25, 64]`` in bf16 tiles to (32, 128), 2.56 x its bytes, so the
#     compiler gave the old pool a block-index-minor default layout and
#     every wave copied the donated pool to the kernel's layout and back.
#     A row that is a whole number of lane tiles has the row-major default
#     layout the kernel reads: the pool is aliased in place.
#   - the pool stays in HBM (``memory_space=pl.ANY``) with the LAYER in the
#     page copy (``pool.at[layer, page]``, the layer a prefetched scalar):
#     no ``pool[layer]`` slice is materialised before each of a model's
#     layers, and all of them share one trace and lowering of the kernel.
#   - a 16-position page handed to the grid as a block cost a grid step
#     each (1,024 steps a layer for a decode wave of 16 slots whatever they
#     held).  The grid is (slot, q-tile); inside a step a loop runs over as
#     many GROUPS of pages as the tile can see, the K and V pages of group
#     g + 1 in flight while group g is scored (``_page_loop``, shared with
#     the latent kernel below).
#
# Heads are split inside the kernel.  The row is scored a lane CHUNK at a
# time (``_lane_chunk``: 128 lanes, two heads of 64): the chunk's heads
# are stacked along the matmul's rows, each with the other heads' lanes
# of the query zeroed, so ONE [G * tq, 128] x [128, span] product scores
# them all with no lane shuffle and no contraction narrower than the MXU;
# ``p @ v`` over the chunk's 128 value lanes gives every stacked row all
# G heads' columns, of which the finalize keeps the row's own.

_PAGE_GROUP = 16


def _lane_chunk(W, Dh):
    """Lanes scored by one matmul: the smallest whole number of lane
    tiles that holds whole heads (128 for a head of 64), or the whole
    row where that does not divide it (tiny test widths)."""
    cw = math.lcm(_LANES, Dh)
    return cw if W % cw == 0 else W


def _page_group(T, bs, W, dtype):
    """Pages copied and scored together: ``_PAGE_GROUP``, fewer where
    the table is narrower or a group's K (or V) buffer would pass 1 MiB
    (two kinds x two buffers are then at most 4 MiB of VMEM)."""
    page = bs * W * jnp.dtype(dtype).itemsize
    return max(1, min(_PAGE_GROUP, T, (1 << 20) // page))


def tile_heights(q_len, t, tq, short):
    """THE rule of a q-tile's height in the DENSE hand-paged kernels: of
    q-tile ``t`` (``tq`` queries) of a q-block with ``q_len`` live rows,
    whether it is LIVE (holds a live query) and whether it is scored at
    its FULL height, all ``tq`` queries; a live tile whose live rows fit
    the program's ``short`` height (``< tq``) is scored at that height.
    Plain operators, so the latent kernel asks it of a prefetched scalar
    (:func:`mla_tiling`: a decoding slot's one row beside a prompt
    chunk's 256 costs 8 queries of scores, not 64) and the engine's
    counters (``serve.attn.tiles_live``, ``serve.attn.tiles_short``) of
    a wave's ``q_len`` array.  A program with one height (``short`` 0:
    every dense program of the K/V rows kernel, whose second height is
    the PACKED entry's, :func:`row_tile_visits`) never asks:
    :func:`_tile_in_sight`."""
    rows = q_len - t * tq
    return rows > 0, rows > short


def row_tile_visits(start, q_len, t, tq, short):
    """THE rule of a VISIT in the packed kernels, whose q-tiles are tiles
    of ``tq`` PACKED query rows and not of one slot's q-block: row tile
    ``t`` visits every slot whose rows cross it.  Of a slot whose
    q-block is the packed rows ``[start, start + q_len)``: (``lo``,
    ``hi``) its rows inside the tile, counted from the tile's first;
    whether there are any (LIVE); and whether the visit is scored at the
    FULL tile.  A live visit whose rows lie inside one aligned window of
    ``short`` queries (a tile is a whole number of them, so the windows
    are the packed rows' own) is scored at that window: a decoding
    slot's one row costs ``short`` queries of scores wherever it lies.
    Plain operators, as :func:`tile_heights`: the kernels ask it of
    prefetched scalars, their wrappers of the wave's arrays (which slots
    a tile's loop runs over) and the engine's counters of a wave's
    ``q_len`` (``serve.attn.tiles_live``, ``tiles_short``)."""
    lo = start - t * tq
    hi = lo + q_len
    lo = lo * (lo > 0)                      # max(lo, 0)
    hi = hi - (hi - tq) * (hi > tq)         # min(hi, tq)
    live = hi > lo
    if not short:
        return lo, hi, live, live
    return lo, hi, live, live & (lo // short != (hi - 1) // short)


def _short_height(tq, sub):
    """The short height of a program whose q-tiles are ``tq`` queries:
    ``sub`` where that is less than a tile, else 0 (one height)."""
    return sub if sub < tq else 0


def rows_tiling(Q, H, dtype):
    """(padded Q, queries a q-tile) of the K/V rows kernel's DENSE
    program for a q-block of ``Q`` queries of ``H`` query heads in
    ``dtype``: whole sublane tiles of query rows (8 of f32, 16 of bf16)
    in tiles of at most ``_MAX_ROWS`` (head, query) rows, each scored
    whole (ONE height: the second was built for decoding rows beside a
    chunk's, ISSUE 43, and every such wave of 16 slots or more is packed
    since ISSUE 54; an unpacked chunk wave, 8 slots or fewer or a
    capacity router's, scores its live tiles whole)."""
    sub = 32 // jnp.dtype(dtype).itemsize
    Qp = -(-Q // sub) * sub
    return Qp, _fit_block(max(_MAX_ROWS // H, 1), Qp)


# Rows a lane chunk's product must have at the SHORT height for a second
# height to pay.  At GPT-2 XL's width (two heads of 64 a lane chunk, one
# query head a K/V head) the short product is 32 rows against the full
# one's 128, and the MXU's weight loads bound both: a page group cost
# 3.7 us at the one height and 4.4 under two (my chip runs, PR 43; the
# cell lost 4 % of its rate and `attention_chunk_wave_ms` rose 11.6 ->
# 14.3).  With 4 to 8 query heads a K/V head it is 80-128 rows against
# 320-512, 2.5 us a group against 6.1.
_SHORT_MIN_ROWS = 64


def rows_packed_tiling(R, H, head_dim, groups, dtype):
    """(padded R, packed queries a row tile, short window) of the K/V
    rows kernel's PACKED program for ``R`` packed rows of ``H`` query
    heads of ``head_dim``, ``groups`` a K/V head: :func:`rows_tiling`
    asked of the packed rows as of one q-block (the dense entry's tile
    at the same widths), and a second height of one sublane tile of
    queries where a tile is a whole number of them (the window starts
    where a slot's rows lie: a traced, aligned start) and a lane chunk's
    product at it still has ``_SHORT_MIN_ROWS`` rows."""
    Rp, tq = rows_tiling(R, H, dtype)
    short = _short_height(tq, 32 // jnp.dtype(dtype).itemsize)
    cw = _lane_chunk(kv_row_width(H // groups, head_dim), head_dim)
    rows = min(cw // head_dim, H // groups) * groups * short
    if not short or tq % short or rows < _SHORT_MIN_ROWS:
        return Rp, tq, 0
    return Rp, tq, short


def _tile_in_sight(lens_ref, qlens_ref, tq, bs, group, short=0):
    """This grid step's (slot, q-tile, groups of ``group`` pages the tile
    can see, last page in sight, whether it is scored at the full
    height).  A dead slot, and a tile wholly in the q-block's dead tail,
    see no group.  In a program with two heights (``short`` > 0) the
    tile's live rows decide the height (:func:`tile_heights`; a dead
    tile takes the short one, to finalize its zeros), and a slot with
    ``q_len`` 0 is dead whatever it has filled; a program with one keeps
    ``_live_tile`` (and ``full`` is None)."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    span = group * bs
    end = _visible_end(lens_ref, qlens_ref, b, t, tq)
    if short:
        live, full = tile_heights(qlens_ref[b], t, tq, short)
    else:
        live, full = _live_tile(qlens_ref, b, t, tq), None
    live &= end > 0
    return (b, t, jnp.where(live, (end + span - 1) // span, 0),
            jnp.maximum(end - 1, 0) // bs, full)


def _at_heights(heights, part, *args):
    """Run ``part(height, *args)`` at the height this grid step's tile
    takes: ``heights`` = (tile, short height, ``full``): the one there
    is, or of two the one ``full`` (traced) picks."""
    tq, short, full = heights
    if not short:
        part(tq, *args)
        return
    pl.when(full)(lambda: part(tq, *args))
    pl.when(jnp.logical_not(full))(lambda: part(short, *args))


def _first_group(lens_ref, qlens_ref, b, t, tq, span, window):
    """The first group of ``span`` positions q-tile ``t`` of slot ``b``
    can see under a window: the one that holds the oldest position its
    EARLIEST query admits (a tile's first query is live wherever the
    tile is).  Groups before it are neither copied nor scored."""
    first_q = lens_ref[b] - qlens_ref[b] + t * tq
    return jnp.maximum(first_q - window + 1, 0) // span


def _reset(m_ref, l_ref, acc_ref, at=(Ellipsis,)):
    """Empty accumulators in the rows ``at`` (all of them by default)."""
    for ref, fill in ((m_ref, NEG_INF), (l_ref, 0.0), (acc_ref, 0.0)):
        ref[at] = jnp.full(ref.at[at].shape, fill, ref.dtype)


def _visited_slots(start, q_lens, tiles, tq, short):
    """(first, last) slot whose rows cross each of a packed wave's
    ``tiles`` row tiles (:func:`row_tile_visits`): the layout is
    slot-major, so the slots a tile visits are a range (empty, first >
    last, where nobody's rows lie in it)."""
    live = row_tile_visits(start[:, None], q_lens[:, None],
                           jnp.arange(tiles)[None, :], tq, short)[2]
    slot = jnp.arange(len(start))[:, None]
    first = jnp.min(jnp.where(live, slot, len(start)), axis=0)
    last = jnp.max(jnp.where(live, slot, -1), axis=0)
    return first.astype(jnp.int32), last.astype(jnp.int32)


def _visit_in_sight(lens_ref, qlens_ref, start_ref, b, t, tq, bs, group,
                     short, window):
    """Row tile ``t``'s visit to slot ``b`` in the packed kernels, as
    :func:`_tile_in_sight` is a grid step's in the dense ones: (filled,
    q_len, the slot's first packed row, its first row inside the tile,
    whether it is scored at the full tile, groups of ``group`` pages it
    can see, last page in sight, ``grp(gi)`` the group the page loop's
    step ``gi`` copies and scores).  The visit's rows are the rule's
    (:func:`row_tile_visits`); it sees up to the slot's last row in the
    tile (causality: what lies above it is neither copied nor scored)
    and, under a ``window``, from the first group its EARLIEST row in
    the tile admits."""
    span = group * bs
    filled, qlen, start = lens_ref[b], qlens_ref[b], start_ref[b]
    lo, hi, live, full = row_tile_visits(start, qlen, t, tq, short)
    end = filled - qlen + (t * tq + hi - start)
    live &= end > 0
    n_groups = jnp.where(live, (end + span - 1) // span, 0)
    last = jnp.maximum(end - 1, 0) // bs
    grp = lambda gi: gi                                    # noqa: E731
    if window:
        first_q = filled - qlen + jnp.maximum(t * tq + lo - start, 0)
        g0 = jnp.maximum(first_q - window + 1, 0) // span
        n_groups = jnp.maximum(n_groups - g0, 0)
        grp = lambda gi: g0 + gi                           # noqa: E731
    return filled, qlen, start, lo, full, n_groups, last, grp


def _page_loop(n_groups, copies, score):
    """The two-buffer page pipeline both hand-paged kernels run:
    ``copies(gi, buf)`` lists group ``gi``'s async copies into buffer
    ``buf``; group ``gi + 1``'s are started before group ``gi``'s are
    waited for and ``score(gi, buf)`` runs.  ``n_groups`` is traced: a
    dead slot or tile (0) copies and scores nothing."""

    @pl.when(n_groups > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def step(gi, carry):
        buf = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _next():
            for c in copies(gi + 1, 1 - buf):
                c.start()

        for c in copies(gi, buf):
            c.wait()
        score(gi, buf)
        return carry

    jax.lax.fori_loop(0, n_groups, step, 0)


def _mask_scores(s, qi, gi, filled, qlen, window=0):
    """Causal mask of a group's scores ``s`` [R, span]: row r is query
    ``qi[r]`` of the q-block, at absolute position ``filled - qlen +
    qi`` (dead rows clip to the last live position, as
    ``_query_positions``: the dead rows INSIDE a tile's scored height,
    that is; the rows past a short tile's height are scored by nobody
    and come back zero), and admits kv positions up to itself; under a
    ``window`` the band ``posq - window < kv_pos <= posq``."""
    kv_pos = gi * s.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    posq = jnp.minimum(filled - qlen + qi, filled - 1)
    seen = kv_pos <= posq
    if window:
        seen &= kv_pos > posq - window
    return jnp.where(seen, s, NEG_INF)


def _softmax_step(s, v, m_ref, l_ref, acc_ref, at=(slice(None),),
                  precision=None):
    """One group's online-softmax update of the accumulator rows
    ``at``: masked scores ``s`` [R, span] f32 over values ``v``
    [span, dv]; (m, l) are lane-broadcast f32 rows, acc is [R, dv]."""
    m_prev = m_ref[at][:, 0:1]
    l_prev = l_ref[at][:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    alpha = jnp.exp(jnp.clip(m_prev - m_new, max=0.0))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    acc_ref[at] = acc_ref[at] * alpha + pv
    m_ref[at] = jnp.broadcast_to(m_new, m_ref[at].shape)
    l_ref[at] = jnp.broadcast_to(l_new, l_ref[at].shape)


def _kv_copies(k_pool, v_pool, bt_ref, k_buf, v_buf, sem, layer, b, last,
               group, bs, at=lambda gi: gi):
    """``copies(gi, buf)`` of :func:`_page_loop` for slot ``b`` of the K/V
    pool pair: group ``at(gi)``'s K and V page copies into buffer ``buf``
    (``at``: past the groups a window passes over); pages past the
    ``last`` in sight copy that one again (their positions are masked)."""
    def copies(gi, buf):
        out = []
        for g in range(group):
            page = bt_ref[b, jnp.minimum(at(gi) * group + g, last)]
            dst = pl.ds(g * bs, bs)
            out.append(pltpu.make_async_copy(
                k_pool.at[layer, page], k_buf.at[buf, dst], sem.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_pool.at[layer, page], v_buf.at[buf, dst], sem.at[1, buf]))
        return out
    return copies


def _lane_chunks(W, cw, dh, heads):
    """[(lane chunk, K/V heads in it)] of a row ``W`` wide: the row's
    last chunk may hold fewer (GPT-2 XL: head 24 alone beside 64 pad
    lanes, which are never scored)."""
    per = cw // dh
    return [(c, min(per, heads - c * per)) for c in range(W // cw)]


def _own_lanes(shape, g, dh):
    """Lanes of a chunk that are head ``g``'s (of the chunk)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // dh == g


def _lanes_of(c, cw):
    """Lane chunk ``c``'s lanes (``c`` a loop's index, or static)."""
    if isinstance(c, int):
        return slice(c * cw, (c + 1) * cw)
    return pl.ds(pl.multiple_of(c * cw, cw), cw)


def _over_chunks(chunks, per, loop, part, *args):
    """``part(c, n, *args)`` for every lane chunk ``c`` of ``n`` K/V
    heads: one after another, or (``loop``: the short height) a LOOP
    over the chunks of ``per`` heads, so that a chunk program lowers the
    short height's body once and not once a lane chunk (a program is
    traced and lowered at every warm set-up: 1.43 s at GPT-2 XL's 13
    chunks with both heights unrolled where the one height took 0.71,
    sandbox, PR 43)."""
    looped = sum(n == per for _, n in chunks) if loop else 0
    if looped > 1:
        def body(c, carry):
            part(c, per, *args)
            return carry
        jax.lax.fori_loop(0, looped, body, 0)
    else:
        looped = 0
    for c, n in chunks[looped:]:
        part(c, n, *args)


def _kv_rows_kernel(lens_ref, qlens_ref, bt_ref, layer_ref, q_ref, k_pool,
                    v_pool, o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref,
                    *, scale, bs, group, tq, heads, dh, cw, precision,
                    qpk=1, window=0):
    b, t, n_groups, last, _ = _tile_in_sight(lens_ref, qlens_ref, tq, bs,
                                             group)
    # under a window the page loop starts at the first group in sight:
    # ``g0`` groups are passed over, and ``at(gi)`` is the group the
    # loop's step ``gi`` copies and scores
    at = lambda gi: gi                                     # noqa: E731
    if window:
        g0 = _first_group(lens_ref, qlens_ref, b, t, tq, group * bs, window)
        n_groups = jnp.maximum(n_groups - g0, 0)
        at = lambda gi: g0 + gi                            # noqa: E731
    layer = layer_ref[0]
    chunks = _lane_chunks(q_ref.shape[2], cw, dh, heads)
    copies = _kv_copies(k_pool, v_pool, bt_ref, k_buf, v_buf, sem, layer, b,
                        last, group, bs, at)

    def member(r):
        """Rows of the q (or o) tile that hold member ``r`` of every K/V
        head's ``qpk`` query heads (all of the tile where ``qpk`` is 1:
        a query head then IS its K/V head)."""
        return slice(None) if qpk == 1 else slice(r * tq, (r + 1) * tq)

    def score(c, n, gi, buf, filled, qlen):
        """Group ``gi`` scored by the tile's queries of lane chunk
        ``c``."""
        lanes = _lanes_of(c, cw)
        # the chunk's query heads stacked along the rows (K/V head g's
        # ``qpk`` members one after another), each seeing its own K/V
        # head's lanes of the query alone
        qcs = [q_ref[0, member(r), lanes] for r in range(qpk)]
        q2 = jnp.concatenate(
            [jnp.where(_own_lanes(qc.shape, g, dh), qc, 0)
             for g in range(n) for qc in qcs], axis=0)
        s = jax.lax.dot_general(
            q2, k_buf[buf, :, lanes], (((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * scale
        qi = t * tq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) % tq
        _softmax_step(
            _mask_scores(s, qi, at(gi), filled, qlen, window),
            v_buf[buf, :, lanes], m_ref, l_ref, acc_ref,
            at=(c, slice(0, n * qpk * tq)), precision=precision)

    def finalize(c, n):
        rows = slice(0, n * qpk * tq)
        l = l_ref[c, rows, 0:1]
        o2 = acc_ref[c, rows] / jnp.where(l == 0.0, 1.0, l)
        for r in range(qpk):
            oc = jnp.zeros((tq, cw), jnp.float32)
            for g in range(n):
                row = (g * qpk + r) * tq
                oc = jnp.where(_own_lanes(oc.shape, g, dh),
                               o2[row:row + tq], oc)
            o_ref[0, member(r), _lanes_of(c, cw)] = oc.astype(o_ref.dtype)

    _reset(m_ref, l_ref, acc_ref)
    _page_loop(n_groups, copies, lambda gi, buf: _over_chunks(
        chunks, cw // dh, False, score, gi, buf, lens_ref[b], qlens_ref[b]))
    _over_chunks(chunks, cw // dh, False, finalize)


def _kv_rows_scratch(span, W, cw, rows, pool_k, pool_v):
    """The rows kernels' scratch: two buffers each of a group's K and V
    pages, their semaphores, and a lane chunk's (m, l, acc) for the
    ``rows`` stacked rows of a q-tile."""
    return [
        pltpu.VMEM((2, span, W), pool_k.dtype),            # K pages
        pltpu.VMEM((2, span, W), pool_v.dtype),            # V pages
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((W // cw, rows, _LANES), jnp.float32),  # max
        pltpu.VMEM((W // cw, rows, _LANES), jnp.float32),  # denom
        pltpu.VMEM((W // cw, rows, cw), jnp.float32),      # acc
    ]


@functools.partial(jax.jit,
                   static_argnames=("heads", "head_dim", "tq", "interpret",
                                    "qpk", "window"))
def _paged_rows_call(lengths, q_lens, block_tables, layer, qr, pool_k,
                     pool_v, *, heads, head_dim, tq, interpret, qpk=1,
                     window=0):
    """``_kv_rows_kernel`` over query rows ``qr`` [B, Q, W] (``Q`` whole
    sublane tiles) and the pool pair; with ``qpk`` query heads a K/V
    head, ``qr`` is [B, qpk * Q, W], tile by tile the ``qpk`` members'
    ``tq`` rows one after another (``_grouped_rows``).  Jitted, with the layer a traced
    scalar: a model's layers then share ONE trace of the kernel a
    q-block and ONE lowering a program (the calls of one jitted
    function lower to calls of one function).  With a static layer each
    of GPT-2 XL's 48 calls was traced and lowered to Mosaic anew, 28 s a
    program against the blocked kernel's 3.4 (sandbox, PR 31), which the
    warm set-up of a cell pays for each of its 19 programs before the
    compile cache can be asked.  ``window`` > 0 is the banded kernel,
    named ``ragged_paged_window`` in the trace."""
    B, Q, W = qr.shape
    bs = pool_k.shape[2]
    cw = _lane_chunk(W, head_dim)
    group = _page_group(block_tables.shape[1], bs, W, pool_k.dtype)
    rows = cw // head_dim * qpk * tq
    tile = pl.BlockSpec((1, qpk * tq, W), lambda b, t, *_: (b, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Q // (qpk * tq)),
        in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile,
        scratch_shapes=_kv_rows_scratch(group * bs, W, cw, rows, pool_k,
                                        pool_v),
    )
    return pl.pallas_call(
        functools.partial(_kv_rows_kernel, scale=head_dim ** -0.5, bs=bs,
                          group=group, tq=tq, heads=heads, dh=head_dim,
                          cw=cw, precision=_prec(qr.dtype), qpk=qpk,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, W), qr.dtype),
        name="ragged_paged_window" if window else "ragged_paged_mixed",
        interpret=interpret,
    )(lengths, q_lens, block_tables, layer, qr, pool_k, pool_v)


def _grouped_rows(q, W, tq, groups):
    """Query heads ``[B, Q, Hkv * groups, Dh]`` (``Q`` a multiple of
    ``tq``) as the rows the grouped kernel reads, ``[B, groups * Q, W]``:
    member ``r`` of every K/V head's ``groups`` query heads laid out as
    one pool-shaped row (query head ``kvh * groups + r`` in K/V head
    ``kvh``'s lanes), and tile by tile the members' ``tq`` rows one
    after another.  :func:`_ungrouped_rows` is the way back."""
    B, Q, H, Dh = q.shape
    m = q.reshape(B, Q // tq, tq, H // groups, groups, Dh)
    m = m.transpose(0, 1, 4, 2, 3, 5)            # [B, tiles, G, tq, Hkv, Dh]
    return kv_rows(m, W).reshape(B, groups * Q, W)


def _ungrouped_rows(o, heads, head_dim, tq, groups):
    """``[B, groups * Q, W]`` kernel rows back to ``[B, Q, H, Dh]``."""
    B, GQ, _ = o.shape
    Q = GQ // groups
    m = kv_heads(o.reshape(B, Q // tq, groups, tq, -1),
                 heads // groups, head_dim)
    return m.transpose(0, 1, 3, 4, 2, 5).reshape(B, Q, heads, head_dim)


def _pool_row_width(pool, kv_heads_, head_dim):
    """The float pool's row width, which must be the heads' own."""
    W = pool.shape[3]
    if W != kv_row_width(kv_heads_, head_dim):
        raise ValueError(
            f"ragged_paged_attention reads rows of "
            f"{kv_row_width(kv_heads_, head_dim)} lanes for {kv_heads_} K/V "
            f"heads of {head_dim}; the pool's are {W} wide")
    return W


def ragged_paged_attention(q, pool_k, pool_v, lengths, q_lens,
                           block_tables, *, layer=0, k_scale=None,
                           v_scale=None, interpret=None, groups=1,
                           window=0):
    """The mixed wave over the BLOCK-TABLE paged pool — the serving
    engine's production mixed-mode dispatch.

    q: [B, Q, H, Dh]; pool_k, pool_v: the WHOLE pool pair
    ``[L, N_blocks, bs, W]``, one row a position with K/V head h in
    lanes ``[h * Dh, (h + 1) * Dh)`` and ``W = kv_row_width(H // groups,
    Dh)`` (zeros, or anything finite, in the pad), of which the kernel
    reads layer ``layer`` where it lies: a ``pool[layer]`` outside the
    kernel is a copy of that layer's pool a call, and a row that is not
    a whole number of lane tiles makes the compiler relay the whole
    donated pool a wave (the comment block above).  ``groups`` query
    heads read one K/V head (query head ``n`` reads K/V head ``n //
    groups``; 1: as many K/V heads as query heads, the code path there
    was before there were groups, operation for operation): a page is
    copied once for all of them, and a K/V head's ``groups`` members
    are more rows of the same product against its lanes.  block_tables:
    [B, T] int32 — entry (b, j) is the pool block holding slot b's
    positions [j*bs, (j+1)*bs); lengths / q_lens: [B] int32 as in
    :func:`ragged_attention` (dead table entries may hold any valid
    pool index — the engine points them at scratch block 0).  The q-
    block's own rows are already written.  A live q-tile copies the
    pages it can see, ``_page_group`` at a time; dead slots and dead
    tiles copy and score nothing and return zeros; shared prefix blocks
    are fetched per slot but stored once.  Returns o [B, Q, H, Dh] in
    q's dtype (f32 accumulators).

    This is the DENSE entry, for a wave whose rows lie as ``[B, Q]``
    (every decode and verify wave, a chunk wave too small to pack, and
    every wave of the int8 pool); a packed chunk wave's rows over the
    float pool go to :func:`ragged_paged_attention_rows` as they lie.
    Both run under the same names in a trace.

    Every live q-tile (:func:`rows_tiling`) is scored whole, its dead
    rows clipped to the last live position; a dead tile copies and
    scores nothing and returns zeros (no reader takes a dead row:
    ``_Rows.pack`` gathers live rows and every reader masks by
    ``q_len``).  ONE height: the second, a decoding slot's one row
    beside a prompt chunk scored at one sublane tile of queries, is the
    packed entry's (:func:`row_tile_visits`), where such waves go.

    ``window`` > 0 (static) scores a SLIDING WINDOW: a query at position
    ``p`` admits ``p - window < kv <= p``, itself and the ``window - 1``
    before it.  A (slot, q-tile) step then starts its page loop at the
    first group its earliest live query can see (pages before it are
    neither copied nor scored) and masks the band; the table is over
    logical pages as ever, and may list one pool block under several of
    them (a window layer's ring repeated: whatever a later page has
    overwritten lies before every band).  The float pool's kernel
    alone; it is named ``ragged_paged_window``.  0 is the kernel there
    was, operation for operation.

    An int8 pool — ``(pool_k, k_scale)`` = int8 ``[L, N_blocks, bs, H,
    Dh]`` with f32 scales ``[L, N_blocks, bs, H]`` — keeps its shape and
    the blocked kernel (:func:`_ragged_paged_blocked` over
    ``pool[layer]``), chosen by the scales being there.  The int8 page
    itself copies by hand, but Mosaic refuses the scale page ("Slice
    shape along dimension 3 must be aligned to tiling (128), but is 25",
    sandbox AOT for v5e, PR 31): scale planes padded to 128 lanes would
    cost the int8 pool a quarter more bytes a token, and the path is in
    no benchmark cell (nor has it groups)."""
    if interpret is None:
        interpret = _use_interpret()
    B, Q, H, Dh = q.shape
    if H % groups:
        raise ValueError(f"{H} query heads are not {groups} a K/V head")
    if k_scale is not None:
        if groups != 1 or window:
            raise ValueError("the int8 pool's kernel has no groups and "
                             "no window")
        return _ragged_paged_blocked(
            q, pool_k[layer], pool_v[layer], lengths, q_lens,
            block_tables, k_scale[layer], v_scale[layer], interpret)
    W = _pool_row_width(pool_k, H // groups, Dh)
    # whole sublane tiles of query rows (8 of f32, 16 of bf16): a decode
    # wave's single row rides in one, its dead rows scored by no one
    Qp, tq = rows_tiling(Q, H, q.dtype)
    if groups == 1:
        qr = kv_rows(q, W)
        if Qp > Q:
            qr = jnp.pad(qr, ((0, 0), (0, Qp - Q), (0, 0)))
    else:
        if Qp > Q:
            q = jnp.pad(q, ((0, 0), (0, Qp - Q), (0, 0), (0, 0)))
        qr = _grouped_rows(q, W, tq, groups)
    o = _paged_rows_call(
        lengths.astype(jnp.int32), q_lens.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), qr, pool_k, pool_v,
        heads=H // groups, head_dim=Dh, interpret=interpret, tq=tq,
        qpk=groups, window=int(window))
    if groups == 1:
        return kv_heads(o[:, :Q], H, Dh)
    return _ungrouped_rows(o, H, Dh, tq, groups)[:, :Q]


# The PACKED entry (ISSUE 54): a chunk wave's query rows as they lie
# (``gpt_decode._Rows``), the sibling of ``_mla_rows_kernel`` below.  The
# grid is the packed rows' tiles alone; a tile visits the slots whose rows
# cross it (``row_tile_visits``), each visit the slot's page pipeline
# (``_page_loop`` over ``_kv_copies``), ``_mask_scores`` with every row
# that is not the slot's at query index -2^30 and ``_softmax_step``, which
# leaves a wholly masked row as it was: ONE tile-wide (m, l, acc) a lane
# chunk serves every slot of the tile, reset once and finalized once.
#
# The heads stay on the lanes and a K/V head's ``qpk`` members a stack of
# rows, but WINDOW by window: ``_grouped_rows(q[None], W, wq, qpk)`` with
# ``wq`` the short window's queries (the whole tile where the program has
# one height), so the rows of a tile are (window, member, query) and those
# of a lane chunk's accumulators (window, head of the chunk, member,
# query).  A visit at the short height is then ONE slice of q, of the
# scores' rows and of (m, l, acc), at a traced start that is whole sublane
# tiles; a visit at the full height takes all of the tile, and a row's
# products are the dense entry's row by row whatever the order they are
# stacked in.


def _kv_rows_packed_kernel(lens_ref, qlens_ref, bt_ref, layer_ref, start_ref,
                           first_ref, last_ref, q_ref, k_pool, v_pool, o_ref,
                           k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *,
                           scale, bs, group, tq, heads, dh, cw, precision,
                           qpk=1, window=0, short=0):
    """One row tile of the PACKED wave: ``tq`` packed queries of every
    member, whichever slots they belong to (the layout is slot-major, so
    the slots ``first_ref[t] .. last_ref[t]`` that cross it are a
    range).  A row nobody owns comes back zero."""
    t = pl.program_id(0)
    layer = layer_ref[0]
    per = cw // dh
    chunks = _lane_chunks(q_ref.shape[1], cw, dh, heads)
    wq = short or tq            # queries a window
    win = qpk * wq              # rows of q and o a window
    _reset(m_ref, l_ref, acc_ref)

    def visit(b, carry):
        filled, qlen, start, lo, full, n_groups, last, grp = \
            _visit_in_sight(lens_ref, qlens_ref, start_ref, b, t, tq, bs,
                            group, short, window)
        # the short window that holds the slot's rows
        w = lo // short if short else 0

        def score(c, n, hq, gi, buf):
            """Group ``gi`` scored by lane chunk ``c`` of the whole tile
            (``hq`` = ``tq``) or of window ``w``."""
            lanes = _lanes_of(c, cw)
            if hq == tq:
                wins = [slice(i * win, (i + 1) * win)
                        for i in range(tq // wq)]
                first, at = t * tq, slice(0, n * qpk * tq)
            else:
                wins = [pl.ds(pl.multiple_of(w * win, win), win)]
                first = t * tq + w * wq
                at = pl.ds(pl.multiple_of(w * (n * win), n * win), n * win)
            # a window's rows once a K/V head of the chunk, each seeing
            # its own head's lanes of the query alone
            q2 = jnp.concatenate(
                [jnp.where(_own_lanes((win, cw), g, dh), q_ref[rows, lanes],
                           0) for rows in wins for g in range(n)], axis=0)
            s = jax.lax.dot_general(
                q2, k_buf[buf, :, lanes], (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32) * scale
            # row r of what is scored is packed query ``first + r // (n *
            # win) * wq + r % wq``: query ``qi`` of the slot's q-block,
            # or another slot's row, which sees nothing
            r = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
            qi = first - start + r // (n * win) * wq + r % wq
            qi = jnp.where((qi >= 0) & (qi < qlen), qi, -(1 << 30))
            _softmax_step(
                _mask_scores(s, qi, grp(gi), filled, qlen, window),
                v_buf[buf, :, lanes], m_ref, l_ref, acc_ref, at=(c, at),
                precision=precision)

        def over_chunks(hq, gi, buf):
            _over_chunks(chunks, per, hq < tq, score, hq, gi, buf)

        _page_loop(n_groups,
                   _kv_copies(k_pool, v_pool, bt_ref, k_buf, v_buf, sem,
                              layer, b, last, group, bs, grp),
                   lambda gi, buf: _at_heights((tq, short, full),
                                               over_chunks, gi, buf))
        return carry

    jax.lax.fori_loop(first_ref[t], last_ref[t] + 1, visit, 0)

    def finalize(c, n):
        rows = slice(0, n * qpk * tq)
        l = l_ref[c, rows, 0:1]
        o2 = acc_ref[c, rows] / jnp.where(l == 0.0, 1.0, l)
        for i in range(tq // wq):
            oc = jnp.zeros((win, cw), jnp.float32)
            for g in range(n):
                row = (i * n + g) * win
                oc = jnp.where(_own_lanes(oc.shape, g, dh),
                               o2[row:row + win], oc)
            o_ref[i * win:(i + 1) * win, _lanes_of(c, cw)] = oc.astype(
                o_ref.dtype)

    _over_chunks(chunks, per, False, finalize)


@functools.partial(jax.jit,
                   static_argnames=("heads", "head_dim", "tq", "interpret",
                                    "qpk", "window", "short"))
def _packed_rows_call(lengths, q_lens, block_tables, layer, start, qr,
                      pool_k, pool_v, *, heads, head_dim, tq, interpret,
                      qpk=1, window=0, short=0):
    """``_kv_rows_packed_kernel`` over the packed query rows ``qr``
    [qpk * R, W], window by window the ``qpk`` members' rows one after
    another.  Jitted, with the layer a traced scalar, as
    :func:`_paged_rows_call`, and under its names in a trace."""
    GR, W = qr.shape
    bs = pool_k.shape[2]
    cw = _lane_chunk(W, head_dim)
    group = _page_group(block_tables.shape[1], bs, W, pool_k.dtype)
    rows = cw // head_dim * qpk * tq
    tiles = GR // (qpk * tq)
    first, last = _visited_slots(start, q_lens, tiles, tq, short)
    tile = pl.BlockSpec((qpk * tq, W), lambda t, *_: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(tiles,),
        in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile,
        scratch_shapes=_kv_rows_scratch(group * bs, W, cw, rows, pool_k,
                                        pool_v),
    )
    return pl.pallas_call(
        functools.partial(_kv_rows_packed_kernel, scale=head_dim ** -0.5,
                          bs=bs, group=group, tq=tq, heads=heads,
                          dh=head_dim, cw=cw, precision=_prec(qr.dtype),
                          qpk=qpk, window=window, short=short),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((GR, W), qr.dtype),
        name="ragged_paged_window" if window else "ragged_paged_mixed",
        interpret=interpret,
    )(lengths, q_lens, block_tables, layer, start, first, last, qr, pool_k,
      pool_v)


def ragged_paged_attention_rows(q, pool_k, pool_v, lengths, q_lens, start,
                                block_tables, *, layer=0, interpret=None,
                                groups=1, window=0):
    """:func:`ragged_paged_attention` over a PACKED wave's query rows as
    they lie (``gpt_decode._Rows``: slot-major, slot ``b``'s
    ``q_lens[b]`` live rows at ``start[b]``, dead rows at the tail), the
    float pool's kernel under the same names in a trace.

    q: [R, H, Dh]; the pool pair, lengths, q_lens, block_tables,
    ``groups``, ``window`` and the arithmetic as there; ``layer`` may be
    traced.  Its q-tiles are tiles of packed rows
    (:func:`rows_packed_tiling`): the q and o tiles that move are the
    packed rows', ``R / tq`` a call whatever the slots, and a tile visits
    the slots whose rows cross it (:func:`row_tile_visits`: each at the
    full tile, or at the one short window that holds its rows).  A live
    row's result is the dense entry's (the same products row by row, the
    same order of page groups).  Returns o [R, H * Dh] in q's dtype; a
    row no slot owns, and a slot with lengths 0, return zeros."""
    if interpret is None:
        interpret = _use_interpret()
    R, H, Dh = q.shape
    if H % groups:
        raise ValueError(f"{H} query heads are not {groups} a K/V head")
    W = _pool_row_width(pool_k, H // groups, Dh)
    Rp, tq, short = rows_packed_tiling(R, H, Dh, groups, q.dtype)
    if Rp > R:
        q = jnp.pad(q, ((0, Rp - R), (0, 0), (0, 0)))
    wq = short or tq
    # one query head a K/V head: the rows are the heads side by side
    qr = kv_rows(q, W) if groups == 1 else \
        _grouped_rows(q[None], W, wq, groups)[0]
    i32 = lambda x: jnp.asarray(x, jnp.int32)              # noqa: E731
    o = _packed_rows_call(
        i32(lengths), i32(q_lens), i32(block_tables), i32(layer).reshape(1),
        i32(start), qr, pool_k, pool_v, heads=H // groups, head_dim=Dh,
        interpret=bool(interpret), tq=tq, qpk=groups, window=int(window),
        short=short)
    if groups == 1:
        return o[:R, :H * Dh]
    return _ungrouped_rows(o[None], H, Dh, wq, groups)[0, :R].reshape(
        R, H * Dh)


def ragged_masked_reference(q, k, v, lengths, q_lens=None, k_scale=None,
                            v_scale=None):
    """THE masked-gather oracle (f32) — one parameterized reference for
    every mode and layout: decode (q_lens 1), verify (k+1), prefill
    chunks, and any mix, contiguous or gathered-from-pool, f32 or int8
    (dequantized through the per-(position, head) scale planes first).
    ``q_lens=None`` means every row is live (a full q-block).  Query
    ``jq`` of slot b sits at absolute position
    ``lengths[b] - q_lens[b] + jq`` and admits kv positions up to
    itself; rows past ``q_lens[b]`` clip to the last live position so
    their (discarded) softmax stays finite; a slot with lengths 0
    returns zeros."""
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    B, Q = q.shape[:2]
    if q_lens is None:
        q_lens = jnp.full((B,), Q, jnp.int32)
    S = k.shape[1]
    posq = jnp.clip(
        (lengths - q_lens)[:, None] + jnp.arange(Q)[None, :], 0,
        jnp.maximum(lengths - 1, 0)[:, None])              # [B, Q]
    s = jnp.einsum("bqhd,bshd->bqhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    live = jnp.arange(S)[None, None, None, :] <= posq[:, :, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhs,bshd->bqhd", p, v.astype(jnp.float32))
    return out * (lengths > 0)[:, None, None, None]


def ragged_paged_reference(q, pool_k, pool_v, lengths, q_lens,
                           block_tables, k_scale=None, v_scale=None):
    """Gather-then-mask oracle for the block-table mixed kernel:
    materialize each slot's logical [T*bs] KV view from the pool and
    delegate to :func:`ragged_masked_reference`."""
    B = q.shape[0]
    bs = pool_k.shape[1]
    T = block_tables.shape[1]
    k = pool_k[block_tables].reshape(B, T * bs, *pool_k.shape[2:])
    v = pool_v[block_tables].reshape(B, T * bs, *pool_v.shape[2:])
    ks = vs = None
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(B, T * bs, *k_scale.shape[2:])
        vs = v_scale[block_tables].reshape(B, T * bs, *v_scale.shape[2:])
    return ragged_masked_reference(q, k, v, lengths, q_lens, ks, vs)


# --------------------- latent (MLA) mixed wave --------------------- #
#
# Multi-head latent attention in its absorbed form is multi-query
# attention over ONE cached row a token: every head scores the same
# ``[c_kv | k_r]`` row, and the value is the row's own first
# ``value_width`` columns.  So a page is fetched once for all heads and
# once for key and value, the (query, head) pairs of a q-tile are the
# rows of one matmul, and the output stays in latent space.
#
# The pool stays in HBM (``memory_space=pl.ANY``) and the kernel copies
# pages itself: the grid is (slot, q-tile) alone, and inside a step a
# loop runs over as many groups of ``_PAGE_GROUP`` pages as the tile can
# SEE (scalar-prefetched lengths, q-lengths and block tables decide),
# each group's copies started while the last group is scored (two VMEM
# buffers: ``_page_loop``, the K/V kernel's own).  A 16-position page is an 18 KB copy: handed to the grid as
# a block it cost a step's latency each (1.42 ms a layer for 32 decode
# slots at 1,500 positions); sixteen in flight at once cost 0.27 ms, a
# 256-row chunk beside them 4.5 against 11.3 (my chip run, PR 28:
# PERF.md section 6).  Dead slots and dead tiles copy and score nothing.
#
# Two entries, chosen by how the wave's rows lie (static in the caller).
# DENSE, ``ragged_paged_mla``: q-blocks ``[B, Q]``, grid (slot, q-tile):
# every decode and verify wave.  PACKED, ``ragged_paged_mla_rows``
# (ISSUE 46): a chunk wave's packed rows ``[R]``, grid (row tile) alone:
# the q and o tiles that move are the 1,024 packed rows' and not the
# padded block's 8,192, and a tile loops over the slots whose rows cross
# it (``row_tile_visits``), each visit the same page pipeline, mask and
# softmax step with every row that is not the slot's masked.  At the
# long-answer cell's shapes the attention of a chunk wave's seven layers
# took 19.8 ms in the dense form (the query's unpack, two relayouts of
# 210 MB, 128 q and o tiles a call, the pack) and 3.2 packed (my chip
# run, PR 46: PERF.md section 6).

# (query, head) rows one q-tile may hold.  Sized by the sandbox's AOT
# compile for v5e (PR 28): at 20 heads x 640 a tile of 1280 rows (64
# queries) with its f32 accumulator [1280, 512], score block, page
# buffers and double-buffered q and o tiles compiles inside the 16 MiB
# of scoped VMEM; 2560 does not.
_MLA_TILE_ROWS = 1280
# the widest row that tile was sized at
_MLA_TILE_WIDTH = 640


def _mla_q_tile(Q, H, W=0):
    """Queries a q-tile of ``H`` heads: ``_MLA_TILE_ROWS`` rows of up to
    ``_MLA_TILE_WIDTH`` columns, fewer in proportion of a wider row (a
    window layer's 1,152 beside 1,024 value columns: the q and o tiles
    and the accumulator all scale with the width)."""
    cap = _MLA_TILE_ROWS if W <= _MLA_TILE_WIDTH \
        else _MLA_TILE_ROWS * _MLA_TILE_WIDTH // W
    if H * Q <= cap:
        return Q
    return _fit_block(max(cap // H, 1), Q)


# Queries of the latent kernel's short height.  Its rows are query-major
# ((query, head) pairs, all heads of a query together), so the height
# need not be the query dtype's sublane tile.  One call of a chunk wave
# at the long-answer cell's shapes (2 chunks of 256 beside 30 decoding
# slots at 800-2,200 positions; my chip run, PR 43): 4.37 ms at the one
# height of 64 queries, 3.60 with a short height of 16, 3.43 of 8, 3.39
# of 4.  8: the step is by then bound by what does not scale with its
# rows, and 160 rows of 20 heads are whole sublane tiles of bf16.
_MLA_SHORT_QUERIES = 8


def mla_tiling(Q, H, W=0):
    """(queries a q-tile, short height) of the latent kernel's program
    for a q-block of ``Q`` queries of ``H`` heads (rows ``W`` wide)."""
    tq = _mla_q_tile(Q, H, W)
    return tq, _short_height(tq, _MLA_SHORT_QUERIES)


def _mla_copies(pool_ref, bt_ref, kv_buf, sem, layer, b, last, group, bs,
                at=lambda gi: gi):
    """``copies(gi, buf)`` of :func:`_page_loop` for slot ``b`` of the
    latent pool: group ``at(gi)``'s page copies into buffer ``buf``
    (``at``: past the groups a window passes over); pages past the
    ``last`` in sight copy that one again (their positions are masked)."""
    def copies(gi, buf):
        gi = at(gi)
        return [pltpu.make_async_copy(
            pool_ref.at[layer, bt_ref[b, jnp.minimum(gi * group + g, last)]],
            kv_buf.at[buf, pl.ds(g * bs, bs)], sem.at[buf])
            for g in range(group)]
    return copies


def _mla_kernel(lens_ref, qlens_ref, bt_ref, q_ref, pool_ref, o_ref,
                kv_buf, sem, m_ref, l_ref, acc_ref, *, scale, bs, group,
                tq, heads, dv, layer, short=0, window=0):
    b, t, n_groups, last, full = _tile_in_sight(lens_ref, qlens_ref, tq, bs,
                                                group, short)
    # under a window the page loop starts at the first group in sight
    # (as ``_kv_rows_kernel``'s)
    at = lambda gi: gi                                     # noqa: E731
    if window:
        g0 = _first_group(lens_ref, qlens_ref, b, t, tq, group * bs, window)
        n_groups = jnp.maximum(n_groups - g0, 0)
        at = lambda gi: g0 + gi                            # noqa: E731
    copies = _mla_copies(pool_ref, bt_ref, kv_buf, sem, layer, b, last,
                         group, bs, at)

    def rows(hq):
        """The tile's first ``hq`` queries: its first ``hq * heads``
        rows."""
        return (slice(None) if hq == tq else slice(0, hq * heads),)

    def reset(hq):
        """Empty accumulators for ``hq`` queries; a short tile's rows
        past them come back zero."""
        _reset(m_ref, l_ref, acc_ref, rows(hq))
        if hq < tq:
            o_ref[...] = jnp.zeros_like(o_ref)

    def score(hq, gi, buf):
        q = q_ref[(0, *rows(hq))]                         # [R, W]
        kv = kv_buf[buf]                                  # [span, W]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [R, span]
        # row r of the tile is query t*tq + r // heads
        qi = t * tq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // heads
        _softmax_step(_mask_scores(s, qi, at(gi), lens_ref[b],
                                   qlens_ref[b], window),
                      kv[:, :dv], m_ref, l_ref, acc_ref, at=rows(hq))

    def finalize(hq):
        l = l_ref[(*rows(hq), slice(0, 1))]
        o_ref[(0, *rows(hq))] = (acc_ref[rows(hq)] / jnp.where(
            l == 0.0, 1.0, l)).astype(o_ref.dtype)

    heights = (tq, short, full)
    _at_heights(heights, reset)
    _page_loop(n_groups, copies,
               lambda gi, buf: _at_heights(heights, score, gi, buf))
    _at_heights(heights, finalize)


def _mla_interpret(W, interpret):
    """Whether a latent kernel's call is interpreted (``interpret``, or
    the platform's answer); a row ``W`` wide must be whole lane tiles
    wherever it is not: pages are copied by hand."""
    if interpret is None:
        interpret = _use_interpret()
    if W % _LANES and not interpret:
        raise ValueError(
            f"ragged_paged_mla copies pages by hand: the row width {W} "
            f"must be a multiple of {_LANES}")
    return bool(interpret)


def ragged_paged_mla(q, pool, lengths, q_lens, block_tables, *,
                     value_width, scale, layer=0, interpret=None, window=0):
    """The mixed wave over the paged LATENT pool.

    q: [B, Q, H, W] (``[q_lat | q_rope | 0]``, already absorbed); pool:
    [L, N_blocks, bs, W], every layer's rows ``[c_kv | k_r | 0]``, of
    which the kernel takes layer ``layer`` (static) where it lies: a
    ``pool[layer]`` outside the kernel is a copy of that layer's pool a
    wave.  ``W`` is a multiple of the 128 lanes on the TPU (a page is
    copied by hand: ``LatentSpec.row_width`` pads 576 to 640); the
    q-block's own rows are already written; lengths / q_lens /
    block_tables as in :func:`ragged_paged_attention`.  Scores run over
    all ``W`` columns times ``scale``; the value is a row's first
    ``value_width`` columns.  A q-tile is scored at the height its live
    rows need, as in :func:`ragged_paged_attention` (:func:`mla_tiling`
    has the short height: ``_MLA_SHORT_QUERIES`` queries, all their
    heads): a short tile's rows past it come back zero.
    Returns o [B, Q, H, value_width] in q's dtype (f32 accumulators); a
    slot with lengths 0 returns zeros.

    This is the DENSE entry, for a wave whose rows lie as ``[B, Q]``
    (every decode and verify wave, and a chunk wave too small to pack);
    a packed chunk wave's rows go to :func:`ragged_paged_mla_rows` as
    they lie.  Both run under this name in a trace.  ``window`` > 0
    (static) scores a SLIDING WINDOW as :func:`ragged_paged_attention`
    does (the band ``p - window < kv <= p``, the page loop starting at
    the first group in sight, ``block_tables`` the ring repeated), under
    the name ``ragged_paged_mla_window``; 0 is the kernel there was."""
    B, Q, H, W = q.shape
    bs = pool.shape[2]
    group = min(_PAGE_GROUP, block_tables.shape[1])
    tq, short = mla_tiling(Q, H, W)
    interpret = _mla_interpret(W, interpret)
    rows = tq * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Q // tq),
        in_specs=[pl.BlockSpec((1, rows, W), lambda b, t, *_: (b, t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, value_width),
                               lambda b, t, *_: (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group * bs, W), pool.dtype),    # page buffers
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, _LANES), jnp.float32),       # running max
            pltpu.VMEM((rows, _LANES), jnp.float32),       # running denom
            pltpu.VMEM((rows, value_width), jnp.float32),  # output acc
        ],
    )
    o = pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, bs=bs, group=group,
                          tq=tq, heads=H, dv=value_width, layer=layer,
                          short=short, window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q * H, value_width), q.dtype),
        name="ragged_paged_mla_window" if window else "ragged_paged_mla",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_lens.astype(jnp.int32),
      block_tables.astype(jnp.int32), q.reshape(B, Q * H, W), pool)
    return o.reshape(B, Q, H, value_width)


def mla_rows_tiling(R, H, dtype, W=0):
    """(packed queries a row tile, short window) of the packed latent
    kernel's program for ``R`` packed rows of ``H`` heads in ``dtype``:
    the dense entry's tile, and a short window of ``_MLA_SHORT_QUERIES``
    queries where a tile is a whole number of them, more than one, and a
    window's ``H`` x queries rows are whole sublane tiles (it starts
    where a slot's rows lie: a traced, aligned start)."""
    tq = _mla_q_tile(R, H, W)
    short = _MLA_SHORT_QUERIES
    sub = 32 // jnp.dtype(dtype).itemsize
    if tq <= short or tq % short or (short * H) % sub:
        short = 0
    return tq, short


def _mla_rows_kernel(lens_ref, qlens_ref, bt_ref, layer_ref, start_ref,
                     first_ref, last_ref, q_ref, pool_ref, o_ref, kv_buf,
                     sem, m_ref, l_ref, acc_ref, *, scale, bs, group, tq,
                     heads, dv, short, window=0, allowed=None):
    """One row tile of the PACKED wave: ``tq`` packed queries x ``heads``
    rows, whichever slots they belong to.  The slots ``first_ref[t] ..
    last_ref[t]`` are visited one after another (the layout is
    slot-major, so they are a range); a visit runs the slot's page
    pipeline and scores the tile, or the one short window that holds the
    slot's rows (:func:`row_tile_visits`), with every row that is not the
    slot's masked: the online softmax leaves such a row as it was.  A
    row nobody owns comes back zero.  ``allowed`` (a float32 ``[R, S]``
    in HBM and a two-buffer scratch for a tile of it: the SELECTED-rows
    form) admits, a packed query, the positions marked 1 alone: a
    group's tile of it is copied beside the group's pages."""
    t = pl.program_id(0)
    span = group * bs
    layer = layer_ref[0]
    _reset(m_ref, l_ref, acc_ref)

    def visit(b, carry):
        filled, qlen, start, lo, full, n_groups, last, grp = \
            _visit_in_sight(lens_ref, qlens_ref, start_ref, b, t, tq, bs,
                            group, short, window)
        # the window's first query, counted from the tile's first
        w = lo // short * short if short else 0

        def rows(hq):
            if hq == tq:
                return (slice(None),)
            return (pl.ds(pl.multiple_of(w * heads, hq * heads),
                          hq * heads),)

        def score(hq, gi, buf):
            at = rows(hq)
            kv = kv_buf[buf]                                  # [span, W]
            s = jax.lax.dot_general(
                q_ref[at], kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [R, span]
            # row r of what is scored is packed query ``first + r //
            # heads``: query ``qi`` of the slot's q-block, or another
            # slot's row, which sees nothing
            first = t * tq + (0 if hq == tq else w)
            qi = first - start + jax.lax.broadcasted_iota(
                jnp.int32, (s.shape[0], 1), 0) // heads
            qi = jnp.where((qi >= 0) & (qi < qlen), qi, -(1 << 30))
            s = _mask_scores(s, qi, grp(gi), filled, qlen, window)
            if allowed is not None:
                # a query's row of the tile for all its heads: [hq, span]
                # spread over the (query, head) rows by a 0/1 product
                al = allowed[1][buf] if hq == tq \
                    else allowed[1][buf, pl.ds(w, hq)]
                spread = (jax.lax.broadcasted_iota(
                    jnp.int32, (hq * heads, hq), 0) // heads
                    == jax.lax.broadcasted_iota(
                        jnp.int32, (hq * heads, hq), 1)).astype(al.dtype)
                s = jnp.where(jnp.dot(
                    spread, al, preferred_element_type=jnp.float32) > 0.5,
                    s, NEG_INF)
            _softmax_step(s, kv[:, :dv], m_ref, l_ref, acc_ref, at=at)

        pages = _mla_copies(pool_ref, bt_ref, kv_buf, sem, layer, b, last,
                            group, bs, grp)
        copies = pages
        if allowed is not None:
            copies = lambda gi, buf: pages(gi, buf) + [   # noqa: E731
                pltpu.make_async_copy(
                    allowed[0].at[pl.ds(t * tq, tq),
                                  pl.ds(grp(gi) * span, span)],
                    allowed[1].at[buf], sem.at[buf])]
        _page_loop(n_groups, copies,
                   lambda gi, buf: _at_heights((tq, short, full), score,
                                               gi, buf))
        return carry

    jax.lax.fori_loop(first_ref[t], last_ref[t] + 1, visit, 0)
    l = l_ref[:, 0:1]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def _mla_rows_selected_kernel(lens_ref, qlens_ref, bt_ref, layer_ref,
                              start_ref, first_ref, last_ref, q_ref,
                              pool_ref, allowed_ref, o_ref, kv_buf, sem,
                              m_ref, l_ref, acc_ref, allowed_buf, **sizes):
    """``_mla_rows_kernel`` handed the positions every packed query may
    read (the argument order of a call with one more input and one more
    scratch)."""
    _mla_rows_kernel(lens_ref, qlens_ref, bt_ref, layer_ref, start_ref,
                     first_ref, last_ref, q_ref, pool_ref, o_ref, kv_buf,
                     sem, m_ref, l_ref, acc_ref,
                     allowed=(allowed_ref, allowed_buf), **sizes)


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "tq",
                                             "short", "interpret",
                                             "window"))
def _mla_rows_call(lengths, q_lens, block_tables, layer, start, qr, pool, *,
                   value_width, scale, tq, short, interpret, window=0,
                   allowed=None):
    """``_mla_rows_kernel`` over the packed query rows ``qr`` [R, H, W].
    Jitted, with the layer a traced scalar, as :func:`_paged_rows_call`:
    a model's layers share one trace and one lowering a program."""
    R, H, W = qr.shape
    bs = pool.shape[2]
    group = min(_PAGE_GROUP, block_tables.shape[1])
    rows = tq * H
    first, last = _visited_slots(start, q_lens, R // tq, tq, short)
    selected = allowed is not None
    span = group * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(R // tq,),
        in_specs=[pl.BlockSpec((rows, W), lambda t, *_: (t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * selected,
        out_specs=pl.BlockSpec((rows, value_width), lambda t, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group * bs, W), pool.dtype),    # page buffers
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, _LANES), jnp.float32),       # running max
            pltpu.VMEM((rows, _LANES), jnp.float32),       # running denom
            pltpu.VMEM((rows, value_width), jnp.float32),  # output acc
        ] + [pltpu.VMEM((2, tq, span), jnp.float32)] * selected,
    )
    more = ()
    if selected:
        # whole groups of positions: a tile of it is copied a group
        S = allowed.shape[1]
        more = (jnp.pad(allowed.astype(jnp.float32),
                        ((0, 0), (0, -S % span))),)
    o = pl.pallas_call(
        functools.partial(
            _mla_rows_selected_kernel if selected else _mla_rows_kernel,
            scale=scale, bs=bs, group=group,
            tq=tq, heads=H, dv=value_width, short=short, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R * H, value_width), qr.dtype),
        name="ragged_paged_mla_sparse" if selected
        else "ragged_paged_mla_window" if window else "ragged_paged_mla",
        interpret=interpret,
    )(lengths, q_lens, block_tables, layer, start, first, last,
      qr.reshape(R * H, W), pool, *more)
    return o.reshape(R, H, value_width)


def ragged_paged_mla_rows(q, pool, lengths, q_lens, start, block_tables, *,
                          value_width, scale, layer=0, interpret=None,
                          window=0, allowed=None):
    """:func:`ragged_paged_mla` over a PACKED wave's query rows as they
    lie (``gpt_decode._Rows``: slot-major, slot ``b``'s ``q_lens[b]``
    live rows at ``start[b]``, dead rows at the tail), under the same
    name in a trace.

    q: [R, H, W] (``R`` packed rows, a whole number of row tiles:
    :func:`mla_rows_tiling`); pool, lengths, q_lens, block_tables, the
    scores, the value and the arithmetic as there; ``layer`` may be
    traced.  Its q-tiles are tiles of packed rows: the q and o tiles
    that move are the packed rows', ``R / tq`` a call whatever the slots,
    and a tile visits the slots whose rows cross it
    (:func:`row_tile_visits`: each at the full tile, or at the one short
    window that holds its rows).  ``window`` as :func:`ragged_paged_mla`
    takes it (``ragged_paged_mla_window`` in a trace).  ``allowed`` ([R,
    S] of 0 / 1, ``S`` the table's positions) is the SELECTED-rows form,
    ``ragged_paged_mla_sparse`` in a trace: packed query ``r`` reads the
    positions of its slot that ``allowed[r]`` marks and no others (a
    dense walk of the slot's pages under the mask: what a chunk's rows
    chose is, together, nearly all their slot holds).  Returns o [R, H,
    value_width]; a row no slot owns, and a slot with lengths 0, return
    zeros."""
    R, H, W = q.shape
    interpret = _mla_interpret(W, interpret)
    tq, short = mla_rows_tiling(R, H, q.dtype, W)
    i32 = lambda x: jnp.asarray(x, jnp.int32)              # noqa: E731
    return _mla_rows_call(
        i32(lengths), i32(q_lens), i32(block_tables), i32(layer).reshape(1),
        i32(start), q, pool, value_width=value_width, scale=float(scale),
        tq=tq, short=short, interpret=interpret, window=int(window),
        allowed=allowed)


def ragged_paged_mla_reference(q, pool, lengths, q_lens, block_tables, *,
                               value_width, scale, layer=0, window=0):
    """Gather-then-mask oracle (f32) for :func:`ragged_paged_mla`, with
    ``ragged_masked_reference``'s conventions (dead rows clip to the
    last live position, where the kernel has zeros past a short tile's
    height; a slot with lengths 0 returns zeros)."""
    B, Q = q.shape[:2]
    bs = pool.shape[2]
    T = block_tables.shape[1]
    kv = pool[layer][block_tables].reshape(B, T * bs, -1).astype(jnp.float32)
    posq = jnp.clip(
        (lengths - q_lens)[:, None] + jnp.arange(Q)[None, :], 0,
        jnp.maximum(lengths - 1, 0)[:, None])              # [B, Q]
    s = jnp.einsum("bqhc,bsc->bqhs", q.astype(jnp.float32), kv) * scale
    kv_pos = jnp.arange(T * bs)[None, None, None, :]
    live = kv_pos <= posq[:, :, None, None]
    if window:
        live &= kv_pos > posq[:, :, None, None] - window
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    out = jnp.einsum("bqhs,bsc->bqhc", p, kv[..., :value_width])
    return out * (lengths > 0)[:, None, None, None]
