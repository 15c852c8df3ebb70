"""Ragged decode- and verify-attention Pallas kernels over a
slot-CONTIGUOUS cache ``[B, S_max, H, Dh]`` (``_decode_step`` and
``_verify_step`` with ``attn="ragged"``).  The serving engine's wave,
paged pool included, is ``kernels/ragged_attention.py``, which computes
both of these at q_len 1 and k+1 and shares this file's online-softmax
update.

One fused decode step attends q_len=1 per cache slot over that slot's
OWN filled prefix.  The masked reference path (``_decode_step``'s
einsum) streams and masks the full padded ``S_max`` for every slot, so
a slot holding 80 tokens in a 2048-position bucket pays ~25x the
attention FLOPs and KV DMA it needs.  This kernel makes the step scale
with actual tokens: grid (slots, kv_blocks), per-slot filled lengths
ride in as SCALAR-PREFETCH (``PrefetchScalarGridSpec``) so the kv
block-index map can see them — blocks wholly past a slot's filled
length map back to its LAST LIVE block (flash_attention's
``_causal_kv_index`` revisit trick: a repeated index skips the DMA
entirely), and their compute is separately skipped with ``@pl.when``.
A slot therefore fetches exactly ``ceil(filled / block_k)`` KV blocks,
and the ragged batch's total traffic is O(sum(filled)) instead of
O(B * S_max).

The online-softmax accumulators (m, l, acc) live in VMEM scratch and
persist across the kv steps of one slot (TPU grids execute
sequentially, kv innermost).  Scores and the output accumulate in f32
regardless of the cache dtype (bf16 caches keep full-precision
softmax), matching the flash prefill kernel's accounting.

Decode is inference-only — no VJP.  On non-TPU backends the kernel
runs in interpret mode, so the same code path is testable on the CPU
harness (parity suite in tests/test_serve_fastpath.py).

INT8 KV (``HETU_KV_QUANT``, Ragged Paged Attention lineage): both
kernels take optional ``k_scale``/``v_scale`` planes — the cache stays
int8 in HBM and dequantizes INSIDE the online-softmax loop (per
(position, head) scales ride the same revisit index maps, so dead
blocks skip their DMA too); no f32 pool is ever materialized, which is
what lets ~3.7x more tokens fit per HBM byte.

MULTI-TOKEN VERIFY (speculative decoding, ISSUE 10): the ``*_verify_*``
kernels generalize q_len=1 to a ``k+1``-position q-block per slot —
the target model's batched verification of a draft's proposals.  Same
grid, same scalar-prefetched lengths, same revisit-index DMA
skipping; the q-block is causal INSIDE itself (query ``jq`` at absolute
position ``lens - q_len + jq`` admits kv positions up to itself), so
one kernel call scores all proposed positions exactly as ``k+1``
sequential decode steps would.  Accumulators widen to one online-softmax
state per (head, query) pair; everything else is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _fit_block, _prec

_LANES = 128


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, bk, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    filled = lens_ref[b]

    # blocks wholly past this slot's filled prefix are dead: their DMA
    # was already skipped by the revisit index map; skip the compute too
    @pl.when(j * bk < filled)
    def _compute():
        # a q-block of ONE query through the verify kernels' update (its
        # mask degenerates to ``kv_pos < filled``).  A dedicated [H, Dh]
        # matvec does not compile for the chip: Mosaic refuses a batched
        # dot whose lhs has no non-contracting dim
        _online_softmax_multi(q_ref[0], k_ref[0], v_ref[0], filled, 1,
                              j, bk, scale, m_ref, l_ref, acc_ref)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _decode_kernel_int8(lens_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, scale, bk,
                        n_kv):
    """Int8 twin of ``_decode_kernel``: the KV blocks arrive as int8
    payloads plus per-(position, head) f32 scales (two extra refs with
    the same revisit index maps, so dead blocks skip the scale DMA
    too), and dequantize to f32 INSIDE the online-softmax loop — the
    HBM traffic is int8, the softmax accounting identical to the f32
    kernel."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    filled = lens_ref[b]

    @pl.when(j * bk < filled)
    def _compute():
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][..., None]
        _online_softmax_multi(q_ref[0].astype(jnp.float32), k, v,
                              filled, 1, j, bk, scale, m_ref, l_ref,
                              acc_ref)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _use_interpret():
    return jax.default_backend() != "tpu"


def paged_decode_attention(q, k, v, lengths, *, block_k=128,
                           k_scale=None, v_scale=None, interpret=None):
    """One decode position per slot over a paged/ragged KV cache.

    q: [B, H, Dh] (this step's query per slot); k, v: [B, S_max, H, Dh]
    (the cache rows, one per slot — the layer's ``cache_k[i]``);
    lengths: [B] int32 — positions 0..lengths[b]-1 of slot b are live
    (the slot's filled count INCLUDING the position just written).
    Returns o [B, H, Dh] in q's dtype.  Each slot fetches only
    ``ceil(lengths[b] / block_k)`` KV blocks; a slot with lengths 0
    returns zeros (matching the masked reference's fully-dead-row
    convention).

    INT8 caches: pass k/v as int8 with ``k_scale``/``v_scale``
    [B, S_max, H] f32 (one scale per position per head — the
    ``HETU_KV_QUANT`` layout); the kernel DMAs int8 and dequantizes
    inside the online-softmax loop.
    """
    B, H, Dh = q.shape
    S = k.shape[1]
    bk = _fit_block(block_k, S)
    n_kv = S // bk
    scale = Dh ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    quantized = k_scale is not None

    def kv_idx(b, j, lens_ref):
        # dead blocks revisit the slot's last live block: the repeated
        # index skips the DMA (same trick as _causal_kv_index)
        last = jnp.maximum(lens_ref[b] - 1, 0) // bk
        return (b, jnp.minimum(j, last), 0, 0)

    def sc_idx(b, j, lens_ref):
        last = jnp.maximum(lens_ref[b] - 1, 0) // bk
        return (b, jnp.minimum(j, last), 0)

    if quantized:
        kernel = _decode_kernel_int8
        in_specs = [
            pl.BlockSpec((1, 1, H, Dh), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H), sc_idx),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H), sc_idx),
        ]
        operands = (q[:, None], k, k_scale, v, v_scale)
    else:
        kernel = _decode_kernel
        in_specs = [
            pl.BlockSpec((1, 1, H, Dh), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
        ]
        operands = (q[:, None], k, v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, H, Dh),
                               lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),   # running max
            pltpu.VMEM((H, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((H, Dh), jnp.float32),       # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, bk=bk, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, Dh), q.dtype),
        name="paged_decode",
        interpret=interpret,
    )(lengths.astype(jnp.int32), *operands)
    return out[:, 0]


# ------------------------------------------------------------------- #
# multi-token verify kernels (speculative decoding)
# ------------------------------------------------------------------- #


def _query_positions(filled, qlen, nq, q0=0):
    """Absolute position of each query in a slot's verify q-block:
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
    """One KV block's contribution to a VERIFY q-block's online softmax:
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


def _verify_kernel(lens_ref, qlens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, bk, n_kv, nq):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    filled = lens_ref[b]

    @pl.when(j * bk < filled)
    def _compute():
        _online_softmax_multi(q_ref[0], k_ref[0], v_ref[0], filled,
                              qlens_ref[b], j, bk, scale, m_ref, l_ref,
                              acc_ref)

    @pl.when(j == n_kv - 1)
    def _finalize():
        _verify_finalize(o_ref, m_ref, l_ref, acc_ref, nq,
                         q_ref.shape[2], q_ref.shape[3])


def _verify_kernel_int8(lens_ref, qlens_ref, q_ref, k_ref, ks_ref,
                        v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        scale, bk, n_kv, nq):
    """Int8 twin of ``_verify_kernel`` (see ``_decode_kernel_int8`` for
    the dequant-inside-the-loop rationale)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    filled = lens_ref[b]

    @pl.when(j * bk < filled)
    def _compute():
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][..., None]
        _online_softmax_multi(q_ref[0].astype(jnp.float32), k, v,
                              filled, qlens_ref[b], j, bk, scale,
                              m_ref, l_ref, acc_ref)

    @pl.when(j == n_kv - 1)
    def _finalize():
        _verify_finalize(o_ref, m_ref, l_ref, acc_ref, nq,
                         q_ref.shape[2], q_ref.shape[3])


def paged_verify_attention(q, k, v, lengths, q_lens, *, block_k=128,
                           k_scale=None, v_scale=None, interpret=None):
    """A ``Q``-position verify q-block per slot over the slot-contiguous
    ragged cache.

    q: [B, Q, H, Dh] — this wave's q-block per slot (the draft's k
    proposals plus the carried token, already written to the cache);
    k, v: [B, S_max, H, Dh]; lengths: [B] int32 — the slot's filled
    count INCLUDING the q-block's live positions; q_lens: [B] int32 —
    live queries per slot (rows jq >= q_lens[b] are inert: their output
    is finite garbage the host discards).  Returns o [B, Q, H, Dh].
    Each slot still fetches only ``ceil(lengths[b] / block_k)`` KV
    blocks; the causal structure inside the q-block is enforced by
    per-query position masks, so the call scores exactly what q_lens[b]
    sequential decode steps would.  Int8 caches: pass
    ``k_scale``/``v_scale`` [B, S_max, H] f32 as in
    :func:`paged_decode_attention`."""
    B, Q, H, Dh = q.shape
    S = k.shape[1]
    bk = _fit_block(block_k, S)
    n_kv = S // bk
    scale = Dh ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    quantized = k_scale is not None

    def kv_idx(b, j, lens_ref, qlens_ref):
        last = jnp.maximum(lens_ref[b] - 1, 0) // bk
        return (b, jnp.minimum(j, last), 0, 0)

    def sc_idx(b, j, lens_ref, qlens_ref):
        last = jnp.maximum(lens_ref[b] - 1, 0) // bk
        return (b, jnp.minimum(j, last), 0)

    if quantized:
        kernel = _verify_kernel_int8
        in_specs = [
            pl.BlockSpec((1, Q, H, Dh),
                         lambda b, j, lens, qlens: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H), sc_idx),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H), sc_idx),
        ]
        operands = (q, k, k_scale, v, v_scale)
    else:
        kernel = _verify_kernel
        in_specs = [
            pl.BlockSpec((1, Q, H, Dh),
                         lambda b, j, lens, qlens: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
            pl.BlockSpec((1, bk, H, Dh), kv_idx),
        ]
        operands = (q, k, v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Q, H, Dh),
                               lambda b, j, lens, qlens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H * Q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((H * Q, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((H * Q, Dh), jnp.float32),       # output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, bk=bk, n_kv=n_kv, nq=Q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, H, Dh), q.dtype),
        name="paged_verify",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_lens.astype(jnp.int32), *operands)


def masked_verify_reference(q, k, v, lengths, q_lens, k_scale=None,
                            v_scale=None):
    """Exact masked oracle (f32) for the verify kernels: per-query
    causal masks over the full padded cache — the same arithmetic
    ``_verify_step``'s einsum path runs.  Now a thin delegate of the
    unified ragged reference (a verify wave IS a ragged wave)."""
    from .ragged_attention import ragged_masked_reference
    return ragged_masked_reference(q, k, v, lengths, q_lens, k_scale,
                                   v_scale)


def masked_decode_reference(q, k, v, lengths, k_scale=None,
                            v_scale=None):
    """Exact masked-``S_max`` oracle (f32) for the parity suite: the
    same arithmetic ``_decode_step``'s einsum path runs, minus the
    compute-dtype shortcuts.  A decode step is the q_len-1 degenerate
    of the unified ragged reference (position ``lengths - 1`` admits
    kv < ``lengths``; a dead slot is zeroed by the same guard), so this
    is now a thin delegate of it."""
    from .ragged_attention import ragged_masked_reference
    ones = jnp.ones_like(lengths)
    return ragged_masked_reference(q[:, None], k, v, lengths, ones,
                                   k_scale, v_scale)[:, 0]
