"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism (SURVEY.md §5.7 — verified
absent); this module is the new-capability requirement for long-context
training.  Two idiomatic TPU designs over a 'cp' mesh axis:

1. ``ring_attention`` — Q stays put, K/V blocks rotate around the ring via
   ``lax.ppermute`` while each device accumulates its attention output with
   online (streaming) softmax, so the full S x S score matrix never
   materializes and sequence length scales linearly with the number of
   devices.  Communication rides ICI neighbor links (ppermute), overlapping
   with the blockwise compute.  Causal masking is applied per (q-block,
   kv-block) pair from the ring offsets, skipping fully-masked blocks'
   contribution numerically (they contribute exp(-inf)=0).

2. ``ulysses_attention`` — all_to_all swaps sequence sharding for head
   sharding ([B, S/cp, H, D] -> [B, S, H/cp, D]), runs ordinary full
   attention per local head group, and swaps back.  Cheaper at moderate S
   (two all_to_alls vs cp ppermute rounds), requires cp | H.

Both are pure jax (differentiable; autodiff through scan/ppermute yields
the reverse ring) and compose with dp/tp axes of the same mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import batch_axis

NEG_INF = -1e30


def _block_attn_update(q, k, v, bias, m, l, o):
    """One streaming-softmax accumulation step.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; bias: [Sq, Sk] additive
    (0 or NEG_INF); m, l: [B, H, Sq]; o: [B, Sq, H, D].
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = s + bias[None, None, :, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard rows where everything so far is masked (m_new == NEG_INF)
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(jnp.clip(m - m_new, max=0.0))
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def _finalize(l, o):
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return o / denom


def ring_attention(q, k, v, *, mesh, axis="cp", causal=False, impl=None,
                   block_q=512, block_k=1024):
    """Blockwise ring attention over sequence-sharded q/k/v.

    Args:
      q, k, v: [B, S, H, D] arrays; the S dim is (or will be) sharded over
        ``axis``.  Pass either global (replicated/sharded jax.Arrays under
        jit) — shard_map slices per device.  If the mesh has a 'dp' axis
        and B divides it, the batch dim is dp-sharded too (replicated
        otherwise).
      causal: apply a causal mask using global positions.
      impl: ``'flash'`` — each rotation's block runs the fused Pallas
        kernel (``flash_attention_with_carry``): the previous rotation's
        (o, lse) partial seeds the kernel's streaming state, so the
        cross-rotation merge happens in the kernel prologue with no
        separate pass; blocks wholly above the causal diagonal are
        SKIPPED (lax.switch), so causal costs ~half the FLOPs.
        ``'exact'`` — unfused streaming-softmax oracle.
        ``None`` — flash on TPU, exact elsewhere (the oracle doubles as
        the CPU-mesh test path; flash still runs there in interpret mode
        when requested explicitly).
      block_q, block_k: flash kernel block sizes (flash impl only).

    Returns [B, S, H, D] attention output, sequence-sharded like q.
    """
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "exact"
    if impl not in ("flash", "exact"):
        raise ValueError(f"ring_attention impl must be 'flash', 'exact' "
                         f"or None (auto), got {impl!r}")
    if impl == "flash":
        return _ring_attention_flash(q, k, v, mesh=mesh, axis=axis,
                                     causal=causal, block_q=block_q,
                                     block_k=block_k)
    cp = mesh.shape[axis]
    S = q.shape[1]
    assert S % cp == 0, f"seq {S} not divisible by cp={cp}"
    blk = S // cp
    bax = batch_axis(mesh, q.shape[0], exclude=axis)

    def per_device(q, k, v):
        # local blocks [B, blk, H, D]
        my = jax.lax.axis_index(axis)
        B, _, H, D = q.shape
        m = jnp.full((B, H, blk), NEG_INF, q.dtype)
        l = jnp.zeros((B, H, blk), q.dtype)
        o = jnp.zeros_like(q)  # varying already (derived from sharded q)
        # carry typing: m/l must vary over every manual axis q varies
        # over, or the scan carry changes type after the first update
        vary = (axis,) if bax is None else (axis, bax)
        m = jax.lax.pcast(m, vary, to="varying")
        l = jax.lax.pcast(l, vary, to="varying")
        shift = [(i, (i + 1) % cp) for i in range(cp)]
        q_pos = my * blk + jnp.arange(blk)

        def step(carry, t):
            k_t, v_t, m, l, o = carry
            # after t rotations we hold the kv block of device (my - t) % cp
            kv_owner = (my - t) % cp
            kv_pos = kv_owner * blk + jnp.arange(blk)
            if causal:
                bias = jnp.where(q_pos[:, None] >= kv_pos[None, :],
                                 0.0, NEG_INF).astype(q.dtype)
            else:
                bias = jnp.zeros((blk, blk), q.dtype)
            m, l, o = _block_attn_update(q, k_t, v_t, bias, m, l, o)
            k_n = jax.lax.ppermute(k_t, axis, shift)
            v_n = jax.lax.ppermute(v_t, axis, shift)
            return (k_n, v_n, m, l, o), None

        (k, v, m, l, o), _ = jax.lax.scan(
            step, (k, v, m, l, o), jnp.arange(cp))
        return _finalize(l, o)

    spec = P(bax, axis, None, None)
    return shard_map(per_device, mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


def _ring_attention_flash(q, k, v, *, mesh, axis, causal, block_q,
                          block_k):
    """Flash-in-ring (VERDICT r2 item 6): every rotation's (q-block,
    kv-block) pair runs the fused Pallas kernel.  Per rotated block
    exactly one of three cases applies, dispatched at runtime on the
    ring offset (lax.switch):

      kv_owner  > mine (causal): fully masked -> skipped outright
      kv_owner == mine (causal): the diagonal -> flash(causal=True)
      otherwise:                 fully live   -> flash(causal=False)

    Block alignment makes the diagonal case plain local causal masking,
    so the kernel needs no offset plumbing.

    Two r4 perf changes (VERDICT r3 item 2):
    * the per-rotation (o, lse) merge is FUSED into the kernel prologue
      — ``flash_attention_with_carry`` seeds the kernel's streaming
      (m, l, acc) state from the previous rotation's partial, so no
      separate elementwise pass over the output runs per rotation;
    * the KV ppermute is issued BEFORE the block compute, so the
      latency-hiding scheduler can run the ICI rotation underneath the
      flash kernel (the next iteration, not this one, consumes it).

    Backward differentiates the chained kernel VJPs (the carry behaves
    as one virtual key row; see _flash_stats_carry_bwd_rule)."""
    from ..kernels.flash_attention import flash_attention_with_carry
    cp = mesh.shape[axis]
    S = q.shape[1]
    assert S % cp == 0, f"seq {S} not divisible by cp={cp}"
    bax = batch_axis(mesh, q.shape[0], exclude=axis)

    def per_device(q, k, v):
        my = jax.lax.axis_index(axis)
        B, blk, H, D = q.shape
        shift = [(i, (i + 1) % cp) for i in range(cp)]
        o0 = jnp.zeros((B, blk, H, D), jnp.float32)
        lse0 = jnp.full((B, H, blk), NEG_INF, jnp.float32)

        def blk_full(k_t, v_t, o, lse):
            return flash_attention_with_carry(
                q, k_t, v_t, o, lse, causal=False,
                block_q=block_q, block_k=block_k)

        def blk_diag(k_t, v_t, o, lse):
            return flash_attention_with_carry(
                q, k_t, v_t, o, lse, causal=True,
                block_q=block_q, block_k=block_k)

        def blk_skip(k_t, v_t, o, lse):
            return o, lse

        def step(carry, t):
            k_t, v_t, o, lse = carry
            # rotation first: independent of the block compute, so the
            # scheduler can overlap the ppermute with the kernel
            k_n = jax.lax.ppermute(k_t, axis, shift)
            v_n = jax.lax.ppermute(v_t, axis, shift)
            kv_owner = (my - t) % cp
            if causal:
                case = jnp.where(kv_owner > my, 2,
                                 jnp.where(kv_owner == my, 1, 0))
            else:
                case = jnp.zeros((), jnp.int32)
            o, lse = jax.lax.switch(
                case, [blk_full, blk_diag, blk_skip], k_t, v_t, o, lse)
            return (k_n, v_n, o, lse), None

        (_, _, o, lse), _ = jax.lax.scan(
            step, (k, v, o0, lse0), jnp.arange(cp))
        return o.astype(q.dtype)

    spec = P(bax, axis, None, None)
    # check_vma off: pallas_call out_shapes carry no varying-axes info
    return shard_map(per_device, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ulysses_attention(q, k, v, *, mesh, axis="cp", causal=False,
                      attn_fn=None):
    """DeepSpeed-Ulysses-style: all_to_all seq<->head, full local attention.

    q, k, v: [B, S, H, D] with S sharded over ``axis``; requires cp | H.
    The batch dim additionally shards over a 'dp' mesh axis when B
    divides it.  ``attn_fn(q, k, v, causal)`` may override the local
    attention (e.g. the Pallas flash kernel); default is exact softmax
    attention.
    """
    cp = mesh.shape[axis]
    B, S, H, D = q.shape
    assert H % cp == 0, f"heads {H} not divisible by cp={cp}"

    if attn_fn is None:
        def attn_fn(q, k, v, causal):
            # lazy import: single source of the exact-attention math
            from ..kernels.flash_attention import mha_reference
            return mha_reference(q, k, v, causal=causal)

    def per_device(q, k, v):
        # [B, S/cp, H, D] -> gather seq, scatter heads -> [B, S, H/cp, D]
        def seq_to_head(x):
            x = jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                   tiled=True)
            return x

        def head_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        ql, kl, vl = seq_to_head(q), seq_to_head(k), seq_to_head(v)
        ol = attn_fn(ql, kl, vl, causal)
        return head_to_seq(ol)

    spec = P(batch_axis(mesh, q.shape[0], exclude=axis), axis, None, None)
    # check_vma off: attn_fn may be a pallas_call, whose out_shape carries
    # no varying-axes info under shard_map's vma tracking
    return shard_map(per_device, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def blockwise_attention(q, k, v, *, block_size=512, causal=False):
    """Single-device blockwise (memory-efficient) attention with the same
    streaming-softmax math as the ring — the cp=1 degenerate case and the
    numerics oracle for tests."""
    B, S, H, D = q.shape
    Sk = k.shape[1]
    blk = min(block_size, Sk)
    m = jnp.full((B, H, S), NEG_INF, q.dtype)
    l = jnp.zeros((B, H, S), q.dtype)
    o = jnp.zeros_like(q)
    q_pos = jnp.arange(S)
    # ragged final block handled by python slicing (shapes are static)
    for start in range(0, Sk, blk):
        kj = k[:, start:start + blk]
        vj = v[:, start:start + blk]
        kv_pos = start + jnp.arange(kj.shape[1])
        if causal:
            bias = jnp.where(q_pos[:, None] >= kv_pos[None, :],
                             0.0, NEG_INF).astype(q.dtype)
        else:
            bias = jnp.zeros((S, kj.shape[1]), q.dtype)
        m, l, o = _block_attn_update(q, kj, vj, bias, m, l, o)
    return _finalize(l, o)
