"""Pallas TPU flash attention (forward kernel + custom VJP).

The hot op of every transformer in the model zoo (SURVEY.md §2.1 "TPU
equivalent": the genuinely custom kernels become Pallas).  Blockwise
online-softmax attention: for each query block the kernel streams key/value
blocks through VMEM, keeping running max/denominator, so the S x S score
matrix never leaves VMEM and HBM traffic is O(S*D) instead of O(S^2).

Grid: (batch*heads, q_blocks, kv_blocks); the kv dimension is innermost so
the VMEM scratch accumulators (m, l, acc) persist across kv steps of one
query block (TPU grids execute sequentially).  Causal blocks strictly above
the diagonal are skipped with @pl.when — ~2x fewer FLOPs for causal LM.

Backward: fused Pallas kernels (FlashAttention-2 style).  The forward
additionally emits the per-row logsumexp; the backward recomputes P
block-by-block from (q, k, lse) in VMEM — never materializing the S x S
matrix — with two passes: a dK/dV kernel whose grid iterates query blocks
innermost (accumulating [bk, D] scratch per kv block) and a dQ kernel
iterating kv blocks innermost.  delta = rowsum(dO * O) is a cheap fused
XLA reduction outside the kernels.  This covers the 2/3 of attention
FLOPs that the old oracle-recompute backward left to XLA's generic path
(the hot-op role of reference src/ops/MatrixMult.cu-class kernels).

On non-TPU backends the kernels run in interpret mode, so the same code
path is testable on the 8-device CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128



def _prec(dtype):
    """fp32 inputs get full-precision MXU passes (the accuracy path);
    bf16 stays on the fast path.  Without this, fp32 attention grads on
    TPU drift ~4e-3 from exact (default matmul precision is bf16)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT

def _fit_block(block, length):
    """Largest divisor of ``length`` that is <= min(block, length), so any
    sequence length works (non-divisible requests shrink the block rather
    than assert)."""
    b = min(block, length)
    while length % b:
        b -= 1
    return b


def _causal_kv_index(bq, bk):
    """kv-block index map with the dead-block DMA skip: above-diagonal
    (causally dead) kv blocks map to the LAST LIVE block for the q row —
    pallas skips the DMA when a block's index repeats across grid steps,
    so the dead half of the grid moves no bytes (compute is separately
    skipped by pl.when).  At 32k this halves the kv streaming traffic."""
    def idx(b, i, j):
        return (b, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)
    return idx


def _causal_q_row(bq, bk, n_q):
    """q-row mirror of _causal_kv_index for the dkv kernel: below-diagonal
    (dead) q rows map to the FIRST LIVE row, upper-clamped to n_q - 1 for
    cross-attention where kv runs longer than q (every row of such a
    column is dead, but the DMA index must stay in range)."""
    def row(b, j, i):
        return jnp.maximum(i, jnp.minimum((j * bk) // bq, n_q - 1))
    return row


def _fwd_kernel(*refs, scale, causal, masked, carried, bq, bk, n_kv):
    oc_ref = lc_ref = None
    if masked:
        (kvlen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    elif carried:
        (q_ref, k_ref, v_ref, oc_ref, lc_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        if carried:
            # fused merge epilogue (ring attention): seed the running
            # (m, l, acc) from the PREVIOUS rotation's normalized output
            # and lse.  Any (m, l, acc) with acc/l == o_c and
            # m + log l == lse_c continues the stream exactly; we pick
            # l = 1, m = lse_c — so the cross-rotation combine costs no
            # separate pass over the output at all.
            lse_c = lc_ref[0, 0]                       # [bq] f32
            live = lse_c > NEG_INF / 2
            m_ref[:] = jnp.broadcast_to(
                jnp.where(live, lse_c, NEG_INF)[:, None], m_ref.shape)
            l_ref[:] = jnp.broadcast_to(
                jnp.where(live, 1.0, 0.0)[:, None], l_ref.shape)
            acc_ref[:] = oc_ref[0] * jnp.where(live, 1.0, 0.0)[:, None]
        else:
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # block (qi, kj) is live unless every q position < every kv position
        run = (kj * bk) <= (qi * bq + bq - 1)
    if masked:
        # blocks entirely past this sequence's kv length are dead
        run = jnp.logical_and(run, kj * bk < kvlen_ref[b])

    @pl.when(run)
    def _compute():
        q = q_ref[0]          # [bq, D]
        k = k_ref[0]          # [bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=_prec(q.dtype),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal or masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kv_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            live = jnp.ones((bq, bk), jnp.bool_)
            if causal:
                live = q_pos >= kv_pos
            if masked:
                live = jnp.logical_and(live, kv_pos < kvlen_ref[b])
            s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[:, 0:1]                      # [bq, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.clip(m_prev - m_new, max=0.0))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=_prec(v.dtype),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # per-row logsumexp for the fused backward; +inf on fully-masked
        # rows so exp(s - lse) recomputes p = 0 there
        m = m_ref[:, 0]
        lse = jnp.where(l[:, 0] == 0.0, -NEG_INF,
                        jnp.where(m <= NEG_INF / 2, -NEG_INF,
                                  m + jnp.log(l[:, 0])))
        lse_ref[0, 0] = lse


def _flash_fwd(q, k, v, kv_lens, *, causal, block_q, block_k, interpret,
               carry=None):
    """q, k, v: [BH, S, D] (+ optional kv_lens [BH]) -> o: [BH, S, D].

    ``carry``: optional (o_carry [BH, S, D] f32, lse_carry [BH, 1, S]
    f32) — the previous partial's normalized output and lse, merged in
    the kernel prologue (ring attention).  With a carry the output o is
    f32 (it keeps accumulating across rotations)."""
    BH, S, D = q.shape
    Sk = k.shape[1]
    bq = _fit_block(block_q, S)
    bk = _fit_block(block_k, Sk)
    n_q, n_kv = S // bq, Sk // bk
    scale = D ** -0.5
    masked = kv_lens is not None
    carried = carry is not None
    assert not (masked and carried), "kv_lens + carry not combined"

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, masked=masked,
        carried=carried, bq=bq, bk=bk, n_kv=n_kv)
    lens_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)] if masked else []
    lens_arg = (kv_lens,) if masked else ()
    carry_spec = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
    ] if carried else []
    carry_arg = (carry[0].astype(jnp.float32),
                 carry[1].astype(jnp.float32)) if carried else ()

    if causal:
        kv_idx = _causal_kv_index(bq, bk)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_kv),
        in_specs=lens_spec + [
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
        ] + carry_spec,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D),
                                 jnp.float32 if carried else q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, D), jnp.float32),        # output accumulator
        ],
        name="flash_fwd",
        interpret=interpret,
    )(*lens_arg, q, k, v, *carry_arg)


# --------------------------------------------------------------------------- #
# fused backward (FlashAttention-2): recompute P per block from (q, k, lse)
# --------------------------------------------------------------------------- #

def _recompute_p(q, k, lse, *, scale, causal, qi, kj, bq, bk, kvlen=None):
    """[bq, bk] probabilities for one block pair, fp32."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=_prec(q.dtype),
        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse[:, None])
    if causal or kvlen is not None:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kv_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        live = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            live = q_pos >= kv_pos
        if kvlen is not None:
            live = jnp.logical_and(live, kv_pos < kvlen)
        p = jnp.where(live, p, 0.0)
    return p


def _bwd_dkv_kernel(*refs, scale, causal, masked, bq, bk, n_q):
    if masked:
        (kvlen_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    b = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi * bq + bq - 1) >= (kj * bk)
    if masked:
        run = jnp.logical_and(run, kj * bk < kvlen_ref[b])

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        p = _recompute_p(q, k, lse, scale=scale, causal=causal,
                         qi=qi, kj=kj, bq=bq, bk=bk,
                         kvlen=kvlen_ref[b] if masked else None)
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            precision=_prec(do.dtype),
            preferred_element_type=jnp.float32)
        # dP = dO V^T ; dS = P * (dP - delta) * scale ; dK += dS^T Q
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            precision=_prec(do.dtype),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            precision=_prec(q.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, masked, bq, bk, n_kv):
    if masked:
        (kvlen_ref, k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (kj * bk) <= (qi * bq + bq - 1)
    if masked:
        run = jnp.logical_and(run, kj * bk < kvlen_ref[b])

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k = k_ref[0]
        v = v_ref[0]
        p = _recompute_p(q, k, lse, scale=scale, causal=causal,
                         qi=qi, kj=kj, bq=bq, bk=bk,
                         kvlen=kvlen_ref[b] if masked else None)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            precision=_prec(do.dtype),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        # dQ += dS K
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            precision=_prec(k.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, kv_lens, o, lse, g, *, causal, block_q, block_k,
               interpret, g_lse=None):
    """[BH, S, D] gradients via the fused kernels.

    ``g_lse``: optional cotangent of the lse output (ring attention's
    combine differentiates it); folds into delta since d lse/d s = P,
    giving dS = P*(dP - delta + g_lse)."""
    BH, S, D = q.shape
    Sk = k.shape[1]
    bq = _fit_block(block_q, S)
    bk = _fit_block(block_k, Sk)
    n_q, n_kv = S // bq, Sk // bk
    scale = D ** -0.5
    masked = kv_lens is not None
    lens_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)] if masked else []
    lens_arg = (kv_lens,) if masked else ()
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                          # [BH, S]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = delta[:, None, :]                         # [BH, 1, S]

    if causal:
        q_row = _causal_q_row(bq, bk, n_q)

        def q_idx(b, j, i):
            return (b, q_row(b, j, i), 0)

        def stat_idx(b, j, i):
            return (b, 0, q_row(b, j, i))
    else:
        def q_idx(b, j, i):
            return (b, i, 0)

        def stat_idx(b, j, i):
            return (b, 0, i)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          masked=masked, bq=bq, bk=bk, n_q=n_q),
        grid=(BH, n_kv, n_q),
        in_specs=lens_spec + [
            pl.BlockSpec((1, bq, D), q_idx),                       # q
            pl.BlockSpec((1, bq, D), q_idx),                       # dO
            pl.BlockSpec((1, 1, bq), stat_idx),                    # lse
            pl.BlockSpec((1, 1, bq), stat_idx),                    # delta
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),   # v
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(*lens_arg, q, g, lse, delta, k, v)

    if causal:
        kv_idx_dq = _causal_kv_index(bq, bk)
    else:
        def kv_idx_dq(b, i, j):
            return (b, j, 0)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          masked=masked, bq=bq, bk=bk, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=lens_spec + [
            pl.BlockSpec((1, bk, D), kv_idx_dq),                   # k
            pl.BlockSpec((1, bk, D), kv_idx_dq),                   # v
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # dO
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(*lens_arg, k, v, q, g, lse, delta)
    return dq, dk, dv


def _use_interpret():
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, masked, causal, block_q, block_k):
    # kv_lens rides inside q's tuple when masked (custom_vjp wants a
    # fixed arity of differentiable args; lens are integers, not
    # differentiable)
    q, kv_lens = q if masked else (q, None)
    o, _ = _flash_fwd(q, k, v, kv_lens, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=_use_interpret())
    return o


def _flash_fwd_rule(q, k, v, masked, causal, block_q, block_k):
    q, kv_lens = q if masked else (q, None)
    o, lse = _flash_fwd(q, k, v, kv_lens, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_use_interpret())
    return o, (q, k, v, kv_lens, o, lse)


def _flash_bwd_rule(masked, causal, block_q, block_k, res, g):
    q, k, v, kv_lens, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, kv_lens, o, lse, g, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=_use_interpret())
    if masked:
        import numpy as np
        zeros_lens = np.zeros(kv_lens.shape, dtype=jax.dtypes.float0)
        return (dq, zeros_lens), dk, dv
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_stats(q, k, v, causal, block_q, block_k):
    """Like ``_flash`` but also returns the per-row logsumexp — the
    combination statistic ring attention needs to merge per-KV-block
    partial outputs (o_i, lse_i) across rotations."""
    o, lse = _flash_fwd(q, k, v, None, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_use_interpret())
    return o, lse[:, 0, :]


def _flash_stats_fwd_rule(q, k, v, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, None, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_use_interpret())
    return (o, lse[:, 0, :]), (q, k, v, o, lse)


def _flash_stats_bwd_rule(causal, block_q, block_k, res, g):
    # With lse = m + log l an OUTPUT carrying cotangent g_lse, the FA2
    # dS formula gains a P*g_lse term: dS = P*(dP - delta + g_lse) —
    # i.e. the same kernels with delta shifted by -g_lse (d lse/d s = P).
    q, k, v, o, lse = res
    g_o, g_lse = g
    dq, dk, dv = _flash_bwd(
        q, k, v, None, o, lse, g_o, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_use_interpret(), g_lse=g_lse)
    return dq, dk, dv


_flash_stats.defvjp(_flash_stats_fwd_rule, _flash_stats_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_stats_carry(q, k, v, o_c, lse_c, causal, block_q, block_k):
    """``_flash_stats`` with the cross-block merge fused into the kernel
    prologue: (o_c, lse_c) is the previous partial (normalized output +
    lse, [BH, S, D] f32 / [BH, S] f32) and the returned (o, lse) is the
    EXACT streaming-softmax continuation — ring attention's per-rotation
    combine costs zero extra passes over the output."""
    o, lse = _flash_fwd(q, k, v, None, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_use_interpret(),
                        carry=(o_c, lse_c[:, None, :]))
    return o, lse[:, 0, :]


def _flash_stats_carry_fwd_rule(q, k, v, o_c, lse_c, causal, block_q,
                                block_k):
    o, lse = _flash_fwd(q, k, v, None, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_use_interpret(),
                        carry=(o_c, lse_c[:, None, :]))
    return (o, lse[:, 0, :]), (q, k, v, o_c, lse_c, o, lse)


def _flash_stats_carry_bwd_rule(causal, block_q, block_k, res, g):
    """dq/dk/dv run the unchanged FA2 kernels — with the carry folded
    into lse, the recomputed P = exp(s - lse_total) and delta =
    rowsum(dO*O) are already the right normalized quantities.  The carry
    behaves like one virtual key row with "value" o_c and score lse_c:

        w_c    = exp(lse_c - lse_total)
        d o_c  = w_c * dO
        d lse_c = w_c * (dO . o_c - delta + g_lse)

    (the same dS = P*(dP - delta + g_lse) shape the kernels use)."""
    q, k, v, o_c, lse_c, o, lse = res
    g_o, g_lse = g
    dq, dk, dv = _flash_bwd(
        q, k, v, None, o, lse, g_o.astype(q.dtype), causal=causal,
        block_q=block_q, block_k=block_k, interpret=_use_interpret(),
        g_lse=g_lse)
    lse_tot = lse[:, 0, :]                               # [BH, S]
    g_o32 = g_o.astype(jnp.float32)
    w_c = jnp.where(lse_c <= NEG_INF / 2, 0.0,
                    jnp.exp(lse_c - lse_tot))            # [BH, S]
    d_o_c = w_c[:, :, None] * g_o32
    delta = jnp.sum(g_o32 * o.astype(jnp.float32), axis=-1)
    dot_c = jnp.sum(g_o32 * o_c.astype(jnp.float32), axis=-1)
    g_lse32 = (jnp.zeros_like(delta) if g_lse is None
               else g_lse.astype(jnp.float32))
    d_lse_c = w_c * (dot_c - delta + g_lse32)
    return dq, dk, dv, d_o_c, d_lse_c


_flash_stats_carry.defvjp(_flash_stats_carry_fwd_rule,
                          _flash_stats_carry_bwd_rule)


def flash_attention_with_carry(q, k, v, o_carry, lse_carry, *,
                               causal=False, block_q=512, block_k=1024):
    """Flash attention on [B, S, H, D] continuing a previous partial.

    ``o_carry`` [B, S, H, D] float32 (normalized), ``lse_carry``
    [B, H, S] float32 (NEG_INF where the carry is empty).  Returns
    (o [B, S, H, D] float32, lse [B, H, S] float32) — the streaming
    combination of the carry with attention over THIS (k, v), exactly
    equal to attending over the concatenated key sets.  Differentiable
    in all five array arguments; ring attention chains it so the
    per-rotation (o, lse) merge runs inside the kernel prologue instead
    of as a separate elementwise pass."""
    B, S, H, D = q.shape

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
    o, lse = _flash_stats_carry(
        fold(q), fold(k), fold(v), fold(o_carry),
        lse_carry.reshape(B * H, S), causal, block_q, block_k)
    o = o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, S)
    lse = jnp.where(lse >= -NEG_INF / 2, NEG_INF, lse)
    return o, lse


def flash_attention_with_lse(q, k, v, *, causal=False, block_q=512,
                             block_k=1024):
    """Flash attention on [B, S, H, D] returning (o, lse).

    ``o`` is [B, S, H, D]; ``lse`` is [B, H, S] float32 per-row
    logsumexp (``-1e30`` on rows with no live keys).  Differentiable in
    both outputs — the building block for ring attention's cross-block
    combine."""
    B, S, H, D = q.shape

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
    o, lse = _flash_stats(fold(q), fold(k), fold(v), causal,
                          block_q, block_k)
    o = o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, S)
    # dead rows carry +1e30 from the kernel (so exp(s-lse)=0 in its own
    # backward); for cross-block combination they must read as "empty"
    lse = jnp.where(lse >= -NEG_INF / 2, NEG_INF, lse)
    return o, lse


def flash_attention(q, k, v, *, causal=False, kv_lens=None, block_q=512,
                    block_k=1024):
    """Flash attention on [B, S, H, D] (framework layout).

    Differentiable; Pallas kernels forward AND backward (interpret mode
    off-TPU).  ``kv_lens`` [B] int32 masks keys/values at positions >=
    kv_lens[b] (the BERT-style padding mask); blocks wholly past the
    length are skipped, so ragged batches also save FLOPs.  Default
    blocks are tuned on v5e: 512x1024 is 1.8-2.4x faster than the
    unfused softmax(QK^T)V chain at S=4k-8k causal and at parity for
    S=512, with O(S) instead of O(S^2) memory; 128x128 blocks
    underutilize the MXU (2-4x slower than these defaults).
    """
    B, S, H, D = q.shape
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)
    if kv_lens is not None:
        lens = jnp.repeat(kv_lens.astype(jnp.int32), H)      # [B*H]
        o = _flash((fold(q), lens), fold(k), fold(v), True, causal,
                   block_q, block_k)
    else:
        o = _flash(fold(q), fold(k), fold(v), False, causal,
                   block_q, block_k)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def mha_reference(q, k, v, *, causal=False, kv_lens=None):
    """Exact attention oracle on [B, S, H, D] for tests."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    Sq, Sk = q.shape[1], k.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_lens is not None:
        live = jnp.arange(Sk)[None, :] < kv_lens[:, None]    # [B, Sk]
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if kv_lens is not None:
        # fully-padded rows: softmax over all-NEG_INF degenerates to
        # uniform; the kernel emits exactly 0 there — match it
        out = out * (kv_lens > 0)[:, None, None, None]
    return out
