"""Power retention for the mixed ragged wave: a decoder whose every layer
keeps NO K/V page, only a gated degree-2 state a K/V head a slot (the
``brumby`` family: the grouped-query block with the softmax replaced by
power retention, arXiv:2507.04239).

The layer, with ``h`` the RMSNorm of the residual, ``n`` query heads over
``g`` K/V heads of ``d`` columns (query head ``n`` reading K/V head ``n //
(heads / g)``), ``D = d (d + 1) / 2``:

  front   q, k, v = h W_q, h W_k, h W_v (no bias); q, k through the
          per-head RMSNorm, then rotate-half RoPE over the whole head:
          the grouped-query branch's own code (``gpt_decode._qkv_heads``)
  gate    lg = log sigmoid(h W_g + b_g), float32, one a K/V head a token;
          gamma = exp(lg) in (0, 1)
  state   S_t = gamma_t S_{t-1} + phi(k_t) v_t^T   [D, d] a K/V head
          z_t = gamma_t z_{t-1} + phi(k_t)         [D]
          y_t = S_t^T phi(q_t) / (z_t . phi(q_t)), a query head
  out     concat(y) W_o

``phi`` (``sympow2``) is the symmetric degree-2 embedding: all ``u_a
u_b``, ``a <= b``, times ``sqrt 2`` where ``a < b``, so that ``phi(q) .
phi(k) = (q . k)^2``: the layer IS attention with the weight ``(q_t .
k_j)^2`` times the gates between ``j`` and ``t``, normalised by the sum
of a row's weights (what ``reference_retention`` computes), written as a
recurrence.  No scale on ``q . k`` (it cancels), no epsilon in the
denominator (the ``j = t`` term is a square).

What a sequence carries from one q-block to its next, a slot a layer:
``S`` ``[g, D, d]`` and ``z`` ``[g, D]`` in FLOAT32 (sums decayed over
thousands of steps; at ``d`` 128 and ``g`` 8 that is 34.08 MB, the K/V
of 8,320 positions).  They live in the ``PagedKVManager`` that admits
the slots, an array a layer of each (``RetentionSpec.state_shapes``),
zeroed when a slot is claimed and handed through the donated step; a
model of such layers alone holds no pool at all (``layers=0``).

One program a bucket serves every kind of row, as ``ssm_decode.ssm_mixer``
does.  A slot with ONE live row takes one step of the recurrence, in
float32.  A slot with a wider
q-block takes the CHUNKED form over chunks of ``RetentionSpec.chunk``
rows: inside a chunk the scores ``(q_t . k_j)^2`` directly (products
``d`` wide, never through ``phi``) under the gates' decay, masked before
the exponential; from the carry ``G_t phi(q_t)^T S``; then ONE state
update a chunk.  A wave's few such slots are taken ``WIDE_LANES`` at a
time.  A dead row and a dead slot have ``lg`` 0 and ``k`` 0: decay 1,
increment 0, the state stays where it was, bit for bit.  The matrix
products take their operands in the activations' dtype and accumulate in
float32; the state is read, decayed, added to and stored in float32.

Both forms have two bodies, chosen by static shape alone
(``takes_kernel``).  Where the head is a whole number of lane tiles (the
published 128), ``kernels/retention_scan`` has a kernel for each, on the
manager's arrays where they lie, the two kernels' slots disjoint and
ordered through the arrays: ``retention_step_scan`` takes the slots
with ONE row, compacted to the front of its grid, and reads a head's
``S`` once, decays it, adds to it, reads it out and writes it once
(``retention_step_inplace``); ``retention_chunk_scan`` takes what goes
through ``phi`` in the chunked form (the carry's read, the normaliser's
read, the state's update) of a q-block wider than one row, building
``phi(q)`` and ``phi(k)`` a stripe at a time in VMEM and never writing
them (``retention_chunked_inplace``).  A slot with no row is touched by
neither.  Anywhere else ``retention_step`` over every slot at once (a
slot that does not move decayed by 1 and added 0) and
``retention_chunked`` on states sliced out and written back, in XLA's
own operations: the forms the kernels are held to.

Scopes: ``ret_qkvg`` (``gpt_decode``: projections, q/k norm, rotation,
gate), ``ret_expand`` (``phi`` of q and k where XLA forms them),
``ret_scan`` (step and chunked forms, numerator and denominator, the
kernels ``retention_step_scan`` and ``retention_chunk_scan`` and with
them their slots' state stores), ``state_write`` (the shared name: the
store of the one-step pass and the slices of the chunked form where
they run in XLA), ``ret_out`` (``gpt_decode``: ``W_o``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

SQRT2 = math.sqrt(2.0)


class RetentionSpec(NamedTuple):
    """The mixer's sizes: ``kv_heads`` states of ``head_dim`` columns a
    layer a slot, ``chunk`` rows a state update of the chunked form,
    ``state_dtype`` the dtype ``S`` and ``z`` are KEPT in ("float32";
    "bfloat16" is the control the comparison has to refuse), ``degree``
    the power (2: the one ``sympow2`` embeds)."""

    kv_heads: int
    head_dim: int
    chunk: int = 256
    state_dtype: str = "float32"
    degree: int = 2

    @property
    def state(self):
        """``D``: the columns of ``phi``."""
        return self.head_dim * (self.head_dim + 1) // 2

    def state_shapes(self, layers):
        """The manager's set of slot states for ``layers`` such layers:
        every layer's ``S`` ``[1, g, D, d]`` first, then every layer's
        normaliser ``z`` ``[1, g, D]``; an array a layer of each
        (``SSMSpec.state_shapes`` says why)."""
        dtype = jnp.dtype(self.state_dtype)
        return (((1, self.kv_heads, self.state, self.head_dim), dtype),
                ) * layers \
            + (((1, self.kv_heads, self.state), dtype),) * layers


def sympow2(u):
    """``phi(u)`` [..., d (d + 1) / 2] float32 of ``u`` [..., d] (``d``
    even): ``phi(a) . phi(b) = (a . b)^2``.  Built without a gather, as
    STRIPES: stripe ``o`` is ``u_a u_{(a + o) mod d}`` for every ``a``,
    so stripes ``0 .. d / 2 - 1`` hold each pair at circular distance
    ``o`` once (stripe 0 the squares, weight 1; the others ``sqrt 2``)
    and the first half of stripe ``d / 2`` holds the pairs half a turn
    apart once."""
    u = u.astype(jnp.float32)
    half = u.shape[-1] // 2
    stripes = [u * u] + [SQRT2 * u * jnp.roll(u, -o, axis=-1)
                         for o in range(1, half)]
    stripes.append(SQRT2 * u[..., :half] * u[..., half:])
    return jnp.concatenate(stripes, axis=-1)


def retention_step(q, k, v, lg, S, z):
    """One step of the recurrence for every slot: ``q`` [B, g, n/g, d],
    ``k`` / ``v`` [B, g, d], ``lg`` [B, g] float32 (0 with ``k`` 0: the
    slot does not move), ``S`` [B, g, D, d], ``z`` [B, g, D] float32.
    Returns (y [B, g, n/g, d] float32, S, z)."""
    f32 = jnp.float32
    with jax.named_scope("ret_expand"):
        pk, pq = sympow2(k), sympow2(q)           # [B, g, D], [B, g, m, D]
    gamma = jnp.exp(lg)
    S = S * gamma[:, :, None, None] \
        + pk[..., None] * v.astype(f32)[:, :, None, :]
    z = z * gamma[:, :, None] + pk
    # the read-out reads the state as WRITTEN: without the barrier the
    # compiler fuses a second copy of the update into it, one more pass
    # over every slot's state and a temporary of its size (on the chip
    # 2.4 ms and 818 MB a layer at 24 slots: PERF.md section 6, PR 44)
    S, z = jax.lax.optimization_barrier((S, z))
    # (a product with float32 operands at the default precision: the MXU
    # rounds them as it reads them, as the chunked form's carry read
    # does; elementwise on the vector unit it took three times its
    # bytes' time)
    num = jnp.einsum("bgmD,bgDd->bgmd", pq, S,
                     preferred_element_type=f32)
    den = jnp.sum(pq * z[:, :, None], axis=-1)             # [B, g, m]
    return _read_out(num, den), S, z


def _chunk_rows(q, k, v, lg, chunk):
    """The rows of ``retention_chunked`` cut for chunks of ``chunk``:
    (q, k, v, lg padded to whole chunks with rows of lg 0 and k 0, which
    move nothing; the chunk's rows ``c``; the starts of the chunks)."""
    Q = q.shape[1]
    c = min(int(chunk), Q)
    pad = -Q % c
    if pad:
        q, k, v, lg = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (q, k, v, lg))
    return q, k, v, lg, c, range(0, Q + pad, c)


def _inside_chunk(qz, kz, vz, lgz):
    """What a chunk's rows give each other, and the chunk's decays:
    ``qz`` [B, c, g, m, d], ``kz`` / ``vz`` [B, c, g, d], ``lgz`` [B, c,
    g].  Returns (num [B, g, m, c, d], den [B, g, m, c] float32 from
    inside the chunk; since [B, g, 1, c]: the decay from the chunk's
    start to each row; left [B, c, g]: from each row to the chunk's end;
    end [B, g]: the whole chunk's)."""
    f32 = jnp.float32
    c = qz.shape[1]
    tri = jnp.tril(jnp.ones((c, c), bool))
    cum = jnp.cumsum(lgz.transpose(0, 2, 1), axis=-1)      # [B, g, c]
    # row i weighs row j <= i by (q_i . k_j)^2 under the gates between
    # them (masked BEFORE the exponential: above the diagonal the
    # difference is positive and may overflow)
    s = jnp.einsum("bigmd,bjgd->bgmij", qz, kz,
                   preferred_element_type=f32)
    decay = jnp.exp(jnp.where(
        tri, cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
    a = s * s * decay[:, :, None]                          # [B, g, m, c, c]
    num = jnp.einsum("bgmij,bjgd->bgmid", a.astype(qz.dtype), vz,
                     preferred_element_type=f32)
    den = jnp.sum(a, axis=-1)                              # [B, g, m, c]
    since = jnp.exp(cum)[:, :, None]                       # [B, g, 1, c]
    left = jnp.exp(cum[:, :, -1:] - cum).transpose(0, 2, 1)  # [B, c, g]
    return num, den, since, left, jnp.exp(cum[:, :, -1])


def _read_out(num, den):
    """``num / den``; a row nobody reads (a dead one, or a slot that does
    not move and holds nothing yet) has 0 / 0 and must not be a NaN
    beside the others."""
    return num / jnp.where(den == 0, 1.0, den)[..., None]


def _rows_first(ys):
    """The chunks' y [B, g, m, c, d] as one q-block [B, Q, g, m, d]."""
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=3)
    return y.transpose(0, 3, 1, 2, 4)


def retention_chunked(q, k, v, lg, S, z, chunk):
    """The chunked form over every lane's q-block: ``q`` [B, Q, g, n/g,
    d], ``k`` / ``v`` [B, Q, g, d], ``lg`` [B, Q, g] float32 (0 with
    ``k`` 0 on dead rows), ``S`` [B, g, D, d] and ``z`` [B, g, D]
    float32 (the lane's carry).  Equal to ``retention_step`` row after
    row.  Returns (y [B, Q, g, n/g, d] float32, S, z after the
    q-block).  This is the form in XLA's own operations: what small
    heads run, and what ``retention_chunked_inplace`` is held to."""
    Q = q.shape[1]
    f32 = jnp.float32
    cd = q.dtype                       # the products' operand dtype
    q, k, v, lg, c, starts = _chunk_rows(q, k, v, lg, chunk)
    ys = []
    for z0 in starts:
        qz, kz, vz = q[:, z0:z0 + c], k[:, z0:z0 + c], v[:, z0:z0 + c]
        num, den, since, left, end = _inside_chunk(qz, kz, vz,
                                                   lg[:, z0:z0 + c])
        # from the carry: phi(q_i) reads S and z under the decay since
        # the chunk began
        with jax.named_scope("ret_expand"):
            pq = sympow2(qz).astype(cd)                    # [B, c, g, m, D]
        num = num + jnp.einsum("bigmD,bgDd->bgmid", pq, S.astype(cd),
                               preferred_element_type=f32) * since[..., None]
        den = den + jnp.sum(pq.astype(f32) * z[:, None, :, None],
                            axis=-1).transpose(0, 2, 3, 1) * since
        ys.append(_read_out(num, den))
        # ONE state update a chunk: every row's increment under the
        # decay that is left to the chunk's end
        with jax.named_scope("ret_expand"):
            pk = sympow2(kz) * left[..., None]             # [B, c, g, D]
        S = S * end[:, :, None, None] \
            + jnp.einsum("bjgD,bjgd->bgDd", pk.astype(cd), vz,
                         preferred_element_type=f32)
        z = z * end[:, :, None] + jnp.sum(pk, axis=1)
    return _rows_first(ys)[:, :Q], S, z


def takes_kernel(head_dim, q_block=None):
    """The shape rule: whether a program whose q-blocks are ``q_block``
    rows wide runs the wide slots' chunked form through
    ``kernels/retention_scan`` (else through ``retention_chunked``): a
    head of whole lane tiles (the kernels' stripes are the head's width:
    the published model's 128) and a q-block wider than one row.  Asked
    of the head alone (no ``q_block``): whether the one-row slots' step
    goes through the one-step kernel (else through ``retention_step``),
    which every program of such a head has.  Static shapes alone decide,
    so a program is one or the other, and the engine can ask the same
    questions of a wave (``serve.ret.kernel_slot_steps``)."""
    from ..kernels._shared import _LANES
    return head_dim % _LANES == 0 and (q_block is None or q_block > 1)


def retention_step_inplace(q, k, v, lg, mats, norms, one):
    """``retention_step`` for the slots ``one`` [B] marks (those with ONE
    live row), on the manager's ``mats`` [1, slots, g, D, d] / ``norms``
    [1, slots, g, D]: ``q`` [B, g, n/g, d], ``k`` / ``v`` [B, g, d],
    ``lg`` [B, g] float32, a slot's row each.  The marked slots go
    through ``kernels.retention_scan.retention_step_scan`` in slot
    order; every other slot's state is neither read nor written.
    Returns (y [B, g, n/g, d] float32, 0 on the slots not marked, mats,
    norms)."""
    from ..kernels.retention_scan import retention_step_scan
    B = one.shape[0]
    order = jnp.argsort(jnp.logical_not(one), stable=True)
    n = jnp.sum(one)
    live = jnp.arange(B) < n
    # (the lanes past the last marked slot name it again: the kernel
    # stays on its last block)
    slot = jnp.where(live, order, order[jnp.maximum(n - 1, 0)])
    num, den, mats, norms = retention_step_scan(
        slot, n, q[slot], k[slot], v[slot], jnp.exp(lg[slot]), mats, norms)
    y = _read_out(num, den)
    return jnp.zeros_like(y).at[jnp.where(live, slot, B)].set(
        y, mode="drop"), mats, norms


def retention_chunked_inplace(q, k, v, lg, mats, norms, slot, q_len,
                              chunk):
    """``retention_chunked`` for lanes whose carry is slot ``slot[b]`` of
    the manager's ``mats`` [1, slots, g, D, d] / ``norms`` [1, slots, g,
    D] (no two lanes the same slot; ``q_len`` [B]: a lane's live rows, 0
    an idle lane): the scores inside a chunk and the division as there,
    everything that goes through ``phi`` in
    ``kernels.retention_scan.retention_chunk_scan``, which reads and
    rewrites the lanes' states where they lie.  Returns (y [B, Q, g,
    n/g, d] float32, mats, norms)."""
    from ..kernels.retention_scan import retention_chunk_scan
    Q = q.shape[1]
    q, k, v, lg, c, starts = _chunk_rows(q, k, v, lg, chunk)
    ys = []
    for z0 in starts:
        qz, kz, vz = q[:, z0:z0 + c], k[:, z0:z0 + c], v[:, z0:z0 + c]
        num, den, since, left, end = _inside_chunk(qz, kz, vz,
                                                   lg[:, z0:z0 + c])
        carry, norm, mats, norms = retention_chunk_scan(
            slot, jnp.maximum(q_len - z0, 0), qz, kz, vz, left, end,
            mats, norms)
        ys.append(_read_out(num + carry * since[..., None],
                            den + norm * since))
    return _rows_first(ys)[:, :Q], mats, norms


# how many slots with a q-block wider than one row the chunked form
# takes at a time: a packed wave of 1,024 rows holds three whole chunks
# of 256 beside its decoding rows, so three lanes are one pass with no
# idle lane.  What bounds the count is the scores inside a chunk, [lanes,
# g, m, c, c] float32 (31 MB at three lanes of 256 rows), and the idle
# lanes of a pass that is not full; ``phi(q)`` no longer does where the
# kernel runs (it was 169 MB a lane in bfloat16, written and read back),
# and where XLA forms it the heads are small
WIDE_LANES = 3


def retention_mixer(sp, q, k, v, lg, state, si, q_len, rows=None):
    """One layer's retention over the wave's rows: ``q`` [B, Q, n, d],
    ``k`` / ``v`` [B, Q, g, d] after the norm and the rotation, ``lg``
    [B, Q, g] float32 (or a packed wave's [1, R, ..] with ``rows``).
    ``state`` is the manager's set (``RetentionSpec.state_shapes``:
    every layer's ``S`` ``[1, slots, g, D, d]``, then every layer's
    ``z`` ``[1, slots, g, D]``), of which layer ``si``'s two are read
    and rewritten whole.

    The scan never unpacks the wave (``ssm_decode.ssm_mixer``'s
    discipline).  Every slot with ONE live row takes one step at its
    row: ``retention_step_inplace`` by ``takes_kernel`` of the head,
    else ``retention_step`` over the whole batch at once.  The slots
    with a wider q-block are taken ``WIDE_LANES`` at a time, widest
    first, by a ``while_loop`` that gathers their rows and runs the
    chunked form on their states.  Returns (y laid out as ``q`` in its
    dtype, state)."""
    g, d, D = sp.kv_heads, sp.head_dim, sp.state
    n = q.shape[2]
    m = n // g
    n_state = len(state) // 2
    mats, norms = state[si], state[n_state + si]
    kept = mats.dtype
    Br, Qr = q.shape[:2]
    q_len = jnp.asarray(q_len)
    B_ = q_len.shape[0]
    f32 = jnp.float32
    with jax.named_scope("ret_scan"):
        # the wave's rows as they lie, slot b's from ``start[b]`` on
        Q = Qr if rows is None else rows.q
        start = jnp.arange(B_) * Q if rows is None else rows.start
        q_f = q.reshape(-1, g, m, d)
        k_f, v_f = k.reshape(-1, g, d), v.reshape(-1, g, d)
        lg_f = lg.reshape(-1, g)
        R = q_f.shape[0]
        # the slots with one live row: one step of the recurrence (in
        # XLA's form a slot with none, or with more, has lg 0 and k 0
        # here and stays)
        first = jnp.minimum(start, R - 1)
        step_kernel = takes_kernel(d)
        if step_kernel:
            y1, mats, norms = retention_step_inplace(
                q_f[first], k_f[first], v_f[first], lg_f[first], mats,
                norms, q_len == 1)
        else:
            one = (q_len == 1)[:, None]
            k1 = jnp.where(one[..., None], k_f[first], 0)
            lg1 = jnp.where(one, lg_f[first], 0.0)
            y1, S, z = retention_step(
                q_f[first], k1, v_f[first], lg1, mats[0].astype(f32),
                norms[0].astype(f32))
        y1 = y1.reshape(B_, n * d)
    if not step_kernel:
        with jax.named_scope("state_write"):
            mats, norms = S.astype(kept)[None], z.astype(kept)[None]
    if Q == 1:
        y = y1.reshape(Br, Qr, n * d)
    else:
        with jax.named_scope("ret_scan"):
            # (Q rows more than the wave's: a wide slot's q-block is
            # written back as ONE slice of Q rows from its start, which
            # may run past the last row)
            y_f = jnp.zeros((R + Q, n * d), f32).at[
                jnp.where(q_len == 1, first, R + Q)].set(y1, mode="drop")
            lanes = math.gcd(WIDE_LANES, B_)     # divides the slots
            kernel = takes_kernel(d, Q)
            order = jnp.argsort(-q_len)                    # widest first
            n_wide = jnp.sum(q_len > 1)

            def through_slices(qc, kc, vc, lgc, mats, norms, slot):
                # a lane's state by a slice of its own: a gather over
                # the slots makes the compiler copy the whole state
                S0 = jnp.concatenate([jax.lax.dynamic_slice(
                    mats, (0, slot[j], 0, 0, 0), (1, 1, g, D, d))[0]
                    for j in range(lanes)]).astype(f32)
                z0 = jnp.concatenate([jax.lax.dynamic_slice(
                    norms, (0, slot[j], 0, 0), (1, 1, g, D))[0]
                    for j in range(lanes)]).astype(f32)
                yc, Sc, zc = retention_chunked(qc, kc, vc, lgc, S0, z0,
                                               sp.chunk)
                # every read of the lanes' old states ends here, before
                # the writes below overwrite them in place
                # (``ssm_mixer``: a slice read again after its write is
                # the new state)
                yc, Sc, zc = jax.lax.optimization_barrier((yc, Sc, zc))
                with jax.named_scope("state_write"):
                    for j in range(lanes):
                        mats = jax.lax.dynamic_update_slice(
                            mats, Sc[j].astype(kept)[None, None],
                            (0, slot[j], 0, 0, 0))
                        norms = jax.lax.dynamic_update_slice(
                            norms, zc[j].astype(kept)[None, None],
                            (0, slot[j], 0, 0))
                return yc, mats, norms

            def wide(carry):
                j0, mats, norms, y_f = carry
                slot = jax.lax.dynamic_slice_in_dim(order, j0 * lanes, lanes)
                # an idle lane (a slot of one row or none, at the order's
                # tail) has lg 0 and k 0 throughout: its state is written
                # back as it was read
                ql = jnp.where(q_len[slot] > 1, q_len[slot], 0)
                at = start[slot][:, None] + jnp.arange(Q)[None, :]
                live = jnp.arange(Q)[None, :] < ql[:, None]  # [lanes, Q]
                got = jnp.minimum(at, R - 1)
                kc = jnp.where(live[..., None, None], k_f[got], 0)
                lgc = jnp.where(live[..., None], lg_f[got], 0.0)
                if kernel:
                    # the lanes' states read and rewritten where they
                    # lie, once (``kernels/retention_scan``)
                    yc, mats, norms = retention_chunked_inplace(
                        q_f[got], kc, v_f[got], lgc, mats, norms, slot, ql,
                        sp.chunk)
                else:
                    yc, mats, norms = through_slices(
                        q_f[got], kc, v_f[got], lgc, mats, norms, slot)
                # a lane's live rows into the wave's, by a slice of its
                # own (a scatter of 768 rows took 0.42 ms a layer on the
                # chip, ten times its bytes: PERF.md section 6, PR 45)
                for j in range(lanes):
                    at0 = start[slot[j]]
                    old = jax.lax.dynamic_slice_in_dim(y_f, at0, Q)
                    y_f = jax.lax.dynamic_update_slice_in_dim(
                        y_f, jnp.where(live[j][:, None],
                                       yc[j].reshape(Q, n * d), old), at0, 0)
                return j0 + 1, mats, norms, y_f

            _, mats, norms, y_f = jax.lax.while_loop(
                lambda c: c[0] * lanes < n_wide, wide,
                (jnp.int32(0), mats, norms, y_f))
            y = y_f[:R].reshape(Br, Qr, n * d)
    return y.astype(q.dtype), (
        state[:si] + (mats,) + state[si + 1:n_state + si]
        + (norms,) + state[n_state + si + 1:])


# ------------------------- the configuration ------------------------- #


# what each weight product's output is, in units of its input's RMS, at
# the seeded weights (``init_retention_params``: the weight's deviation
# is ``gain / sqrt(fan_in)``)
DEFAULT_GAINS = {
    "embedding": 1.0, "attn_q": 1.0, "attn_k": 1.0, "attn_v": 1.0,
    "attn_out": 1.0, "gate": 0.5, "mlp_gate": 1.0, "mlp_up": 1.0,
    "mlp_down": 1.0, "lm_head": 1.0}


class RetentionConfig:
    """A decoder whose every layer is the grouped-query block (RMSNorm,
    no biases, per-head q/k RMSNorm, rotate-half RoPE, dense SwiGLU,
    untied head) with power retention in place of the softmax: built
    from the source's own ``config.json`` keys (the ``brumby`` family's,
    which are Qwen3's) and the family's conventions the file has no key
    for (``retention_degree`` 2; the gate's projection and bias;
    ``state_dtype``; ``retention_chunk``).  It yields the jit-static
    ``BlockSpec`` the mixed wave reads.  Values it cannot run raise:
    biases, a sliding window, a tied head, a RoPE scaling, another
    degree, sizes that do not divide."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 intermediate_size, attention_bias=False,
                 tie_word_embeddings=False, sliding_window=None,
                 use_sliding_window=False, rope_scaling=None,
                 rope_theta=1e6, rms_norm_eps=1e-6,
                 max_position_embeddings=32768, retention_degree=2,
                 retention_chunk=256, state_dtype="float32", **ignored):
        bad = [k for k, v in (
            ("attention_bias", attention_bias),
            ("tie_word_embeddings", tie_word_embeddings),
            ("sliding_window", sliding_window),
            ("use_sliding_window", use_sliding_window),
            ("rope_scaling", rope_scaling)) if v]
        if int(retention_degree) != 2:
            bad.append(f"retention_degree={retention_degree}")
        if bad:
            raise ValueError(f"RetentionConfig cannot run {bad}")
        if num_attention_heads % num_key_value_heads or head_dim % 2 \
                or int(retention_chunk) < 1:
            raise ValueError(
                f"RetentionConfig: sizes do not fit: {num_attention_heads} "
                f"over {num_key_value_heads} heads of {head_dim}, chunks "
                f"of {retention_chunk}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.intermediate_size = int(intermediate_size)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.retention = RetentionSpec(
            self.num_key_value_heads, self.head_dim, int(retention_chunk),
            str(jnp.dtype(state_dtype)))

    @classmethod
    def from_hf(cls, config):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise)."""
        return cls(**config)

    def block_spec(self):
        from .gpt_decode import BlockSpec
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="gqa", bias=False,
            kv_heads=self.num_key_value_heads, qk_norm=True,
            ops=("retention",) * self.num_hidden_layers, ffn="swiglu",
            head="untied", head_dim=self.head_dim,
            retention=self.retention)

    def param_shapes(self, name="bru"):
        """{leaf: shape} of the serving parameter dict."""
        d, dh, f = self.hidden_size, self.head_dim, self.intermediate_size
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,),
                  f"{name}_lm_head_weight": (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            us = f"{name}_h{i}"
            shapes.update({
                f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,),
                f"{us}_attn_q_weight": (d, hq * dh),
                f"{us}_attn_k_weight": (d, hkv * dh),
                f"{us}_attn_v_weight": (d, hkv * dh),
                f"{us}_attn_q_norm_scale": (dh,),
                f"{us}_attn_k_norm_scale": (dh,),
                f"{us}_attn_proj_weight": (hq * dh, d),
                f"{us}_ret_gate_weight": (d, hkv),
                f"{us}_ret_gate_bias": (hkv,),
                f"{us}_ffn_gate_weight": (d, f),
                f"{us}_ffn_up_weight": (d, f),
                f"{us}_ffn_down_weight": (f, d)})
        return shapes


# float32 whatever the serving dtype: the recurrence's own constant
F32_LEAVES = ("_ret_gate_bias",)


def init_retention_params(config, name="bru", seed=0, gains=None,
                          dtype=jnp.float32, memory_range=(16.0, 16384.0)):
    """Seeded random serving params for a ``RetentionConfig``, made on
    the device in one jitted call.  Every weight matrix is ``normal(gain
    / sqrt(fan_in))`` (``DEFAULT_GAINS``; ``gains`` overrides entries),
    norm scales 1, and the gate's bias drawn so that a head's memory ``1
    / (1 - gamma)`` at a zero gate input is log-uniform in
    ``memory_range`` steps (``b_g = log(memory - 1)``, float32): a
    zero-mean gate would give every head a memory of two tokens and the
    state nothing to hold."""
    g = dict(DEFAULT_GAINS, **(gains or {}))
    c = config
    d, f = c.hidden_size, c.intermediate_size
    root = math.sqrt
    dev = {
        "_wte_table": g["embedding"],
        "_lm_head_weight": g["lm_head"] / root(d),
        "_attn_q_weight": g["attn_q"] / root(d),
        "_attn_k_weight": g["attn_k"] / root(d),
        "_attn_v_weight": g["attn_v"] / root(d),
        "_attn_proj_weight": g["attn_out"] / root(
            c.num_attention_heads * c.head_dim),
        "_ret_gate_weight": g["gate"] / root(d),
        "_ffn_gate_weight": g["mlp_gate"] / root(d),
        "_ffn_up_weight": g["mlp_up"] / root(d),
        "_ffn_down_weight": g["mlp_down"] / root(f),
    }
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        for k, (leaf, shape) in zip(jax.random.split(key, len(shapes)),
                                    sorted(shapes.items())):
            if leaf.endswith("_scale"):
                out[leaf] = jnp.ones(shape, dtype)
            elif leaf.endswith("_ret_gate_bias"):
                memory = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(memory_range[0]),
                    math.log(memory_range[1])))
                out[leaf] = jnp.log(memory - 1.0)
            else:
                s = next(v for suffix, v in dev.items()
                         if leaf.endswith(suffix))
                out[leaf] = (s * jax.random.normal(k, shape, jnp.float32)
                             ).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
