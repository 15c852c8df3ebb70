"""The power-retention layer on the manager's state where it lies: the
chunked form's part that goes through ``phi`` as one kernel (ISSUE 45)
and, further down, the one-step form as another (ISSUE 63).

For a chunk of ``c`` rows of a slot with a q-block wider than one row
(``models/retention_decode.retention_chunked`` has the mathematics), per
K/V head of ``d`` columns with ``m`` query heads:

  read     num_i = phi(q_i)^T S        [c m, d]   (the carry's part of y)
           den_i = phi(q_i) . z        [c m]
  update   S <- end S + sum_j left_j phi(k_j) v_j^T     [D, d]
           z <- end z + sum_j left_j phi(k_j)           [D]

``phi`` (``sympow2``) is ``d / 2`` STRIPES of ``d`` columns and a half
stripe: stripe ``o`` is ``u_a u_{(a + o) mod d}``, ``sqrt 2`` off the
diagonal, so the state's rows ``o d .. (o + 1) d`` are stripe ``o``'s.
The XLA form writes ``phi(q)`` (``c m D`` values) and ``phi(k)`` to
memory and reads them back; here a stripe's ``[c m, d]`` tile of
``phi(q)`` and ``[c, d]`` tile of ``phi(k)`` are built on the vector unit
from the rows and a copy of them turned one lane a stripe, used and
dropped:

  - grid (lane, K/V head); the lane's ``q`` ``[c m, d]``, ``k``, ``v``
    ``[c, d]``, ``left`` ``[c, 1]`` and ``end`` sit in VMEM, with the
    head's whole ``S`` ``[D, d]`` (4.2 MB of float32 at ``d`` 128, the
    next head's on its way in and the last one's on its way out);
  - a stripe: the ``phi(q)`` tile times the old ``S`` stripe into a
    float32 ``[c m, d]``, the tile times ``z``'s stripe into the
    denominator's ``[c m, d]`` of partial sums (the caller adds the
    lanes), then the stripe decayed by ``end``, ``phi(k left)^T v``
    added, and stored; a loop over PAIRS of stripes (their tiles side
    by side in one product), not ``d / 2`` bodies (stripe 0, which has
    no ``sqrt 2``, and the half stripe are bodies of their own);
  - ``S`` and ``z`` are addressed IN the manager's arrays ``[1, slots,
    g, D, d]`` / ``[1, slots, g, D]`` by the lane's slot number (scalar
    prefetch into the index map) and aliased to the outputs: a slot's
    state is read once and written once where it lies, every other
    slot's is not touched;
  - an idle lane (``q_len`` 0: a slot of one row or none at the tail of
    the mixer's order) copies one head's ``S`` and its ``z`` back as
    they were and computes nothing.

Precision is the XLA form's: ``phi`` in float32 from the rows' dtype
(times ``left`` in float32 for ``k``), rounded to the rows' dtype where
it enters the MXU, ``S`` rounded the same way for the read; every
accumulation, the decay, the add and the store in float32 (the store in
the state's own dtype).  ``_chunk_scan_call`` is jitted: a model's
layers share one trace and one Mosaic lowering a program
(``ragged_attention._paged_rows_call`` has the story).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._shared import _use_interpret

SQRT2 = math.sqrt(2.0)
# a head's S twice in and twice out (17 MB of float32 at d 128), the
# rows and the two [c m, d] float32 outputs twice, 2 MB of scratch
_VMEM_LIMIT = 48 << 20
# stripes a step of the loop, their tiles side by side in one product
PAIR = 2


def _chunk_scan_kernel(slot_ref, ql_ref, q_ref, k_ref, v_ref, left_ref,
                       end_ref, s_ref, z_ref, num_ref, den_ref, so_ref,
                       zo_ref, qs_ref, qrot_ref, ks_ref, krot_ref, zf_ref,
                       *, d):
    """Grid (lane, K/V head).  ``zf_ref`` [g, (d / 2 + 1) d] float32 is
    the lane's ``z`` for the length of its heads' steps (read at the
    first, written back at the last), padded so that the half stripe is
    a whole stripe of lanes."""
    lane, h = pl.program_id(0), pl.program_id(1)
    g = pl.num_programs(1)
    f32 = jnp.float32
    cd = q_ref.dtype
    half = d // 2
    D = half * d + half
    live = ql_ref[lane] > 0

    @pl.when(h == 0)
    def _():
        zf_ref[:, pl.ds(0, D)] = z_ref[...].astype(f32)
        zf_ref[:, pl.ds(D, half)] = jnp.zeros((zf_ref.shape[0], half), f32)

    @pl.when(jnp.logical_not(live))
    def _():
        # (the index map sends every head's step of an idle lane to the
        # slot's head 0: one block in, the same block out)
        so_ref[...] = s_ref[...]
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    def stripes(o, n=1, rows=d, first=False):
        """Stripes ``o .. o + n - 1`` (the last one's first ``rows``
        columns live): read through the old state in ONE product over
        the ``n d`` columns of their tiles, then each stripe's update."""
        tqs, olds = [], []
        for i in range(n):
            if first:
                qa = qb = qrot_ref[...]
                ka = kb = krot_ref[...]
            else:
                # u_{(a + o) mod d}: the copy turned one more lane
                qb = pltpu.roll(qrot_ref[...], d - 1, 1)
                kb = pltpu.roll(krot_ref[...], d - 1, 1)
                qrot_ref[...] = qb
                krot_ref[...] = kb
                qa, ka = qs_ref[...], ks_ref[...]
            tq = qa * qb                                   # [c m, d]
            tk = ka * kb * left_ref[...]                   # [c, d]
            if rows < d:
                tq = jnp.where(jax.lax.broadcasted_iota(
                    jnp.int32, tq.shape, 1) < rows, tq, 0.0)
                tk = jnp.where(jax.lax.broadcasted_iota(
                    jnp.int32, tk.shape, 1) < rows, tk, 0.0)
            base = (o + i) * d
            if not isinstance(o, int):
                base = pl.multiple_of(base, d)
            S = s_ref[pl.ds(base, rows), :].astype(f32)    # [rows, d]
            # (this head's row of the lane's z under a mask: a load at a
            # traced sublane is not a thing the compiler has)
            zall = zf_ref[:, pl.ds(base, d)]               # [g, d]
            mine = jax.lax.broadcasted_iota(jnp.int32, zall.shape, 0) == h
            zrow = jnp.sum(jnp.where(mine, zall, 0.0), axis=0, keepdims=True)
            tqs.append(tq.astype(cd))
            olds.append((base, S, tk, zall, mine, zrow))
        Sc = [S.astype(cd) for _, S, *_ in olds]
        if rows < d:
            Sc.append(jnp.zeros((d - rows, d), cd))
        num_ref[...] += jnp.dot(
            tqs[0] if n == 1 else jnp.concatenate(tqs, axis=1),
            Sc[0] if len(Sc) == 1 else jnp.concatenate(Sc, axis=0),
            preferred_element_type=f32)
        den_ref[...] += sum(tqc.astype(f32) * zrow
                            for tqc, (*_, zrow) in zip(tqs, olds))
        end = end_ref[...]                                 # [1, d]
        for base, S, tk, zall, mine, zrow in olds:
            inc = jax.lax.dot_general(
                tk.astype(cd), v_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=f32)                # [d, d]
            so_ref[pl.ds(base, rows), :] = (
                S * end + inc[:rows]).astype(so_ref.dtype)
            zf_ref[:, pl.ds(base, d)] = jnp.where(
                mine, zrow * end + jnp.sum(tk, axis=0, keepdims=True), zall)

    @pl.when(live)
    def _():
        qf, kf = q_ref[...].astype(f32), k_ref[...].astype(f32)
        qrot_ref[...] = qf
        krot_ref[...] = kf
        qs_ref[...] = SQRT2 * qf
        ks_ref[...] = SQRT2 * kf
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        # stripe 0 has no sqrt 2; the whole stripes after it two at a
        # time (one pass of the rows through the MXU and one turn of
        # the accumulator for both: 0.87 -> 0.75 ms a layer on the chip,
        # PERF.md section 6, PR 45), those left over alone first; then
        # the half stripe
        stripes(0, first=True)
        lone = (half - 1) % PAIR
        for i in range(lone):
            stripes(1 + i)

        def body(i, carry):
            stripes(1 + lone + PAIR * i, PAIR)
            return carry

        jax.lax.fori_loop(0, (half - 1) // PAIR, body, 0)
        stripes(half, rows=half)

    @pl.when(h == g - 1)
    def _():
        zo_ref[...] = zf_ref[:, pl.ds(0, D)].astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_scan_call(slot, q_len, q, k, v, left, end, mats, norms, *,
                     interpret):
    """``_chunk_scan_kernel`` over ``q`` [lanes, g, c m, d], ``k`` / ``v``
    [lanes, g, c, d], ``left`` [lanes, g, c, 1], ``end`` [lanes, g, 1,
    d] (the head's one number in every lane) and the manager's pair."""
    lanes, g, R, d = q.shape
    c = k.shape[2]
    D = mats.shape[3]

    def rows(lane, h, slot, ql):
        return lane, h, 0, 0

    def state(lane, h, slot, ql):
        return 0, slot[lane], jnp.where(ql[lane] > 0, h, 0), 0, 0

    def norm(lane, h, slot, ql):
        return 0, slot[lane], 0, 0

    wide = pl.BlockSpec((None, None, R, d), rows)
    row = pl.BlockSpec((None, None, c, d), rows)
    s_spec = pl.BlockSpec((None, None, None, D, d), state)
    z_spec = pl.BlockSpec((None, None, g, D), norm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes, g),
        in_specs=[wide, row, row, pl.BlockSpec((None, None, c, 1), rows),
                  pl.BlockSpec((None, None, 1, d), rows), s_spec, z_spec],
        out_specs=[wide, wide, s_spec, z_spec],
        scratch_shapes=[
            pltpu.VMEM((R, d), jnp.float32),       # sqrt 2 q
            pltpu.VMEM((R, d), jnp.float32),       # q, turned
            pltpu.VMEM((c, d), jnp.float32),       # sqrt 2 k
            pltpu.VMEM((c, d), jnp.float32),       # k, turned
            pltpu.VMEM((g, (d // 2 + 1) * d), jnp.float32),   # the lane's z
        ],
    )
    acc = jax.ShapeDtypeStruct((lanes, g, R, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_chunk_scan_kernel, d=d),
        grid_spec=grid_spec,
        out_shape=[acc, acc,
                   jax.ShapeDtypeStruct(mats.shape, mats.dtype),
                   jax.ShapeDtypeStruct(norms.shape, norms.dtype)],
        # the state is rewritten where it lies (operands count the two
        # prefetched scalars)
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="retention_chunk_scan",
        interpret=interpret,
    )(slot, q_len, q, k, v, left, end, mats, norms)


def retention_chunk_scan(slot, q_len, q, k, v, left, end, mats, norms, *,
                         interpret=None):
    """One chunk of the lanes' q-blocks through ``phi``, on the manager's
    state where it lies.

    ``slot`` [lanes] int32: the lanes' slots, no two the same;
    ``q_len`` [lanes] int32: a lane with 0 is idle; ``q`` [lanes, c, g,
    m, d], ``k`` / ``v`` [lanes, c, g, d] (``k`` 0 on dead rows), ``left``
    [lanes, c, g] float32 (the decay from each row to the chunk's end),
    ``end`` [lanes, g] float32 (the chunk's whole decay), ``mats`` [1,
    slots, g, D, d] and ``norms`` [1, slots, g, D] (``D = d (d + 1) /
    2``, ``d`` a multiple of 128).  Returns (num [lanes, g, m, c, d]
    float32 = ``phi(q_i)^T S``, den [lanes, g, m, c] float32 = ``phi(q_i)
    . z`` through the state as it was, mats, norms after the chunk: a
    K/V head's query heads one after another, as the scores inside a
    chunk come out of their products); an
    idle lane's num and den are 0 and its state keeps its bits."""
    lanes, c, g, m, d = q.shape
    if interpret is None:
        interpret = _use_interpret()
    heads = lambda a: jnp.moveaxis(a, 2, 1)                # noqa: E731
    num, den, mats, norms = _chunk_scan_call(
        slot.astype(jnp.int32), q_len.astype(jnp.int32),
        q.transpose(0, 2, 3, 1, 4).reshape(lanes, g, m * c, d), heads(k),
        heads(v), heads(left)[..., None],
        jnp.broadcast_to(end[:, :, None, None], (lanes, g, 1, d)),
        mats, norms, interpret=interpret)
    return (num.reshape(lanes, g, m, c, d),
            den.reshape(lanes, g, m, c, d).sum(-1), mats, norms)


# ------------------------------------------------------------------ #
# the one-step form (ISSUE 63)
# ------------------------------------------------------------------ #
#
# For every slot with ONE live row (``retention_decode.retention_step``
# has the mathematics, and stays as the XLA form), a K/V head:
#
#   S <- gamma S + phi(k) v^T      [D, d]
#   z <- gamma z + phi(k)          [D]
#   num = phi(q)^T S, den = phi(q) . z    through the NEW state, m heads
#
# The XLA form is three passes over EVERY slot's state (the update reads
# and writes it, the read-out reads it again); here a head's ``S`` is in
# VMEM once, and only a slot that moves is read at all:
#
#   - grid (lane, K/V head); the one-row slots are the first ``n`` of a
#     prefetched slot list, and a lane past them names the ``n``-th
#     slot's last block again: no block moves in or out for it, and the
#     block goes out once, as the last live step left it (a wave with no
#     one-row slot at all copies one block in and out as it was);
#   - the increment is a float32 product on the vector unit: ``k`` as a
#     COLUMN (``k_a`` in every lane of row ``a``, one ``[d, d]``
#     transpose a head step) times ``v`` as a row gives ``k_a v_j``
#     once, and stripe ``o``'s increment is that times ``sqrt 2 k_{a +
#     o}``, a column turned ``o`` sublanes: eight turns are kept (a
#     transpose each, a head step), so stripe ``o`` reads turn ``o mod
#     8`` at a whole sublane tile and nothing is turned or transposed a
#     stripe.  The chunk kernel's transposed product through the MXU
#     rounds ``phi(k)`` to bfloat16 at the default precision (read on
#     the chip: 2e-3 of the state, PERF.md section 6, PR 63), which the
#     step's float32 state does not take;
#   - the stripe just stored is read out where it is: ``phi(q)``'s tile
#     (rows as the chunk kernel builds them, a lane turn a stripe) times
#     the stripe on the MXU, both rounded to the rows' dtype as
#     ``retention_step``'s default-precision product rounds them; ``z``
#     and the denominator beside it as the chunk kernel holds them.
#
# On the chip a layer of the documents cell (21 of 24 slots with a row)
# takes 2.23 ms against the XLA form's 3.85, and 2.22 built without the
# read-out: the bytes' 78 % of 819 GB/s that a copying pass reaches
# (PERF.md section 6, PR 63).

# rows of a head's queries in a step of the one-step kernel: the ``m``
# query heads of a K/V head, padded to a packed tile of the read's dtype
STEP_ROWS = 16
# copies of ``phi(k)``'s column form the one-step kernel keeps, each
# turned one sublane more than the last: stripe ``o`` reads copy ``o mod
# 8`` at the whole sublane tile ``o - o mod 8``
_TURNS = 8


def _step_scan_kernel(slot_ref, n_ref, q_ref, k_ref, v_ref, gam_ref, s_ref,
                      z_ref, num_ref, den_ref, so_ref, zo_ref, kb_ref,
                      zf_ref, *, d, read):
    """Grid (lane, K/V head): lane ``i < n`` is the ``i``-th slot with
    ONE live row.  ``q_ref`` [STEP_ROWS, d], ``k_ref`` / ``v_ref`` /
    ``gam_ref`` [8, d] float32 (the row, and the head's decay, in every
    sublane); ``kb_ref`` [_TURNS, d + d / 2, d] float32: ``sqrt 2 k_{(i +
    r) mod d}`` in every lane of row ``i`` of copy ``r``; ``zf_ref`` as
    the chunk kernel's."""
    lane, h = pl.program_id(0), pl.program_id(1)
    g = pl.num_programs(1)
    f32 = jnp.float32
    half = d // 2
    D = half * d + half
    n = n_ref[0]
    live = lane < n

    @pl.when((n == 0) & (lane == 0) & (h == 0))
    def _():
        # no slot has one row: every step is the same block (the index
        # maps say which), in once and out once as it was
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(jnp.logical_not(live))
    def _():
        # (a lane past the last one-row slot stays on that slot's last
        # block: nothing moves, and the block goes out as the last live
        # step left it)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(live & (h == 0))
    def _():
        zf_ref[:, pl.ds(0, D)] = z_ref[...].astype(f32)
        zf_ref[:, pl.ds(D, half)] = jnp.zeros((zf_ref.shape[0], half), f32)

    @pl.when(live)
    def _():
        qf, kf = q_ref[...], k_ref[...]
        gam = gam_ref[...][:1]                             # [1, d]
        # the increment's column form: k_a in every lane of row a, so
        # that stripe o's ``phi(k)_a v_j`` is ``(sqrt 2 k_{a+o}) (k_a
        # v_j)`` elementwise on the vector unit, in float32, with no
        # product through the MXU and no transpose a stripe
        kt = jnp.broadcast_to(kf[:1], (d, d))
        ka = kt.T
        w = ka * v_ref[...][:1]                            # [d, d]
        for r in range(_TURNS):
            kr = ka if r == 0 else pltpu.roll(kt, d - r, 1).T
            kb_ref[r, pl.ds(0, d), :] = SQRT2 * kr
            kb_ref[r, pl.ds(d, half), :] = SQRT2 * kr[:half]
        qs, ks = SQRT2 * qf, SQRT2 * kf
        mine = jax.lax.broadcasted_iota(jnp.int32, (g, d), 0) == h

        def whole(at, tile):
            # (a traced offset says what it is a multiple of)
            return at if isinstance(at, int) else pl.multiple_of(at, tile)

        def stripe(o, qrot, krot, num, den, rows=d, first=False):
            """Stripe ``o`` (its first ``rows`` rows live): decayed,
            added to, stored, and the same stripe read out."""
            if first:
                kb = ka
                tq, tk = qf * qf, kf * kf
            else:
                qrot = pltpu.roll(qrot, d - 1, 1)
                krot = pltpu.roll(krot, d - 1, 1)
                tq, tk = qs * qrot, ks * krot
                kb = kb_ref[o % _TURNS,
                            pl.ds(whole(o - o % _TURNS, _TURNS), d), :]
            if rows < d:
                tq, tk = (jnp.where(jax.lax.broadcasted_iota(
                    jnp.int32, t.shape, 1) < rows, t, 0.0) for t in (tq, tk))
            base = whole(o * d, d)
            S = s_ref[pl.ds(base, rows), :].astype(f32) * gam \
                + kb[:rows] * w[:rows]
            so_ref[pl.ds(base, rows), :] = S.astype(so_ref.dtype)
            zall = zf_ref[:, pl.ds(base, d)]               # [g, d]
            znew = jnp.sum(jnp.where(mine, zall, 0.0), axis=0,
                           keepdims=True) * gam + tk[:1]
            zf_ref[:, pl.ds(base, d)] = jnp.where(mine, znew, zall)
            Sc = S.astype(read)
            if rows < d:
                Sc = jnp.concatenate(
                    [Sc, jnp.zeros((d - rows, d), read)], axis=0)
            num = num + jnp.dot(tq.astype(read), Sc,
                                preferred_element_type=f32)
            return qrot, krot, num, den + tq * znew

        zero = jnp.zeros((STEP_ROWS, d), f32)
        carry = stripe(0, qf, kf, zero, zero, first=True)
        # (one stripe a loop step: three and seven a step read the same
        # 2.23 ms a layer on the chip, PERF.md section 6, PR 63)
        carry = jax.lax.fori_loop(
            1, half, lambda o, c: stripe(o, *c), carry)
        _, _, num, den = stripe(half, *carry, rows=half)
        num_ref[...] = num
        den_ref[...] = den

    @pl.when(live & (h == g - 1))
    def _():
        zo_ref[...] = zf_ref[:, pl.ds(0, D)].astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("read", "interpret"))
def _step_scan_call(slot, n, q, k, v, gam, mats, norms, *, read, interpret):
    """``_step_scan_kernel`` over ``q`` [lanes, g, STEP_ROWS, d], ``k`` /
    ``v`` / ``gam`` [lanes, g, 8, d] float32 and the manager's pair;
    ``read`` the dtype ``phi(q)`` and the new ``S`` enter the MXU in."""
    lanes, g, R, d = q.shape
    D = mats.shape[3]

    def rows(lane, h, slot, n):
        return lane, h, 0, 0

    def state(lane, h, slot, n):
        return 0, slot[lane], jnp.where(lane < n[0], h, g - 1), 0, 0

    def norm(lane, h, slot, n):
        return 0, slot[lane], 0, 0

    wide = pl.BlockSpec((None, None, R, d), rows)
    row = pl.BlockSpec((None, None, 8, d), rows)
    s_spec = pl.BlockSpec((None, None, None, D, d), state)
    z_spec = pl.BlockSpec((None, None, g, D), norm)
    acc = jax.ShapeDtypeStruct((lanes, g, R, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_step_scan_kernel, d=d, read=jnp.dtype(read)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, g),
            in_specs=[wide, row, row, row, s_spec, z_spec],
            out_specs=[wide, wide, s_spec, z_spec],
            scratch_shapes=[
                pltpu.VMEM((_TURNS, d + d // 2, d), jnp.float32),
                pltpu.VMEM((g, (d // 2 + 1) * d), jnp.float32),
            ]),
        out_shape=[acc, acc,
                   jax.ShapeDtypeStruct(mats.shape, mats.dtype),
                   jax.ShapeDtypeStruct(norms.shape, norms.dtype)],
        input_output_aliases={6: 2, 7: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="retention_step_scan",
        interpret=interpret,
    )(slot, n, q, k, v, gam, mats, norms)


def retention_step_scan(slot, n, q, k, v, gamma, mats, norms, *,
                        interpret=None):
    """One step of the recurrence for the slots with ONE live row, on
    the manager's state where it lies.

    ``slot`` [lanes] int32: the first ``n`` the one-row slots, no two
    the same, every later entry the ``n``-th's again (``n`` 0: any one
    slot); ``q`` [lanes, g, m, d], ``k`` / ``v`` [lanes, g, d] (a lane's
    row), ``gamma`` [lanes, g] float32 (the step's decay), ``mats`` [1,
    slots, g, D, d] and ``norms`` [1, slots, g, D] (``D = d (d + 1) /
    2``, ``d`` a multiple of 128).  Returns (num [lanes, g, m, d]
    float32 = ``phi(q)^T S``, den [lanes, g, m] float32 = ``phi(q) . z``
    through the state as the step LEFT it, mats, norms); a lane from the
    ``n``-th on reads 0 and 0, and every slot but the first ``n`` keeps
    its bits."""
    lanes, g, m, d = q.shape
    if interpret is None:
        interpret = _use_interpret()
    f32 = jnp.float32
    spread = lambda a: jnp.broadcast_to(               # noqa: E731
        a.astype(f32)[:, :, None, :], (lanes, g, 8, d))
    num, den, mats, norms = _step_scan_call(
        slot.astype(jnp.int32), jnp.reshape(n, (1,)).astype(jnp.int32),
        jnp.pad(q.astype(f32), ((0, 0), (0, 0), (0, STEP_ROWS - m), (0, 0))),
        spread(k), spread(v), spread(gamma[..., None]), mats, norms,
        read=jnp.dtype(q.dtype).name, interpret=interpret)
    return num[:, :, :m], den[:, :, :m].sum(-1), mats, norms
