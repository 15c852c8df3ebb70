"""The power-retention layer's chunked form, the part that goes through
``phi``, as one kernel (ISSUE 45).

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
