"""The gated delta rule's chunked form as one kernel (ISSUE 59).

For the wave's wide slots (``models/kda_decode.kda_chunked`` has the
mathematics, and stays as the XLA form), a head of ``D`` columns (key and
value alike) over chunks of ``c`` = ``CHUNK`` rows in sub-blocks of
``SUB``, with ``G`` the running sum of ``g`` inside a chunk:

    A = strict_lower(beta_i (k_i exp(G_i - G_j)) . k_j)          [c, c]
    [W | U] = (I + A)^-1 [beta k exp(G) | beta v]                [c, 2 D]
    o = (q exp(G)) S + lower((q_i exp(G_i - G_j)) . k_j) (U - W S)
    S <- Diag(exp(G_end)) S + (k exp(G_end - G))^T (U - W S)     [D, D]

The XLA form writes float32 copies of q, k and v turned to ``[n, B, H, c,
D]``, the sub-block decays ``[n, B, H, m, m, sub, D]`` (50 MB a layer at
the published widths) and both score matrices to memory, and solves the
384 ``[64, 64]`` systems a layer a row at a time there.  Here:

  - grid (lane, head); a head's q, k, v and g are the ``(Q, D)`` block
    at column block ``h`` of the gathered rows ``[lanes, Q, H D]`` as
    they lie, the result leaves as ``[lanes, Q, H D]`` float32;
  - the head's ``S`` ``[D, D]`` is addressed IN the manager's array ``[1,
    slots, H, D, D]`` by the lane's slot number (scalar prefetch into
    the index map) and aliased to the output: a wide slot's state is
    read once and written once where it lies, every other slot's is not
    touched;
  - a loop over the q-block's chunks that hold a live row, ``S`` carried
    in VMEM in float32 across them: the sub-block references and the
    ``up`` / ``down`` decays formed a sub-block row at a time and
    dropped (the SAME factorisation as ``kda_chunked``: a row's decay
    since its sub-block began, a column's up to that point, masked
    BEFORE the exponential), ``A`` and ``P`` on the MXU from operands in
    the rows' dtype, the solve by forward substitution in float32 on the
    vector unit, the two products that READ the state with float32
    operands at precision highest, ONE state update a chunk;
  - a row past the lane's ``q_len`` has ``g`` 0, ``beta`` 0 and ``k`` 0
    (masked here: the caller hands the rows as gathered); a chunk of
    such rows alone is not run, so the state keeps its bits over it;
  - an idle lane (``q_len`` 0: a slot of one row or none at the tail of
    the mixer's order) copies one head's ``S`` back as it was and
    computes nothing.

``exact`` (a decay with no lower bound, ``KDASpec.unbounded``) forms the
pairs inside a chunk LEVEL BY LEVEL instead (``kda_decode._levels``, built
here from iotas and shifts): at the level of half-blocks of ``s`` rows a
row in the second half of its block of ``2 s`` and a column in the first
half take the first half's last row as their reference, so that both
exponents are sums of ``g`` (one product of 0/1 matrices with the chunk's
``g`` gives every level's) and at most 0 whatever ``g`` is; six levels a
chunk of 64, each one product on the MXU as a sub-block's row is, and a
seventh for the diagonal of ``P``.  Everything after the two score
matrices is the same code.

Precision is the XLA form's, nowhere lower: the state read, decayed,
corrected and stored in float32 (stored in the state's own dtype, once a
q-block); the running sums, the exponentials and the solve in float32;
operands rounded to the rows' dtype where ``kda_chunked`` rounds them.
``_kda_chunk_scan_call`` is jitted: a model's layers share one trace and
one Mosaic lowering a program (``ragged_attention._paged_rows_call`` has
the story).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._shared import _use_interpret

_HI = jax.lax.Precision.HIGHEST


def _solve_unit_lower(A, rhs):
    """``(I + A)^-1 rhs`` for ``A`` [c, c] strictly lower, ``rhs`` [c,
    n], by forward substitution in float32, a column of ``A`` a step:
    once row ``j`` is final, every later row ``i`` loses ``A[i, j]``
    times it.  Only the sublane tiles that hold a later row are touched
    (static slices at multiples of 8).  63 steps are 0.28 of a call's
    0.64 ms on the chip; diagonal blocks of 16 with products under them
    read 0.63 and 0.72, an inverse times the right-hand side 0.70
    (PERF.md section 6, PR 59): the products' float32 operands are split
    on the vector unit, which is what binds."""
    c = A.shape[0]
    X = rhs
    for j in range(c - 1):
        lo = (j + 1) // 8 * 8                   # the tile of row j + 1
        X = jnp.concatenate(
            [X[:lo], X[lo:] - A[lo:, j:j + 1] * X[j:j + 1]], axis=0) \
            if lo else X - A[:, j:j + 1] * X[j:j + 1]
    return X


def _level_sums(ii, jj, c):
    """``kda_decode._levels`` for a chunk of ``c`` rows from the iotas
    ``ii`` / ``jj`` [c, c]: (the 0/1 matrices stacked down the rows
    [(1 + 2 levels) c, c] float32: the inclusive running sum first, then
    every level's row sums, then every level's column sums; the levels'
    pair masks)."""
    levels = (c - 1).bit_length()
    second = [((ii >> lv) & 1) == 1 for lv in range(levels)]
    parts = [jj <= ii]
    parts += [second[lv] & (jj >= ((ii >> lv) << lv)) & (jj <= ii)
              for lv in range(levels)]
    parts += [~second[lv] & (jj > ii) & (jj < (((ii >> lv) + 1) << lv))
              for lv in range(levels)]
    pairs = [second[lv] & (((jj >> lv) & 1) == 0)
             & ((ii >> (lv + 1)) == (jj >> (lv + 1)))
             for lv in range(levels)]
    return jnp.concatenate(
        [jnp.where(p, 1.0, 0.0) for p in parts], axis=0).astype(
            jnp.float32), pairs


def _scores_by_level(qz, kz, gz, bz, sums, pairs, cd):
    """(A [c, c] strictly lower and times its row's beta, P [c, c] lower
    with its diagonal, the inclusive running sum G [c, D]) of one chunk
    by ``_level_sums``' pairing: every exponent a sum of ``g``."""
    f32 = jnp.float32
    c, levels = qz.shape[0], len(pairs)
    s_all = jnp.dot(sums, gz, precision=_HI, preferred_element_type=f32)
    cross = (((1,), (1,)), ((), ()))
    A = P = jnp.zeros((c, c), f32)
    for lv in range(levels):
        er = jnp.exp(s_all[(1 + lv) * c:(2 + lv) * c])              # <= 1
        ec = jnp.exp(s_all[(1 + levels + lv) * c:(2 + levels + lv) * c])
        got = jax.lax.dot_general(
            jnp.concatenate([(kz * er).astype(cd), (qz * er).astype(cd)],
                            axis=0), (kz * ec).astype(cd), cross,
            preferred_element_type=f32)                        # [2 c, c]
        A = A + jnp.where(pairs[lv], got[:c], 0.0)
        P = P + jnp.where(pairs[lv], got[c:], 0.0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # a row's own column decays by nothing
    P = P + jnp.where(ii == jj, jax.lax.dot_general(
        qz.astype(cd), kz.astype(cd), cross, preferred_element_type=f32),
        0.0)
    return A * bz, P, s_all[:c]


def _kda_chunk_scan_kernel(slot_ref, ql_ref, q_ref, k_ref, v_ref, g_ref,
                           b_ref, s_ref, y_ref, so_ref, sf_ref, *, chunk,
                           sub, exact=False):
    """Grid (lane, head).  ``q_ref`` / ``k_ref`` / ``v_ref`` [Q, D] in
    the rows' dtype, ``g_ref`` [Q, D] float32, ``b_ref`` [Q, H] float32
    (the lane's beta, every head's), ``s_ref`` / ``so_ref`` [D, D] in the
    state's dtype, ``y_ref`` [Q, D] float32; ``sf_ref`` [D, D] float32
    carries ``S`` across the chunks."""
    lane, h = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    cd = q_ref.dtype
    Q, D = q_ref.shape
    c, m = chunk, chunk // sub
    ql = ql_ref[lane]

    @pl.when(ql == 0)
    def _():
        # (the index map sends every head's step of an idle lane to the
        # slot's head 0: one block in, the same block out)
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ql > 0)
    def _():
        sf_ref[...] = s_ref[...].astype(f32)
        ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        if exact:
            sums, pairs = _level_sums(ii, jj, c)
        else:
            # a row's sub-block by comparisons (no vector division)
            blk_i = sum((ii >= b * sub).astype(jnp.int32)
                        for b in range(1, m))
            blk_j = sum((jj >= b * sub).astype(jnp.int32)
                        for b in range(1, m))
            # [sums inside a row's sub-block up to it; sums over the
            # sub-blocks before it]: the running sum of g in two parts
            sums = jnp.concatenate(
                [jnp.where((blk_i == blk_j) & (jj <= ii), 1.0, 0.0),
                 jnp.where(blk_j < blk_i, 1.0, 0.0)], axis=0).astype(f32)
        ones = jnp.ones((c, D), f32)
        row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        head = jax.lax.broadcasted_iota(jnp.int32, (c, b_ref.shape[1]), 1)

        def one(z, carry):
            r0 = pl.multiple_of(z * c, c)
            at = pl.ds(r0, c)
            live = row + r0 < ql                               # [c, 1]
            kz = jnp.where(live, k_ref[at, :].astype(f32), 0.0)
            qz, vz = q_ref[at, :].astype(f32), v_ref[at, :].astype(f32)
            gz = jnp.where(live, g_ref[at, :], 0.0)
            # this head's beta: a column of the lane's [c, H] under a mask
            bz = jnp.where(live, jnp.sum(
                jnp.where(head == h, b_ref[at, :], 0.0), axis=1,
                keepdims=True), 0.0)                           # [c, 1]
            if exact:
                A, P, G = _scores_by_level(qz, kz, gz, bz, sums, pairs, cd)
            else:
                two = jnp.dot(sums, gz, precision=_HI,
                              preferred_element_type=f32)      # [2 c, D]
                Gl, Rf = two[:c], two[c:]
                G = Gl + Rf
                up = jnp.exp(Gl)                               # <= 1
                ku, qu = (kz * up).astype(cd), (qz * up).astype(cd)
                A, P = [], []
                for i in range(m):
                    # column j seen from sub-block i: exp(R_i - G_j)
                    # for j's sub-block at or before i (masked BEFORE
                    # the exponential: a later one's is e^320)
                    R = Rf[i * sub:i * sub + 1]                # [1, D]
                    down = jnp.exp(jnp.where(row < (i + 1) * sub, R - G,
                                             -jnp.inf))
                    at_i = slice(i * sub, (i + 1) * sub)
                    both = jax.lax.dot_general(
                        jnp.concatenate([ku[at_i], qu[at_i]], axis=0),
                        (kz * down).astype(cd), (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)            # [2 sub, c]
                    A.append(both[:sub])
                    P.append(both[sub:])
                A = jnp.where(jj < ii, jnp.concatenate(A, axis=0) * bz, 0.0)
                P = jnp.where(jj <= ii, jnp.concatenate(P, axis=0), 0.0)
            eG = jnp.exp(G)
            WU = _solve_unit_lower(
                A, jnp.concatenate([kz * eG, vz], axis=1) * bz)
            # the chunk's whole decay a KEY channel, down the sublanes
            # as the state's rows lie (every lane the same: a product is
            # the transpose the vector unit has not)
            dend = jnp.exp(jax.lax.dot_general(
                gz, ones, (((0,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=f32))                   # [D, D]
            kend = (kz * jnp.exp(G[c - 1:c] - G)).astype(cd)
            S = sf_ref[...]
            # the two products that READ the state take it in float32
            # at precision highest
            read = jnp.dot(jnp.concatenate([WU[:, :D], qz * eG], axis=0),
                           S, precision=_HI, preferred_element_type=f32)
            u = (WU[:, D:] - read[:c]).astype(cd)
            y_ref[at, :] = read[c:] + jnp.dot(
                P.astype(cd), u, preferred_element_type=f32)
            # ONE state update a chunk
            sf_ref[...] = S * dend + jax.lax.dot_general(
                kend, u, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
            return carry

        n_live = (ql + c - 1) // c
        jax.lax.fori_loop(0, n_live, one, 0)

        def dead(z, carry):
            y_ref[pl.ds(pl.multiple_of(z * c, c), c), :] = jnp.zeros(
                (c, D), f32)
            return carry

        jax.lax.fori_loop(n_live, Q // c, dead, 0)
        so_ref[...] = sf_ref[...].astype(so_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret",
                                             "exact"))
def _kda_chunk_scan_call(slot, q_len, q, k, v, g, beta, mats, *, chunk,
                         sub, interpret, exact=False):
    """``_kda_chunk_scan_kernel`` over ``q`` / ``k`` / ``v`` / ``g``
    [lanes, Q, H D], ``beta`` [lanes, Q, H] and the manager's state."""
    lanes, Q, H = beta.shape
    D = mats.shape[-1]

    def rows(lane, h, slot, ql):
        return lane, 0, h

    def betas(lane, h, slot, ql):
        return lane, 0, 0

    def state(lane, h, slot, ql):
        return 0, slot[lane], jnp.where(ql[lane] > 0, h, 0), 0, 0

    row = pl.BlockSpec((None, Q, D), rows)
    s_spec = pl.BlockSpec((None, None, None, D, D), state)
    return pl.pallas_call(
        functools.partial(_kda_chunk_scan_kernel, chunk=chunk, sub=sub,
                          exact=exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(lanes, H),
            in_specs=[row, row, row, row,
                      pl.BlockSpec((None, Q, H), betas), s_spec],
            out_specs=[row, s_spec],
            scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((lanes, Q, H * D), jnp.float32),
                   jax.ShapeDtypeStruct(mats.shape, mats.dtype)],
        # the state is rewritten where it lies (operands count the two
        # prefetched scalars)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_chunk_scan",
        interpret=interpret,
    )(slot, q_len, q, k, v, g, beta, mats)


def kda_chunk_scan(slot, q_len, q, k, v, g, beta, mats, *, chunk, sub,
                   exact=False, interpret=None):
    """The lanes' q-blocks through the chunked delta rule, on the
    manager's state where it lies.

    ``slot`` [lanes] int32: the lanes' slots, no two the same; ``q_len``
    [lanes] int32: a lane's live rows (0: the lane is idle; the rows
    past it move nothing, whatever they hold); ``q`` / ``k`` / ``v``
    [lanes, Q, H D] after the conv and the normalisation, a head's ``D``
    columns side by side as the wave's rows hold them (``Q`` whole
    chunks of ``chunk`` rows, ``D`` whole lane tiles), ``g`` [lanes, Q,
    H D] float32, ``beta`` [lanes, Q, H] float32, ``mats`` [1, slots, H,
    D, D].  Returns (o [lanes, Q, H D] float32, mats after the
    q-blocks): ``kda_chunked``'s, from and into the lanes' slots; an
    idle lane's rows are 0 and its state keeps its bits.  ``exact``: the
    pairs level by level, for a ``g`` with no lower bound."""
    if interpret is None:
        interpret = _use_interpret()
    return _kda_chunk_scan_call(
        slot.astype(jnp.int32), q_len.astype(jnp.int32), q, k, v, g, beta,
        mats, chunk=chunk, sub=sub, interpret=interpret, exact=exact)
