"""The Mamba-2 mixer's one-step form as one kernel (ISSUE 50).

For every slot with ONE live row (``models/ssm_decode.ssd_step`` has the
mathematics, and stays as the XLA form), a head of ``P`` columns over
``N`` state columns, ``H / G`` heads sharing a group's ``B`` and ``C``:

  a = exp(dt A)                      a number a head
  S <- S a + (dt x) B^T              [P, N] float32
  y = S C                            [P]    float32 (``D x`` is the caller's)

The XLA form is three passes over the state (the update reads and
writes it, the read-out reads it again in a fusion of its own); here a
block of heads is in VMEM once:

  - grid (slot, head block); the slot's ``[heads, P, N]`` block of the
    matrix state is addressed IN the manager's array ``[1, slots, H, P,
    N]`` by slot number and aliased to the output: read once, written
    once, where it lies (every slot is a step, so the grid's index is
    the slot's number);
  - a head's ``[P, N]`` tile is decayed by its number (a scalar out of
    SMEM, the prefetched ``exp(dt A)``) and the increment added: its
    column ``dt x`` ``[P, 1]`` spread over the lanes times the group's
    row ``B`` ``[1, N]`` spread over the sublanes; the columns come in
    TRANSPOSED (``[P, heads]``: a head a lane), so that a value is not
    padded to a lane tile in memory;
  - the read-out ``sum_n S C`` is a product on the MXU, the block's
    heads (one group's, or part of one) against their ``C``, at float32
    precision (``HIGHEST``): its result is a row of ``[heads x P]``,
    laid out as ``y`` is;
  - a slot that does not move (``dt`` 0: none or more than one live row)
    has decay 1 and increment 0: its state is written back as it was
    read, bit for bit, as in the XLA form.

Everything is float32: the state in and out, the products and the sums.
``_ssm_step_call`` is jitted: a model's layers share one trace and one
Mosaic lowering a program (``ragged_attention._paged_rows_call`` has the
story).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._shared import _use_interpret

# a block of the state twice in and twice out, the rows beside them
_VMEM_LIMIT = 48 << 20
# the most of the state a grid step moves
BLOCK_BYTES = 4 << 20


def head_block(heads, groups, head_dim, state):
    """Heads a grid step: a group's (they share a ``B`` and a ``C``, and
    one product reads them all out), or where a group's ``[P, N]``
    float32 tiles do not fit ``BLOCK_BYTES`` a divisor of them.  Not
    more than a group, though blocks of 4 MB read 1-2 % ahead of a
    group's 0.5 MB and 2 MB on the chip: the kernel's body is unrolled
    over its heads, and a body of 128 heads took 3.4 s to lower, a
    program, at every start, with or without a compile cache (PERF.md
    section 6, PR 50)."""
    hg = heads // groups
    fit = max(1, BLOCK_BYTES // (head_dim * state * 4))
    return max(k for k in range(1, hg + 1) if hg % k == 0 and k <= fit)


def _ssm_step_kernel(a_ref, xt_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *,
                     hg):
    """Grid (slot, head block), a block inside one group.  ``a_ref``
    [slots x H] float32 in SMEM; ``xt_ref`` [P, hb] (``dt x``, a head a
    lane); ``b_ref`` / ``c_ref`` [G, N]; ``s_ref`` / ``so_ref`` [hb, P,
    N]; ``y_ref`` [1, hb P]."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb, P, N = s_ref.shape
    first = (i * pl.num_programs(1) + j) * hb
    g = (j * hb) // hg
    xt, b = xt_ref[...], b_ref[pl.ds(g, 1), :]
    for h in range(hb):
        so_ref[h] = s_ref[h] * a_ref[first + h] + xt[:, h:h + 1] * b
    # the read-out on the MXU, which is idle, at float32 precision:
    # summed over the lanes a tile at a time the cross-lane unit binds
    # (PERF.md section 6, PR 50), and the result of this product lies as
    # y does, a row of [heads x P]
    y_ref[...] = jax.lax.dot_general(
        jnp.broadcast_to(c_ref[pl.ds(g, 1), :], (8, N)),
        so_ref[...].reshape(hb * P, N), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_call(a, xt, Bm, Cm, mats, *, interpret):
    """``_ssm_step_kernel`` over ``a`` [slots x H], ``xt`` [slots, H /
    hb, P, hb], ``Bm`` / ``Cm`` [slots, G, N] and the manager's state."""
    B_, nb, P, hb = xt.shape
    _, _, H, _, N = mats.shape
    G = Bm.shape[1]

    def rows(i, j, a):
        return i, j, 0, 0

    def group(i, j, a):
        return i, 0, 0

    def state(i, j, a):
        return 0, i, j, 0, 0

    row = pl.BlockSpec((None, G, N), group)
    s_spec = pl.BlockSpec((None, None, hb, P, N), state)
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, hg=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B_, nb),
            in_specs=[pl.BlockSpec((None, None, P, hb), rows), row, row,
                      s_spec],
            out_specs=[pl.BlockSpec((None, None, 1, hb * P), rows),
                       s_spec]),
        out_shape=[jax.ShapeDtypeStruct((B_, nb, 1, hb * P), jnp.float32),
                   jax.ShapeDtypeStruct(mats.shape, mats.dtype)],
        # the state is rewritten where it lies (operands count the
        # prefetched scalars)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssm_step",
        interpret=interpret,
    )(a, xt, Bm, Cm, mats)


def ssm_step(x, dt, A, Bm, Cm, mats, *, interpret=None):
    """One step of the recurrence for every slot, on the manager's state
    where it lies: ``x`` [slots, H, P], ``dt`` [slots, H] float32 (0:
    the slot does not move and its state keeps its bits), ``A`` [H],
    ``Bm`` / ``Cm`` [slots, G, N], ``mats`` [1, slots, H, P, N] float32
    (``N`` whole lane tiles, ``P`` whole sublane tiles).  Returns (y
    [slots, H, P] float32 without the ``D x`` term, mats): what
    ``ssd_step`` returns of ``mats[0]``."""
    B_, H, P = x.shape
    f32 = jnp.float32
    if interpret is None:
        interpret = _use_interpret()
    hb = head_block(H, Bm.shape[1], P, mats.shape[-1])
    # a head a lane: [slots, H, P] -> [slots, H / hb, P, hb]
    xt = (dt[:, :, None] * x.astype(f32)).reshape(B_, H // hb, hb, P)
    y, mats = _ssm_step_call(
        jnp.exp(dt * A).reshape(-1), xt.transpose(0, 1, 3, 2),
        Bm.astype(f32), Cm.astype(f32), mats, interpret=interpret)
    return y.reshape(B_, H, P), mats
