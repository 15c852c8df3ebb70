"""A grouped matmul whose time follows the touched groups' bytes
(ISSUE 41: the chunk wave; ISSUE 49: the decode wave too).

``lhs`` [M, K] rows sorted by group times ``rhs`` [G, K, N], group ``g``
owning the next ``group_sizes[g]`` rows: the routed experts' three
products (``models/moe_decode.routed_ffn``).  The compiler's own kernel
for ``jax.lax.ragged_dot`` takes 1.4 to 5.3 times the touched experts'
bytes where a group holds a few rows (a decode wave: 2-4 rows an
expert) and about three times where it holds tens to hundreds (a chunk
wave); this one takes 1.2 times in both (PERF.md section 6, PR 41 and
PR 49):

  - the rows are cut into tiles of ``tm`` and a grid step is one
    (group, row tile) pair in the order of the rows, so that a tile two
    groups share is visited once by each under a row mask (the sort and
    the unsort stay as they are: no group is padded to the tile);
  - the K dimension is whole in one block: the steps of one group find
    the expert's ``[K, tn]`` block resident, so each touched expert's
    matrix is read ONCE a call, copied by hand one GROUP ahead of its
    use; an expert without rows has no step and costs nothing;
  - row tiles wholly past the groups' sum have no step either: the
    static grid's tail repeats the last live step's blocks and skips its
    work (``ragged_attention``'s dead-tile rule), so the live rows set
    the time, not the padded ``M``;
  - bf16 operands, ONE float32 product a step, one rounding to the
    output dtype;
  - with ``up`` given the step computes ``silu(x gate) * (x up)`` from
    one read of the rows (the two ``[M, N]`` intermediates never reach
    HBM).

The (group, tile) pairs are data (``group_tiles``), computed once a
layer from the load and shared by the layer's products; they ride in as
scalar prefetch.  ``_gmm_call`` is jitted with static tiles, so that a
program's call sites of one shape share one trace and one Mosaic
lowering (``ragged_attention._paged_rows_call`` has the story).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._shared import _LANES, _use_interpret

# rows a tile (swept 64 / 128 / 256 at a chunk wave's shapes, PR 41, and 16
# to 128 at a decode wave's, all within 1 %, PR 49: PERF.md section 6)
TILE_M = 128
# bytes one ``[K, tn]`` block of an expert's matrix may take: a whole
# [2048, 2048] bf16 matrix, so that at the cells' widths ``tn`` is N (the
# widest was the fastest at every shape of the sweep: the rows are read
# once).  It is double-buffered, twice over with ``up`` (29 MB at [2048,
# 1792]), beside the row and output tiles, under ``_VMEM_LIMIT`` of the
# v5e's 128 MiB.
_RHS_BLOCK_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20


class GroupTiles(NamedTuple):
    """The grid's data.  ``offsets`` [G + 1]: group ``g`` owns rows
    ``offsets[g] .. offsets[g + 1]``.  For grid step ``s``, the first
    ``steps[0]`` of them live (the rest repeat the last live step): its
    group ``group_ids[s]`` and row tile ``tile_ids[s]``; ``first[s]``, 1
    where the step is its group's first; ``ordinal[s]``, the group's
    place among the groups that have rows (``steps[1]`` of them); and
    ``next_ids[s]``, the next group that has rows (-1 after the last)."""

    offsets: jax.Array
    group_ids: jax.Array
    tile_ids: jax.Array
    steps: jax.Array
    first: jax.Array
    ordinal: jax.Array
    next_ids: jax.Array


def grid_steps(m, groups, tm=TILE_M):
    """The static grid: every row tile once, and once more for each
    group that can start inside a tile."""
    return -(-m // tm) + groups - 1


def group_tiles(group_sizes, m, tm=TILE_M):
    """:class:`GroupTiles` of ``group_sizes`` [G] int32 over ``m`` rows
    cut into tiles of ``tm``.  A group of ``n > 0`` rows starting at
    ``a`` takes the tiles ``a // tm .. (a + n - 1) // tm``; an empty one
    none.  Comparisons and sums over ``[steps, G]`` and ``[G, G]``: no
    sort, no gather."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ids = jnp.arange(G, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    upto = jnp.cumsum(count)                      # steps through group g
    total = upto[-1]
    step = jnp.arange(grid_steps(m, G, tm), dtype=jnp.int32)
    s = jnp.minimum(step, jnp.maximum(total - 1, 0))
    gid = jnp.sum(upto[None, :] <= s[:, None], axis=1, dtype=jnp.int32)
    gid = jnp.minimum(gid, G - 1)                 # total == 0: group G - 1
    own = gid[:, None] == ids[None, :]

    def of_group(per_group):
        """``per_group[gid[s]]`` for every step."""
        return jnp.sum(jnp.where(own, per_group[None, :], 0), axis=1,
                       dtype=jnp.int32)

    # step s is tile ``first_tile[g] + (s - steps before g)`` of its group
    tile = jnp.clip(of_group(first_tile - upto + count) + s,
                    0, -(-m // tm) - 1)
    has = (sizes > 0).astype(jnp.int32)
    after = jnp.where((ids[None, :] > ids[:, None]) & (has[None, :] > 0),
                      ids[None, :], G).min(axis=1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return GroupTiles(
        offsets, gid, tile, jnp.stack([total, jnp.sum(has)]),
        ((step < total) & (s == of_group(upto - count))).astype(jnp.int32),
        of_group(jnp.cumsum(has) - 1),
        of_group(jnp.where(after == G, -1, after)))


def col_tile(K, N, itemsize=2, budget=_RHS_BLOCK_BYTES):
    """Output columns a step: the largest divisor of ``N`` that is a
    whole number of lane tiles and keeps ``[K, tn]`` within ``budget``
    (``N`` itself where no lane multiple divides it: the tiny widths of
    the tests)."""
    if N % _LANES:
        return N
    fits = [d for d in range(_LANES, N + 1, _LANES)
            if N % d == 0 and K * d * itemsize <= budget]
    return max(fits) if fits else _LANES


def _gmm_kernel(offs_ref, gid_ref, tid_ref, steps_ref, first_ref, ord_ref,
                next_ref, lhs_ref, *refs, tm, tn, n_rhs, act=None):
    """Grid (column tile ``n``, step ``s``).  The expert's ``[K, tn]``
    blocks are copied by hand a GROUP ahead: at a group's first step its
    own copy is awaited and the next group's started into the other
    buffer, so that the copy has all of this group's steps to land in
    (the pipeline's own prefetch looks one STEP ahead: a group of two
    steps then waits for most of the next group's 7 MB)."""
    w_hbm, out_ref = refs[:n_rhs], refs[n_rhs]
    w_buf, sem = refs[n_rhs + 1:2 * n_rhs + 1], refs[-1]
    n, s = pl.program_id(0), pl.program_id(1)

    def copies(g, col, slot):
        cols = slice(None) if tn == w_hbm[0].shape[2] else pl.ds(
            pl.multiple_of(col * tn, _LANES), tn)
        return [pltpu.make_async_copy(w.at[g, :, cols], buf.at[slot],
                                      sem.at[i, slot])
                for i, (w, buf) in enumerate(zip(w_hbm, w_buf))]

    @pl.when(s < steps_ref[0])
    def _():
        g = gid_ref[s]
        slot = (n * steps_ref[1] + ord_ref[s]) % 2

        @pl.when(first_ref[s] == 1)
        def _():
            @pl.when((n == 0) & (s == 0))
            def _():
                for c in copies(g, n, slot):
                    c.start()
            for c in copies(g, n, slot):
                c.wait()
            more = next_ref[s] >= 0           # a group after this one,
            wrap = n + 1 < pl.num_programs(0)  # or the next column tile

            @pl.when(more | wrap)
            def _():
                for c in copies(jnp.where(more, next_ref[s], gid_ref[0]),
                                jnp.where(more, n, n + 1), 1 - slot):
                    c.start()

        x = lhs_ref[...]
        acc = jnp.dot(x, w_buf[0][slot], preferred_element_type=jnp.float32)
        if n_rhs == 2:
            acc = jax.nn.silu(acc) * jnp.dot(
                x, w_buf[1][slot], preferred_element_type=jnp.float32)
        elif act == "relu2":
            acc = jnp.square(jnp.maximum(acc, 0.0))
        row = tid_ref[s] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        # a tile's other rows are another step's (or nobody's)
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "interpret", "act"))
def _gmm_call(tiles, lhs, *rhs, tm, tn, interpret, act=None):
    """The kernel over ``lhs`` [M, K] and one ``rhs`` [G, K, N], or two
    (gate, up) for the gated product.  Steps innermost: a group's steps
    are consecutive, so its ``[K, tn]`` block is copied once a column
    tile.  ``act`` "relu2": the one product's squared ReLU, on the
    float32 accumulator."""
    M, K = lhs.shape
    G, _, N = rhs[0].shape

    def rows(n, s, offs, gid, tid, *_):
        return tid[s], 0

    def out(n, s, offs, gid, tid, *_):
        return tid[s], n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tiles),
        grid=(N // tn, tiles.group_ids.shape[0]),
        in_specs=[pl.BlockSpec((tm, K), rows)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(rhs),
        out_specs=pl.BlockSpec((tm, tn), out),
        scratch_shapes=[pltpu.VMEM((2, K, tn), w.dtype) for w in rhs]
        + [pltpu.SemaphoreType.DMA((len(rhs), 2))],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, n_rhs=len(rhs),
                          act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(*tiles, lhs, *rhs)


def grouped_matmul_tiled(lhs, rhs, tiles, *, up=None, tm=TILE_M, tn=None,
                         interpret=None, act=None):
    """``lhs`` [M, K] (rows sorted by group, ``M`` a multiple of ``tm``)
    times ``rhs`` [G, K, N] under ``tiles = group_tiles(sizes, M, tm)``;
    rows past the groups' sum come out as anything.  With ``up`` [G, K,
    N] the result is ``silu(lhs rhs) * (lhs up)``; with ``act`` "relu2"
    (and no ``up``) it is ``relu(lhs rhs) ** 2``."""
    M, K = lhs.shape
    if act is not None and (act != "relu2" or up is not None):
        raise ValueError(f"act={act!r} with up={up is not None}: the "
                         f"kernel has relu2 on one product alone")
    if M % tm:
        raise ValueError(f"{M} rows are no whole number of {tm}-row tiles")
    if tiles.group_ids.shape[0] != grid_steps(M, rhs.shape[0], tm):
        raise ValueError(f"the tiles are not group_tiles(sizes, {M}, {tm})")
    if interpret is None:
        interpret = _use_interpret()
    both = (rhs,) if up is None else (rhs, up)
    if tn is None:
        tn = col_tile(K, rhs.shape[2], rhs.dtype.itemsize)
    return _gmm_call(tiles, lhs, *both, tm=tm, tn=tn, interpret=interpret,
                     act=act)
