"""The float K/V pool's WIDE write as one kernel (ISSUE 56).

A wave's rows lie slot-major (``gpt_decode._Rows``: slot ``b``'s
``q_len[b]`` rows from packed row ``start[b]``; a padded wave is the
same layout with ``start[b] = b * Q``) and go to positions ``pos[b] ..``
of the slot's pages.  A position is one row of a (16, 128) tile, and the
TPU runs a scatter an update at a time whatever it writes, so rows are
written as PAGES (PR 31).  This is that write over the pages the live
rows touch and no others:

  - ``touched_pages`` lists them, once a wave and a table, from ``pos``,
    ``q_len`` and ``start`` alone: at most ``R / block + 2 B``, typically
    a chunk's and one a decoding slot;
  - ``paged_kv_write`` is ONE call a layer for K and V (they share the
    table): the pools stay in HBM and are aliased to the outputs; a
    step of the kernel's loop is one touched page, whose rows are a run
    of the packed rows at an offset no tile knows: the run is copied
    from where it lies in a window of whole sublane tiles and read out
    of it at its offset, laid over the page (which is READ only where a
    row of it stays: the first and the last of a chunk, a decoding
    slot's) and copied back.  ``_DEPTH`` pages are in flight; the loop
    runs over the pages there are (a traced count), so a wave of one
    chunk pays for one chunk.

The rows come in 32 bits a lane (a bfloat16 pool's as float32, which
holds every bfloat16 as it is): a row at an odd offset is then whole
sublanes and not half of one.  Two steps never write one page (two slots
never share a block they write; a ring is wider than a chunk), and no
step reads what another writes.  Dead rows go nowhere: scratch block 0
is not touched.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._shared import _use_interpret

# pages in flight: a page's copies are latency and not bytes
_DEPTH = 8
# sublanes of a 32-bit tile: what a window of source rows starts on
_SUB = 8


class Touched(NamedTuple):
    """The pages a wave's live rows touch under one table, a step of
    the kernel each (``[P_max]`` int32, the first ``count`` of them
    meant): the pool block, the packed row that the page's row 0 would
    be (up to a page before the slot's first row: from ``-block + 1``)
    and the live rows' range in the page."""

    count: jax.Array
    block: jax.Array
    row: jax.Array
    lo: jax.Array
    hi: jax.Array


def max_touched(slots, q, rows, bs):
    """The most pages ``rows`` packed rows of ``slots`` q-blocks ``q``
    wide can touch: a slot's run of ``n`` rows lies in at most ``n / bs +
    2`` pages, and in no more than a q-block can."""
    return min(slots * (-(-q // bs) + 1), rows // bs + 2 * slots)


def touched_pages(pos, q_len, start, tables, bs, rows, q):
    """``Touched`` of a wave: slot ``b``'s ``q_len[b]`` rows (those of
    them inside the ``rows`` packed rows) at packed row ``start[b]`` go to
    positions ``pos[b] ..`` of the blocks ``tables[b]`` names."""
    B, T = tables.shape
    pmax = max_touched(B, q, rows, bs)
    n = jnp.clip(jnp.minimum(q_len, rows - start), 0, q)
    p0 = pos // bs
    pages = jnp.where(n > 0, (pos + n - 1) // bs - p0 + 1, 0)
    ends = jnp.cumsum(pages)
    i = jnp.arange(pmax)
    b = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), B - 1)
    page = p0[b] + i - (ends[b] - pages[b])
    first = pos[b] - page * bs         # the slot's first row, in the page
    i32 = lambda x: x.astype(jnp.int32)                    # noqa: E731
    return Touched(
        i32(jnp.minimum(ends[-1], pmax)).reshape(1),
        i32(tables[b, jnp.clip(page, 0, T - 1)]), i32(start[b] - first),
        i32(jnp.maximum(first, 0)), i32(jnp.minimum(first + n[b], bs)))


def _write_kernel(n_ref, layer_ref, blk_ref, row_ref, lo_ref, hi_ref,
                  k_rows, v_rows, _k_in, _v_in, k_pool, v_pool, src_buf,
                  page_buf, out_buf, sem, *, bs, depth):
    """The loop over the touched pages.  ``k_rows`` / ``v_rows`` [bs + R
    + bs + 8, W] (a page of padding in front) and the pools stay in HBM;
    ``src_buf`` [depth, 2, bs + 8, W], ``page_buf`` / ``out_buf``
    [depth, 2, bs, W]; ``sem`` [3, depth, 2] (window in, page in, page
    out)."""
    n, layer = n_ref[0], layer_ref[0]
    win = src_buf.shape[2]
    pools = ((k_rows, k_pool), (v_rows, v_pool))

    def partial(i):
        return (lo_ref[i] > 0) | (hi_ref[i] < bs)

    def window(i):
        """(first row, the page's place in it) of step ``i``'s window of
        source rows: whole sublane tiles from the one its row 0 is in."""
        at = row_ref[i] + bs
        base = at // _SUB * _SUB
        return pl.multiple_of(base, _SUB), at - base

    def reads(i, s, what):
        """Step ``i``'s copies in, into buffer ``s``: the windows of
        source rows (``what`` 0) or the pages themselves (1)."""
        out = []
        for j, (rows, pool) in enumerate(pools):
            if what == 0:
                src = rows.at[pl.ds(window(i)[0], win)]
                dst = src_buf.at[s, j]
            else:
                src, dst = pool.at[layer, blk_ref[i]], page_buf.at[s, j]
            out.append(pltpu.make_async_copy(src, dst, sem.at[what, s, j]))
        return out

    def writes(i, s):
        return [pltpu.make_async_copy(out_buf.at[s, j],
                                      pool.at[layer, blk_ref[i]],
                                      sem.at[2, s, j])
                for j, (_, pool) in enumerate(pools)]

    def fetch(i):
        s = i % depth
        for c in reads(i, s, 0):
            c.start()

        @pl.when(partial(i))
        def _page():
            for c in reads(i, s, 1):
                c.start()

    def drain(i):
        for c in writes(i, i % depth):
            c.wait()

    def step(i):
        s = i % depth

        @pl.when(i >= depth)
        def _free():                       # the buffer's last page is out
            drain(i - depth)

        for c in reads(i, s, 0):
            c.wait()

        @pl.when(partial(i))
        def _page():
            for c in reads(i, s, 1):
                c.wait()

        r = jax.lax.broadcasted_iota(jnp.int32, out_buf.shape[2:], 0)
        live = (r >= lo_ref[i]) & (r < hi_ref[i])
        for j in range(2):
            # the window turned so that the page's row 0 is its first
            new = pltpu.roll(src_buf[s, j], win - window(i)[1], 0)[:bs]
            old = page_buf[s, j].astype(new.dtype)
            out_buf[s, j] = jnp.where(live, new, old).astype(out_buf.dtype)
        for c in writes(i, s):
            c.start()

        @pl.when(i + depth < n)
        def _next():
            fetch(i + depth)

    def over(lo, hi, body):
        """``body(i)`` for ``lo <= i < hi``, both traced."""
        def one(i, carry):
            body(i)
            return carry
        jax.lax.fori_loop(lo, hi, one, 0)

    over(0, jnp.minimum(depth, n), fetch)
    over(0, n, step)
    over(jnp.maximum(n - depth, 0), n, drain)     # the last pages out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_call(touched, layer, k_rows, v_rows, pool_k, pool_v, *,
                interpret):
    """``_write_kernel`` over the padded source rows ``[Rp, W]`` and the
    pool pair.  Jitted, with the layer a traced scalar: a model's layers
    share one trace and one Mosaic lowering a program
    (``ragged_attention._paged_rows_call`` has the story)."""
    bs, W = pool_k.shape[2:]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    page = (_DEPTH, 2, bs, W)
    return pl.pallas_call(
        functools.partial(_write_kernel, bs=bs, depth=_DEPTH),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(1,),
            in_specs=[anywhere] * 4, out_specs=[anywhere] * 2,
            scratch_shapes=[
                pltpu.VMEM((_DEPTH, 2, bs + _SUB, W), k_rows.dtype),
                pltpu.VMEM(page, pool_k.dtype),
                pltpu.VMEM(page, pool_k.dtype),
                pltpu.SemaphoreType.DMA((3, _DEPTH, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in (pool_k, pool_v)],
        # the pools are rewritten where they lie (operands count the
        # prefetched scalars)
        input_output_aliases={8: 0, 9: 1},
        name="paged_kv_write",
        interpret=interpret,
    )(touched.count, layer, touched.block, touched.row, touched.lo,
      touched.hi, k_rows, v_rows, pool_k, pool_v)


def paged_kv_write(pool_k, pool_v, layer, k_rows, v_rows, touched, *,
                   interpret=None):
    """Layer ``layer`` (may be traced) of the pool pair ``[L, N, bs,
    W]`` with the wave's rows ``k_rows`` / ``v_rows`` [R, W] written to
    the pages ``touched`` lists (``touched_pages`` of the same ``R``).
    Every block but those pages, scratch block 0 among them, keeps its
    bits; returns the pair."""
    if interpret is None:
        interpret = _use_interpret()
    bs = pool_k.shape[2]
    # 32 bits a lane, a page of rows in front (a first page's rows before
    # the slot's first) and room for the last window behind
    wide = jnp.promote_types(pool_k.dtype, jnp.float32)

    def padded(rows):
        return jnp.pad(rows.astype(pool_k.dtype).astype(wide),
                       ((bs, bs + _SUB), (0, 0)))

    return _write_call(
        touched, jnp.asarray(layer, jnp.int32).reshape(1), padded(k_rows),
        padded(v_rows), pool_k, pool_v, interpret=bool(interpret))
