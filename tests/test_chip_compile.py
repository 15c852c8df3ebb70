"""Ask the chip's compiler, without the chip (ISSUE 22).

The TPU compiler is installed wherever the tests run, and it compiles
for a chip that is described and not attached.  Every kernel of the two
main paths is compiled here for a TPU v5e at the real GPT-2 widths with
``interpret=False`` — what interpret mode cannot show (VMEM overruns,
unaligned tiles, kernels GSPMD cannot partition) fails here, at no chip
time.  Nothing runs, so nothing here is a result or a time.

All of these live in this one file, and the topology is described
inside a module-scoped fixture: only the worker that is handed this
file loads libtpu.  Do not move the call to import time, to
``conftest.py`` or into an ``autouse`` fixture.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from hetu_tpu.kernels import flash_attention as fa
from hetu_tpu.kernels.ragged_attention import (
    ragged_paged_attention, ragged_paged_mla)
from hetu_tpu.kv_layout import kv_row_width
from hetu_tpu.models import gpt_decode as gd

DH, S_MAX, BLOCK, SLOTS = 64, 1024, 16, 8


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """Shape-with-sharding factory on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, \
        "the kernel was interpreted or replaced: nothing was proven"
    return text


# ------------------------------------------------------------------- #
# training path: the flash kernel
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("seq,heads,masked", [
    (1024, 12, False), (512, 12, True), (1024, 16, False)],
    ids=["gpt2-s1024-causal", "bert-s512-kv_lens", "gpt2-medium-s1024-cell"])
def test_flash_fwd_bwd(sds, monkeypatch, seq, heads, masked):
    """Forward and backward lower through Mosaic at the training shapes,
    the last the training cell's own (B 8, S 1,024, H 16, D 64, bfloat16,
    causal).  The operands are the ``[B * S, H * D]`` the layer's
    projections give: the kernels address them where they lie, so the
    compiled step holds no ``copy`` or ``transpose`` the size of q and no
    temporary of that size either."""
    # flash_attention takes no interpret argument: it asks the backend,
    # which is the CPU here — steer it in the test, not by a program option
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    B = 8
    qkv = sds((B * seq, heads * DH), jnp.bfloat16)

    def loss(q, k, v, lens=None):
        q, k, v = (t.reshape(B, seq, heads, DH) for t in (q, k, v))
        o = fa.flash_attention(q, k, v, causal=not masked, kv_lens=lens)
        return (o.reshape(qkv.shape).astype(jnp.float32) ** 2).sum()

    args = (qkv, qkv, qkv) + ((sds((B,), jnp.int32),) if masked else ())
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2          # fwd, one backward
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)
        if np.prod([int(n) for n in m.group(1).split(",")]) == qkv.size]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes \
        < qkv.size * qkv.dtype.itemsize


def test_flash_op_under_a_mesh(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"): the graph op runs it per shard inside
    shard_map, or a dp x tp train step does not lower on the chip."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import TraceContext
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    op = ht.flash_attention_op(ht.placeholder_op("q"), ht.placeholder_op("k"),
                               ht.placeholder_op("v"), causal=True)
    x = jax.ShapeDtypeStruct(
        (8, 1024, 12, DH), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))
    tc = TraceContext(mesh=mesh)
    text = compiled_text(lambda q, k, v: op.compute([q, k, v], tc), x, x, x)
    assert "all-gather" not in text     # batch and heads stay sharded


# ------------------------------------------------------------------- #
# serving path: the mixed wave's ragged kernel (scores the wave on a TPU)
# ------------------------------------------------------------------- #

def _pool(sds, heads, quant, layers=2):
    """The whole pool as the engine holds it: bf16 rows ``[L, N, bs,
    W]`` (``kv_layout``), or the int8 pair's ``[L, N, bs, H, Dh]`` with
    its scale planes."""
    T = S_MAX // BLOCK
    N = SLOTS * T + 1
    if quant:
        pool = sds((layers, N, BLOCK, heads, DH), jnp.int8)
        scales = (sds((layers, N, BLOCK, heads), jnp.float32),) * 2
    else:
        pool = sds((layers, N, BLOCK, kv_row_width(heads, DH)),
                   jnp.bfloat16)
        scales = ()
    return pool, sds((SLOTS, T), jnp.int32), scales


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("heads,q_len", [
    (12, 1), (12, 256), (12, 1024),      # GPT-2 small; 1024 = a whole prompt
    (25, 1), (25, 512),                  # gpt2-xl width
], ids=lambda v: str(v))
def test_ragged_paged(sds, heads, q_len, kv):
    """Q=1024 at H=12 and Q=512 at H=25 were refused before ISSUE 22
    ("Scoped allocation with size 18.11M and limit 16.00M"): the q-block
    was one VMEM tile that grew with the prompt.  bf16: the pool's rows
    read in place; int8: the blocked kernel over ``pool[layer]``."""
    quant = kv == "int8"
    pool, tables, scales = _pool(sds, heads, quant)
    lens = sds((SLOTS,), jnp.int32)

    def fn(q, pk, pv, lengths, q_lens, bt, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return ragged_paged_attention(q, pk, pv, lengths, q_lens, bt,
                                      layer=1, interpret=False, **kw)

    text = compiled_text(fn, sds((SLOTS, q_len, heads, DH), jnp.bfloat16),
                         pool, pool, lens, lens, tables, *scales)
    assert "ragged_paged_mixed" in text


# the gpt2-xl cell's own sizes (benchmarks/configs/gpt2-xl.json): 48
# layers, 449 blocks of 16, rows of 1664 lanes, 16 slots, a table 64 wide
CELL = dict(layers=48, blocks=449, slots=16, table=64)


@pytest.mark.parametrize("heads,q_len", [
    (25, 1), (25, 64), (25, 256), (25, 512),
    (12, 1), (12, 256), (16, 1), (16, 256),
], ids=lambda v: str(v))
def test_ragged_paged_rows_at_the_cell_sizes(sds, heads, q_len):
    """ISSUE 31: the pool pair left in HBM, pages copied by hand with
    the layer in the copy.  At 25 heads a page is 16 x 1664 x 2 B = 53
    KB each for K and V; 16 pages a group and two buffers are 3.4 MB of
    VMEM beside a q-tile of at most 2048 (head, query) rows."""
    from hetu_tpu.kernels import ragged_attention as ra
    B, T = CELL["slots"], CELL["table"]
    W = kv_row_width(heads, DH)
    assert W % 128 == 0 and ra._lane_chunk(W, DH) == 128
    assert ra._page_group(T, BLOCK, W, jnp.bfloat16) == 16
    pool = sds((CELL["layers"], CELL["blocks"], BLOCK, W), jnp.bfloat16)
    lens = sds((B,), jnp.int32)

    def fn(q, pk, pv, lengths, q_lens, bt):
        return ragged_paged_attention(q, pk, pv, lengths, q_lens, bt,
                                      layer=47, interpret=False)

    text = compiled_text(fn, sds((B, q_len, heads, DH), jnp.bfloat16),
                         pool, pool, lens, lens, sds((B, T), jnp.int32))
    assert "ragged_paged_mixed" in text


# the chunk wave's write at ISSUE 56's probe shapes: (slots, row width,
# pages a table, blocks of the pool, layers, blocks of the ring pool)
WRITES = {"gpt2-xl": (16, 1664, 64, 449, 48, 0),
          "mellum2-code": (32, 1024, 1024, 3001, 3, 2593),
          "falcon-chat": (64, 512, 64, 2049, 2, 0)}


@pytest.mark.parametrize("cell", list(WRITES))
def test_paged_kv_write_at_the_cells_sizes(sds, cell):
    """ISSUE 56: the float pool's wide write as ONE call a layer for K
    and V, the pool pair left in HBM and aliased to the results, the
    layer traced, the 1,024 packed rows of a 256-row bucket as they lie
    (32 bits a lane, a window of 24 rows turned by a dynamic sublane
    roll: Mosaic refuses a 16-row load at an offset it cannot prove a
    multiple of 8).  A window layer's call takes the ring pool under the
    ring repeated.  No copy of a pool: what the program holds beside
    the donated pools is the rows."""
    from hetu_tpu.kernels.paged_kv_write import paged_kv_write, touched_pages
    B, W, T, N, L, ring = WRITES[cell]
    R, Q = 1024, 256
    pools = [sds((L, N, BLOCK, W), jnp.bfloat16)] * 2
    if ring:
        pools += [sds((9, ring, BLOCK, W), jnp.bfloat16)] * 2
    rows = sds((R, W), jnp.bfloat16)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731

    def write(pools, k, v, pos, q_len, start, tables, rings, layer):
        tabs = [tables]
        if ring:
            tabs.append(rings[:, jnp.arange(T) % rings.shape[1]])
        out = []
        for n, t in enumerate(tabs):
            ck, cv = pools[2 * n:2 * n + 2]
            touched = touched_pages(pos, q_len, start, t, BLOCK, R, Q)
            for i in range(2):      # two layers: one lowering
                ck, cv = paged_kv_write(ck, cv, layer + i, k, v, touched,
                                        interpret=False)
            out += [ck, cv]
        return out

    compiled = jax.jit(write, donate_argnums=(0,)).lower(
        pools, rows, rows, i32(B), i32(B), i32(B), i32(B, T),
        i32(B, (ring - 1) // B if ring else 1), i32()).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "paged_kv_write" in line]
    assert len(calls) == (4 if ring else 2)
    assert all("tpu_custom_call" in c for c in calls)
    _no_copy_of_a_pool(compiled, pools)
    # beside the pools: the rows, 32 bits a lane (2 x 7 MB at 1,664)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def _one_page_write_a_kv_layer(compiled, layers):
    """A program a page or more wide writes each of its ``layers`` K/V
    layers through ONE Mosaic call named ``paged_kv_write`` (ISSUE 56);
    a narrower one (``layers`` 0) scatters rows and names none.  The
    caller steers ``paged_kv_write._use_interpret`` as it does the
    attention kernel's, or the write it compiles is the interpreted
    one, which the chip never runs."""
    writes = [line for line in compiled.as_text().splitlines()
              if "custom-call(" in line and "paged_kv_write" in line]
    assert len(writes) == layers
    assert all("tpu_custom_call" in c for c in writes)


def _no_copy_of_a_pool(compiled, pools):
    """The donated ``pools`` are rewritten where they lie: aliased, and
    no ``copy`` makes one of their shape."""
    shapes = {f"bf16[{','.join(map(str, p.shape))}]" for p in pools}
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line and any(
                  line.split(" copy(")[0].split("= ")[-1].startswith(sh)
                  for sh in shapes)]
    assert not copies, copies[:2]
    held = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= held


def _gpt_shapes(sds, name, layers, hidden, vocab, positions):
    """The parameter dict of a GPT-2 of these sizes, as bf16 shapes."""
    def w(*shape):
        return sds(shape, jnp.bfloat16)
    p = {f"{name}_wte_table": w(vocab, hidden),
         f"{name}_wpe": w(positions, hidden),
         f"{name}_ln_f_scale": w(hidden), f"{name}_ln_f_bias": w(hidden)}
    for i in range(layers):
        us = f"{name}_h{i}"
        for leaf, (a, b) in [("attn_q", (1, 1)), ("attn_k", (1, 1)),
                             ("attn_v", (1, 1)), ("attn_proj", (1, 1)),
                             ("ffn_wi", (1, 4)), ("ffn_wo", (4, 1))]:
            p[f"{us}_{leaf}_weight"] = w(a * hidden, b * hidden)
            p[f"{us}_{leaf}_bias"] = w(b * hidden)
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = w(hidden)
            p[f"{us}_{ln}_bias"] = w(hidden)
    return p


@pytest.mark.parametrize("q_len", [1, 256], ids=["Q1", "Q256"])
def test_mixed_step_reads_the_donated_pool_in_place(sds, monkeypatch,
                                                     q_len):
    """The test that keeps the copy from coming back.  Eight layers of
    the gpt2-xl cell's mixed step (25 heads, 16 slots, pool 449 x 16,
    the pair donated) through ``serve_mixed_paged_fn``: with the pool
    ``[.., 25, 64]`` the compiler's temporaries were 3.4 x the pool
    whatever Q and the slots were, the donated pool relaid for the
    kernel and back (ledger, PR 30: ``copy`` 1.995 s of a 6 s trace).
    Rows of whole lane tiles are aliased in place: what is left does
    not grow with the pool (a small vocabulary here, so that the head's
    transposed table, 0.16 GB at 50257, is not what is measured)."""
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    # the step asks the backend, which is the CPU here: steer it in the
    # test, or the kernels are interpreted and nothing is proven
    for module in (ra, pw):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    L, H, B = 8, 25, CELL["slots"]
    W = kv_row_width(H, DH)
    pool = sds((L, CELL["blocks"], BLOCK, W), jnp.bfloat16)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    compiled = fn.func.lower(
        _gpt_shapes(sds, "gpt", L, H * DH, 512, 1024),
        ("gpt", L, H, DH, 1024), pool, pool, i32(B, CELL["table"]),
        i32(B), i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=False).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "ragged_paged_mixed" in line]
    assert len(calls) == L and all("tpu_custom_call" in c for c in calls)
    # each call takes the WHOLE pool pair, no layer sliced out of it
    assert all(c.count(f"bf16[{L},{CELL['blocks']},{BLOCK},{W}]") >= 2
               for c in calls)
    _one_page_write_a_kv_layer(compiled, L if q_len >= BLOCK else 0)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * L * CELL["blocks"] * BLOCK * W * 2
    assert mem.alias_size_in_bytes >= pool_bytes      # donated, aliased
    assert mem.temp_size_in_bytes < pool_bytes // 2


@pytest.mark.slow
@pytest.mark.parametrize("window", [1, 5], ids=["W1", "W5-spec_k4"])
def test_mixed_wave_tail(sds, window):
    """What follows the last block of a chunk wave of the gpt2-xl cell
    (16 slots, a 256-row q-block, 1600 wide, vocabulary 50257): the
    window's gather, the final LN, the tied head and ``_spec_sample``,
    alone.  The q-block never meets the vocabulary: no [16, 256, 50257]
    tensor, and temporaries far under its 0.82 GB.  (``slow``: the head
    and the sampler over a real vocabulary are 30-50 s of the chip
    compiler's time, which is why the whole-wave programs of this file
    that are in tier-1 have a vocabulary of 512.)"""
    B, Q, D, V = 16, 256, 1600, 50257

    def tail(h, wte, scale, bias, first_row, q_len, temp, top_k, keys):
        params = {"gpt_wte_table": wte, "gpt_ln_f_scale": scale,
                  "gpt_ln_f_bias": bias}
        logits = gd._window_logits(params, "gpt", h, first_row, window)
        return gd._spec_sample(logits, temp, top_k, keys, q_len - first_row)

    slot = sds((B,), jnp.int32)
    compiled = jax.jit(tail).lower(
        sds((B, Q, D), jnp.bfloat16), sds((V, D), jnp.bfloat16),
        sds((D,), jnp.bfloat16), sds((D,), jnp.bfloat16), slot, slot,
        sds((B,), jnp.float32), slot, sds((B, 2), jnp.uint32)).compile()
    text = compiled.as_text()
    assert f"[{B},{Q},{V}]" not in text
    assert f"f32[{B},{window},{V}]" in text or f"f32[{B},{V}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < B * Q * V * 4 // 8


@pytest.mark.parametrize("q_block", [1, 64, 256])
def test_ragged_paged_mla_at_the_published_widths(sds, q_block):
    """ISSUE 28: 20 heads x 640 (576 padded to the lanes) over a latent
    pool of 16-position pages copied by hand, 32 slots, a table of 512
    entries: wider than anything else compiled here.  A 256-row q-block
    is four tiles of 1280 (query, head) rows."""
    from hetu_tpu.kernels import ragged_attention as ra
    B, H, W, T, pool_blocks = 32, 20, 640, 512, 10241
    assert ra._mla_q_tile(256, H) * H <= ra._MLA_TILE_ROWS

    def f(q, pool, lens, q_len, tables):
        return ragged_paged_mla(q, pool, lens, q_len, tables,
                                value_width=512, scale=1 / 16, layer=3,
                                interpret=False)

    compiled = jax.jit(f).lower(
        sds((B, q_block, H, W), jnp.bfloat16),
        sds((7, pool_blocks, BLOCK, W), jnp.bfloat16),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, T), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged_paged_mla" in text


def test_ragged_dot_becomes_the_compilers_grouped_matmul(sds):
    """What ``moe_decode.grouped_matmul`` rests on where the rows are no
    whole row tiles (120 here): on the TPU ``jax.lax.ragged_dot`` is not
    expanded into a dense product an expert; it becomes one kernel with
    group metadata."""
    from hetu_tpu.models.moe_decode import grouped_matmul
    compiled = jax.jit(grouped_matmul).lower(
        sds((120, 2048), jnp.bfloat16), sds((64, 2048, 1536), jnp.bfloat16),
        sds((64,), jnp.int32)).compile()
    assert "ragged-dot" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------------- #
# ISSUE 41: the chunk wave's grouped matmul
# ------------------------------------------------------------------- #

# (M, K, G, N) of the routed experts' products in a Q 256 chunk wave of
# the two routed cells: 1,024 packed rows x top-4 over 32 experts of 1792
# (lfm2-8b-a1b) and 64 of 1536 (glm-4.7-flash), hidden 2048
GMM_CELL_SHAPES = {
    "lfm2-gate-up": (4096, 2048, 32, 1792),
    "lfm2-down": (4096, 1792, 32, 2048),
    "glm-gate-up": (4096, 2048, 64, 1536),
    "glm-down": (4096, 1536, 64, 2048)}


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated-pair"])
@pytest.mark.parametrize("cell", GMM_CELL_SHAPES)
def test_grouped_matmul_at_the_cells_shapes(sds, cell, gated):
    """A whole ``[K, N]`` matrix a buffer, two buffers an operand (29 MB
    for the gated pair at [2048, 1792]) under the kernel's own
    ``vmem_limit_bytes``; and a function that calls it three times has
    ONE Mosaic lowering (the inner jit: the calls of one jitted function
    lower to calls of one function)."""
    from hetu_tpu.kernels import grouped_matmul as gm
    M, K, G, N = GMM_CELL_SHAPES[cell]
    assert gm.col_tile(K, N) == N

    def three(xs, w0, w1, w2, load):
        tiles = gm.group_tiles(load, M)
        return [gm.grouped_matmul_tiled(xs, w, tiles, interpret=False,
                                        up=w0 if gated else None)
                for w in (w0, w1, w2)]

    w = sds((G, K, N), jnp.bfloat16)
    lowered = jax.jit(three).lower(sds((M, K), jnp.bfloat16), w, w, w,
                                   sds((G,), jnp.int32))
    text = lowered.as_text()
    assert text.count("call @_gmm_call") == 3
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    compiled = lowered.compile().as_text()
    assert "tpu_custom_call" in compiled and "moe_grouped_matmul" in compiled


@pytest.mark.parametrize("rows,kernel",
                         [(128, True), (4096, True), (8, False)],
                         ids=["decode-wave", "chunk-wave", "no-whole-tile"])
@pytest.mark.parametrize("experts,width", [(32, 1792), (64, 1536)],
                         ids=["lfm2", "glm"])
def test_the_shape_rule_picks_the_product(sds, monkeypatch, rows, kernel,
                                          experts, width):
    """``moe_decode.grouped_matmul`` at a decode wave's 128 assignment
    rows (since PR 49) and at a chunk wave's 4,096 names
    ``moe_grouped_matmul`` and no ``ragged-dot``; at 8 rows, no whole
    row tile, it is the reverse.  Nothing but the row count differs."""
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.models.moe_decode import grouped_matmul, takes_kernel
    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    assert takes_kernel(rows) == kernel
    w = sds((experts, 2048, width), jnp.bfloat16)
    text = jax.jit(lambda x, g, u, n: grouped_matmul(x, g, n, up=u)).lower(
        sds((rows, 2048), jnp.bfloat16), w, w,
        sds((experts,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("moe_grouped_matmul" in text) == kernel
    assert ("ragged-dot" in text) != kernel


# ------------------------------------------------------------------- #
# ISSUE 34: grouped query heads over the K/V pool, conv state beside it
# ------------------------------------------------------------------- #

# the lfm2-8b-a1b cell's own sizes (benchmarks/configs/lfm2-8b-a1b.json):
# 32 query heads over 8 K/V heads of 64, rows of 512 lanes, 32 slots, a
# table of 1024 pages (s_max 16,384), 25,601 blocks
LFM = dict(heads=32, kv_heads=8, slots=32, table=1024, blocks=25601)


@pytest.mark.parametrize("q_len", [1, 128, 256])
def test_grouped_rows_at_the_lfm2_cell_sizes(sds, q_len):
    """Four query heads read one K/V head's lanes: a q-tile of 64
    queries stacks 2 K/V heads x 4 members x 64 = 512 rows a lane chunk;
    a page is 16 x 512 x 2 B = 16 KB, four whole lane tiles with no
    padding."""
    from hetu_tpu.kernels import ragged_attention as ra
    B, T, G = LFM["slots"], LFM["table"], LFM["heads"] // LFM["kv_heads"]
    W = kv_row_width(LFM["kv_heads"], DH)
    assert W == 512 and ra._lane_chunk(W, DH) == 128
    pool = sds((3, LFM["blocks"], BLOCK, W), jnp.bfloat16)
    lens = sds((B,), jnp.int32)

    def fn(q, pk, pv, lengths, q_lens, bt):
        return ragged_paged_attention(q, pk, pv, lengths, q_lens, bt,
                                      layer=2, interpret=False, groups=G)

    text = compiled_text(fn, sds((B, q_len, LFM["heads"], DH), jnp.bfloat16),
                         pool, pool, lens, lens, sds((B, T), jnp.int32))
    assert "ragged_paged_mixed" in text


@pytest.mark.parametrize("q_len,vocab", [
    (1, 512), (256, 512),
    pytest.param(1, None, marks=pytest.mark.slow),
    pytest.param(256, None, marks=pytest.mark.slow)],
    ids=["Q1", "Q256", "Q1-own_vocab", "Q256-own_vocab"])
def test_hybrid_mixed_step_updates_pool_and_state_in_place(sds, monkeypatch,
                                                           q_len, vocab):
    """The first three layers of the cell (c c A: both dense FFNs, a
    routed one, one attention layer) at the published widths through
    ``serve_mixed_paged_fn``, under a vocabulary of 512 or ``slow`` the
    configuration's own 65,536: ONE kernel call (the pool holds the
    attention layer alone), the pool pair and the conv state donated and
    aliased, temporaries that do not grow with the pool."""
    import json
    import os
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.models.moe_decode import HybridMoEConfig
    for module in (ra, pw):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        conf = json.load(f)
    L = 3
    cfg = HybridMoEConfig.from_hf(dict(
        conf, num_hidden_layers=L, layer_types=conf["layer_types"][:L],
        vocab_size=vocab or conf["vocab_size"]))
    blk = cfg.block_spec()
    B, T = LFM["slots"], LFM["table"]
    params = {k: sds(s, jnp.float32 if "_moe_router_" in k else jnp.bfloat16)
              for k, s in cfg.param_shapes("lfm").items()}
    pool = sds((1, LFM["blocks"], BLOCK, 512), jnp.bfloat16)
    state = sds((2, B, 2, cfg.hidden_size), jnp.bfloat16)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    compiled = fn.func.lower(
        params, ("lfm", L, 32, DH, T * BLOCK, blk), pool, pool, i32(B, T),
        i32(B), i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=q_len > 1, state=state).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "ragged_paged_mixed" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert calls[0].count(f"bf16[1,{LFM['blocks']},{BLOCK},512]") >= 2
    _one_page_write_a_kv_layer(compiled, 1 if q_len >= BLOCK else 0)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * LFM["blocks"] * BLOCK * 512 * 2
    state_bytes = 2 * B * 2 * cfg.hidden_size * 2
    assert mem.alias_size_in_bytes >= pool_bytes + state_bytes
    assert mem.temp_size_in_bytes < pool_bytes


# ------------------------------------------------------------------- #
# ISSUE 35: the packed chunk wave at the three serving cells' sizes
# ------------------------------------------------------------------- #

def _cell_config(name):
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", name)) as f:
        return json.load(f)


def _chunk_wave_case(sds, cell, own_vocab=False):
    """(params, cfg_tuple, pool_k, pool_v, state, slots, table, kernel
    name, kernel calls[, what else the wave is handed]) of a few layers
    of a serving cell at its published widths, slots, table and pool,
    and a vocabulary of 512 (``own_vocab``: the LFM2 cell's own
    65,536)."""
    from hetu_tpu.models.moe_decode import HybridMoEConfig, LatentMoEConfig
    if cell == "mellum2-12b-a2.5b":
        # a window layer and a full one: two pools, two kernels
        conf = _cell_config("mellum2-12b-a2.5b.json")
        L, B, T = 2, 32, 12800 // BLOCK
        cfg = HybridMoEConfig.from_hf(dict(
            conf, num_hidden_layers=L, vocab_size=512,
            layer_types=["sliding_attention", "full_attention"],
            mlp_layer_types=["sparse"] * L))
        params = {k: sds(v, jnp.float32 if "_moe_router_" in k
                         else jnp.bfloat16)
                  for k, v in cfg.param_shapes("mel").items()}
        pool = sds((1, 16385, BLOCK, 512), jnp.bfloat16)
        ring = sds((1, B * 81 + 1, BLOCK, 512), jnp.bfloat16)
        return (params, ("mel", L, 32, 128, T * BLOCK, cfg.block_spec()),
                pool, pool, None, B, T, "ragged_paged_", 2,
                dict(win=(ring, ring), ring=sds((B, 81), jnp.int32)))
    if cell == "falcon-h1-34b":
        from hetu_tpu.models.ssm_decode import SSMHybridConfig
        L, B, T = 1, 64, 2048 // BLOCK
        cfg = SSMHybridConfig.from_hf(dict(
            _cell_config("falcon-h1-34b.json"), num_hidden_layers=L,
            vocab_size=512))
        blk = cfg.block_spec()
        params = {k: sds(v, jnp.bfloat16)
                  for k, v in cfg.param_shapes("fh1").items()}
        pool = sds((L, 8193, BLOCK, 512), jnp.bfloat16)
        state = tuple(
            sds((sh[0], B) + tuple(sh[1:]),
                jnp.bfloat16 if dt is None else dt)
            for sh, dt in blk.state_shapes(L, cfg.hidden_size))
        return (params, ("fh1", L, 20, 128, T * BLOCK, blk), pool, pool,
                state, B, T, "ragged_paged_mixed", L)
    if cell == "gpt2-xl":
        # two layers over the cell's WHOLE pool (48 layers' pages, 1.1 GB
        # a side, the layer an index in the page copy).  Two layers'
        # pages alone are 45.6 MiB a side: they fit the chip's 128 MiB
        # of VMEM, the compiler stages them there and evicts the page
        # write's rows to HBM to make room, and ``temp_size_in_bytes``
        # (HBM alone) then reads that choice, not the program (sandbox
        # AOT, PR 54: packed 24.6 MB on its parent, 66.8 MB since, 58.4
        # padded with two layers' pages; 6.2, 5.9 and 58.2 with the
        # cell's: PERF.md section 7 (ay) has the buffers)
        L, H = 2, 25
        pool = sds((CELL["layers"], CELL["blocks"], BLOCK,
                    kv_row_width(H, DH)),
                   jnp.bfloat16)
        return (_gpt_shapes(sds, "gpt", L, H * DH, 512, 1024),
                ("gpt", L, H, DH, 1024), pool, pool, None, CELL["slots"],
                CELL["table"], "ragged_paged_mixed", L)
    if cell == "glm-4.7-flash":
        L, B, T = 2, 32, 320                  # the dense layer + a routed
        cfg = LatentMoEConfig.from_hf(dict(
            _cell_config("glm-4.7-flash.json"), num_hidden_layers=L,
            vocab_size=512))
        blk = cfg.block_spec()
        params = {k: sds(v, jnp.float32 if "_moe_router_" in k
                         else jnp.bfloat16)
                  for k, v in cfg.param_shapes("glm").items()}
        pool = sds((L, 10241, BLOCK, blk.latent.row_width), jnp.bfloat16)
        return (params, ("glm", L, 20, 2048 // 20, T * BLOCK, blk), pool,
                None, None, B, T, "ragged_paged_mla", L)
    L, B, T = 3, LFM["slots"], LFM["table"]   # c c A: dense, dense, routed
    conf = _cell_config("lfm2-8b-a1b.json")
    cfg = HybridMoEConfig.from_hf(dict(
        conf, num_hidden_layers=L, layer_types=conf["layer_types"][:L],
        vocab_size=conf["vocab_size"] if own_vocab else 512))
    params = {k: sds(v, jnp.float32 if "_moe_router_" in k else jnp.bfloat16)
              for k, v in cfg.param_shapes("lfm").items()}
    pool = sds((1, LFM["blocks"], BLOCK, 512), jnp.bfloat16)
    return (params, ("lfm", L, 32, DH, T * BLOCK, cfg.block_spec()), pool,
            pool, sds((2, B, 2, cfg.hidden_size), jnp.bfloat16), B, T,
            "ragged_paged_mixed", 1)


# the 8,192-row blocks of the latent cell's Q 256 chunk wave: the query
# unpacked, and what the dense kernel is handed and hands back
LATENT_BLOCKS = ("[32,256,20,640]", "[32,5120,640]", "[32,5120,512]")


@pytest.mark.parametrize("against_padded", [
    False, pytest.param(True, marks=pytest.mark.slow)],
    ids=["alone", "against-padded"])
@pytest.mark.parametrize("cell", [
    "gpt2-xl", "glm-4.7-flash", "lfm2-8b-a1b",
    pytest.param("mellum2-12b-a2.5b", marks=pytest.mark.slow),
    pytest.param("falcon-h1-34b", marks=pytest.mark.slow)])
def test_packed_chunk_wave_at_the_cells_sizes(sds, monkeypatch, cell,
                                              against_padded):
    """The chunk program of each serving cell (a 256-row bucket on 16,
    32 or 64 slots) runs its row-wise operators over 1,024 packed rows:
    the kernels are the ones the padded program calls, handed the PACKED
    query rows as they lie (the latent kernel since ISSUE 46, the K/V
    rows kernel since ISSUE 54: the program holds no block of the query
    or of the result that is slots x 256 rows; and since ISSUE 56 none
    of K or V either: the page write, ``paged_kv_write``, one call a
    K/V layer, takes the packed rows too), the pool (and the conv
    state) are still updated in place with no copy of a pool, and the
    compiler's peak is no higher than the padded program's.  What is
    read off the padded program costs a second
    compile of the same wave and is ``slow`` (``against-padded``: there
    the LFM2 cell's two programs have its own vocabulary of 65,536), as
    are the code and short-chat cells' waves."""
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.kernels import ssm_step as ss
    for module in (ra, gm, ss, pw):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    params, cfg_tuple, pk, pv, state, B, T, kernel, n_calls, *more = \
        _chunk_wave_case(sds, cell, own_vocab=against_padded)
    more = more[0] if more else {}
    Q = 256
    assert gd.wave_rows(cfg_tuple, B, 1, Q) == 1024 < B * Q
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731

    def compile_wave():
        # a function of its own: the row count is read while tracing,
        # and a trace is cached by the function traced
        def wave(*args, **kw):
            return gd._serve_mixed_paged(*args, **kw)
        fn = jax.jit(wave, static_argnums=(1,),
                     static_argnames=("attn", "has_fresh", "window"),
                     donate_argnums=(2, 3),
                     donate_argnames=("state", "win"))
        return fn.lower(
            params, cfg_tuple, pk, pv, i32(B, T), i32(B), i32(B, Q), i32(B),
            i32(B), sds((B,), jnp.bool_), sds((B,), jnp.float32), i32(B),
            sds((B, 2), jnp.uint32), attn="ragged", window=1,
            has_fresh=True, state=state, **more).compile()

    packed = compile_wave()
    text = packed.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and kernel in line]
    assert len(calls) == n_calls
    assert all("tpu_custom_call" in c for c in calls)
    shape_of = lambda line: line.split(  # noqa: E731
        " custom-call(")[0].split("= ")[-1].split("{")[0]
    H = cfg_tuple[2]
    if cell == "glm-4.7-flash":
        # the latent kernel is handed the packed rows, as they lie
        dc = cfg_tuple[5].latent.kv_lora_rank
        assert {shape_of(c) for c in calls} == {f"bf16[{1024 * H},{dc}]"}
        assert not any(block in text for block in LATENT_BLOCKS)
    else:
        # and so is the K/V rows kernel: the members' rows of the 1,024
        # packed queries, one pool row wide
        G = H // (cfg_tuple[5].kv_heads or H) if len(cfg_tuple) > 5 else 1
        assert {shape_of(c) for c in calls} == {
            f"bf16[{1024 * G},{pk.shape[3]}]"}
        # nothing under the attention's scope or the page write's is a
        # q-block: both take the packed rows as they lie
        for scope in ("/attention/", "/kv_write/"):
            under = [line for line in text.splitlines() if scope in line]
            assert under and not any(f"[{B},{Q}," in line or
                                     f"[{B},{Q * G}," in line
                                     for line in under)
        # the write is one call a K/V layer (a window layer's beside a
        # full layer's), the pool pair whole
        attends = [line for line in text.splitlines()
                   if "custom-call(" in line and (
                       "ragged_paged_mixed" in line
                       or "ragged_paged_window" in line)]
        assert attends
        _one_page_write_a_kv_layer(packed, len(attends))
    # 4,096 assignment rows over 64 or 32 experts: the routed products
    # are the chunk wave's own kernel (ISSUE 41), not the compiler's
    assert ("moe_grouped_matmul" in text) == (
        cell not in ("gpt2-xl", "falcon-h1-34b"))
    assert "ragged-dot" not in text
    # the weight products run over the packed rows, not over B x Q
    products = [line for line in text.splitlines()
                if " dot(" in line or " convolution(" in line]
    assert products and not any(f"[{B * Q}," in line or f"[{B},{Q},"
                                in line.split(" = ")[0]
                                for line in products)
    pools = [a for a in (pk, pv) if a is not None]
    pools += list(more.get("win", ()))
    held = [] if state is None else \
        list(state) if isinstance(state, tuple) else [state]
    donated = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in pools + held)
    mem = packed.memory_analysis()
    assert mem.alias_size_in_bytes >= donated
    _no_copy_of_a_pool(packed, pools)
    if not against_padded:
        return
    with monkeypatch.context() as m:
        m.setattr(gd, "wave_rows",
                  lambda cfg, slots, window, q, *a, **k: slots * q)
        padded = compile_wave()
    # the padded program's kernels take the dense entries: q-blocks
    padded_calls = [line for line in padded.as_text().splitlines()
                    if "custom-call(" in line and kernel in line]
    if cell == "glm-4.7-flash":
        assert {shape_of(c) for c in padded_calls} == {
            f"bf16[{B},{Q * H},{dc}]"}
        assert all(block in padded.as_text() for block in LATENT_BLOCKS[1:])
    else:
        assert {shape_of(c) for c in padded_calls} == {
            f"bf16[{B},{Q * G},{pk.shape[3]}]"}
    # no higher than the padded program's
    assert mem.temp_size_in_bytes <= padded.memory_analysis(
        ).temp_size_in_bytes


def strip_kernel_locations(text):
    """StableHLO text with every Mosaic kernel's base64 bytecode replaced
    by its location-free assembly."""
    import base64
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            return '\\22body\\22: \\22' + module.operation.get_asm(
                enable_debug_info=False) + '\\22'
    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


# ------------------------------------------------------------------- #
# ISSUE 43: a chunk program's hand-paged kernel has two heights
# ------------------------------------------------------------------- #

def matmul_rows(lowered):
    """The row counts of the left operands of the Mosaic kernels'
    products in a lowered program."""
    import re
    return {int(n) for n in re.findall(
        r'tpu\.matmul"[^\n]*? : \(vector<(\d+)x',
        strip_kernel_locations(lowered.as_text()))}


# name: (slots, query heads, K/V heads (None: the latent kernel), head,
# table, pool blocks, window, rows of a product at the full and at the
# short height).  The K/V rows kernel's DENSE programs have one height
# (64 queries of every member: 512 rows; GPT-2 XL's 128, and 64 in the
# last lane chunk's one head); its PACKED programs, and the latent
# kernel's, two, but GPT-2 XL's, whose short product would have 32 rows
# (``_SHORT_MIN_ROWS`` says why).
CHUNK_KERNELS = {
    "lfm2-groups4-head64": (32, 32, 8, 64, 1024, 25601, 0, {512}),
    "mellum2-groups8-head128-window1024": (
        32, 32, 4, 128, 1024, 32 * 81 + 1, 1024, {512}),
    "mellum2-groups8-head128-full": (32, 32, 4, 128, 1024, 16385, 0, {512}),
    "glm-latent-20x640": (32, 20, None, 640, 512, 10241, 0, {1280, 160}),
    # the same widths over the cell's 1,024 PACKED rows (ISSUE 46)
    "glm-latent-20x640-packed": (32, 20, None, 640, 512, 10241, 0,
                                 {1280, 160}),
    "gpt2-xl-25x64": (16, 25, 25, 64, 64, 449, 0, {128, 64}),
    # the K/V rows kernel over 1,024 PACKED rows (ISSUE 54): the dense
    # entry's tile and short window, so its products' rows
    "lfm2-groups4-head64-packed": (32, 32, 8, 64, 1024, 25601, 0,
                                   {512, 128}),
    "mellum2-groups8-head128-window1024-packed": (
        32, 32, 4, 128, 1024, 32 * 81 + 1, 1024, {512, 128}),
    "mellum2-groups8-head128-full-packed": (32, 32, 4, 128, 1024, 16385, 0,
                                            {512, 128}),
    "falcon-groups5-head128-packed": (64, 20, 4, 128, 128, 8193, 0,
                                      {320, 80}),
    "gpt2-xl-25x64-packed": (16, 25, 25, 64, 64, 449, 0, {128, 64}),
}


@pytest.mark.parametrize("name", list(CHUNK_KERNELS))
def test_a_chunk_programs_kernel_compiles_with_its_heights(sds, monkeypatch,
                                                           name):
    """Q 256 at the cells' widths: a q-tile is 64 queries, and where a
    program has a short height (16 in the K/V rows kernel's packed
    programs, 8 in the latent kernel's) the kernel's products are there
    at both; the Q 1 program of the same widths has one height, one
    sublane tile of queries, and no product of the tall one's rows.
    Compiled for the described v5e: two bodies' worth of scratch and
    code fit the scoped VMEM."""
    from hetu_tpu.kernels import ragged_attention as ra
    B, H, Hkv, Dh, T, blocks, window, rows = CHUNK_KERNELS[name]
    lens = sds((B,), jnp.int32)
    if name.endswith("-packed") and Hkv is None:
        monkeypatch.setattr(ra, "_use_interpret", lambda: False)
        _packed_latent_kernel_compiles(sds, B, H, Dh, T, blocks, rows)
        return
    if name.endswith("-packed"):
        _packed_rows_kernel_compiles(sds, B, H, Hkv, Dh, T, blocks, window,
                                     rows)
        return
    if Hkv is None:
        assert ra.mla_tiling(256, H) == (64, 8)
        assert ra.mla_tiling(1, H) == (1, 0)
        pool = (sds((7, blocks, BLOCK, Dh), jnp.bfloat16),)

        def fn(q, pool, lengths, q_lens, bt):
            return ragged_paged_mla(q, pool, lengths, q_lens, bt,
                                    value_width=512, scale=1 / 16, layer=3,
                                    interpret=False)
    else:
        assert ra.rows_tiling(256, H, jnp.bfloat16) == (256, 64)
        assert ra.rows_tiling(1, H, jnp.bfloat16) == (16, 16)
        pool = (sds((3, blocks, BLOCK, kv_row_width(Hkv, Dh)),
                    jnp.bfloat16),) * 2

        def fn(q, pk, pv, lengths, q_lens, bt):
            return ragged_paged_attention(
                q, pk, pv, lengths, q_lens, bt, layer=2, interpret=False,
                groups=H // Hkv, window=window)
    lowered = jax.jit(fn).lower(sds((B, 256, H, Dh), jnp.bfloat16), *pool,
                                lens, lens, sds((B, T), jnp.int32))
    assert rows <= matmul_rows(lowered) and (
        Hkv is None or rows == matmul_rows(lowered))
    assert "tpu_custom_call" in lowered.compile().as_text()
    q1 = jax.jit(fn).lower(sds((B, 1, H, Dh), jnp.bfloat16), *pool,
                           lens, lens, sds((B, T), jnp.int32))
    assert matmul_rows(q1) and max(rows) not in matmul_rows(q1)


def _packed_rows_kernel_compiles(sds, B, H, Hkv, Dh, T, blocks, window,
                                 rows):
    """The K/V rows kernel's packed entry at a cell's widths (1,024
    packed rows, 3 layers' pool): row tiles of 64 packed queries and
    (but at GPT-2 XL's one query head a K/V head) a short window of 16,
    the dense entry's products row for row, compiled for the described
    v5e inside the scoped VMEM under the dense entry's name; its call is
    handed ``[members x 1,024, W]`` rows and no q-block."""
    from hetu_tpu.kernels import ragged_attention as ra
    R, bf, G = 1024, jnp.bfloat16, H // Hkv
    W = kv_row_width(Hkv, Dh)
    assert ra.rows_packed_tiling(R, H, Dh, G, bf) == (
        R, 64, 16 if G > 1 else 0)
    lens, bt = sds((B,), jnp.int32), sds((B, T), jnp.int32)
    pool = sds((3, blocks, BLOCK, W), bf)

    def call(q, pk, pv, lengths, q_lens, start, bt):
        return ra.ragged_paged_attention_rows(
            q, pk, pv, lengths, q_lens, start, bt, layer=2, interpret=False,
            groups=G, window=window)
    lowered = jax.jit(call).lower(sds((R, H, Dh), bf), pool, pool, lens,
                                  lens, lens, bt)
    assert matmul_rows(lowered) == rows
    text = lowered.compile().as_text()
    name = "ragged_paged_window" if window else "ragged_paged_mixed"
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and name in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert f"bf16[{R * G},{W}]" in calls[0].split(" custom-call(")[0]
    assert f"[{B},256," not in text


def _packed_latent_kernel_compiles(sds, B, H, W, T, blocks, rows):
    """The packed entry at the long-answer cell's widths (32 slots, 1,024
    packed rows, 7 layers' pool): row tiles of 64 packed queries and a
    window of 8, products of 1,280 and 160 rows, compiled for the
    described v5e inside the scoped VMEM; and neither the kernel's call
    nor ``_latent_attention`` over a packed wave holds an 8,192-row
    block of the query or of the result."""
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.models.moe_decode import LatentMoEConfig
    R, Q, bf = 1024, 256, jnp.bfloat16
    assert ra.mla_rows_tiling(R, H, bf) == (64, 8)
    lens, bt = sds((B,), jnp.int32), sds((B, T), jnp.int32)
    pool = sds((7, blocks, BLOCK, W), bf)

    def call(q, pool, lengths, q_lens, start, bt):
        return ra.ragged_paged_mla_rows(
            q, pool, lengths, q_lens, start, bt, value_width=512,
            scale=1 / 16, layer=3, interpret=False)
    lowered = jax.jit(call).lower(sds((R, H, W), bf), pool, lens, lens,
                                  lens, bt)
    assert matmul_rows(lowered) == rows
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "ragged_paged_mla" in text
    assert not any(b in lowered.as_text() or b in text
                   for b in LATENT_BLOCKS)

    cfg = LatentMoEConfig.from_hf(dict(
        _cell_config("glm-4.7-flash.json"), num_hidden_layers=1,
        vocab_size=512))
    blk = cfg.block_spec()
    assert blk.latent.row_width == W
    params = {k: sds(v, bf) for k, v in cfg.param_shapes("glm").items()}

    def layer(params, h, pool, wblk, woff, posns, lengths, q_len, bt):
        return gd._latent_attention(
            params, "glm_h0", blk, H, h, pool, 0, wblk, woff, posns, None,
            lengths, q_len, bt, "ragged", gd._Rows.of(q_len, Q, R))
    row = sds((1, R), jnp.int32)
    wave = jax.jit(layer).lower(
        params, sds((1, R, cfg.hidden_size), bf), pool, row, row, row,
        lens, lens, bt)
    text = wave.as_text()
    assert "ragged_paged_mla" in text and f"{R * H}x512xbf16" in text
    as_mlir = [b.strip("[]").replace(",", "x") + "x" for b in LATENT_BLOCKS]
    assert not any(b in text for b in as_mlir)
    assert "tpu_custom_call" in wave.compile().as_text()


def test_gpt2_xl_chunk_program_keeps_one_height():
    """GPT-2 XL's packed chunk programs have the one height: with two,
    the short products' 32 rows cost the cell 4 % of its rate (PERF.md,
    PR 43)."""
    from hetu_tpu.kernels import ragged_attention as ra
    for rows in (256, 512, 1024):
        assert ra.rows_packed_tiling(rows, 25, DH, 1, jnp.bfloat16)[2] == 0


# ------------------------------------------------------------------- #
# ISSUE 45: the retention layer's chunked form as one kernel
# ------------------------------------------------------------------- #

BRUMBY = {"slots": 24, "lanes": 3, "chunk": 256}


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_retention_chunk_scan_at_the_cells_sizes(sds, kept):
    """Three lanes of 256 rows x 5 query heads over 8 K/V heads of 128,
    the state of 24 slots (float32 as the configuration keeps it,
    bfloat16 as the control the comparison refuses does): the kernel
    compiles for the chip, both states aliased, no temporary."""
    from hetu_tpu.kernels import retention_scan as rs
    lanes, c, slots = BRUMBY["lanes"], BRUMBY["chunk"], BRUMBY["slots"]
    g, m, d = 8, 5, 128
    D = d * (d + 1) // 2
    bf, f32, kept = jnp.bfloat16, jnp.float32, jnp.dtype(kept)
    args = (sds((lanes,), jnp.int32), sds((lanes,), jnp.int32),
            sds((lanes, g, c * m, d), bf), sds((lanes, g, c, d), bf),
            sds((lanes, g, c, d), bf), sds((lanes, g, c, 1), f32),
            sds((lanes, g, 1, d), f32), sds((1, slots, g, D, d), kept),
            sds((1, slots, g, D), kept))
    compiled = jax.jit(
        lambda *a: rs._chunk_scan_call(*a, interpret=False),
        donate_argnums=(7, 8)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # (the normaliser's rows are padded to whole lane tiles)
    assert mem.alias_size_in_bytes >= slots * g * D * (d + 1) * kept.itemsize
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_retention_step_scan_at_the_cells_sizes(sds, kept):
    """The one-step kernel (ISSUE 63) over 24 lanes of 5 query heads on
    8 K/V heads of 128, the state of 24 slots (float32, and the
    bfloat16 control): compiles for the chip, both states aliased, no
    temporary."""
    from hetu_tpu.kernels import retention_scan as rs
    slots = lanes = BRUMBY["slots"]
    g, d = 8, 128
    D = d * (d + 1) // 2
    f32, kept = jnp.float32, jnp.dtype(kept)
    row = sds((lanes, g, 8, d), f32)
    args = (sds((lanes,), jnp.int32), sds((1,), jnp.int32),
            sds((lanes, g, rs.STEP_ROWS, d), f32), row, row, row,
            sds((1, slots, g, D, d), kept), sds((1, slots, g, D), kept))
    compiled = jax.jit(
        lambda *a: rs._step_scan_call(*a, read="bfloat16", interpret=False),
        donate_argnums=(6, 7)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= slots * g * D * (d + 1) * kept.itemsize
    assert mem.temp_size_in_bytes == 0


def test_retention_mixed_step_rewrites_the_states_where_they_lie(
        sds, monkeypatch):
    """Two layers of the documents cell at its published widths and 24
    slots, the Q 256 program: ONE lowering of each kernel for both
    layers, a call of each a layer (the one-step kernel, then the chunk
    kernel in the wide slots' loop), the four states aliased and no
    temporary of a state's size (the un-aliased copy PR 44's first build
    paid for)."""
    from hetu_tpu.kernels import retention_scan as rs
    from hetu_tpu.models import retention_decode as rd
    monkeypatch.setattr(rs, "_use_interpret", lambda: False)
    L, B, Q = 2, BRUMBY["slots"], BRUMBY["chunk"]
    cfg = rd.RetentionConfig.from_hf(dict(
        _cell_config("brumby-14b.json"), num_hidden_layers=L,
        vocab_size=512))
    blk = cfg.block_spec()
    params = {k: sds(s, jnp.float32 if k.endswith(rd.F32_LEAVES)
                     else jnp.bfloat16)
              for k, s in cfg.param_shapes("bru").items()}
    state = tuple(sds((sh[0], B) + tuple(sh[1:]), dt)
                  for sh, dt in blk.state_shapes(L, cfg.hidden_size))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    lowered = fn.func.lower(
        params, ("bru", L, cfg.num_attention_heads, cfg.head_dim, 16896,
                 blk), None, None, i32(B, 1), i32(B), i32(B, Q), i32(B),
        i32(B), sds((B,), jnp.bool_), sds((B,), jnp.float32), i32(B),
        sds((B, 2), jnp.uint32), attn="ragged", window=1, has_fresh=True,
        state=state)
    assert lowered.as_text().count("tpu_custom_call") == 2
    compiled = lowered.compile()
    for kernel in ("retention_step_scan", "retention_chunk_scan"):
        calls = [line for line in compiled.as_text().splitlines()
                 if "custom-call(" in line and kernel in line]
        assert len(calls) == L and all("tpu_custom_call" in c for c in calls)
    one_state = B * 8 * 8256 * 128 * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * B * 8 * 8256 * 129 * 4
    assert mem.temp_size_in_bytes < one_state / 2


# ------------------------------------------------------------------- #
# ISSUE 48: the one-part layers and the held experts at the cell's sizes
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("q_len,pattern,vocab", [
    (1, "M*E", 512), (64, "M*E", 512), (128, "M*E", 512), (256, "M*E", 512),
    *(pytest.param(q, "M*E", None, marks=pytest.mark.slow)
      for q in (1, 64, 128)),
    pytest.param(256, None, None, marks=pytest.mark.slow)],
    ids=["Q1-one_of_each", "Q64-one_of_each", "Q128-one_of_each",
         "Q256-one_of_each", "Q1-one_of_each-own_vocab",
         "Q64-one_of_each-own_vocab", "Q128-one_of_each-own_vocab", "Q256"])
def test_nemotron_wave_programs_at_the_published_widths(sds, monkeypatch,
                                                        q_len, pattern,
                                                        vocab):
    """The agent cell's four wave programs at the published widths, 64
    slots and the cell's pool through ``serve_mixed_paged_fn``: one
    layer of each kind and a vocabulary of 512 (the same kernels at
    their own tiles; the whole depth of all four is in the
    configuration's ``memory_analysis``), and ``slow`` the same three
    layers under the 32,768 rows the cell holds and the widest chunk
    program with all eleven layers (5 mixers, 1 attention, 5 expert
    layers of 128 held experts of 512) under them: a whole-depth compile
    is 40-70 s here, and the suite has a limit.  The
    pool pair and every state array updated in place, no temporary of a
    state array's size (the compiler, short of memory, once recomputed a
    recurrence: PR 37), ONE attention kernel call, the held experts'
    products by the rule: ``moe_grouped_matmul`` twice a
    layer in every wave, the decode wave's 1,408 sorted rows (352
    landing on 128 experts) among them since PR 49, and no
    ``ragged-dot`` anywhere; every mixer's one-step form ONE
    ``ssm_step`` call on its state where it lies (PR 50)."""
    import json
    import os
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.kernels import ssm_step as ss
    from hetu_tpu.models import nemotron_h as nh
    for module in (ra, gm, ss, pw):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        conf = json.load(f)
    args = conf["runner_args"]
    source = dict(conf, n_routed_experts=conf["published"][
        "n_routed_experts"])
    if pattern:
        source.update(hybrid_override_pattern=pattern,
                      num_hidden_layers=len(pattern))
    if vocab:
        source.update(vocab_size=vocab)
    cfg = nh.NemotronHConfig.from_hf(
        source, held_experts=tuple(conf["deployment"]["experts_held"]))
    blk = cfg.block_spec()
    L, B, S = cfg.num_hidden_layers, args["slots"], args["max_seq_len"]
    mixers = cfg.pattern.count("M")
    T, N = S // BLOCK, args["pool_blocks"]
    params = {k: sds(s, jnp.float32 if "_moe_router_" in k
                     or k.endswith(nh.F32_LEAVES) else jnp.bfloat16)
              for k, s in cfg.param_shapes("nmh").items()}
    pool = sds((1, N, BLOCK, kv_row_width(2, 128)), jnp.bfloat16)
    state = tuple(
        sds((sh[0], B) + tuple(sh[1:]), jnp.bfloat16 if dt is None else dt)
        for sh, dt in blk.state_shapes(L, cfg.hidden_size))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    compiled = fn.func.lower(
        params, ("nmh", L, 32, 128, S, blk), pool, pool, i32(B, T), i32(B),
        i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=q_len > 1, state=state).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    assert sum("ragged_paged_mixed" in c for c in calls) == 1
    _one_page_write_a_kv_layer(compiled, 1 if q_len >= BLOCK else 0)
    experts = sum("moe_grouped_matmul" in c for c in calls)
    assert experts == 2 * cfg.pattern.count("E")
    assert "ragged-dot" not in text
    # every mixer's one-row slots through ``ssm_step`` (since PR 50), in
    # the decode program and in every chunk bucket's
    assert sum("ssm_step" in c for c in calls) == mixers
    mem = compiled.memory_analysis()
    pool_bytes = 2 * N * BLOCK * kv_row_width(2, 128) * 2
    one_state = B * 128 * 64 * 128 * 4
    tails = mixers * B * 3 * 10240 * 2
    assert mem.alias_size_in_bytes >= pool_bytes + mixers * one_state + tails
    # the widest program's temporaries are the packed rows' (1,024 x
    # 18,560 projections, 22,528 sorted rows x 2,688): 0.4 GB, growing
    # with neither the pool nor the states
    assert mem.temp_size_in_bytes < 2 * one_state
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert peak < 12e9


# ------------------------------------------------------------------- #
# ISSUE 50: the state-space mixer's one-step form as one kernel
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("H,P,N,G", [(128, 64, 128, 8), (32, 128, 256, 2)],
                         ids=["nemotron-3-super", "falcon-h1"])
def test_ssm_step_at_the_cells_sizes(sds, H, P, N, G):
    """64 slots of both cells' mixers, a group's 16 heads a grid step
    (0.5 and 2 MB of a slot's 4.19 MB state): the kernel compiles for
    the chip, the state aliased, no temporary of its size (the
    transposed ``dt x`` and the row of results alone)."""
    from hetu_tpu.kernels import ssm_step as ss
    from hetu_tpu.models import ssm_decode as sd
    assert sd.takes_kernel(sd.SSMSpec(H, P, N, G, 4, 128))
    assert ss.head_block(H, G, P, N) == 16
    B, bf, f32 = 64, jnp.bfloat16, jnp.float32
    compiled = jax.jit(
        lambda *a: ss.ssm_step(*a, interpret=False),
        donate_argnums=(5,)).lower(
            sds((B, H, P), bf), sds((B, H), f32), sds((H,), f32),
            sds((B, G, N), bf), sds((B, G, N), bf),
            sds((1, B, H, P, N), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_step" in text
    mem = compiled.memory_analysis()
    state = B * H * P * N * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 16


# ------------------------------------------------------------------- #
# ISSUE 51: latent attention by layer at the notes cell's sizes
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("kinds", [
    ["full_attention", "sliding_attention"],
    pytest.param(None, marks=pytest.mark.slow)],
    ids=["one_of_each", "all_five"])
def test_sparse_latent_chunk_program_at_the_published_widths(sds,
                                                             monkeypatch,
                                                             kinds):
    """The notes cell's ONE chunk program (Q 256; its prompts are
    multiples of the chunk) at the published widths, 32 slots and the
    cell's pools through ``serve_mixed_paged_fn``, a full layer (with
    the dense FFN) and a sliding one (with the experts) under a
    vocabulary of 512, or ``slow`` all five layers and the head over
    19,008 columns (148.5 lane tiles): the selected-rows kernel once a
    full layer (the dense walk under the chosen rows' mask, 128 heads,
    rows of 640), the window kernel once a sliding layer (64 heads, rows
    of 1,152: the tile is cut in proportion of the width), the 32 held
    experts through ``moe_grouped_matmul``; the latent pool, the index keys' pool and the
    latent ring updated in place.  The configuration's
    ``memory_analysis`` states this compile and the decode program's."""
    import json
    import os
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.models import sparse_latent as sl
    for module in (ra, gm):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        conf = json.load(f)
    args, dep = conf["runner_args"], conf["deployment"]
    vocab, vocab_rows = conf["published"]["vocab_size"], tuple(
        dep["vocab_rows_held"])
    if kinds:
        conf = dict(conf, layer_types=kinds, num_hidden_layers=len(kinds))
        vocab, vocab_rows = 512, None
    full = conf["layer_types"].count("full_attention")
    sliding = len(conf["layer_types"]) - full
    cfg = sl.SparseLatentConfig.from_hf(
        dict(conf, n_routed_experts=conf["published"]["n_routed_experts"],
             vocab_size=vocab),
        held_experts=tuple(dep["experts_held"]), vocab_rows=vocab_rows)
    blk = cfg.block_spec()
    L, B, S = cfg.num_hidden_layers, args["slots"], args["max_seq_len"]
    T, N, Q = S // BLOCK, args["pool_blocks"], args["prefill_chunk"]
    params = {k: sds(s, jnp.float32 if "_moe_router_" in k else jnp.bfloat16)
              for k, s in cfg.param_shapes("d3n").items()}
    pool = sds((full, N, BLOCK, 640), jnp.bfloat16)
    keys = sds((full, N, BLOCK, 128), jnp.bfloat16)
    ring = -(-(blk.window + Q) // BLOCK) + 1
    win = (sds((sliding, B * ring + 1, BLOCK, 1152), jnp.bfloat16), None)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    compiled = fn.func.lower(
        params, ("d3n", L, 128, 40, S, blk), pool, keys, i32(B, T), i32(B),
        i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=True, win=win,
        ring=i32(B, ring)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    assert sum("ragged_paged_mla_sparse" in c for c in calls) == full
    assert sum("ragged_paged_mla_window" in c for c in calls) == sliding
    assert sum("moe_grouped_matmul" in c for c in calls) == 2 * (L - 1)
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    pools = 2 * (full * N * BLOCK * (640 + 128)
                 + sliding * (B * ring + 1) * BLOCK * 1152)
    assert mem.alias_size_in_bytes >= pools
    # the widest temporaries are the index scores and the chosen rows'
    # mask (1,024 x 12,800 float32 each): they grow with neither pool
    assert mem.temp_size_in_bytes < 1.0e9
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert peak < 12e9
    if not kinds:
        stated = conf["memory_analysis"][f"slots_{B}_Q_{Q}_pool_{N}"]
        assert abs(peak / 1e9 - stated["peak_GB"]) < 0.3


# ------------------------------------------------------------------- #
# ISSUE 55: the parallel block at the grounded cell's sizes
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("q_len,kinds", [
    (256, ["sliding_attention", "full_attention"]),
    pytest.param(1, ["sliding_attention", "full_attention"],
                 marks=pytest.mark.slow),
    pytest.param(256, None, marks=pytest.mark.slow),
    pytest.param(1, None, marks=pytest.mark.slow)],
    ids=["Q256-one_of_each", "Q1-one_of_each", "Q256-period", "Q1-period"])
def test_parallel_moe_wave_programs_at_the_published_widths(sds, monkeypatch,
                                                            q_len, kinds):
    """The grounded cell's two programs (its prompts are multiples of
    the chunk: ONE chunk program, Q 256, beside the decode program) at
    the published widths, 32 slots and the cell's pools through
    ``serve_mixed_paged_fn``: a sliding layer beside a full one, or
    ``slow`` the whole period of four.  128 query heads over 8 K/V heads
    of 128 (group 16, a query row of 16,384 lanes, a pool row of 1,024):
    ``ragged_paged_window`` once a sliding layer over a ring of 273
    blocks a slot, ``ragged_paged_mixed`` once a full layer, the 16 held
    experts through ``moe_grouped_matmul`` in BOTH programs (a decode
    wave's 256 assignment rows are two row tiles), both pool pairs
    updated in place.  The configuration's ``memory_analysis`` states
    the period's compiles."""
    import json
    import os
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.models.parallel_moe import ParallelMoEConfig
    for module in (ra, gm, pw):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "command-a-plus-05-2026.json")) as f:
        conf = json.load(f)
    args, dep, pub = (conf["runner_args"], conf["deployment"],
                      conf["published"])
    kinds = kinds or conf["layer_types"]
    cfg = ParallelMoEConfig.from_hf(
        dict(conf, num_experts=pub["num_experts"],
             vocab_size=pub["vocab_size"], layer_types=kinds,
             num_hidden_layers=len(kinds)),
        held_experts=tuple(dep["experts_held"]),
        vocab_rows=tuple(dep["vocab_rows_held"]))
    blk = cfg.block_spec()
    full = kinds.count("full_attention")
    sliding = len(kinds) - full
    L, B, S = len(kinds), args["slots"], args["max_seq_len"]
    T, N = S // BLOCK, args["pool_blocks"]
    W = kv_row_width(8, 128)
    assert W == 1024
    assert ra.rows_packed_tiling(1024, 128, 128, 16, jnp.bfloat16) == (
        1024, 16, 0)
    params = {k: sds(s, jnp.float32 if "_moe_router_" in k else jnp.bfloat16)
              for k, s in cfg.param_shapes("cmd").items()}
    pool = sds((full, N, BLOCK, W), jnp.bfloat16)
    ring = -(-(blk.window + args["prefill_chunk"]) // BLOCK) + 1
    assert ring == 273
    win = (sds((sliding, B * ring + 1, BLOCK, W), jnp.bfloat16),) * 2
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    compiled = fn.func.lower(
        params, ("cmd", L, 128, 128, S, blk), pool, pool, i32(B, T), i32(B),
        i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=q_len > 1, win=win,
        ring=i32(B, ring)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    assert sum("ragged_paged_window" in c for c in calls) == sliding
    assert sum("ragged_paged_mixed" in c for c in calls) == full
    # the ring pool's write beside the full pool's, each a Mosaic call
    _one_page_write_a_kv_layer(compiled, L if q_len >= BLOCK else 0)
    assert sum("moe_grouped_matmul" in c for c in calls) == 2 * L
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    pools = 2 * 2 * BLOCK * W * (full * N + sliding * (B * ring + 1))
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 0.5e9
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert peak < 13.0e9
    if L == conf["num_hidden_layers"]:
        stated = conf["memory_analysis"][f"slots_{B}_Q_{q_len}"]
        assert abs(peak / 1e9 - stated["peak_GB"]) < 0.3


# ------------------------------------------------------------------- #
# ISSUE 58: delta-rule layers beside latent attention at the digest
# cell's sizes
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_kda_chunk_scan_at_the_cells_sizes(sds, kept):
    """Three lanes of 256 rows x 32 heads of 128 over the state of 48
    slots (float32 as the configuration keeps it, bfloat16 as the control
    the comparison refuses does): the kernel compiles for the chip, the
    state aliased, no temporary (``S`` is carried across a q-block's
    chunks in 64 KB of VMEM scratch, which is no temporary of the
    program's)."""
    from hetu_tpu.kernels import kda_scan as ks
    from hetu_tpu.models import kda_decode as kd
    lanes, Q, H, D, slots = kd.WIDE_LANES, 256, 32, 128, 48
    bf, f32, kept = jnp.bfloat16, jnp.float32, jnp.dtype(kept)
    assert kd.takes_kernel(D, Q)
    rows = sds((lanes, Q, H * D), bf)
    args = (sds((lanes,), jnp.int32), sds((lanes,), jnp.int32), rows, rows,
            rows, sds((lanes, Q, H * D), f32), sds((lanes, Q, H), f32),
            sds((1, slots, H, D, D), kept))
    compiled = jax.jit(
        lambda *a: ks._kda_chunk_scan_call(
            *a, chunk=kd.CHUNK, sub=kd.SUB, interpret=False),
        donate_argnums=(7,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= slots * H * D * D * kept.itemsize
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("q_len,group", [
    (256, 2),
    pytest.param(1, 2, marks=pytest.mark.slow),
    pytest.param(256, None, marks=pytest.mark.slow),
    pytest.param(1, None, marks=pytest.mark.slow)],
    ids=["Q256-one_of_each", "Q1-one_of_each", "Q256-period", "Q1-period"])
def test_kda_latent_wave_programs_at_the_published_widths(sds, monkeypatch,
                                                          q_len, group):
    """The digest cell's two programs (its prompts are multiples of the
    chunk: ONE chunk program, Q 256, beside the decode program) at the
    published widths, the cell's 48 slots (exact: a manager with state
    does not round them) and its latent pool through ``serve_mixed_paged_fn``: one KDA
    layer (with a dense FFN) beside the MLA layer (with the experts)
    under a vocabulary of 512, or ``slow`` the whole period of six and
    the head over 39,296 columns.  The chunk program runs the chunked
    delta rule through ``kda_chunk_scan`` (ISSUE 59: ONE lowering, a call
    a KDA layer, no triangular solve left in the program); the decode
    program holds neither; ``ragged_paged_mla`` once
    (the packed rows' entry in the chunk program) over rows of 640, the
    128 held experts through ``moe_grouped_matmul`` (every Pallas call a
    Mosaic one: ``_use_interpret`` off in every kernel module the wave
    can reach); the latent pool and the twelve (or two) states updated in
    place.  The configuration's ``memory_analysis`` states the period's
    compiles."""
    import json
    import os
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import kda_scan as ks
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.kernels import ssm_step as ss
    from hetu_tpu.models.kda_latent import F32_LEAVES, KDALatentConfig
    for module in (ra, gm, pw, ss, ks):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        conf = json.load(f)
    args, dep, pub = (conf["runner_args"], conf["deployment"],
                      conf["published"])
    vocab, vocab_rows = pub["vocab_size"], tuple(dep["vocab_rows_held"])
    over = {}
    if group:
        over = dict(layer_group_size=group, num_hidden_layers=group,
                    first_k_dense_replace=1)
        vocab, vocab_rows = 512, None
    cfg = KDALatentConfig.from_hf(
        dict(conf, num_experts=pub["num_experts"], vocab_size=vocab, **over),
        held_experts=tuple(dep["experts_held"]), vocab_rows=vocab_rows)
    blk = cfg.block_spec()
    L, S = cfg.num_hidden_layers, args["max_seq_len"]
    B, T, N = args["slots"], S // BLOCK, args["pool_blocks"]
    kda = blk.op_layers(L, "kda")
    assert (B, blk.op_layers(L, "pool"), kda) == (48, 1, L - 1)
    params = {k: sds(s, jnp.float32 if k.endswith(F32_LEAVES)
                     else jnp.bfloat16)
              for k, s in cfg.param_shapes("lng").items()}
    pool = sds((1, N, BLOCK, 640), jnp.bfloat16)
    state = tuple(sds((shape[0], B) + shape[1:], dtype or jnp.bfloat16)
                  for shape, dtype in blk.state_shapes(L, cfg.hidden_size))
    assert state[-1].shape == (1, B, 32, 128, 128) \
        and state[-1].dtype == jnp.float32
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    lowered = fn.func.lower(
        params, ("lng", L, 32, 128, S, blk), pool, None, i32(B, T), i32(B),
        i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=q_len > 1, state=state)
    # the KDA layers share one trace and one Mosaic lowering of the scan
    assert lowered.as_text().count(
        "func.func private @_kda_chunk_scan_call") == (1 if q_len > 1 else 0)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    scans = [c for c in calls if "kda_chunk_scan" in c]
    assert len(scans) == (kda if q_len > 1 else 0)
    assert all("tpu_custom_call" in c for c in scans)
    assert not any("triangular" in c.lower() or "InvertDiagBlocks" in c
                   for c in calls)
    assert sum("ragged_paged_mla" in c for c in calls) == 1
    routed = L - cfg.first_k_dense_replace
    assert sum("moe_grouped_matmul" in c for c in calls) == 2 * routed
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held = N * BLOCK * 640 * 2 + sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 1.5e9
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert peak < 12.0e9
    if not group:
        stated = conf["memory_analysis"][f"slots_{B}_Q_{q_len}"]
        assert abs(peak / 1e9 - stated["peak_GB"]) < 0.3


# ------------------------------------------------------------------- #
# ISSUE 62: the published delta rule beside gated grouped-query
# attention at the longdoc cell's sizes
# ------------------------------------------------------------------- #

def test_kda_chunk_scan_level_by_level_at_the_cells_sizes(sds):
    """Three lanes of 256 rows x 64 heads of 128 over the state of 48
    slots with ``exact`` (a decay free of any bound: the pairs level by
    level, the levels' 0/1 matrices from iotas and shifts): the kernel
    compiles for the chip, the state aliased, no temporary."""
    from hetu_tpu.kernels import kda_scan as ks
    from hetu_tpu.models import kda_decode as kd
    lanes, Q, H, D, slots = kd.WIDE_LANES, 256, 64, 128, 48
    bf, f32 = jnp.bfloat16, jnp.float32
    rows = sds((lanes, Q, H * D), bf)
    args = (sds((lanes,), jnp.int32), sds((lanes,), jnp.int32), rows, rows,
            rows, sds((lanes, Q, H * D), f32), sds((lanes, Q, H), f32),
            sds((1, slots, H, D, D), f32))
    compiled = jax.jit(
        lambda *a: ks._kda_chunk_scan_call(
            *a, chunk=kd.CHUNK, sub=kd.SUB, interpret=False, exact=True),
        donate_argnums=(7,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= slots * H * D * D * 4
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("q_len,cut", [
    (256, True),
    pytest.param(1, True, marks=pytest.mark.slow),
    pytest.param(256, False, marks=pytest.mark.slow),
    pytest.param(1, False, marks=pytest.mark.slow)],
    ids=["Q256-one_of_each", "Q1-one_of_each", "Q256-period", "Q1-period"])
def test_kda_gqa_wave_programs_at_the_published_widths(sds, monkeypatch,
                                                       q_len, cut):
    """The longdoc cell's two programs (its prompts are multiples of the
    chunk: ONE chunk program, Q 256, beside the decode program) at the
    published widths, the cell's 48 slots (exact) and its K/V pool of
    75,265 blocks through ``serve_mixed_paged_fn``: the GQA layer and
    one KDA layer under a vocabulary of 512, or ``slow`` the whole
    period of four and the head over 24,576 columns.  The chunk program
    runs the chunked delta rule through ``kda_chunk_scan`` (ONE lowering,
    a call a KDA layer, ``exact``); the decode program holds none;
    ``ragged_paged_mixed`` once at 64 over 8; the 40 held experts of
    EVERY layer through ``moe_grouped_matmul``; the pool pair and the
    six (or two) states updated in place.  The configuration's
    ``memory_analysis`` states the period's compiles."""
    import json
    import os
    from hetu_tpu.kernels import grouped_matmul as gm
    from hetu_tpu.kernels import kda_scan as ks
    from hetu_tpu.kernels import paged_kv_write as pw
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.models.kda_gqa import KDAGQAConfig
    from hetu_tpu.models.kda_latent import F32_LEAVES
    for module in (ra, gm, pw, ks):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "solar-open2-250b.json")) as f:
        conf = json.load(f)
    args, dep, pub = (conf["runner_args"], conf["deployment"],
                      conf["published"])
    vocab, vocab_rows = pub["vocab_size"], tuple(dep["vocab_rows_held"])
    over = {}
    if cut:
        over = dict(num_hidden_layers=2)
        vocab, vocab_rows = 512, None
    cfg = KDAGQAConfig.from_hf(
        dict(conf, n_routed_experts=pub["n_routed_experts"],
             vocab_size=vocab, **over),
        held_experts=tuple(dep["experts_held"]), vocab_rows=vocab_rows)
    blk = cfg.block_spec()
    L, S = cfg.num_hidden_layers, args["max_seq_len"]
    B, T, N = args["slots"], S // BLOCK, args["pool_blocks"]
    kda = blk.op_layers(L, "kda")
    assert (B, blk.op_layers(L, "pool"), kda) == (48, 1, L - 1)
    assert blk.kda.unbounded and blk.attn_gate
    params = {k: sds(s, jnp.float32 if k.endswith(F32_LEAVES)
                     else jnp.bfloat16)
              for k, s in cfg.param_shapes("slr").items()}
    W = kv_row_width(8, 128)
    pool = sds((1, N, BLOCK, W), jnp.bfloat16)
    state = tuple(sds((shape[0], B) + shape[1:], dtype or jnp.bfloat16)
                  for shape, dtype in blk.state_shapes(L, cfg.hidden_size))
    assert state[-1].shape == (1, B, 64, 128, 128) \
        and state[-1].dtype == jnp.float32
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    fn = gd.serve_mixed_paged_fn(donate=True, attn="ragged", window=1)
    lowered = fn.func.lower(
        params, ("slr", L, 64, 128, S, blk), pool, pool, i32(B, T), i32(B),
        i32(B, q_len), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="ragged", window=1, has_fresh=q_len > 1, state=state)
    assert lowered.as_text().count(
        "func.func private @_kda_chunk_scan_call") == (1 if q_len > 1 else 0)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    scans = [c for c in calls if "kda_chunk_scan" in c]
    assert len(scans) == (kda if q_len > 1 else 0)
    assert all("tpu_custom_call" in c for c in scans)
    assert sum("ragged_paged_mixed" in c for c in calls) == 1
    assert sum("moe_grouped_matmul" in c for c in calls) == 2 * L
    assert "ragged-dot" not in text
    mem = compiled.memory_analysis()
    held = 2 * N * BLOCK * W * 2 + sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert mem.alias_size_in_bytes >= held
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < 1.5e9
    assert peak < 14.5e9
    if not cut:
        stated = conf["memory_analysis"][f"slots_{B}_Q_{q_len}"]
        assert abs(peak / 1e9 - stated["peak_GB"]) < 0.3
