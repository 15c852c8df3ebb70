"""The latent-attention (MLA), dropless routed-FFN decoder on the serving
path, at a small size on the CPU (ISSUE 28): hidden 64, 4 heads, latent
32/16, rope 8, nope 8, v 16, 8 experts top-2 + 1 shared, 1 dense + 2
routed layers, vocabulary 257.  Every comparison is of LOGITS against
the plain reference's full forward (``models/reference_latent_moe.py``),
never of tokens alone.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import hf
from hetu_tpu.kernels import ragged_attention as ra
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_latent_moe as ref
from hetu_tpu.models.gpt import GPTConfig
from hetu_tpu.models.moe_decode import (
    LatentMoEConfig, RoutedSpec, init_latent_moe_params, route, routed_ffn)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager

SMALL = dict(
    vocab_size=257, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=48, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, first_k_dense_replace=1, rope_theta=1e6,
    rms_norm_eps=1e-5, max_position_embeddings=256)
# float32 weights and a float32 cache on both sides: what is left is the
# order of the sums (absorbed against expanded products, grouped against
# dense expert matmuls, online against whole softmax): 1e-5 of logits
# whose standard deviation is 1.6
TOL = 2e-4


@pytest.fixture(scope="module")
def cfg():
    return LatentMoEConfig(**SMALL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_latent_moe_params(cfg, seed=3, scale=0.2)


def engine(params, cfg, **kw):
    kw = dict(dict(slots=4, max_seq_len=64, kv_block=4,
                   prefill_chunk=8, fast_path=False,
                   prefix_share=False), **kw)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 257, n).astype(np.int32), m,
                    request_id=f"r{i}") for i, (n, m) in enumerate(sizes)]
    return eng.run(reqs)


def gaps(params, cfg, result, omit=()):
    """(the widest gap between a row's largest reference logit and the
    reference logit of the token the engine chose, the rows' smallest
    routing margin)."""
    seq = np.asarray(result.tokens, np.int32)
    lg, margin = ref.forward(params, cfg, seq[:-1], omit=omit)
    rows = np.asarray(lg)[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max()), float(
        np.asarray(margin)[result.prompt_len - 1:].min())


# ------------------------------------------------------------------ #
# engine over the latent pool against the reference's full forward
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_engine_over_latent_pool_matches_reference(params, cfg, fast):
    """Chunked prefill (19 tokens in chunks of 8), then decode, two
    requests of different length in one wave."""
    eng = engine(params, cfg, fast_path=fast)
    assert eng.kv.latent and eng.kv.cache_v is None
    # one row a token a layer: [c_kv 16 | k_r 8], padded to the 128 lanes
    assert eng.kv.cache_k.shape == (3, eng.kv.n_blocks, 4, 128)
    out = serve(eng, [(19, 6), (7, 9)])
    assert eng.prefill_chunks >= 4
    for r in out.values():
        gap, _ = gaps(params, cfg, r)
        assert gap <= TOL, (r.request_id, gap)
    snap = eng.metrics.snapshot()
    rows = (19 + 5) + (7 + 8)
    assert snap["moe_assignments"] == rows * 2 * 2 == sum(snap["moe_load"])
    assert 0 < snap["moe_experts_touched"] <= eng.steps * 2 * 8
    assert snap["attn_ctx_tokens"] > 0 and snap["attn_score_pairs"] > 0
    assert eng.kv.free_blocks == eng.kv.capacity_blocks   # all released


def test_counters_follow_the_wave_descriptor(params, cfg):
    """One request alone: ctx_tokens is the filled length after each
    wave, score_pairs the positions each row sees."""
    eng = engine(params, cfg)
    mark = eng.metrics.mark()
    serve(eng, [(8, 3)])
    snap = eng.metrics.snapshot(since=mark)
    # one chunk wave of 8 rows (filled 8), then decode rows at 8 and 9
    assert snap["attn_ctx_tokens"] == 8 + 9 + 10
    assert snap["attn_score_pairs"] == sum(range(1, 9)) + 9 + 10
    assert snap["moe_assignments"] == 10 * 2 * 2
    assert eng.metrics.snapshot(since=eng.metrics.mark())[
        "moe_assignments"] == 0


def test_absorbed_equals_expanded_attention(params, cfg):
    """``_latent_attention`` (q carried into latent space, the cached
    row as key and value, the output carried out) against the expanded
    form on the same weights (k_nope and v made from c_kv)."""
    blk, H = cfg.block_spec(), cfg.num_attention_heads
    rng = np.random.default_rng(1)
    B, Q, D = 2, 5, cfg.hidden_size
    h = jnp.asarray(rng.standard_normal((B, Q, D)), jnp.float32)
    pool = jnp.zeros((1, 9, 4, 128), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    posns = jnp.broadcast_to(jnp.arange(Q), (B, Q))
    wblk = tables[jnp.arange(B)[:, None], posns // 4]
    live = jnp.arange(12)[None, None, :] <= posns[:, :, None]
    p1 = {k.replace("glm_h1", "glm_h0"): v for k, v in params.items()
          if k.startswith("glm_h1_")}
    out, _, _ = gd._latent_attention(
        p1, "glm_h0", blk, H, h, pool, 0, wblk, posns % 4, posns, live,
        jnp.full((B,), Q), jnp.full((B,), Q), tables, "masked")
    dn, dr, dv, dc = 8, 8, 16, 16
    with jax.default_matmul_precision("highest"):
        x = ref._rms(h, p1["glm_h0_ln1_scale"], 1e-5)
        for b in range(B):
            cq = ref._rms(x[b] @ p1["glm_h0_attn_q_a_weight"],
                          p1["glm_h0_attn_q_a_norm_scale"], 1e-5)
            q = (cq @ p1["glm_h0_attn_q_b_weight"]).reshape(Q, H, dn + dr)
            kva = x[b] @ p1["glm_h0_attn_kv_a_weight"]
            ckv = ref._rms(kva[:, :dc], p1["glm_h0_attn_kv_a_norm_scale"],
                           1e-5)
            kv = (ckv @ p1["glm_h0_attn_kv_b_weight"]).reshape(
                Q, H, dn + dv)
            s = jnp.einsum("qhd,shd->hqs", q[..., :dn], kv[..., :dn]) \
                + jnp.einsum("qhd,sd->hqs", ref._rope(q[..., dn:], 1e6),
                             ref._rope(kva[:, dc:], 1e6))
            causal = jnp.arange(Q)[None, :] <= jnp.arange(Q)[:, None]
            p = jax.nn.softmax(jnp.where(causal[None], s / 4.0, -jnp.inf),
                               -1)
            o = jnp.einsum("hqs,shd->qhd", p, kv[..., dn:]).reshape(Q, -1)
            want = h[b] + o @ p1["glm_h0_attn_proj_weight"]
            np.testing.assert_allclose(out[b], want, atol=2e-5)


# ------------------------------------------------------------------ #
# the kernel in interpret mode against its masked reference
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Q,lens,qlens,tile_rows", [
    (1, [37, 8, 0, 80], [1, 1, 0, 1], None),          # decode, a dead slot
    (8, [37, 8, 0, 80], [8, 1, 0, 5], None),          # mixed, ragged q_len
    (8, [37, 9, 16, 80], [8, 3, 8, 5], 8),            # q-block of 4 tiles
    (8, [5, 1, 0, 33], [5, 1, 0, 8], 16),             # chunk from empty
    # chunks with DECODING slots beside them, tiles of 32 queries over a
    # short height of 8 (ISSUE 43): one row, exactly 8 and 9, a short
    # tail in the second tile (33, 40; 41 is a full one), a dead slot,
    # and a slot with q_len 0 whose pages are filled
    (64, [70, 37, 16, 80, 0, 40], [64, 1, 8, 9, 0, 0], 128),
    (64, [64, 33, 80, 61, 79, 40], [64, 33, 1, 40, 41, 0], 128),
], ids=["decode", "mixed", "tiled", "from-empty", "decoding-beside",
        "decoding-beside-tails"])
def test_ragged_paged_mla_matches_masked_reference(monkeypatch, Q, lens,
                                                   qlens, tile_rows, dtype):
    if tile_rows:
        monkeypatch.setattr(ra, "_MLA_TILE_ROWS", tile_rows)
        assert ra._mla_q_tile(Q, 4) < Q
    rng = np.random.default_rng(0)
    B, H, W, dv, bs, T, N = len(lens), 4, 48, 32, 4, 20, 64
    q = jnp.asarray(rng.standard_normal((B, Q, H, W)), dtype)
    pool = jnp.asarray(rng.standard_normal((3, N, bs, W)), dtype)
    tables = jnp.asarray(rng.integers(1, N, (B, T)), jnp.int32)
    lens, qlens = jnp.asarray(lens, jnp.int32), jnp.asarray(qlens, jnp.int32)
    kw = dict(value_width=dv, scale=0.2, layer=1)
    got = np.asarray(ra.ragged_paged_mla(
        q, pool, lens, qlens, tables, interpret=True, **kw), np.float32)
    want = np.asarray(ra.ragged_paged_mla_reference(
        q, pool, lens, qlens, tables, **kw))
    assert got.shape == (B, Q, H, dv)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    tq, short = ra.mla_tiling(Q, H)
    assert short == (8 if tq > 8 else 0)
    for b, n in enumerate(np.asarray(qlens)):
        if lens[b] == 0:
            assert not got[b].any()
            continue
        for t in range(Q // tq):
            # live rows, and a scored tile's dead rows (clipped to the
            # last live position), are the reference's; rows past the
            # scored height, and dead tiles, are zeros
            rows = min(max(int(n) - t * tq, 0), tq)
            if short:
                h = 0 if rows == 0 else short if rows <= short else tq
            else:
                h = tq if rows or t == 0 else 0
            at = t * tq
            np.testing.assert_allclose(got[b, at:at + h], want[b, at:at + h],
                                       atol=tol, rtol=tol)
            assert not got[b, at + h:at + tq].any()
            live, full = ra.tile_heights(int(n), t, tq, short)
            assert not short or (bool(live), bool(full)) == (h > 0, h == tq)


# The PACKED entry (ISSUE 46): row tiles of 16 packed queries x 4 heads,
# a short window of 8.  Q is the q-block's padded width (what the dense
# entry and the reference are asked), R the packed rows.
PACKED_WAVES = {
    # a chunk that starts at packed row 3 and crosses the tile edges at
    # 16 and 32, one-row slots before and after it
    "chunk-across-two-edges": (
        [9, 30, 7, 55, 12, 21], [1, 1, 1, 40, 1, 1], jnp.float32),
    # tile 0 is sixteen one-row slots; a chunk fills the next tile and a half
    "tile-of-one-row-slots": (
        list(range(5, 21)) + [40, 9], [1] * 16 + [24, 1], jnp.bfloat16),
    # dead slots between live ones (one of them with pages filled), and
    # three tiles nobody owns
    "dead-slots-and-a-dead-tail": (
        [8, 0, 11, 30, 0, 5, 0], [1, 0, 0, 9, 0, 1, 0], jnp.float32),
    # chunk pieces of 2 to 7 rows: 6 rows inside window 0, 5 that
    # straddle the windows' edge at 8, 7 that straddle the TILE's edge at
    # 16 (two visits, each inside one window), 2 inside a window
    "short-pieces-that-straddle": (
        [6, 25, 17, 2, 9], [6, 5, 7, 2, 1], jnp.float32),
}
# one shape for all of them (18 slots, dead past a case's own; q-blocks
# 64 wide, what the dense entry and the reference are asked; 64 packed
# rows), so that the interpreted kernels are built once a dtype
PACKED_SHAPE = dict(B=18, Q=64, R=64)


def brute_force_visits(q_lens, tq, short):
    """{(tile, slot): short?} row by row: a tile visits every slot with
    a packed row in it, at the short window iff those rows share one."""
    windows = {}
    for row, slot in enumerate(np.repeat(np.arange(len(q_lens)), q_lens)):
        windows.setdefault((row // tq, int(slot)), set()).add(row // short)
    return {k: len(w) == 1 for k, w in windows.items()}


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "layer"))
def _dense_mla(q, pool, lens, qlens, tables, **kw):
    return ra.ragged_paged_mla(q, pool, lens, qlens, tables, interpret=True,
                               **kw)


@pytest.mark.parametrize("name", list(PACKED_WAVES))
def test_packed_mla_rows_match_the_reference_and_the_dense_entry(
        monkeypatch, name):
    lens, qlens, dtype = PACKED_WAVES[name]
    B, Q, R = (PACKED_SHAPE[k] for k in "BQR")
    lens, qlens = (v + [0] * (B - len(v)) for v in (lens, qlens))
    monkeypatch.setattr(ra, "_MLA_TILE_ROWS", 64)
    rng = np.random.default_rng(0)
    H, W, dv, bs, T, N = 4, 48, 32, 4, 16, 64
    tq, short = ra.mla_rows_tiling(R, H, dtype)
    assert (tq, short) == (16, 8)
    q = jnp.asarray(rng.standard_normal((B, Q, H, W)), dtype)
    pool = jnp.asarray(rng.standard_normal((3, N, bs, W)), dtype)
    tables = jnp.asarray(rng.integers(1, N, (B, T)), jnp.int32)
    lens, qlens = jnp.asarray(lens, jnp.int32), jnp.asarray(qlens, jnp.int32)
    kw = dict(value_width=dv, scale=0.2, layer=1)
    rows = gd._Rows.of(qlens, Q, R)
    got = np.asarray(ra.ragged_paged_mla_rows(
        rows.pack(q)[0], pool, lens, qlens, rows.start, tables,
        interpret=True, **kw), np.float32)
    assert got.shape == (R, H, dv)
    live = np.asarray(rows.live)
    assert live.sum() == int(qlens.sum()) and got[live].any(-1).all()
    # dead packed rows (the last live tile's tail, tiles nobody owns)
    # come back zero
    assert not got[~live].any()
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    want = rows.pack(ra.ragged_paged_mla_reference(
        q, pool, lens, qlens, tables, **kw))[0]
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    # ... and equal ``rows.pack`` of the dense entry's output on the
    # same wave
    dense = rows.pack(_dense_mla(q, pool, lens, qlens, tables, **kw))[0]
    np.testing.assert_allclose(
        got[live], np.asarray(dense, np.float32)[live], atol=tol, rtol=tol)
    # the visit rule (what the wrapper's slot ranges and the engine's
    # counters ask) against a count row by row
    ql = np.asarray(qlens)
    lo, hi, visit, full = ra.row_tile_visits(
        (np.cumsum(ql) - ql)[:, None], ql[:, None],
        np.arange(R // tq)[None, :], tq, short)
    want_visits = brute_force_visits(ql, tq, short)
    assert {(t, b) for b, t in zip(*np.nonzero(visit))} == set(want_visits)
    assert all(bool(full[b, t]) != is_short
               for (t, b), is_short in want_visits.items())
    assert (hi - lo)[visit].sum() == ql.sum()
    if name == "short-pieces-that-straddle":
        # slot 1's five rows straddle the windows' edge: the full tile;
        # slot 2's seven straddle the tile's edge: two short visits
        assert want_visits == {(0, 0): True, (0, 1): False, (0, 2): True,
                               (1, 2): True, (1, 3): True, (1, 4): True}


def test_mla_rows_tiling_takes_a_short_window_where_it_can_start_aligned():
    """The packed program of the long-answer cell: 64 packed queries x 20
    heads a row tile, a window of 8 queries = 160 rows, whole sublane
    tiles of bfloat16; a window whose rows are no whole sublane tiles,
    or a tile no taller than it, has the one height."""
    assert ra.mla_rows_tiling(1024, 20, jnp.bfloat16) == (64, 8)
    assert ra.mla_rows_tiling(1024, 3, jnp.bfloat16) == (256, 0)
    assert ra.mla_rows_tiling(1024, 3, jnp.float32) == (256, 8)
    assert ra.mla_rows_tiling(256, 160, jnp.bfloat16) == (8, 0)


def test_kernel_refuses_an_unaligned_row_on_the_chip():
    """Pages are copied by hand, so a row is a whole number of lane
    tiles wherever the kernel is not interpreted."""
    q = jnp.zeros((1, 1, 4, 48), jnp.float32)
    pool = jnp.zeros((1, 3, 4, 48), jnp.float32)
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        ra.ragged_paged_mla(q, pool, one, one, jnp.zeros((1, 2), jnp.int32),
                            value_width=32, scale=1.0, interpret=False)
    assert LatentMoEConfig(**SMALL).block_spec().latent.row_width == 128
    assert gd.LatentSpec(768, 512, 192, 64, 256).row_width == 640


# ------------------------------------------------------------------ #
# routing
# ------------------------------------------------------------------ #

def _route_inputs(cfg, params, T=12, seed=5):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, cfg.hidden_size)), jnp.float32)
    return x, params["glm_h1_moe_router_weight"], \
        params["glm_h1_moe_router_bias"]


def test_route_chooses_by_biased_score_and_weighs_by_score(params, cfg):
    x, wg, b = _route_inputs(cfg, params)
    b = b.at[3].set(5.0)                  # expert 3 is chosen by its bias
    sel, w = route(x, wg, b, cfg.routed_spec())
    s = np.asarray(jax.nn.sigmoid(x @ wg))
    want = np.argsort(-(s + np.asarray(b)), axis=1)[:, :2]
    assert (np.sort(np.asarray(sel), 1) == np.sort(want, 1)).all()
    assert (np.asarray(sel) == 3).any(axis=1).all()
    picked = np.take_along_axis(s, np.asarray(sel), 1)
    np.testing.assert_allclose(
        w, picked / picked.sum(1, keepdims=True) * 1.8, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.8, rtol=1e-5)


@pytest.mark.parametrize("T,kernel", [(12, False), (64, True)],
                         ids=["compilers-kernel", "grouped-kernel"])
def test_invalid_rows_are_routed_nowhere(params, cfg, T, kernel):
    """12 rows x top-2 are no whole row tile and stay with
    ``ragged_dot``; 64 rows x top-2 are one, which the shape rule hands
    to ``kernels/grouped_matmul`` (ISSUE 41, ISSUE 49), most of whose
    rows then lie past the groups' sum."""
    from hetu_tpu.models.moe_decode import takes_kernel
    assert takes_kernel(T * 2) == kernel
    x, _, _ = _route_inputs(cfg, params, T=T)
    valid = jnp.asarray([True] * 5 + [False] * (T - 5))
    stats = {}
    y = routed_ffn(params, "glm_h1", x, cfg.routed_spec(), valid=valid,
                   stats=stats)
    assert int(stats["load"].sum()) == 5 * 2            # = assignments
    assert int(stats["touched"]) == int((stats["load"] > 0).sum())
    # an invalid row gets the shared expert's part and nothing routed
    shared = gd.swiglu(x, params["glm_h1_moe_shared_gate_weight"],
                       params["glm_h1_moe_shared_up_weight"],
                       params["glm_h1_moe_shared_down_weight"])
    np.testing.assert_allclose(y[5:], shared[5:], atol=1e-6)
    assert np.abs(np.asarray(y[:5] - shared[:5])).max() > 1e-3
    # and changes no valid row's result
    alone = routed_ffn(params, "glm_h1", x[:5], cfg.routed_spec())
    # (the five alone are ten assignment rows: ``ragged_dot``, another
    # order of the float32 sums where the crowd took the kernel)
    np.testing.assert_allclose(y[:5], alone, atol=2e-5 if kernel else 1e-6)


@pytest.mark.parametrize("kw,kernel", [
    (dict(), False), (dict(slots=8, prefill_chunk=16), True)],
    ids=["compilers-kernel", "grouped-kernel"])
def test_batch_company_changes_no_requests_logits(params, cfg, kw, kernel):
    """4 slots x chunks of 8 are 64 assignment rows a chunk wave (no
    whole row tile: ``ragged_dot``); 8 slots x 16 are 256 over 8 experts,
    which the shape rule gives the grouped kernel, and the engine counts
    those waves (``serve.moe.kernel_waves``)."""
    alone_eng = engine(params, cfg, **kw)
    alone = serve(alone_eng, [(19, 6)])["r0"]
    eng = engine(params, cfg, **kw)
    crowd = serve(eng, [(19, 6), (7, 9), (30, 4), (5, 12)])
    assert list(crowd["r0"].tokens) == list(alone.tokens)
    for r in crowd.values():
        assert gaps(params, cfg, r)[0] <= TOL
    for e in (alone_eng, eng):
        waves = e.metrics.snapshot()["moe_kernel_waves"]
        # chunk waves take the kernel; a decode wave's 8 slots x top-2
        # are no whole row tile
        assert (0 < waves <= e.prefill_chunks) if kernel else waves == 0


def test_a_decode_wave_of_whole_row_tiles_counts_as_a_kernel_wave(params,
                                                                   cfg):
    """ISSUE 49: 64 slots x top-2 are 128 sorted rows a DECODE wave, one
    whole row tile, so its experts' products run through
    ``kernels/grouped_matmul`` like a chunk wave's (64 x 8 x 2 = 1,024
    rows) and ``serve.moe.kernel_waves`` counts every wave served."""
    eng = engine(params, cfg, slots=64)
    out = serve(eng, [(19, 6), (7, 9)])
    for r in out.values():
        assert gaps(params, cfg, r)[0] <= TOL
    snap = eng.metrics.snapshot()
    assert snap["steps"] > eng.prefill_chunks       # decode waves among them
    assert snap["moe_kernel_waves"] == snap["steps"]


# ------------------------------------------------------------------ #
# prefix sharing and copy-on-write on latent blocks
# ------------------------------------------------------------------ #

def test_prefix_sharing_and_cow_on_latent_blocks(params, cfg):
    rng = np.random.default_rng(7)
    head = rng.integers(0, 257, 10).astype(np.int32)    # ends mid-block
    tails = [rng.integers(0, 257, n).astype(np.int32) for n in (5, 9)]
    eng = engine(params, cfg, prefix_share=True)
    first = eng.run([Request(np.concatenate([head, tails[0]])[:10], 4,
                             request_id="a")])["a"]
    out = eng.run([Request(np.concatenate([head, t]), 5, request_id=f"b{i}")
                   for i, t in enumerate(tails)])
    st = eng.kv.stats()
    assert st["prefix_hits"] >= 2 and st["cow_copies"] >= 1, st
    assert st["latent"] and eng.kv.cache_v is None
    assert gaps(params, cfg, first)[0] <= TOL
    for r in out.values():
        assert gaps(params, cfg, r)[0] <= TOL, r.request_id
    # truncate on a shared latent block forks it, contents kept
    kv = PagedKVManager(layers=1, heads=1, head_dim=1, slots=2,
                        max_seq_len=16, block=4, prefix_share=True,
                        row_shape=(6,))
    kv.cache_k = kv.cache_k.at[:].set(
        jnp.arange(kv.cache_k.size, dtype=jnp.float32).reshape(
            kv.cache_k.shape))
    s0, _ = kv.alloc("x", list(range(8)), 12)
    kv.advance(s0, 8)
    kv.register_prefix(list(range(8)), s0)
    b = int(kv.tables[s0, 1])
    before = np.asarray(kv.cache_k[:, b])
    kv.truncate(s0, 6)
    nb = int(kv.tables[s0, 1])
    assert nb != b and kv.cow_copies == 1
    np.testing.assert_array_equal(np.asarray(kv.cache_k[:, nb]), before)
    assert kv.cache_bytes == kv.cache_k.nbytes and kv.cache_v is None


# ------------------------------------------------------------------ #
# what a latent spec refuses
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("kw,names", [
    (dict(spec=2), "speculation"),
    (dict(kv_quant="int8"), "int8"),
], ids=["speculation", "int8-kv"])
def test_engine_refuses_other_paths(params, cfg, kw, names):
    with pytest.raises(ValueError, match=names):
        engine(params, cfg, **kw)


def test_latent_pool_refuses_the_wire_and_the_tiers(params, cfg):
    from hetu_tpu.serving import kv_tiers
    eng = engine(params, cfg)
    eng.submit(Request(np.arange(6, dtype=np.int32), 2, request_id="x"))
    eng.step()
    slot = eng.kv.live()[0]
    with pytest.raises(ValueError, match="wire format"):
        eng.kv.export_blocks(slot)
    with pytest.raises(ValueError, match="wire format"):
        eng.kv.import_blocks({"layout": "paged"}, "y")
    with pytest.raises(ValueError, match="int8"):
        PagedKVManager(layers=1, heads=1, head_dim=1, slots=1,
                       max_seq_len=16, dtype="int8", row_shape=(6,))
    store = next(v for v in vars(kv_tiers).values()
                 if isinstance(v, type) and hasattr(v, "attach"))
    with pytest.raises(ValueError, match="latent"):
        store.attach(object.__new__(store), 0, eng.kv)


@pytest.mark.parametrize("bad", [
    dict(n_group=2), dict(topk_group=2), dict(rope_scaling={"type": "yarn"}),
    dict(partial_rotary_factor=0.5), dict(topk_method="greedy"),
    dict(attention_bias=True), dict(hidden_act="gelu"),
    dict(num_experts_per_tok=9), dict(qk_rope_head_dim=7),
], ids=lambda d: next(iter(d)))
def test_config_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        LatentMoEConfig(**dict(SMALL, **bad))


def test_block_spec_check():
    gd.check_block_spec(gd.GPT2_BLOCK)
    gd.check_block_spec(LatentMoEConfig(**SMALL).block_spec())
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(gd.GPT2_BLOCK._replace(norm="rmsnorm"))
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(LatentMoEConfig(**SMALL).block_spec()._replace(
            routed=None))
    dense = LatentMoEConfig(**dict(SMALL, first_k_dense_replace=3))
    assert dense.block_spec().ffn == "swiglu"
    assert LatentMoEConfig.from_hf(dict(SMALL, model_type="glm4_moe_lite",
                                        num_nextn_predict_layers=1)
                                   ).block_spec() == LatentMoEConfig(
                                       **SMALL).block_spec()


# ------------------------------------------------------------------ #
# GPT-2's spec still builds exactly the programs it built
# ------------------------------------------------------------------ #

def test_gpt2_spec_lowers_to_the_same_program():
    """A GPT-2 cfg_tuple carries no spec; carrying ``GPT2_BLOCK``
    explicitly lowers to the same text, and both keep the jitted name,
    GPT-2's scopes and none of the new block's."""
    from hetu_tpu.models.gpt_decode import GPT2_BLOCK
    c = GPTConfig(vocab_size=97, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=4, max_position_embeddings=64,
                  seq_len=64, dropout_rate=0.0)
    rng = np.random.default_rng(0)
    from benchmarks.runners import serve as serve_runner
    params = serve_runner.init_params(c, 1, jnp.float32)
    B, Q, T = 4, 8, 4
    pool = jnp.zeros((2, 9, 16, 4, 8), jnp.float32)
    args = (pool, pool, jnp.zeros((B, T), jnp.int32),
            jnp.zeros(B, jnp.int32),
            jnp.asarray(rng.integers(0, 97, (B, Q)), jnp.int32),
            jnp.full(B, Q, jnp.int32), jnp.full(B, Q - 1, jnp.int32),
            jnp.ones(B, bool), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.zeros((B, 2), jnp.uint32))
    fn = gd.serve_mixed_paged_fn(False, "masked", 1)
    base = ("gpt", 2, 4, 8, 64)
    texts = [fn.func.lower(params, t, *args, attn="masked", has_fresh=True,
                           window=1).as_text(debug_info=True)
             for t in (base, base + (GPT2_BLOCK,))]
    assert texts[0] == texts[1]
    assert "jit__serve_mixed_paged" in texts[0]
    for scope in ("attn_qkv", "kv_write", "attention", "attn_out", "mlp",
                  "lm_head", "sample"):
        assert f"/{scope}" in texts[0], scope
    for scope in ("mla_qkv", "mla_absorb", "moe_route", "moe_experts"):
        assert scope not in texts[0]


def test_latent_wave_carries_its_scopes(params, cfg):
    cfg_tuple = ("glm", 3, 4, 16, 64, cfg.block_spec())
    B, Q, T = 2, 4, 4
    pool = jnp.zeros((3, 9, 16, 128), jnp.float32)
    fn = gd.serve_mixed_paged_fn(False, "masked", 1)
    text = fn.func.lower(
        params, cfg_tuple, pool, None, jnp.zeros((B, T), jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.zeros((B, Q), jnp.int32),
        jnp.full(B, Q, jnp.int32), jnp.full(B, Q - 1, jnp.int32),
        jnp.ones(B, bool), jnp.zeros(B, jnp.float32),
        jnp.zeros(B, jnp.int32), jnp.zeros((B, 2), jnp.uint32),
        attn="masked", has_fresh=True, window=1).as_text(debug_info=True)
    assert "jit__serve_mixed_paged" in text
    for scope in ("embed", "mla_qkv", "mla_absorb", "kv_write", "attention",
                  "attn_out", "moe_route", "moe_experts", "moe_shared", "mlp",
                  "lm_head", "sample"):
        assert f"/{scope}" in text, scope


# ------------------------------------------------------------------ #
# the comparison is tight: each omission fails it
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def answers(params, cfg):
    return serve(engine(params, cfg),
                 [(19, 24), (7, 30), (30, 20), (12, 28)], seed=11)


def test_reference_comparison_passes_whole(params, cfg, answers):
    assert max(gaps(params, cfg, r)[0] for r in answers.values()) <= TOL


@pytest.mark.parametrize("omit", ref.OMISSIONS)
def test_reference_comparison_fails_each_omission(params, cfg, answers,
                                                  omit):
    """Leaving out the shared expert, the 1.8, the normalisation, the
    selection bias or the ``k_r`` term of the score, or running the
    router in bf16, is seen: the widest gap passes the tolerance the
    whole comparison holds, a hundred times over."""
    worst = max(gaps(params, cfg, r, omit=(omit,))[0]
                for r in answers.values())
    assert worst > 100 * TOL, (omit, worst)


def test_unknown_omission_is_an_error(params, cfg):
    with pytest.raises(ValueError):
        ref.forward(params, cfg, np.arange(4), omit=("nothing",))


# ------------------------------------------------------------------ #
# the checkpoint converter
# ------------------------------------------------------------------ #

def test_convert_glm4_moe_lite_round_trips_names_and_rope(cfg, params):
    """A state dict in the checkpoint's layout (torch [out, in],
    per-expert leaves, neighbour-paired rope columns, an MTP layer
    behind the last) converts to leaves that serve the same logits."""
    c = cfg
    H, dn, dr, dc = 4, 8, 8, 16
    inv = np.argsort(np.concatenate([np.arange(0, dr, 2),
                                     np.arange(1, dr, 2)]))
    P = {k: np.asarray(v) for k, v in params.items()}
    sd = {"model.embed_tokens.weight": P["glm_wte_table"],
          "model.norm.weight": P["glm_ln_f_scale"],
          "lm_head.weight": P["glm_lm_head_weight"].T}
    for i in range(3):
        us, hfk = f"glm_h{i}", f"model.layers.{i}"
        q_b = P[f"{us}_attn_q_b_weight"].reshape(-1, H, dn + dr)
        q_b = np.concatenate([q_b[..., :dn], q_b[..., dn:][..., inv]], -1)
        kv_a = P[f"{us}_attn_kv_a_weight"]
        kv_a = np.concatenate([kv_a[:, :dc], kv_a[:, dc:][:, inv]], -1)
        sd.update({
            f"{hfk}.input_layernorm.weight": P[f"{us}_ln1_scale"],
            f"{hfk}.post_attention_layernorm.weight": P[f"{us}_ln2_scale"],
            f"{hfk}.self_attn.q_a_proj.weight":
                P[f"{us}_attn_q_a_weight"].T,
            f"{hfk}.self_attn.q_a_layernorm.weight":
                P[f"{us}_attn_q_a_norm_scale"],
            f"{hfk}.self_attn.q_b_proj.weight":
                q_b.reshape(q_b.shape[0], -1).T,
            f"{hfk}.self_attn.kv_a_proj_with_mqa.weight": kv_a.T,
            f"{hfk}.self_attn.kv_a_layernorm.weight":
                P[f"{us}_attn_kv_a_norm_scale"],
            f"{hfk}.self_attn.kv_b_proj.weight":
                P[f"{us}_attn_kv_b_weight"].T,
            f"{hfk}.self_attn.o_proj.weight": P[f"{us}_attn_proj_weight"].T})
        if i < 1:
            for nm in ("gate", "up", "down"):
                sd[f"{hfk}.mlp.{nm}_proj.weight"] = \
                    P[f"{us}_ffn_{nm}_weight"].T
            continue
        sd[f"{hfk}.mlp.gate.weight"] = P[f"{us}_moe_router_weight"].T
        sd[f"{hfk}.mlp.gate.e_score_correction_bias"] = \
            P[f"{us}_moe_router_bias"]
        for nm in ("gate", "up", "down"):
            for e in range(8):
                sd[f"{hfk}.mlp.experts.{e}.{nm}_proj.weight"] = \
                    P[f"{us}_moe_experts_{nm}"][e].T
            sd[f"{hfk}.mlp.shared_experts.{nm}_proj.weight"] = \
                P[f"{us}_moe_shared_{nm}_weight"].T
    sd["model.layers.3.input_layernorm.weight"] = np.ones(64)   # the MTP
    got = hf.convert_glm4_moe_lite(sd, c)
    assert set(got) == set(c.param_shapes("glm"))
    for k, shape in c.param_shapes("glm").items():
        assert got[k].shape == shape, k
        np.testing.assert_array_equal(got[k], P[k], err_msg=k)


# ------------------------------------------------------------------ #
# the trace checker and the step records
# ------------------------------------------------------------------ #

def test_hetu_trace_check_passes_on_the_new_engines_log(params, cfg,
                                                        tmp_path):
    """``Σ load = assignments`` and no drops: the attribution rule
    balances on every step record, and the whole stream is valid."""
    import json
    from hetu_tpu.telemetry.trace import check_moe_attribution, main
    log = str(tmp_path / "serve.jsonl")
    eng = engine(params, cfg, log_path=log)
    serve(eng, [(19, 6), (7, 9)])
    steps = [json.loads(l) for l in open(log)]
    steps = [r for r in steps if r.get("event") == "serve_step"]
    assert steps and all(r["moe_dropped"] == 0 and r["moe_drop_rate"] == 0
                         for r in steps)
    assert all(r["moe_routed"] == r["moe_tokens"] * 2 * 2 for r in steps)
    assert all(r["moe_k"] == 2 and r["moe_layers"] == 2 for r in steps)
    assert any(r["moe_imb"] > 1 for r in steps)
    assert check_moe_attribution(steps) == []
    bad = [dict(steps[0], moe_routed=steps[0]["moe_routed"] - 1)]
    assert check_moe_attribution(bad)
    assert main([log, "--check"]) == 0
