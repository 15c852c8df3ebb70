"""The packed chunk wave (ISSUE 35): a wave over the paged pool that
carries a prompt chunk runs its row-wise operators over
``gpt_decode.wave_rows`` packed rows, not over slots x the widest
q-block, and the scheduler keeps a wave's live rows within that many.

Three halves: the step (a packed wave's logits, pool, conv state and
routed load equal the padded ``[B, Q]`` step's on the same descriptor,
for every block kind and both scorings), the scheduler (capacity,
deferral oldest first, the counters, one program a bucket) and the
engine end to end against a sequential decode and the references.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import telemetry
from hetu_tpu.kv_layout import kv_row_width
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_hybrid_moe as ref_hybrid
from hetu_tpu.models import reference_latent_moe as ref_latent
from hetu_tpu.models.gpt import GPTConfig
from hetu_tpu.models.moe_decode import (
    HybridMoEConfig, LatentMoEConfig, MoESpec, init_hybrid_moe_params,
    init_latent_moe_params)
from hetu_tpu.serving import Request, ServingEngine

from jitted import reference

HYBRID = dict(
    vocab_size=257, hidden_size=64, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=2,
    layer_types=["conv", "conv", "full_attention", "conv",
                 "full_attention", "conv"],
    conv_L_cache=3, conv_bias=False, intermediate_size=96,
    moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
    num_dense_layers=2, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1.0, rope_theta=1e6, norm_eps=1e-5,
    max_position_embeddings=256, model_type="lfm2_moe")
LATENT = dict(
    vocab_size=257, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=48, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, first_k_dense_replace=1, rope_theta=1e6,
    rms_norm_eps=1e-5, max_position_embeddings=256)
TOL = 2e-4       # engine against a reference: the order of the sums


def rand_gpt(name="pw", L=2, H=2, Dh=8, V=61, S=256, seed=0):
    rng = np.random.RandomState(seed)
    hd = H * Dh
    p = {f"{name}_wte_table": rng.randn(V, hd) * 0.05,
         f"{name}_wpe": rng.randn(S, hd) * 0.05,
         f"{name}_ln_f_scale": np.ones(hd), f"{name}_ln_f_bias": np.zeros(hd)}
    for i in range(L):
        us = f"{name}_h{i}"
        for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                       ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                       ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
            p[f"{us}_{w}_weight"] = rng.randn(*shp) * 0.05
            p[f"{us}_{w}_bias"] = rng.randn(shp[1]) * 0.02
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = np.ones(hd)
            p[f"{us}_{ln}_bias"] = np.zeros(hd)
    cfg = GPTConfig(vocab_size=V, hidden_size=hd, num_hidden_layers=L,
                    num_attention_heads=H, max_position_embeddings=S,
                    batch_size=1, seq_len=S, dropout_rate=0.0)
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}, cfg


@pytest.fixture(scope="module")
def gpt():
    return rand_gpt()


@pytest.fixture(scope="module")
def hybrid():
    cfg = HybridMoEConfig.from_hf(HYBRID)
    return init_hybrid_moe_params(cfg, seed=3, scale=0.2), cfg


@pytest.fixture(scope="module")
def latent():
    cfg = LatentMoEConfig(**LATENT)
    return init_latent_moe_params(cfg, seed=3, scale=0.2), cfg


# ------------------------------------------------------------------ #
# the row count
# ------------------------------------------------------------------ #

GPT = ("g", 2, 2, 8, 256)


@pytest.mark.parametrize("slots,window,q,rows", [
    (32, 1, 256, 1024), (32, 1, 128, 512), (32, 1, 64, 256),
    (16, 1, 256, 1024), (16, 1, 128, 512), (16, 1, 64, 256),
    (16, 1, 32, 256), (32, 1, 16, 256),   # the floor: 256 rows
    (16, 1, 16, 256), (4, 1, 32, 128),    # R = slots x q: the identity
    (16, 5, 256, 1024), (64, 5, 256, 1024),   # the window is in R
    (32, 1, 1, 32)])
def test_wave_rows_of_a_chunk_wave(slots, window, q, rows):
    assert gd.wave_rows(GPT, slots, window, q) == rows
    assert rows <= slots * q
    # every slot's window and two chunks of the bucket always fit
    assert rows >= min(slots * q, slots * window + 2 * q)


@pytest.mark.parametrize("kw,cfg_tuple", [
    ({"has_fresh": False}, GPT),
    ({}, GPT + (MoESpec(num_experts=4, top_k=1, capacity_factor=1.0,
                        moe_every=1, draft=False, ep_axis=None),))],
    ids=["decode-or-verify", "capacity-router"])
def test_wave_rows_of_every_other_wave_is_the_padded_block(kw, cfg_tuple):
    assert gd.wave_rows(cfg_tuple, 32, 1, 256, **kw) == 32 * 256


def test_rows_layout_packs_and_unpacks():
    q_len = jnp.asarray([0, 3, 0, 1, 4], jnp.int32)
    rows = gd._Rows.of(q_len, 4, 12)
    assert list(np.asarray(rows.slot)[:8]) == [1, 1, 1, 3, 4, 4, 4, 4]
    assert list(np.asarray(rows.off)[:8]) == [0, 1, 2, 0, 0, 1, 2, 3]
    assert list(np.asarray(rows.live)) == [True] * 8 + [False] * 4
    assert list(np.asarray(rows.start)) == [0, 0, 3, 3, 4]
    x = jnp.arange(5 * 4 * 2).reshape(5, 4, 2)
    packed = rows.pack(x)
    assert packed.shape == (1, 12, 2)
    back = np.asarray(rows.unpack(packed))
    for b, n in enumerate(np.asarray(q_len)):
        assert np.array_equal(back[b, :n], np.asarray(x)[b, :n])


# ------------------------------------------------------------------ #
# the step: packed against padded on one descriptor
# ------------------------------------------------------------------ #

B, Q, W, BS, T = 8, 64, 3, 4, 64
# slot: (q_len, pos, first_row, self_fresh); slots 0 and 6 are dead
WAVE = {1: (43, 21, 42, True),     # a FINAL chunk and its one-row window
        2: (64, 64, 64, True),     # a mid-prompt chunk, nothing sampled
        3: (1, 7, 0, True),        # a final chunk shorter than K - 1
        4: (3, 9, 0, False),       # a verify block beside the chunks
        5: (1, 80, 0, False),      # decode
        7: (1, 3, 0, False)}       # decode
LIVE = sum(n for n, *_ in WAVE.values())


def descriptor(vocab, seed=0):
    rng = np.random.RandomState(seed)
    tokens = np.zeros((B, Q), np.int32)
    pos, q_len, first = (np.zeros(B, np.int32) for _ in range(3))
    fresh = np.zeros(B, bool)
    for s, (n, p, fr, f) in WAVE.items():
        tokens[s, :n] = rng.randint(1, vocab, n)
        pos[s], q_len[s], first[s], fresh[s] = p, n, fr, f
    tables = (1 + np.arange(B * T, dtype=np.int32)).reshape(B, T)
    return tokens, pos, q_len, first, fresh, tables


def model_case(kind, gpt, hybrid, latent, quant=False):
    """(params, cfg_tuple, cache_k, cache_v, state, vocab) with a pool
    and a state that hold something."""
    rng = np.random.RandomState(7)

    def filled(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)

    N = B * T + 1
    if kind == "gpt2":
        p = gpt[0]
        if quant:
            def pair():
                return (jnp.asarray(rng.randint(-90, 90, (2, N, BS, 2, 8)),
                                    jnp.int8),
                        jnp.asarray(rng.rand(2, N, BS, 2) * 0.01 + 0.001,
                                    jnp.float32))
            return p, ("pw", 2, 2, 8, 256), pair(), pair(), None, 61
        w = kv_row_width(2, 8)
        return p, ("pw", 2, 2, 8, 256), filled(2, N, BS, w), \
            filled(2, N, BS, w), None, 61
    if kind == "latent":
        p, cfg = latent
        blk = cfg.block_spec()
        return p, ("glm", 3, 4, 16, 256, blk), \
            filled(3, N, BS, blk.latent.row_width), None, None, 257
    p, cfg = hybrid
    w = kv_row_width(2, 8)
    return p, ("lfm", 6, 8, 8, 256, cfg.block_spec()), \
        filled(2, N, BS, w), filled(2, N, BS, w), filled(4, B, 2, 64), 257


def run_step(case, desc, attn, padded, monkeypatch):
    params, cfg_tuple, ck, cv, state, _ = case
    tokens, pos, q_len, first, fresh, tables = desc

    def wave(params, ck, cv, state, tables, *desc):
        # jitted as the engine's own step is, and a function of its own:
        # the row count is read while tracing
        stats = {}
        out = gd._mixed_step(
            params, cfg_tuple, ck, cv, *desc, window=W, attn=attn,
            block_tables=tables, has_fresh=True, moe_stats=stats,
            state=state)
        return out, stats
    with monkeypatch.context() as m:
        if padded:
            m.setattr(gd, "wave_rows",
                      lambda cfg, slots, window, q, *a, **k: slots * q)
        return jax.jit(wave)(
            params, ck, cv, state, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(tokens), jnp.asarray(q_len), jnp.asarray(first),
            jnp.asarray(fresh))


def pool_body(cache):
    """A pool's blocks but scratch block 0 (dead rows' writes land
    there, and which row's lands last is nobody's business)."""
    if cache is None:
        return []
    parts = cache if isinstance(cache, (tuple, list)) else (cache,)
    return [np.asarray(a)[:, 1:] for a in parts]


CASES = [("gpt2", "masked", False), ("gpt2", "ragged", False),
         ("gpt2", "masked", True), ("gpt2", "ragged", True),
         ("latent", "masked", False), ("latent", "ragged", False),
         ("hybrid", "masked", False), ("hybrid", "ragged", False)]


@pytest.mark.parametrize(
    "kind,attn,quant", CASES,
    ids=[f"{k}-{a}{'-int8' if q else ''}" for k, a, q in CASES])
def test_packed_wave_equals_the_padded_wave(kind, attn, quant, gpt, hybrid,
                                            latent, monkeypatch):
    """Dead slots, a final chunk with its window, a chunk shorter than
    K - 1, a verify block and two decode rows beside the chunks: 113
    live rows of 512, computed over 256."""
    case = model_case(kind, gpt, hybrid, latent, quant)
    desc = descriptor(case[-1])
    assert gd.wave_rows(case[1], B, W, Q) == 256 < B * Q
    assert int(desc[2].sum()) == LIVE == 113
    (lg, ck, cv, st), stats = run_step(case, desc, attn, False, monkeypatch)
    (lg0, ck0, cv0, st0), stats0 = run_step(case, desc, attn, True,
                                            monkeypatch)
    assert lg.shape == lg0.shape == (B, W, case[-1])
    q_len, first = desc[2], desc[3]
    read = 0
    for b in range(B):
        for w in range(max(int(q_len[b] - first[b]), 0)):
            np.testing.assert_allclose(np.asarray(lg)[b, w],
                                       np.asarray(lg0)[b, w], atol=2e-5)
            read += 1
    assert read == 1 + 1 + 3 + 1 + 1
    for got, want in zip(pool_body(ck) + pool_body(cv),
                         pool_body(ck0) + pool_body(cv0)):
        if got.dtype == np.int8:
            assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
        else:
            np.testing.assert_allclose(got, want, atol=2e-6)
    if st0 is not None:
        np.testing.assert_allclose(np.asarray(st), np.asarray(st0),
                                   atol=2e-6)
        # a dead slot's state does not move
        assert np.array_equal(np.asarray(st)[:, [0, 6]],
                              np.asarray(case[4])[:, [0, 6]])
    if "load" in stats0:
        assert np.array_equal(np.asarray(stats["load"]),
                              np.asarray(stats0["load"]))
        layers = {"latent": 2, "hybrid": 4}[kind]
        assert int(np.asarray(stats["load"]).sum()) == LIVE * 2 * layers
        assert int(stats["touched"]) == int(stats0["touched"])


def test_packed_program_routes_packed_rows(hybrid):
    """The packed program's router sorts ``R x k`` assignments and its
    row-wise products run over R rows: no [B x Q]-row tensor is left
    outside the attention's unpack."""
    params = hybrid[0]
    case = model_case("hybrid", None, hybrid, None)
    tokens, pos, q_len, first, fresh, tables = descriptor(257)
    jaxpr = jax.make_jaxpr(
        lambda *a: gd._mixed_step(
            params, case[1], case[2], case[3], *a, window=W,
            attn="masked", block_tables=jnp.asarray(tables), has_fresh=True,
            moe_stats={}, state=case[4]))(
        jnp.asarray(pos), jnp.asarray(tokens), jnp.asarray(q_len),
        jnp.asarray(first), jnp.asarray(fresh))
    def eqns_of(jp):
        for e in jp.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        yield from eqns_of(getattr(sub, "jaxpr", sub))

    eqns = list(eqns_of(jaxpr.jaxpr))
    sorts = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "sort"]
    assert sorts and all(s == (256 * 2,) for s in sorts)
    # weight products (a 2-d right-hand side) see the packed rows alone
    dots = {e.invars[0].aval.shape[:2] for e in eqns
            if e.primitive.name == "dot_general"
            and e.invars[1].aval.ndim == 2}
    assert (1, 256) in dots and (B, Q) not in dots


# ------------------------------------------------------------------ #
# the engine: tokens of a sequential decode, the references' logits
# ------------------------------------------------------------------ #

SIZES = [(150, 4), (70, 6), (200, 3), (30, 4), (64, 5), (129, 3), (190, 2),
         (9, 6), (100, 4), (65, 3), (128, 2)]
SMALL_SIZES = [(150, 4), (70, 5), (130, 3), (3, 4), (64, 3), (129, 2),
               (1, 4)]


def requests(vocab, sizes=SIZES, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(1, vocab, n).astype(np.int32), m,
                    request_id=f"r{i}", **kw)
            for i, (n, m) in enumerate(sizes)]


def rows_of_waves(eng):
    """(live, computed) the engine's counters hold."""
    snap = eng.metrics.snapshot()
    return snap["wave_rows_live"], snap["wave_rows_computed"]


def watch_waves(eng):
    """[(q_len, Q, rows computed)] of the waves ``eng`` lands from now
    on."""
    waves, record = [], eng._wave_record

    def recording(wave, rows_computed):
        waves.append((wave["q_len"].copy(), int(wave["q"]), rows_computed))
        return record(wave, rows_computed)
    eng._wave_record = recording
    return waves


def f32_rows_tile(Q):
    """Queries a q-tile of the rows kernel at these engines' widths:
    the q-block padded to 8 float32 rows, whole up to a chunk's 64."""
    return min(-(-Q // 8) * 8, 64)


def tiles_of(waves, tile, short_queries):
    """(live (slot, q-tile) steps, those at the short height) that the
    waves' ``q_len`` give, written out from the kernels' contract:
    ``tile(Q)`` queries a q-tile, and a live tile whose live rows fit
    ``short_queries`` scored short wherever the tile is taller."""
    live = short = 0
    for q_lens, Q, _ in waves:
        tq = tile(Q)
        for n in q_lens:
            for t in range(-(-Q // tq)):
                rows = min(max(int(n) - t * tq, 0), tq)
                live += rows > 0
                short += 0 < rows <= short_queries < tq
    return live, short


def visits_of(waves, tq, short_queries, tile=None):
    """(visits, those at the short window, q-tiles moved) of a hand-paged
    kernel over the waves, row by row from the packed entries' contract:
    a PACKED wave (computed over fewer rows than slots x Q) is cut into
    row tiles of ``tq`` packed queries, a tile visits every slot that
    has a row in it, and a visit all of whose rows lie in one aligned
    window of ``short_queries`` is short; any other wave is the dense
    entry's (``tiles_of``): the K/V rows kernel's ``tile(Q)`` queries a
    q-tile at ONE height, or (no ``tile``) the latent kernel's one tile
    a slot with its short height."""
    from test_latent_moe import brute_force_visits
    dense_short = 0 if tile else short_queries
    tile = tile or (lambda Q: Q)
    live = short = moved = 0
    for q_lens, Q, rows in waves:
        if rows == len(q_lens) * Q:
            a, b = tiles_of([(q_lens, Q, rows)], tile, dense_short)
            live, short = live + a, short + b
            moved += len(q_lens) * -(-Q // tile(Q))
            continue
        visits = brute_force_visits(q_lens, tq, short_queries)
        live += len(visits)
        short += sum(visits.values())
        moved += rows // tq
    return live, short, moved


def test_attention_tile_counters_follow_the_waves_q_lens(gpt, monkeypatch):
    """``serve.attn.tiles_live`` / ``tiles_short`` of an engine on the
    kernel path: a packed chunk wave is one row tile of 256 float32
    queries, its short window 8, so a decoding slot beside a chunk is a
    short visit and the chunk a full one; a dense program (a decode
    wave's Q 1, a chunk wave too small to pack) has one height and
    nothing in it is short.  An engine on the masked path runs no kernel
    and counts none."""
    from hetu_tpu import telemetry
    from hetu_tpu.kernels import ragged_attention as ra
    params, cfg = gpt
    telemetry.reset()
    # two heads of 8 are 16 rows a short product, under the row count
    # at which the rule gives a program two heights: lowered here
    monkeypatch.setattr(ra, "_SHORT_MIN_ROWS", 1)
    eng = ServingEngine(params, cfg, slots=8, kv_block=16,
                        prefill_chunk=64, fast_path=True)
    waves = watch_waves(eng)
    eng.run(requests(61, SMALL_SIZES))
    mark, first = eng.metrics.mark(), len(waves)
    whole = eng.metrics.snapshot()
    assert ra.rows_tiling(64, 2, jnp.float32) == (64, 64)
    # a chunk wave is packed (256 rows of the block's 512) and goes to
    # the kernel as it lies: ONE row tile of 256 queries visits its slots
    assert ra.rows_packed_tiling(256, 2, 8, 1, jnp.float32) == (256, 256, 8)
    want = visits_of(waves, 256, 8, f32_rows_tile)[:2]
    assert (whole["attn_tiles_live"], whole["attn_tiles_short"]) == want
    # decoding slots rode beside chunks, chunks were scored whole, and
    # the decode waves added live tiles alone
    chunk = [(ql, Q) for ql, Q, _ in waves if Q == 64]
    assert any((ql == 1).any() and (ql > 8).any() for ql, _ in chunk)
    assert 0 < want[1] == sum(int((0 < ql).sum() - (ql > 8).sum())
                              for ql, _ in chunk) < want[0]
    counters = telemetry.snapshot()["counters"]
    assert counters["serve.attn.tiles_live"] == want[0]
    assert counters["serve.attn.tiles_short"] == want[1]
    eng.run(requests(61, SMALL_SIZES[:3], seed=5))
    tail = eng.metrics.snapshot(since=mark)
    assert (tail["attn_tiles_live"], tail["attn_tiles_short"]) == visits_of(
        waves[first:], 256, 8, f32_rows_tile)[:2]
    masked = ServingEngine(params, cfg, slots=8, kv_block=16,
                           prefill_chunk=64, fast_path=False)
    masked.run(requests(61, SMALL_SIZES[:3]))
    snap = masked.metrics.snapshot()
    assert snap["attn_tiles_live"] == snap["attn_tiles_short"] == 0
    telemetry.reset()


@pytest.mark.parametrize("fast,spec", [
    (False, None), (True, None), (False, 2), (True, 2)],
    ids=["masked", "kernel", "masked-spec2", "kernel-spec2"])
def test_gpt2_engine_tokens_equal_a_sequential_decode(gpt, fast, spec):
    """Eight slots and chunks of 64: R = 256 of 512, so chunk waves pack
    and a burst defers chunks (a speculating engine's chunk waves too,
    its verify-only waves not); every request's tokens are what
    ``generate_fast`` gives it alone."""
    params, cfg = gpt
    eng = ServingEngine(params, cfg, slots=8, kv_block=16,
                        prefill_chunk=64, fast_path=fast, spec=spec)
    assert gd.wave_rows(eng.cfg_tuple, 8, eng.spec_k + 1, 64) == 256
    out = eng.run(requests(61))
    assert len(out) == len(SIZES)
    for r in out.values():
        n = r.prompt_len
        want = gd.generate_fast(params, cfg, [list(r.tokens[:n])],
                                len(r.tokens) - n)
        assert list(r.tokens) == [int(t) for t in np.asarray(want)[0]], \
            r.request_id
    assert eng.metrics.snapshot()["chunks_deferred"] > 0
    live, computed = rows_of_waves(eng)
    assert live < computed


def test_int8_engine_tokens_equal_the_padded_engines(gpt, monkeypatch):
    """An int8 pool round-trips its own rows, so ``generate_fast`` is not
    its yardstick: the same engine with packing off is."""
    params, cfg = gpt

    def served():
        eng = ServingEngine(params, cfg, slots=8, kv_block=16,
                            prefill_chunk=64, fast_path=False,
                            kv_quant="int8", prefix_share=False)
        return {k: list(r.tokens) for k, r in eng.run(requests(61)).items()}

    packed = served()
    # the padded engine takes every chunk at once: another schedule, the
    # same tokens
    with monkeypatch.context() as m:
        dense = lambda cfg, slots, window, q, *a, **k: slots * q
        m.setattr(gd, "wave_rows", dense)
        m.setattr("hetu_tpu.serving.engine.wave_rows", dense)
        jax.clear_caches()
        padded = served()
    jax.clear_caches()
    assert packed == padded


@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_hybrid_engine_matches_reference_with_packing_engaged(hybrid, fast):
    params, cfg = hybrid
    eng = ServingEngine(params, cfg, slots=8, max_seq_len=256, kv_block=4,
                        prefill_chunk=64, fast_path=fast)
    assert gd.wave_rows(eng.cfg_tuple, 8, 1, 64) == 256 < 512
    sizes = SMALL_SIZES
    waves = watch_waves(eng)
    out = eng.run(requests(257, sizes))
    for r in out.values():
        seq = np.asarray(r.tokens, np.int32)
        lg, _ = reference(ref_hybrid.forward, params, cfg, seq[:-1])
        rows = np.asarray(lg)[r.prompt_len - 1:]
        chosen = rows[np.arange(len(rows)), seq[r.prompt_len:]]
        assert float((rows.max(-1) - chosen).max()) <= TOL, r.request_id
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in sizes)
    assert snap["wave_rows_live"] == rows
    assert snap["moe_assignments"] == rows * 2 * 4 == sum(snap["moe_load"])
    assert snap["chunks_deferred"] > 0
    # computed: 256 a chunk wave where the padded block is 512, 8 a
    # decode wave; over a half of it is live
    assert rows <= snap["wave_rows_computed"] <= 256 * eng.steps
    assert snap["wave_rows_live"] > snap["wave_rows_computed"] / 2, snap
    assert eng.kv.state_resets == len(sizes)
    assert eng.kv.free_blocks == eng.kv.capacity_blocks
    # the grouped rows kernel's tiles (float32: a short height of 8
    # queries under tiles of 64, a packed chunk wave one row tile of
    # 256); the masked path runs no kernel
    tiles = (snap["attn_tiles_live"], snap["attn_tiles_short"])
    assert tiles == (visits_of(waves, 256, 8, f32_rows_tile)[:2]
                     if fast else (0, 0))
    assert not fast or 0 < tiles[1] < tiles[0]


@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_latent_engine_matches_reference_with_packing_engaged(
        latent, fast, monkeypatch):
    from hetu_tpu.kernels import ragged_attention as ra
    # row tiles of 16 packed queries x 4 heads: a chunk of 64 crosses
    # tile edges wherever it starts
    monkeypatch.setattr(ra, "_MLA_TILE_ROWS", 64)
    assert ra.mla_rows_tiling(256, 4, jnp.float32) == (16, 8)
    telemetry.reset()
    params, cfg = latent
    eng = ServingEngine(params, cfg, slots=8, max_seq_len=256, kv_block=4,
                        prefill_chunk=64, fast_path=fast,
                        prefix_share=False)
    sizes = SMALL_SIZES
    waves = watch_waves(eng)
    out = eng.run(requests(257, sizes))
    for r in out.values():
        seq = np.asarray(r.tokens, np.int32)
        lg, margin = reference(ref_latent.forward, params, cfg, seq[:-1])
        rows = np.asarray(lg)[r.prompt_len - 1:]
        chosen = rows[np.arange(len(rows)), seq[r.prompt_len:]]
        assert float((rows.max(-1) - chosen).max()) <= TOL, r.request_id
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in sizes)
    assert snap["wave_rows_live"] == rows
    assert snap["moe_assignments"] == rows * 2 * 2
    assert snap["chunks_deferred"] > 0
    # the latent kernel's visits: a chunk wave is packed (256 rows where
    # the block is 512) and its kernel runs over 16 row tiles, a decode
    # wave is the dense entry's (8 tiles of one query); the short window
    # is 8 queries whatever the dtype
    tiles = (snap["attn_tiles_live"], snap["attn_tiles_short"],
             snap["attn_q_tiles_moved"])
    assert tiles == (visits_of(waves, 16, 8) if fast else (0, 0, 0))
    if fast:
        chunk = [w for w in waves if w[2] < 8 * w[1]]
        assert chunk and all(w[2] == 256 for w in chunk)
        assert 0 < tiles[1] < tiles[0]
        # a chunk that crosses a tile's edge is visited from both sides
        assert tiles[0] > sum(int((w[0] > 0).sum()) for w in waves)
        assert tiles[2] == 16 * len(chunk) + 8 * (len(waves) - len(chunk))
        counters = telemetry.snapshot()["counters"]
        assert tiles == tuple(counters[f"serve.attn.{name}"] for name in (
            "tiles_live", "tiles_short", "q_tiles_moved"))
    telemetry.reset()


def test_packed_rows_wave_counters_are_the_kernels_visits(hybrid,
                                                          monkeypatch):
    """``engine._attn_tiles`` of a packed wave of the K/V rows kernel
    (ISSUE 54) is ``row_tile_visits`` asked of the same ``q_len``: live
    visits, short visits and ``R / tq`` q-tiles moved, whatever the slots
    hold; and an engine's run counts them wave by wave.  Row tiles of 16
    packed queries x 8 heads: a chunk of 64 crosses tile edges wherever
    it starts."""
    from hetu_tpu.kernels import ragged_attention as ra
    from test_latent_moe import brute_force_visits
    monkeypatch.setattr(ra, "_MAX_ROWS", 16 * 8)
    assert ra.rows_packed_tiling(256, 8, 8, 4, jnp.float32) == (256, 16, 8)
    telemetry.reset()
    params, cfg = hybrid
    eng = ServingEngine(params, cfg, slots=8, max_seq_len=256, kv_block=4,
                        prefill_chunk=64, fast_path=True, prefix_share=False)
    q_len = np.array([64, 1, 0, 7, 64, 1, 1, 30])
    start = np.cumsum(q_len) - q_len
    _, _, live, full = ra.row_tile_visits(
        start[:, None], q_len[:, None], np.arange(16)[None, :], 16, 8)
    got = eng._attn_tiles(q_len, 64, 256)
    assert got == (int(live.sum()), int((live & ~full).sum()), 256 // 16)
    visits = brute_force_visits(q_len, 16, 8)
    assert got[:2] == (len(visits), sum(visits.values())) == (16, 8)
    # the same wave padded is the dense entry's: (slot, q-tile) steps
    # at one height
    assert eng._attn_tiles(q_len, 64, 512) == (
        *tiles_of([(q_len, 64, 512)], lambda Q: 16, 0), 8 * 4) == (
        14, 0, 32)
    waves = watch_waves(eng)
    eng.run(requests(257, SMALL_SIZES))
    snap = eng.metrics.snapshot()
    tiles = (snap["attn_tiles_live"], snap["attn_tiles_short"],
             snap["attn_q_tiles_moved"])
    assert tiles == visits_of(waves, 16, 8, lambda Q: min(Q, 16))
    chunk = [w for w in waves if w[2] < 8 * w[1]]
    assert chunk and all(w[2] == 256 for w in chunk)
    assert 0 < tiles[1] < tiles[0]
    # a chunk that crosses a tile's edge is visited from both sides
    assert tiles[0] > sum(int((w[0] > 0).sum()) for w in waves)
    assert tiles[2] == 16 * len(chunk) + 8 * (len(waves) - len(chunk))
    counters = telemetry.snapshot()["counters"]
    assert tiles == tuple(counters[f"serve.attn.{name}"] for name in (
        "tiles_live", "tiles_short", "q_tiles_moved"))
    telemetry.reset()


# ------------------------------------------------------------------ #
# the scheduler
# ------------------------------------------------------------------ #

def burst_engine(gpt, **kw):
    params, cfg = gpt
    return ServingEngine(params, cfg, **dict(dict(
        slots=8, kv_block=16, prefill_chunk=64,
        fast_path=False, prefix_share=False, queue_limit=64), **kw))


def test_chunks_over_the_capacity_wait_oldest_first(gpt):
    """Eight prompts of three chunks at once: R = 256 rows takes four
    64-row chunks a wave.  The four oldest go first and stay first until
    they decode; none of the others moves until a place frees."""
    eng = burst_engine(gpt)
    rng = np.random.default_rng(1)
    for i in range(8):
        eng.submit(Request(rng.integers(1, 61, 192).astype(np.int32), 4,
                           request_id=f"b{i}"))
    slot_of = {}
    trail = []
    pool0 = None
    while eng.pending:
        off0 = eng._prefill_off.copy()
        launched = eng._launched
        eng.step()
        if eng._launched == launched:
            # the step that hands back a retirement launches no wave
            continue
        if not slot_of:
            slot_of = {eng._reqs[s].request_id: s for s in eng.kv.live()}
            order = [slot_of[f"b{i}"] for i in range(8)]
        # the prompt offsets move when a wave is LAUNCHED
        trail.append([int(eng._prefill_off[s] - off0[s]) for s in order])
        if len(trail) == 1:
            # a deferred slot's pool is untouched: its first block still
            # holds the zeros it was made with
            for s in order[4:]:
                blk = int(eng.kv.tables[s, 0])
                assert not np.asarray(eng.kv.cache_k)[:, blk].any()
            for s in order[:4]:
                blk = int(eng.kv.tables[s, 0])
                assert np.asarray(eng.kv.cache_k)[:, blk].any()
    # waves 1-3: the four oldest take their three chunks; 4-6: the rest
    # (beside four decoding slots: 4 + 256 rows is over R, so three of
    # them a wave until the decoders retire)
    assert trail[0] == [64] * 4 + [0] * 4
    assert trail[1] == [64] * 4 + [0] * 4
    assert trail[2] == [64] * 4 + [0] * 4
    assert trail[3] == [0] * 4 + [64] * 3 + [0]
    moved = np.asarray(trail)
    first_wave = [int(np.flatnonzero(moved[:, i])[0]) for i in range(8)]
    assert first_wave == sorted(first_wave)          # oldest first
    # none waits more waves than there are older chunks
    for i, w in enumerate(first_wave):
        assert w <= 3 * i
    # a chunk deferred is a wave between a prompt's first and last in
    # which it did not move, or one before its first
    last_wave = [int(np.flatnonzero(moved[:, i])[-1]) for i in range(8)]
    waited = sum(int((moved[:w + 1, i] == 0).sum())
                 for i, w in enumerate(last_wave))
    snap = eng.metrics.snapshot()
    assert snap["chunks_deferred"] == waited > 0
    assert snap["requests_finished"] == 8
    # the waves waited out are chunk_stall, not a lifecycle residue
    assert not [e for e in eng.metrics.events
                if e["event"] == "serve_lifecycle_residue"]


def test_a_deferred_slots_state_is_untouched(hybrid):
    """A chunk that waits rides as a dead slot: its conv state keeps the
    zeros of its admission while its neighbours' moves."""
    params, cfg = hybrid
    eng = ServingEngine(params, cfg, slots=8, max_seq_len=256, kv_block=4,
                        prefill_chunk=64, fast_path=False)
    rng = np.random.default_rng(2)
    for i in range(8):
        eng.submit(Request(rng.integers(1, 257, 130).astype(np.int32), 3,
                           request_id=f"b{i}"))
    eng.step()
    went = [s for s in eng.kv.live() if eng._prefill_off[s] > 0]
    waited = [s for s in eng.kv.live() if eng._prefill_off[s] == 0]
    assert len(went) == 4 and len(waited) == 4     # R = 256 = 4 chunks
    assert sorted(eng._admit_no[went]) == [0, 1, 2, 3]
    state = np.asarray(eng.kv.state)
    assert not state[:, waited].any()
    assert all(state[:, s].any() for s in went)
    # counted when the wave lands: the next step
    assert eng.metrics.snapshot()["chunks_deferred"] == 0
    eng.step()
    assert eng.metrics.snapshot()["chunks_deferred"] == 4
    out = eng.run()
    assert len(out) == 8
    for r in out.values():
        seq = np.asarray(r.tokens, np.int32)
        lg, _ = reference(ref_hybrid.forward, params, cfg, seq[:-1])
        rows = np.asarray(lg)[r.prompt_len - 1:]
        chosen = rows[np.arange(len(rows)), seq[r.prompt_len:]]
        assert float((rows.max(-1) - chosen).max()) <= TOL, r.request_id


def test_counters_count_what_happened(gpt):
    """``wave_rows_computed`` sums each wave's ``wave_rows`` (R for a
    chunk wave, slots x q otherwise); live rows are within it wave by
    wave, and over a half of it in this burst."""
    telemetry.reset()
    eng = burst_engine(gpt)
    rng = np.random.default_rng(3)
    reqs = [Request(rng.integers(1, 61, 192).astype(np.int32), 4,
                    request_id=f"c{i}") for i in range(8)]
    for r in reqs:
        eng.submit(r)
    waves = []
    snap0 = eng.metrics.snapshot()
    assert (snap0["wave_rows_live"], snap0["wave_rows_computed"],
            snap0["chunks_deferred"]) == (0, 0, 0)
    before = (0, 0)
    mark = eng.metrics.mark()
    while eng.pending:
        eng.step()
        now = rows_of_waves(eng)
        if now != before:             # a wave landed in this step
            waves.append((now[0] - before[0], now[1] - before[1]))
        before = now
    assert len(waves) == eng.steps
    assert all(live <= computed for live, computed in waves)
    # chunk waves are 256 rows (q 64), decode waves 8 (q 1)
    assert {c for _, c in waves} == {256, 8}
    assert waves[0] == (256, 256)
    live, computed = before
    assert live == 8 * (192 + 3) and live > computed / 2
    since = eng.metrics.snapshot(since=mark)
    assert since["wave_rows_live"] == live
    assert since["wave_rows_computed"] == computed
    assert since["chunks_deferred"] == eng.metrics.chunks_deferred > 0
    counters = telemetry.snapshot()["counters"]
    assert counters["serve.wave.chunks_deferred"] == since["chunks_deferred"]
    assert counters["serve.wave.rows_computed"] == computed
    assert eng.metrics.snapshot(since=eng.metrics.mark())[
        "chunks_deferred"] == 0


def test_kv_write_pages_are_the_pages_whose_bytes_changed(gpt):
    """``serve.kv.write_pages``: what the host counts from a wave's
    ``pos`` and ``q_len`` is the number of pool blocks the wave's launch
    changed, wave by wave, in every wave with a q-block a page or more
    wide (packed or too small to pack), and scratch block 0 is not among
    them; a narrower wave (its rows go as rows, the dead ones to scratch
    block 0) counts nothing."""
    telemetry.reset()
    params, cfg = gpt
    # free slots: the engine never runs ahead, so a step lands the wave
    # before it and launches one
    eng = ServingEngine(params, cfg, slots=8, kv_block=16, prefill_chunk=64,
                        fast_path=False)
    waves = watch_waves(eng)
    for r in requests(61, SMALL_SIZES[:5]):
        eng.submit(r)

    def pools():
        return [np.asarray(c) for c in (eng.kv.cache_k, eng.kv.cache_v)]
    counts, changed, last, seen = [], [], pools(), 0
    before = 0
    while eng.pending:
        eng.step()
        now = pools()
        if eng._launched != seen:
            seen = eng._launched
            moved = [(a != b).any(axis=(0, 2, 3)) for a, b in zip(now, last)]
            assert (moved[0] == moved[1]).all()
            assert not (moved[0][0] and eng._flying.wave["q"] >= 16)
            changed.append(int(moved[0].sum()))
        last = now
        if len(waves) > len(counts):   # a wave landed in this step
            count = eng.metrics.snapshot()["kv_write_pages"]
            counts.append(count - before)
            before = count
    assert len(counts) == len(changed) == len(waves) == eng.steps
    wide = [Q >= 16 for _, Q, _ in waves]
    assert any(wide) and not all(wide)
    for count, pages, (q_len, Q, _), w in zip(counts, changed, waves, wide):
        assert count == (pages if w else 0)
        # a page holds at most 16 of the wave's rows, a slot's run of
        # rows at most two pages more than its rows fill
        if w:
            assert q_len.sum() / 16 <= pages <= q_len.sum() // 16 \
                + 2 * (q_len > 0).sum()
    snap = eng.metrics.snapshot()
    assert snap["kv_write_pages"] == sum(counts)
    assert snap["kv_write_rows"] == sum(
        int(q_len.sum()) for (q_len, _, _), w in zip(waves, wide) if w)
    counters = telemetry.snapshot()["counters"]
    assert counters["serve.kv.write_pages"] == snap["kv_write_pages"]
    assert counters["serve.kv.write_rows"] == snap["kv_write_rows"]
    telemetry.reset()


def test_capacity_router_waves_stay_padded_and_defer_nothing():
    """A ``MoESpec`` sizes its experts' slots from the rows it is
    handed: its engine keeps the padded wave and its schedule."""
    from hetu_tpu.models.moe_decode import (MoEDecodeConfig,
                                            init_moe_params)
    cfg = MoEDecodeConfig(vocab_size=61, hidden_size=16, num_hidden_layers=2,
                          num_attention_heads=2, max_position_embeddings=256,
                          batch_size=1, seq_len=256, dropout_rate=0.0,
                          num_experts=4, top_k=2, capacity_factor=4.0)
    params = init_moe_params(cfg, name="moe", seed=0)
    eng = ServingEngine(params, cfg, slots=8, kv_block=16,
                        prefill_chunk=64, fast_path=False)
    assert gd.wave_rows(eng.cfg_tuple, 8, 1, 64) == 512
    out = eng.run(requests(61, [(192, 3)] * 8))
    assert len(out) == 8
    snap = eng.metrics.snapshot()
    assert snap["chunks_deferred"] == 0
    assert snap["wave_rows_computed"] >= 3 * 512


def test_one_program_a_bucket_after_a_burst_of_32():
    """Warm-up as the benchmark's runner warms up (one lone request a
    bucket, two tokens), then 32 prompts at once on 32 slots: the ramp
    of the RAG cell.  The jitted step holds what the warm-up built."""
    params, cfg = rand_gpt(name="pb", V=67, S=128)
    eng = ServingEngine(params, cfg, slots=32, kv_block=4,
                        prefill_chunk=16, fast_path=False,
                        prefix_share=False, queue_limit=64)
    assert gd.wave_rows(eng.cfg_tuple, 32, 1, 16) == 256 < 32 * 16
    programs = eng._mixed.func._cache_size
    before = programs()
    for n in (8, 16):
        eng.run([Request(((np.arange(n) + n) % 67).tolist(), 2)])
    assert programs() - before == 3      # (1, decode), (8, chunk), (16, ..)
    rng = np.random.RandomState(5)
    reqs = [Request(rng.randint(1, 67, 8 * rng.randint(1, 9)).tolist(),
                    int(rng.randint(2, 9)), request_id=f"p{i}")
            for i in range(32)]
    built = telemetry.counter("compile.programs")    # the program's watch
    built_before = built.get()
    out = eng.run(reqs)
    assert len(out) == 32 and eng.peak_live == 32
    assert built.get() == built_before
    assert programs() - before == 3
    assert eng.metrics.snapshot()["chunks_deferred"] > 0
    for r in out.values():
        n = r.prompt_len
        want = gd.generate_fast(params, cfg, [list(r.tokens[:n])],
                                len(r.tokens) - n)
        assert list(r.tokens) == [int(t) for t in np.asarray(want)[0]]
