"""The ``cohere2_moe`` decoder (``ParallelMoEConfig``: a PARALLEL block on
one bias-free LayerNorm, sliding layers rotated over a ring, full layers
that rotate nothing, a bias-free sigmoid router over a held share of the
experts beside averaged shared experts, a tied head over held rows) on
the serving path, at a small size on the CPU (ISSUE 55): hidden 48, 8
query heads of 16 over 2 K/V heads, window 9, 8 experts top-3 of which 2
are held, 4 shared experts, 257 of 512 table rows held, paged block 4
and chunks of 8, so that the ring (6 blocks) turns after 24 positions.
Every comparison is of LOGITS against the plain reference's full forward
(``models/reference_parallel_moe.py``) on the PUBLISHED layout, never of
tokens alone.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.kernels import ragged_attention as ra
from hetu_tpu.kv_layout import kv_row_width
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_parallel_moe as ref
from hetu_tpu.models.moe_decode import RoutedSpec, route, routed_ffn
from hetu_tpu.models.parallel_moe import (
    ParallelMoEConfig, init_parallel_moe_params)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager

from jitted import mixed_wave, reference  # noqa: E402
from test_window_moe import banded_reference  # noqa: E402

NAME = "cmd"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
SMALL = dict(
    vocab_size=512, hidden_size=48, num_hidden_layers=4, head_dim=16,
    num_attention_heads=8, num_key_value_heads=2, layer_types=PERIOD,
    intermediate_size=32, num_experts=8, num_experts_per_tok=3,
    num_shared_experts=4, sliding_window=9, rope_theta=50000,
    rope_parameters={"rope_theta": 50000, "rope_type": "default"},
    layer_norm_eps=1e-5, logit_scale=1, norm_topk_prob=True,
    first_k_dense_replace=0, use_qk_norm=False, rotary_pct=1,
    shared_expert_combination_strategy="average", use_parallel_block=True,
    attention_bias=False, expert_selection_fn="sigmoid", hidden_act="silu",
    use_gated_activation=True, position_embedding_type="rope_gptj",
    tie_word_embeddings=True, max_position_embeddings=256,
    model_type="cohere2_moe")
HELD, ROWS = (0, 2), (0, 257)
# float32 weights and float32 pools on both sides: what is left is the
# order of the sums: 1e-5 of logits whose standard deviation is 1
TOL = 2e-4
SIZES = [(5, 6), (12, 9), (30, 5), (61, 20), (21, 7), (90, 12)]
# the layer patterns served: the period, a full layer alone, a sliding
# layer beside a full one
PATTERNS = {"period": PERIOD, "full_alone": ["full_attention"],
            "sliding_beside_full": ["sliding_attention", "full_attention"]}


def small(pattern="period"):
    kinds = PATTERNS[pattern]
    return dict(SMALL, layer_types=kinds, num_hidden_layers=len(kinds))


@functools.lru_cache(maxsize=None)
def built(pattern="period"):
    """(config dict, config object, published params, served params)."""
    conf = small(pattern)
    cfg = ParallelMoEConfig.from_hf(conf, held_experts=HELD,
                                    vocab_rows=ROWS)
    pub = init_parallel_moe_params(cfg, name=NAME, seed=3)
    return conf, cfg, pub, cfg.permute_rotary(pub, NAME)


def engine(pattern="period", **kw):
    _, cfg, _, params = built(pattern)
    kw = dict(dict(slots=4, max_seq_len=128, kv_block=4, prefill_chunk=8,
                   fast_path=False), **kw)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 257, n).astype(np.int32), m,
                    request_id=f"r{i}") for i, (n, m) in enumerate(sizes)]
    return eng.run(reqs)


def gap(result, pattern="period", wrong=()):
    """The widest gap between a row's largest reference logit and the
    reference logit of the token the engine chose."""
    conf, _, pub, _ = built(pattern)
    seq = np.asarray(result.tokens, np.int32)
    forward = ref.forward if wrong else functools.partial(reference,
                                                          ref.forward)
    lg, _ = forward(pub, conf, seq[:-1], name=NAME, held=HELD, wrong=wrong)
    rows = np.asarray(lg)[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max())


@pytest.fixture(scope="module")
def served():
    """Six requests on four slots through the masked path: prompts
    below (5), past (12) and several rings past (61, 90) the window."""
    eng = engine()
    return eng, serve(eng, SIZES)


# ------------------------------------------------------------------ #
# the config class and the block spec
# ------------------------------------------------------------------ #

def test_config_reads_the_sources_keys():
    _, cfg, pub, _ = built()
    blk = cfg.block_spec()
    assert blk.ops == ("window_attention",) * 3 + ("attention",)
    assert (blk.norm, blk.residual, blk.attention, blk.kv_heads, blk.bias,
            blk.qk_norm) == ("layernorm_nobias", "parallel", "gqa", 2,
                             False, False)
    assert (blk.window, blk.head_dim, blk.head, blk.mup) == (
        9, 16, "tied", None)
    assert blk.routed == RoutedSpec(8, 3, 1.0, True, 4, "sigmoid", 0, 2,
                                    shared_scale=0.25)
    # the sliding layers rotate, the full layers nothing
    inv, factor = blk.rope_of(0)
    assert len(inv) == 8 and factor == 1.0
    assert blk.rope_of(3) == ("none", 1.0)
    assert (blk.op_layers(4, "pool"), blk.op_layers(4, "window")) == (1, 3)
    gd.check_block_spec(blk, 4)
    hash(blk)                                  # jit-static
    assert cfg.vocab_size == 257 and cfg.published_vocab_size == 512
    assert cfg.n_routed_experts == 8 and cfg.held_experts == (0, 2)
    shapes = cfg.param_shapes(NAME)
    assert shapes["cmd_wte_table"] == (257, 48)
    assert shapes["cmd_h0_attn_q_weight"] == (48, 128)
    assert shapes["cmd_h0_moe_router_weight"] == (48, 8)
    assert shapes["cmd_h3_moe_experts_down"] == (2, 32, 48)
    assert shapes["cmd_h3_moe_shared_up_weight"] == (48, 128)
    # ONE norm a layer, no router bias, no head of its own
    assert not any(s in k for k in shapes
                   for s in ("ln2", "router_bias", "lm_head", "_bias"))
    assert set(pub) == set(shapes)
    assert pub["cmd_h0_moe_router_weight"].dtype == jnp.float32
    # a logit_scale other than 1 rides the multipliers' ``lm_head``
    scaled = ParallelMoEConfig.from_hf(dict(SMALL, logit_scale=0.25))
    assert scaled.block_spec().mup == gd.MuP(lm_head=0.25)
    # rope_theta inside rope_parameters alone
    moved = dict(SMALL, rope_parameters={"rope_theta": 777.0,
                                         "rope_type": "default"})
    del moved["rope_theta"]
    assert ParallelMoEConfig.from_hf(moved).rope_theta == 777.0


@pytest.mark.parametrize("change,message", [
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"shared_expert_combination_strategy": "sum"},
     "shared_expert_combination_strategy"),
    ({"use_parallel_block": False}, "use_parallel_block"),
    ({"attention_bias": True}, "attention_bias"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"layer_types": PERIOD[:3] + ["linear_attention"]},
     "linear_attention"),
    ({"num_hidden_layers": 5}, "sizes do not fit"),
])
def test_config_refuses_what_it_cannot_run(change, message):
    with pytest.raises(ValueError, match=message):
        ParallelMoEConfig.from_hf(dict(SMALL, **change))


@pytest.mark.parametrize("change", [
    {"residual": "both"},
    {"norm": "layernorm"},
    # a parallel block over a state operator, over no FFN, over GELU
    {"ops": ("conv", "attention"), "conv_kernel": 3, "window": 0},
    {"ffns": ("routed", "none")},
    {"ffns": ("routed", "gelu")},
    # the bias-free LayerNorm, the parallel form and "none" by operator
    # in a latent block
    {"attention": "latent"},
    {"routed": RoutedSpec(8, 3, shared_scale=0.0)},
    {"rope_by_op": (("attention", "none", 0.0),)}])
def test_check_block_spec_refuses_the_neighbours(change):
    blk = built()[1].block_spec()._replace(
        ops=("window_attention", "attention"))
    gd.check_block_spec(blk, 2)
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk._replace(**change), 2)
    # the message says the residual forms, the norms and none by operator
    with pytest.raises(
            ValueError,
            match="layernorm_nobias.*sequential, parallel.*none by operator"):
        gd.check_block_spec(blk._replace(residual="both"), 2)


def test_a_latent_block_takes_none_of_the_new_fields():
    from hetu_tpu.models.moe_decode import LatentMoEConfig
    from test_latent_moe import SMALL as LATENT
    blk = LatentMoEConfig.from_hf(LATENT).block_spec()
    gd.check_block_spec(blk, LATENT["num_hidden_layers"])
    for change in ({"residual": "parallel"}, {"norm": "layernorm_nobias"}):
        with pytest.raises(ValueError, match="cannot run"):
            gd.check_block_spec(blk._replace(**change))


# ------------------------------------------------------------------ #
# rotation: interleaved on the published layout against rotate-half on
# the permuted one
# ------------------------------------------------------------------ #

def test_interleaved_equals_rotate_half_under_the_permutation():
    _, cfg, pub, params = built()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(11, 48)), jnp.float32)
    pos = jnp.arange(11)[None]
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    with jax.default_matmul_precision("highest"):
        for leaf, heads in (("q", 8), ("k", 2)):
            key = f"cmd_h0_attn_{leaf}_weight"
            published = ref._rotate((x @ pub[key]).reshape(11, heads, 16),
                                    50000.0)
            served = gd._rope((x @ params[key]).reshape(1, 11, heads, 16),
                              pos, 50000.0)[0]
            # the served head is the published one's columns permuted
            np.testing.assert_allclose(served, published[..., perm],
                                       rtol=1e-5, atol=1e-5)
    # a full layer's leaves are the same arrays; the step is undone by
    # its inverse, leaf for leaf and bit for bit
    assert params["cmd_h3_attn_q_weight"] is pub["cmd_h3_attn_q_weight"]
    assert params["cmd_h0_attn_v_weight"] is pub["cmd_h0_attn_v_weight"]
    back = cfg.permute_rotary(params, NAME, inverse=True)
    for key in pub:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(pub[key]))
    # halves rotated on the published layout are another function
    assert not np.allclose(
        ref._rotate(x.reshape(11, 3, 16), 50000.0),
        ref._rotate(x.reshape(11, 3, 16), 50000.0, halves=True), atol=1e-3)


# ------------------------------------------------------------------ #
# the bias-free sigmoid router and the averaged shared experts
# ------------------------------------------------------------------ #

def test_sigmoid_route_without_a_bias_chooses_by_its_scores():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(33, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(48, 8)), jnp.float32)
    spec = RoutedSpec(8, 3, scoring="sigmoid")
    sel, wt = route(x, w, None, spec)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ w))
    top = np.argsort(-s, axis=-1)[:, :3]
    assert (np.sort(np.asarray(sel), -1) == np.sort(top, -1)).all()
    want = np.take_along_axis(s, np.asarray(sel), -1)
    np.testing.assert_allclose(np.asarray(wt),
                               want / want.sum(-1, keepdims=True), rtol=1e-5)
    # a zero bias chooses the same; a bias that is not zero need not
    sel0, wt0 = route(x, w, jnp.zeros(8), spec)
    np.testing.assert_array_equal(np.asarray(sel0), np.asarray(sel))
    selb, _ = route(x, w, jnp.asarray(rng.normal(size=8), jnp.float32),
                    spec)
    assert (np.sort(np.asarray(selb), -1) != np.sort(top, -1)).any()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts that the eight shares give (one
    expert each here), with the averaged shared part counted ONCE, are
    the uncut reference's FFN of the layer."""
    conf = small("full_alone")
    whole = ParallelMoEConfig.from_hf(conf)
    pub = init_parallel_moe_params(whole, name=NAME, seed=5)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, 48)), jnp.float32)
    us = "cmd_h0"
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.ffn_parts(pub, conf, us, x)
        total = np.zeros((40, 48), np.float32)
        for share in range(8):
            spec = ParallelMoEConfig.from_hf(
                conf, held_experts=(share, 1)).routed_spec()
            assert spec.held == 1 and spec.held_first == share
            mine = dict(pub, **{
                f"{us}_moe_experts_{n}":
                    pub[f"{us}_moe_experts_{n}"][share:share + 1]
                for n in ("gate", "up", "down")})
            y = np.asarray(routed_ffn(mine, us, x, spec))
            # this share against the reference given the same share
            r, _, _ = ref.ffn_parts(mine, conf, us, x, held=(share, 1))
            np.testing.assert_allclose(y, np.asarray(r + shared), atol=1e-5)
            total += y - np.asarray(shared)
    np.testing.assert_allclose(total + np.asarray(shared),
                               np.asarray(routed + shared), atol=2e-5)
    # the average is a quarter of the widened expert, not the sum
    summed = ref.ffn_parts(pub, conf, us, x, wrong=("shared_sum",))[1]
    np.testing.assert_allclose(np.asarray(summed), 4 * np.asarray(shared),
                               rtol=1e-5)


# ------------------------------------------------------------------ #
# the kernels at sixteen query heads a K/V head
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("window", [0, 40], ids=["full", "window40"])
def test_rows_kernel_at_group_sixteen(monkeypatch, window):
    """Interpret mode, float32, one bucket each: 128 packed rows of 32
    query heads over 2 K/V heads (group 16), four slots of unlike length
    (a chunk beside decoding rows, one slot dead), against the banded
    oracle."""
    from test_ragged_kernel import _rows_wave
    H, G, Q, R = 32, 16, 64, 128
    monkeypatch.setattr(ra, "_MAX_ROWS", 16 * H)
    q_lens, lens = (40, 1, 0, 9), (300, 41, 0, 290)
    q, pk, pv, lens, q_lens, tables, layer = _rows_wave(
        H, Q, lens, q_lens, groups=G, garbage=3.0)
    start = np.cumsum(q_lens) - q_lens
    packed = np.full((R, H, 64), 5.0, np.float32)
    for b, n in enumerate(q_lens):
        packed[start[b]:start[b] + n] = np.asarray(q)[b, :n]
    got = np.asarray(ra.ragged_paged_attention_rows(
        jnp.asarray(packed), pk, pv, lens, q_lens, start, tables,
        layer=layer, groups=G, window=window, interpret=True))
    want = np.asarray(banded_reference(q, pk, pv, lens, q_lens, tables,
                                       layer, G, window))
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(
            got[start[b]:start[b] + n].reshape(n, H, 64), want[b, :n],
            atol=2e-5, rtol=2e-5)
    assert not got[q_lens.sum():].any()


def test_tiling_at_the_cells_widths():
    """128 query heads over 8 K/V heads of 128: a row tile is 16 packed
    queries (``_MAX_ROWS`` 2,048 (head, query) rows), one height; a pool
    row is 1,024 lanes."""
    bf16 = jnp.bfloat16
    assert ra.rows_packed_tiling(1024, 128, 128, 16, bf16) == (1024, 16, 0)
    assert ra.rows_tiling(256, 128, bf16) == (256, 16)
    assert ra.rows_tiling(1, 128, bf16) == (16, 16)
    assert kv_row_width(8, 128) == 1024


# ------------------------------------------------------------------ #
# engine through both pools against the reference's full forward
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("pattern,fast", [
    ("period", False), ("period", True), ("full_alone", False),
    ("sliding_beside_full", False)])
def test_engine_matches_reference(pattern, fast, served):
    """Chunked prefill then decode, six requests on four slots (two take
    a slot another has used), prompts below, past and several rings past
    the window, so chunk waves and decode waves of slots of unlike
    length share a wave."""
    if pattern == "period" and not fast:
        eng, out = served
    else:
        eng = engine(pattern, fast_path=fast)
        # the kernel interpreted: three requests, one several rings long
        out = serve(eng, SIZES[1:4] if fast else SIZES)
    sliding = PATTERNS[pattern].count("sliding_attention")
    assert eng.kv.cache_k.shape == (1, eng.kv.n_blocks, 4, 128)
    if sliding:
        # a ring of ceil((9 + 8) / 4) + 1 = 6 blocks a slot + scratch
        assert eng.kv.win_k.shape == (sliding, 4 * 6 + 1, 4, 128)
        assert eng.kv.ring == 6 and not eng.kv.prefix_share
        assert eng.kv.window_blocks_recycled > 0       # the ring turned
        assert eng.kv.free_window_blocks == 4 * 6      # all returned
        assert eng.kv.free_blocks == eng.kv.capacity_blocks
    else:
        assert not eng.kv.window_layers
    for r in out.values():
        assert gap(r, pattern) <= TOL, r.request_id


def test_engine_logits_match_reference_row_for_row():
    """The wave's own logits, every row of chunks and decode steps of TWO
    slots of unlike length in one wave, against the reference's: not only
    the chosen token's.  Slot 0 runs 50 positions (the ring turns twice),
    slot 1 starts later and stays near the window."""
    conf, cfg, pub, params = built()
    cfg_tuple = (NAME, 4, 8, 16, 64, cfg.block_spec())
    kv = PagedKVManager(layers=1, heads=2, head_dim=16, slots=2,
                        max_seq_len=64, dtype=jnp.float32, block=4,
                        window_layers=3, window=9, window_chunk=8)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 257, 50).astype(np.int32),
            rng.integers(0, 257, 11).astype(np.int32)]
    slots = [kv.alloc("a", seqs[0], 64)[0], kv.alloc("b", seqs[1], 64)[0]]
    want = [np.asarray(reference(ref.forward, pub, conf, s, name=NAME,
                                 held=HELD)[0]) for s in seqs]
    got = [[], []]
    plan = [((0, 8), None), ((8, 8), None), ((16, 8), (0, 8)),
            ((24, 8), (8, 3)), ((32, 8), None), ((40, 8), None),
            ((48, 1), None), ((49, 1), None)]
    ck, cv, win = kv.cache_k, kv.cache_v, (kv.win_k, kv.win_v)
    for wave in plan:
        Q = max(n for w in wave if w for _, n in [w])
        tokens = np.zeros((2, Q), np.int32)
        pos = np.zeros(2, np.int32)
        q_len = np.zeros(2, np.int32)
        for who, w in enumerate(wave):
            if w:
                off, n = w
                tokens[slots[who], :n] = seqs[who][off:off + n]
                pos[slots[who]], q_len[slots[who]] = off, n
        logits, ck, cv, _, win = mixed_wave(
            params, cfg_tuple, ck, cv, pos, tokens, q_len,
            np.zeros(2, np.int32), np.zeros(2, bool), window=Q,
            block_tables=kv.tables.copy(), has_fresh=Q > 1, win=win,
            ring=kv.win_tables.copy())
        for who, w in enumerate(wave):
            if w:
                got[who].append(np.asarray(logits[slots[who], :w[1]]))
    for who in range(2):
        np.testing.assert_allclose(np.concatenate(got[who]), want[who],
                                   rtol=0, atol=TOL)


def test_a_full_engine_emits_what_each_request_alone_emits(served):
    """Batch company changes no request's tokens: each of the six, served
    alone on a fresh engine, emits what it emitted among the others."""
    _, out = served
    rng = np.random.default_rng(0)
    eng = engine(slots=1)
    for i, (n, m) in enumerate(SIZES):
        prompt = rng.integers(0, 257, n).astype(np.int32)
        if i not in (1, 3, 5):            # the rng is drawn for all six
            continue
        alone = eng.run([Request(prompt, m, request_id="alone")])["alone"]
        assert list(alone.tokens) == list(out[f"r{i}"].tokens)


# each fault computed on the REFERENCE's side against the sound engine's
# tokens: the comparison must notice every one
@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_comparison_notices(served, wrong):
    _, out = served
    assert max(gap(r) for r in out.values()) <= TOL
    # the faults must show in the two longest alone (several rings past
    # the window)
    faulty = max(gap(out[r], wrong=(wrong,)) for r in ("r3", "r5"))
    assert faulty > 100 * TOL, (wrong, faulty)


def test_reference_refuses_an_unknown_fault():
    conf, _, pub, _ = built()
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(pub, conf, np.arange(4), name=NAME, wrong=("nothing",))


# ------------------------------------------------------------------ #
# counters and scopes
# ------------------------------------------------------------------ #

def test_the_bound_rows_counter(served):
    """``attn_window_bound_rows``: the live rows at a position of the
    window (9) or more, a wave's rows counted once: of a request of n
    prompt and m answer positions, the inputs at positions 9 .. n + m -
    2."""
    eng, _ = served
    snap = eng.metrics.snapshot()
    want = sum(max(n + m - 1 - 9, 0) for n, m in SIZES)
    assert snap["attn_window_bound_rows"] == want
    assert 0 < snap["attn_window_bound_rows"] < snap["wave_rows_live"]
    from hetu_tpu import telemetry
    assert telemetry.counter("serve.attn.window_bound_rows").get() >= want
    # an engine without window layers counts none
    eng = engine("full_alone")
    serve(eng, SIZES[:2])
    assert eng.metrics.snapshot()["attn_window_bound_rows"] == 0


def test_the_engines_records_pass_the_trace_check(served, tmp_path):
    """``hetu_trace --check`` over the serve stream of the new engine;
    every step states the ring."""
    import json
    from hetu_tpu.telemetry.trace import main as trace_main
    eng, _ = served
    path = tmp_path / "serve.jsonl"
    path.write_text("".join(json.dumps(dict(e)) + "\n"
                            for e in eng.metrics.events))
    assert trace_main([str(path), "--check"]) == 0
    steps = [e for e in eng.metrics.events if e["event"] == "serve_step"]
    assert steps and all(e["window_ring"] == eng.kv.ring for e in steps)


def test_the_parallel_layer_is_one_norm_under_its_scope():
    """The lowered chunk wave names ``par_norm`` once a layer and no
    ``ln2``; a sequential spec's text has no such scope (the new fields'
    defaults trace nothing: ``tests/test_program_digests.py`` holds the
    accepted programs byte for byte)."""
    conf, cfg, _, params = built("sliding_beside_full")
    kv = PagedKVManager(layers=1, heads=2, head_dim=16, slots=2,
                        max_seq_len=64, dtype=jnp.float32, block=4,
                        window_layers=1, window=9, window_chunk=8)
    i32 = lambda *s: np.zeros(s, np.int32)              # noqa: E731
    text = mixed_wave.lower(
        params, (NAME, 2, 8, 16, 64, cfg.block_spec()), kv.cache_k,
        kv.cache_v, i32(2), i32(2, 8), i32(2), i32(2), np.zeros(2, bool),
        window=1, block_tables=kv.tables.copy(), has_fresh=True,
        win=(kv.win_k, kv.win_v), ring=kv.win_tables.copy()).as_text(
            debug_info=True)
    assert text.count("par_norm") > 0 and "moe_shared" in text
    assert not any("ln2" in k for k in params)


# ------------------------------------------------------------------ #
# the programs ``tests/test_program_digests.py`` pins
# ------------------------------------------------------------------ #

def parallel_moe_programs(sds, attn, qs=(1, 32), slots=4):
    """{name: lowered mixed step} of a small period of four (32 query
    heads of 64 over 2: group 16, pool rows of one lane tile, so that the
    kernels lower for the chip; 2 of 8 experts held).  16 slots x 32 rows
    are packed into 256 rows (the rows kernel's packed entry)."""
    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    c = ParallelMoEConfig.from_hf(dict(
        SMALL, hidden_size=256, num_attention_heads=32, head_dim=64,
        intermediate_size=128, sliding_window=33), held_experts=HELD,
        vocab_rows=(0, 512))
    p = {k: sds(s, jnp.float32 if "router" in k else jnp.bfloat16)
         for k, s in c.param_shapes(NAME).items()}
    pool = sds((1, N, BS, 128), jnp.bfloat16)
    ring = 6
    win = (sds((3, B * ring + 1, BS, 128), jnp.bfloat16),) * 2
    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"parallel_moe.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (NAME, 4, 32, 64, 128, c.block_spec()), pool, pool,
                i32(B, T), i32(B), i32(B, Q), i32(B), i32(B),
                sds((B,), jnp.bool_), sds((B,), jnp.float32), i32(B),
                sds((B, 2), jnp.uint32), attn=attn, window=1,
                has_fresh=fresh, win=win, ring=i32(B, ring))
    return out
