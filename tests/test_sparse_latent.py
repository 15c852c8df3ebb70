"""Latent attention as an operator BY LAYER
(``sparse_latent.SparseLatentConfig``) on the mixed wave: full layers that
read the rows a learned indexer chose (latent rows and index keys in the
pool), window layers over a latent ring of their own width and head
count, a head-wise gate, the low-rank rescale, the expert layers holding
a SHARE of their experts; the engine's tokens through the paged pools
against ``reference_sparse_latent``'s full forward, float32 both sides on
the CPU.

Tolerance: 1e-4 of the logits' spread, absolute (``test_nemotron_h``'s:
both sides are float32 and differ in the order of their sums)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import ragged_attention as ra
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import index_decode as ix
from hetu_tpu.models import moe_decode as md
from hetu_tpu.models import reference_sparse_latent as ref
from hetu_tpu.models import sparse_latent as sl
from hetu_tpu.serving import Request, ServingEngine

TOL = 1e-4
NAME = "d3n"
PATTERN = ["full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention", "full_attention"]
HELD = (2, 2)            # experts [2, 4) of 8: the second quarter

SMALL = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=5, layer_types=PATTERN,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    swa_num_attention_heads=2, swa_num_key_value_heads=2,
    swa_q_lora_rank=32, swa_kv_lora_rank=32, swa_qk_nope_head_dim=16,
    swa_qk_rope_head_dim=8, swa_v_head_dim=8, swa_rope_theta=50000,
    sliding_window_size=9, index_n_heads=4, index_head_dim=16,
    index_topk=16, apply_mla_qkv_lora_rescale=True,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1,
    norm_topk_prob=True, first_k_dense_replace=1, rope_theta=80000000,
    rms_norm_eps=1e-5, max_position_embeddings=128, scoring_func="sigmoid",
    topk_method="noaux_tc", moe_layer_freq=1, hidden_act="silu",
    rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
    model_type="dots3_note")


def source(**kw):
    out = dict(SMALL, **kw)
    out["num_hidden_layers"] = len(out["layer_types"])
    return out


# (the full layers' ``index_topk`` 12 lies INSIDE the second chunk of 16:
# a slot's context passes it mid-chunk)
KINDS = {"full": source(layer_types=["full_attention"] * 2, index_topk=12),
         # (no indexer: the full layer reads everything, so that what is
         # new in this model is the window layers alone)
         "sliding": source(layer_types=["full_attention"]
                           + ["sliding_attention"] * 2, index_topk=0),
         "pattern": SMALL}


@pytest.fixture(scope="module")
def models():
    """kind -> (source keys, config, weights), built once."""
    done = {}

    def of(kind):
        if kind not in done:
            c = sl.SparseLatentConfig.from_hf(KINDS[kind], held_experts=HELD)
            done[kind] = (KINDS[kind], c, sl.init_sparse_latent_params(
                c, NAME, seed=3))
        return done[kind]
    return of


def engine(params, cfg, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("kv_block", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("fast_path", False)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=1):
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(sizes):
        eng.submit(Request(rng.integers(0, 96, n).astype(np.int32), m,
                           request_id=f"q{i}"))
    return eng.run()


def forward(params, src, seq, **kw):
    return ref.forward(params, src, seq, np.arange(len(seq)), NAME,
                       held=HELD, **kw)


def gap(params, src, result):
    """The widest (largest logit - served token's logit) over the
    answer's rows, in units of the logits' spread."""
    seq = np.asarray(result.tokens, np.int32)
    lg = forward(params, src, seq[:-1])[0]
    rows = lg[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max() / lg.std())


# ------------------------------------------------------------------ #
# the spec: latent operators by layer, and what each keeps
# ------------------------------------------------------------------ #

def test_block_spec_names_every_layers_operator(models):
    _, cfg, _ = models("pattern")
    blk = cfg.block_spec()
    gd.check_block_spec(blk, 5)
    assert [blk.op_kind(i) for i in range(5)] == [
        sl.LAYER_OPS[t] for t in PATTERN]
    assert [blk.holds(i, "pool") for i in range(5)] == [1, 0, 0, 0, 1]
    assert [blk.holds(i, "index") for i in range(5)] == [1, 0, 0, 0, 1]
    assert [blk.holds(i, "window") for i in range(5)] == [0, 1, 1, 1, 0]
    assert [blk.op_index(i) for i in range(5)] == [0, 0, 1, 2, 1]
    assert [blk.op_layers(5, w) for w in ("pool", "window", "index",
                                          "state")] == [2, 3, 2, 0]
    full, swa = blk.latent_of(0), blk.latent_of(1)
    assert (full.heads, full.kv_lora_rank, full.index.topk) == (0, 16, 16)
    assert (swa.heads, swa.kv_lora_rank, swa.index) == (2, 32, None)
    assert full.gate and swa.gate and full.rescale and swa.rescale
    assert full.index == gd.IndexSpec(4, 16, 16, 8)
    assert blk.window == 9 and blk.leading_dense == 1
    assert blk.ffn_kind(0) == "swiglu" and blk.ffn_kind(1) == "routed"
    # each operator rotates with its own theta
    assert blk.rope_of(0)[0] == gd.rope_frequencies(8, rope_theta=8e7)[0]
    assert blk.rope_of(1)[0] == gd.rope_frequencies(8, rope_theta=5e4)[0]
    rt = blk.routed
    assert (rt.num_experts, rt.top_k, rt.held_first, rt.held) == (8, 2, 2, 2)
    # the accepted latent spec answers as it did
    glm = md.LatentMoEConfig(**{k: v for k, v in SMALL.items() if k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok")}).block_spec()
    assert glm.latent_of(3) == glm.latent and not glm.holds(0, "index")
    assert glm.latent.index is None and not glm.latent.gate


@pytest.mark.parametrize("change", [
    dict(ops=("latent_attention",) * 4 + ("attention",)),
    dict(ops=("latent_attention",) * 5),          # a window and no layer
    dict(window=0),
    dict(attention="gqa", latent=None, kv_heads=2),
    dict(ops=("latent_attention",) * 4),
    dict(latent_by_op=(("window_latent_attention", gd.LatentSpec(
        32, 32, 16, 8, 8, heads=2, index=gd.IndexSpec(4, 16, 16, 8))),)),
    dict(latent_by_op=(("attention", gd.LatentSpec(32, 32, 16, 8, 8)),)),
    dict(rope_by_op=(("attention", (1.0,) * 4, 1.0),)),
    dict(ops=None),
], ids=["plain_attention_beside", "window_without_a_layer",
        "layer_without_a_window", "latent_ops_in_a_gqa_block", "four_of_five",
        "an_indexer_on_a_window_layer", "a_spec_for_no_layer",
        "a_rope_for_no_layer", "by_op_without_ops"])
def test_check_block_spec_refuses_from_the_tables(models, change):
    blk = models("pattern")[1].block_spec()._replace(**change)
    with pytest.raises(ValueError, match="cannot run") as e:
        gd.check_block_spec(blk, 5)
    # the message enumerates the operators from the table
    for op in gd.LATENT_OPERATORS:
        assert op in str(e.value)


@pytest.mark.parametrize("bad", [
    dict(n_group=2), dict(rope_scaling={"type": "yarn"}),
    dict(topk_method="greedy"), dict(scoring_func="softmax"),
    dict(attention_bias=True), dict(hidden_act="gelu"),
    dict(moe_layer_freq=2), dict(tie_word_embeddings=True),
    dict(attention_gate_type="elementwise"),
    dict(layer_types=["full_attention", "linear_attention"] * 2 + [
        "full_attention"]),
    dict(num_key_value_heads=2), dict(sliding_window_size=0),
    dict(swa_kv_lora_rank=None), dict(index_n_heads=0),
    dict(held_experts=(7, 2)), dict(vocab_rows=(90, 10)),
    dict(layer_types=["sliding_attention"] * 5),
], ids=lambda d: next(iter(d)))
def test_config_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError, match="SparseLatentConfig"):
        sl.SparseLatentConfig(**dict(SMALL, **bad))


def test_param_shapes_by_layer_kind_and_the_held_share(models):
    _, cfg, params = models("pattern")
    shapes = cfg.param_shapes(NAME)
    assert shapes[f"{NAME}_h0_attn_q_b_weight"] == (32, 4 * 16)
    assert shapes[f"{NAME}_h1_attn_q_b_weight"] == (32, 2 * 24)
    assert shapes[f"{NAME}_h1_attn_kv_a_weight"] == (64, 32 + 8)
    assert shapes[f"{NAME}_h0_attn_gate_weight"] == (64, 4)
    assert shapes[f"{NAME}_h1_attn_gate_weight"] == (64, 2)
    assert shapes[f"{NAME}_h4_attn_index_q_weight"] == (32, 4 * 16)
    assert f"{NAME}_h1_attn_index_q_weight" not in shapes
    assert shapes[f"{NAME}_h0_ffn_gate_weight"] == (64, 128)
    assert shapes[f"{NAME}_h1_moe_experts_up"] == (2, 64, 32)
    assert shapes[f"{NAME}_h1_moe_router_weight"] == (64, 8)
    sliced = sl.SparseLatentConfig.from_hf(SMALL, vocab_rows=(24, 48))
    assert sliced.vocab_size == 48 and sliced.published_vocab_size == 96
    assert sliced.param_shapes(NAME)[f"{NAME}_lm_head_weight"] == (64, 48)
    p16 = sl.init_sparse_latent_params(cfg, NAME, seed=1,
                                       dtype=jnp.bfloat16)
    for k, v in p16.items():
        assert v.dtype == (jnp.float32 if "_moe_router_" in k
                           else jnp.bfloat16), k
    assert set(params) == set(shapes)


# ------------------------------------------------------------------ #
# the wave against the reference: prefill in chunks, then decode
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("kind", [
    "full", pytest.param("sliding", marks=pytest.mark.slow), "pattern"])
def test_engine_serves_what_the_reference_computes(models, kind):
    src, cfg, params = models(kind)
    eng = engine(params, cfg)
    L = cfg.num_hidden_layers
    full = sum(t == "full_attention" for t in src["layer_types"])
    if full:
        assert eng.kv.cache_k.shape[0] == full
        if src["index_topk"]:
            assert eng.kv.cache_v.shape == eng.kv.cache_k.shape[:3] + (16,)
        else:
            assert eng.kv.cache_v is None
    if L - full:
        assert eng.kv.win_k.shape[0] == L - full and eng.kv.win_v is None
        assert eng.kv.win_k.shape[-1] == 128    # 32 + 8 in whole lanes
    # five requests on four slots, out of step: prompts of one to three
    # chunks and a rest, contexts to 56: past ``index_topk``, and the
    # ring of 8 blocks of 4 turns
    sizes = [(40, 6), (24, 8), (48, 4), (33, 5), (20, 6)]
    out = serve(eng, sizes)
    assert len(out) == 5
    for r in out.values():
        assert gap(params, src, r) <= TOL, r.request_id
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in sizes)
    K = src["index_topk"]
    if full and K:
        ctx = [c for n, m in sizes for c in range(1, n + m)]
        assert snap["sparse_rows"] == rows * full
        assert snap["sparse_rows_selecting"] == full * sum(
            c > K for c in ctx)
        assert snap["sparse_keys_in_sight"] == full * sum(ctx)
        assert snap["sparse_keys_read"] == full * sum(
            min(c, K) for c in ctx)
        assert snap["index_ctx_tokens"] == full * snap["attn_ctx_tokens"]
    else:
        assert snap["sparse_rows"] == 0
    if L - full:
        assert eng.kv.window_blocks_recycled > 0          # the ring turned
        assert 0 < snap["attn_window_ctx_tokens"] < snap["attn_ctx_tokens"]
    if cfg.first_k_dense_replace < L:
        routed = L - cfg.first_k_dense_replace
        assert snap["moe_assignments_routed"] == rows * 2 * routed
        assert 0 < snap["moe_assignments"] < snap["moe_assignments_routed"]


def test_a_packed_wave_serves_what_the_reference_computes(models):
    """Eight slots and chunks of 64: a chunk wave is packed into 256
    rows, the window kernel and the indexer's loop take the rows as they
    lie (kernels interpreted)."""
    src, cfg, params = models("pattern")
    eng = engine(params, cfg, slots=8, max_seq_len=128, prefill_chunk=64,
                 fast_path=True)
    out = serve(eng, [(100, 4), (24, 6), (70, 3), (33, 4), (90, 5),
                      (17, 3)])
    assert gd.wave_rows(eng.cfg_tuple, 8, 1, 64) == 256 < 8 * 64
    for r in out.values():
        assert gap(params, src, r) <= TOL, r.request_id


def test_a_full_engine_emits_what_each_request_alone_emits(models):
    _, cfg, params = models("pattern")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, n).astype(np.int32)
               for n in (37, 18, 50, 26)]
    together = engine(params, cfg)
    for i, p in enumerate(prompts):
        together.submit(Request(p, 6, request_id=f"r{i}"))
    out = together.run()
    for i, p in enumerate(prompts):
        alone = engine(params, cfg, slots=1)
        alone.submit(Request(p, 6, request_id="x"))
        assert list(alone.run()["x"].tokens) == list(out[f"r{i}"].tokens)


def test_the_engines_records_pass_the_trace_check(models, tmp_path):
    """``hetu_trace --check`` over the serve stream of this engine, and
    the new counters in ``snapshot(since=)`` and the registry."""
    import json
    from hetu_tpu.telemetry.trace import main as trace_main
    _, cfg, params = models("pattern")
    eng = engine(params, cfg)
    serve(eng, [(30, 3)])
    mark = eng.metrics.mark()
    serve(eng, [(20, 4), (40, 2)], seed=2)
    snap = eng.metrics.snapshot(since=mark)
    rows = (20 + 3) + (40 + 1)
    assert snap["sparse_rows"] == 2 * rows
    assert snap["sparse_keys_needed"] <= snap["sparse_keys_read"]
    assert snap["index_ctx_tokens"] > 0
    path = tmp_path / "serve.jsonl"
    path.write_text("".join(json.dumps(dict(e)) + "\n"
                            for e in eng.metrics.events))
    assert trace_main([str(path), "--check"]) == 0
    steps = [e for e in eng.metrics.events if e["event"] == "serve_step"]
    assert steps and all(e["window_ring"] == eng.kv.ring for e in steps)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_the_comparison_notices_each_control(models, control):
    src, _, params = models("pattern")
    seq = np.random.default_rng(8).integers(0, 96, 48).astype(np.int32)
    want = forward(params, src, seq)[0]
    got = forward(params, src, seq, control=control)[0]
    moved = np.abs(got - want).max() / want.std()
    assert moved > 100 * TOL, (control, moved)


def test_seeded_weights_leave_no_part_vanishing(models):
    src, _, params = models("pattern")
    stats = {}
    seq = np.random.default_rng(2).integers(0, 96, 48).astype(np.int32)
    _, margin, tie = forward(params, src, seq, stats=stats)
    assert [s["kind"] for s in stats["layers"]] == PATTERN
    for s in stats["layers"]:
        assert 0.02 < s["attention"] / s["residual"] < 2.0, s
        assert 0.02 < s["ffn"] / s["residual"] < 2.0, s
    assert 0.3 < stats["logits"] < 5.0
    # a row that sees no more than ``index_topk`` chose everything
    assert np.isinf(tie[:16]).all() and np.isfinite(tie[16:]).all()
    assert np.isfinite(margin).all()


# ------------------------------------------------------------------ #
# the shares add up
# ------------------------------------------------------------------ #

def test_the_shares_routed_parts_and_the_shared_expert_add_up():
    """Four shares of two experts each: their outputs summed, the shared
    expert counted once, are the layer that holds all eight."""
    whole = sl.SparseLatentConfig.from_hf(SMALL)
    params = sl.init_sparse_latent_params(whole, NAME, seed=5)
    us = f"{NAME}_h1"
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 64), jnp.float32)
    uncut = md.routed_ffn(params, us, x, whole.routed_spec())
    shared = gd.swiglu(x, params[f"{us}_moe_shared_gate_weight"],
                       params[f"{us}_moe_shared_up_weight"],
                       params[f"{us}_moe_shared_down_weight"])
    total = 0
    for first in range(0, 8, 2):
        part = sl.SparseLatentConfig.from_hf(SMALL,
                                             held_experts=(first, 2))
        held = dict(params, **{
            f"{us}_moe_experts_{k}":
            params[f"{us}_moe_experts_{k}"][first:first + 2]
            for k in ("gate", "up", "down")})
        stats = {}
        total = total + md.routed_ffn(held, us, x, part.routed_spec(),
                                      stats=stats) - shared
        assert int(stats["routed"]) == 24 * 2
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(uncut), atol=1e-5)


# ------------------------------------------------------------------ #
# the kernels against their masked references (interpreted)
# ------------------------------------------------------------------ #

def _latent_wave(rng, B, Q, H, W, T, bs, q_lens, lens):
    pool = jnp.asarray(rng.normal(size=(2, B * T + 1, bs, W)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * T).reshape(B, T), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Q, H, W)), jnp.float32)
    return q, pool, jnp.asarray(lens, jnp.int32), \
        jnp.asarray(q_lens, jnp.int32), tables


def test_window_latent_kernels_match_the_banded_reference():
    rng = np.random.default_rng(0)
    B, Q, H, W, T, bs = 4, 16, 2, 128, 16, 4
    q_lens, lens = [16, 1, 0, 9], [40, 33, 0, 9]
    q, pool, lens, q_lens, tables = _latent_wave(rng, B, Q, H, W, T, bs,
                                                 q_lens, lens)
    kw = dict(value_width=32, scale=0.25, layer=1, window=9)
    want = ra.ragged_paged_mla_reference(q, pool, lens, q_lens, tables, **kw)
    live = (np.arange(Q)[None, :] < np.asarray(q_lens)[:, None])
    got = ra.ragged_paged_mla(q, pool, lens, q_lens, tables,
                              interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    # ... and it is a band: the same wave without one differs
    full = ra.ragged_paged_mla_reference(q, pool, lens, q_lens, tables,
                                         **dict(kw, window=0))
    assert np.abs(np.asarray(full - want))[live].max() > 1e-2
    # the packed entry over the same rows as they lie
    rows = gd._Rows.of(q_lens, Q, 32)
    packed = ra.ragged_paged_mla_rows(
        rows.pack(q)[0], pool, lens, q_lens, rows.start, tables,
        interpret=True, **kw)
    np.testing.assert_allclose(
        np.asarray(rows.unpack(packed[None]))[live], np.asarray(want)[live],
        atol=2e-5)


def test_the_selected_rows_form_reads_what_the_mask_allows():
    """The packed rows kernel under ``allowed``: a dense walk of a slot's
    pages that admits, a query, the positions its mask marks (and what
    it would see anyway)."""
    rng = np.random.default_rng(2)
    B, Q, H, W, T, bs = 4, 16, 2, 128, 16, 4
    q_lens, lens = [16, 1, 0, 9], [40, 33, 0, 9]
    q, pool, lens, q_lens, tables = _latent_wave(rng, B, Q, H, W, T, bs,
                                                 q_lens, lens)
    rows = gd._Rows.of(q_lens, Q, 32)
    S = T * bs
    allowed = jnp.asarray(rng.random((32, S)) < 0.4, jnp.float32)
    kw = dict(value_width=32, scale=0.25, layer=1)
    got = ra.ragged_paged_mla_rows(
        rows.pack(q)[0], pool, lens, q_lens, rows.start, tables,
        interpret=True, allowed=allowed, **kw)
    # the oracle: the masked softmax over the gathered pages
    kvs = pool[1][tables].reshape(B, S, W)
    posq = (lens - q_lens)[:, None] + jnp.arange(Q)[None, :]
    ok = (jnp.arange(S)[None, None, :] <= posq[:, :, None]) \
        & (rows.unpack(allowed[None]) > 0.5)
    sc = jnp.einsum("bqhc,bsc->bqhs", q, kvs) * 0.25
    p = jax.nn.softmax(jnp.where(ok[:, :, None, :], sc, -1e30), axis=-1)
    p = jnp.where(ok.any(-1)[:, :, None, None], p, 0.0)
    want = jnp.einsum("bqhs,bsc->bqhc", p, kvs[..., :32])
    live = (np.arange(Q)[None, :] < np.asarray(q_lens)[:, None])
    np.testing.assert_allclose(
        np.asarray(rows.unpack(got[None]))[live], np.asarray(want)[live],
        atol=2e-5)


def test_the_mask_marks_the_topk_largest_a_row_sees():
    """``chosen_mask`` (bisection, no sort) marks exactly the ``topk``
    positions of largest score among those a row sees, ties by lower
    position (a stable sort's), all of them while it sees no more."""
    rng = np.random.default_rng(3)
    n, S, K = 12, 40, 7
    scores = rng.normal(size=(n, S)).astype(np.float32).round(1)  # ties
    scores[3] = 0.0                                # a row of one value
    at = rng.integers(0, S, n)
    at[0], at[1] = 3, K - 1                        # fewer than K in sight
    valid = np.ones(n, bool)
    valid[5] = False
    seen = (np.arange(S)[None, :] <= at[:, None]) & valid[:, None]
    allowed = np.asarray(ix.chosen_mask(
        (jnp.asarray(scores), jnp.asarray(seen)), K)) > 0.5
    for r in range(n):
        in_sight = np.nonzero(seen[r])[0]
        order = np.argsort(-scores[r, in_sight], kind="stable")
        want = set(in_sight[order[:K]].tolist())
        assert set(np.nonzero(allowed[r])[0].tolist()) == want, r
        assert len(want) == (min(at[r] + 1, K) if valid[r] else 0)


def test_a_decode_wave_reads_what_the_mask_allows():
    """A decode wave's rows as they lie, one a slot and unpacked
    (``start`` the slots' numbers), through the same selected-rows form:
    a row reads the positions its mask marks, a dead slot returns zeros,
    and what the mask leaves out moves nothing."""
    rng = np.random.default_rng(1)
    B, H, W, T, bs = 8, 4, 128, 16, 4
    q_lens = [1, 1, 0, 1, 1, 1, 0, 1]
    lens = [64, 1, 0, 7, 33, 3, 0, 12]
    q, pool, lens, q_lens, tables = _latent_wave(rng, B, 1, H, W, T, bs,
                                                 q_lens, lens)
    S = T * bs
    allowed = jnp.asarray(rng.random((B, S)) < 0.4, jnp.float32)
    # a row's own position is always among what it chose
    allowed = allowed.at[jnp.arange(B), jnp.maximum(lens - 1, 0)].set(1.0)
    kw = dict(value_width=32, scale=0.3, layer=1)
    got = ra.ragged_paged_mla_rows(q[:, 0], pool, lens, q_lens,
                                   jnp.arange(B), tables, interpret=True,
                                   allowed=allowed, **kw)
    kvs = pool[1][tables].reshape(B, S, W)
    ok = (jnp.arange(S)[None, :] < lens[:, None]) & (allowed > 0.5)
    sc = jnp.einsum("bhc,bsc->bhs", q[:, 0], kvs) * 0.3
    p = jax.nn.softmax(jnp.where(ok[:, None, :], sc, -1e30), axis=-1)
    want = jnp.einsum("bhs,bsc->bhc", p, kvs[..., :32])
    live = np.asarray(q_lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    assert not np.asarray(got)[~live].any()
    # what a row did not choose moves nothing
    hidden = np.nonzero(~np.asarray(ok[4]))[0]
    blk, off = np.asarray(tables)[4, hidden // bs], hidden % bs
    again = ra.ragged_paged_mla_rows(
        q[:, 0], pool.at[1, blk, off].set(9.0), lens, q_lens,
        jnp.arange(B), tables, interpret=True, allowed=allowed, **kw)
    np.testing.assert_allclose(np.asarray(again), np.asarray(got), atol=1e-6)


# ------------------------------------------------------------------ #
# the programs (tests/test_program_digests.py pins their text)
# ------------------------------------------------------------------ #

def sparse_latent_programs(sds, attn, qs=(1, 32), slots=4):
    """{name: lowered mixed step} of a small five-layer model (rows of
    whole lane tiles, so that the kernels lower for the chip)."""
    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    c = sl.SparseLatentConfig.from_hf(source(
        hidden_size=256, q_lora_rank=128, kv_lora_rank=64,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64,
        swa_q_lora_rank=128, swa_kv_lora_rank=128, swa_qk_nope_head_dim=64,
        swa_qk_rope_head_dim=64, swa_v_head_dim=64, index_head_dim=128,
        sliding_window_size=33, vocab_size=512,
        moe_intermediate_size=128), held_experts=HELD)
    blk = c.block_spec()
    p = {k: sds(s, jnp.float32 if "router" in k else jnp.bfloat16)
         for k, s in c.param_shapes(NAME).items()}
    pool = sds((2, N, BS, 128), jnp.bfloat16)
    keys = sds((2, N, BS, 128), jnp.bfloat16)
    ring = 6
    win = (sds((3, B * ring + 1, BS, 256), jnp.bfloat16), None)
    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"sparse_latent.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (NAME, 5, 4, 64, 128, blk), pool, keys, i32(B, T),
                i32(B), i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
                sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                attn=attn, window=1, has_fresh=fresh, win=win,
                ring=i32(B, ring))
    return out


def test_the_wave_traces_the_new_scopes_for_this_spec_alone():
    text = sparse_latent_programs(jax.ShapeDtypeStruct, "masked", qs=(32,))[
        "sparse_latent.Q32.fresh1"].as_text(debug_info=True)
    for scope in ("mla_index", "index_score", "index_topk", "index_write",
                  "mla_gate", "mla_qkv", "mla_absorb", "kv_write",
                  "attention", "attn_out", "moe_route", "moe_experts",
                  "moe_shared", "lm_head"):
        assert f"/{scope}" in text, scope
    from test_hybrid_moe import wave_programs
    glm = wave_programs(jax.ShapeDtypeStruct, "masked")
    for name, low in glm.items():
        t = low.as_text(debug_info=True)
        for scope in ("/mla_index", "/index_score", "/index_topk",
                      "/index_write", "/mla_gate"):
            assert scope not in t, (name, scope)
