"""The hybrid decoder (gated short convolutions and grouped-query
attention layer by layer over a routed FFN: the ``lfm2_moe`` family) on
the serving path, at a small size on the CPU (ISSUE 34): hidden 64, 8
query heads over 2 K/V heads, conv of 3 taps, 8 experts top-2, 2 dense +
4 routed layers in the order c c A c A c, vocabulary 257.  Every
comparison is of LOGITS against the plain reference's full forward
(``models/reference_hybrid_moe.py``), never of tokens alone.
"""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import hf
from hetu_tpu.kernels import ragged_attention as ra
from hetu_tpu.kv_layout import kv_row_width, kv_rows
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_hybrid_moe as ref
from hetu_tpu.models.moe_decode import (
    HybridMoEConfig, LatentMoEConfig, init_hybrid_moe_params)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager
from hetu_tpu.serving.kv_tiers import TieredKVStore

from jitted import mixed_wave, reference

SMALL = dict(
    vocab_size=257, hidden_size=64, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=2,
    layer_types=["conv", "conv", "full_attention", "conv",
                 "full_attention", "conv"],
    conv_L_cache=3, conv_bias=False, intermediate_size=96,
    moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
    num_dense_layers=2, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1.0, rope_theta=1e6, norm_eps=1e-5,
    max_position_embeddings=256, model_type="lfm2_moe")
# float32 weights, a float32 pool and a float32 state on both sides: what
# is left is the order of the sums (grouped against dense expert matmuls,
# online against whole softmax, the conv a chunk at a time): 1e-5 of
# logits whose standard deviation is 1.6
TOL = 2e-4


@pytest.fixture(scope="module")
def cfg():
    return HybridMoEConfig.from_hf(SMALL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_hybrid_moe_params(cfg, seed=3, scale=0.2)


def engine(params, cfg, **kw):
    kw = dict(dict(slots=4, max_seq_len=64, kv_block=4, prefill_chunk=8,
                   fast_path=False), **kw)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 257, n).astype(np.int32), m,
                    request_id=f"r{i}") for i, (n, m) in enumerate(sizes)]
    return eng.run(reqs)


def gap(params, cfg, result, omit=()):
    """The widest gap between a row's largest reference logit and the
    reference logit of the token the engine chose."""
    seq = np.asarray(result.tokens, np.int32)
    lg, _ = reference(ref.forward, params, cfg, seq[:-1], omit=omit)
    rows = np.asarray(lg)[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max())


# ------------------------------------------------------------------ #
# the config class
# ------------------------------------------------------------------ #

def test_config_reads_the_sources_keys(cfg):
    blk = cfg.block_spec()
    assert blk.ops == ("conv", "conv", "attention", "conv", "attention",
                       "conv")
    assert (blk.attention, blk.kv_heads, blk.qk_norm, blk.bias) == (
        "gqa", 2, True, False)
    assert (blk.conv_kernel, blk.leading_dense, blk.head) == (3, 2, "tied")
    assert blk.routed[:6] == (8, 2, 1.0, True, 0, "sigmoid")
    assert not blk.routed.holds_a_share and blk.routed.latent == 0
    assert [blk.op_index(i) for i in range(6)] == [0, 1, 0, 2, 1, 3]
    assert blk.op_layers(6, "conv") == 4 and blk.routed_layers(6) == 4
    gd.check_block_spec(blk, 6)
    hash(blk)                                  # jit-static
    # newer exports keep the theta inside rope_parameters
    moved = dict(SMALL, rope_parameters={"rope_theta": 5e5})
    del moved["rope_theta"]
    assert HybridMoEConfig.from_hf(moved).rope_theta == 5e5
    shapes = cfg.param_shapes("lfm")
    assert shapes["lfm_h0_conv_in_weight"] == (64, 192)
    assert shapes["lfm_h0_conv_weight"] == (3, 64)
    assert shapes["lfm_h2_attn_k_weight"] == (64, 16)
    assert shapes["lfm_h2_attn_q_norm_scale"] == (8,)
    assert "lfm_h1_ffn_gate_weight" in shapes
    assert shapes["lfm_h2_moe_experts_down"] == (8, 48, 64)
    assert "lfm_lm_head_weight" not in shapes


@pytest.mark.parametrize("change,message", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv"] * 5 + ["sliding_attention"]},
     "sliding_attention"),
    ({"layer_types": ["conv"] * 5}, "5 layer_types"),
    ({"num_key_value_heads": 3}, "do not divide"),
    ({"conv_L_cache": 1}, "at least 2 taps"),
    ({"num_experts_per_tok": 9}, "outside"),
])
def test_config_refuses_what_it_cannot_run(change, message):
    with pytest.raises(ValueError, match=message):
        HybridMoEConfig.from_hf(dict(SMALL, **change))


def test_no_expert_bias_is_a_zero_bias():
    c = HybridMoEConfig.from_hf(dict(SMALL, use_expert_bias=False))
    p = init_hybrid_moe_params(c, seed=1)
    assert not np.asarray(p["lfm_h2_moe_router_bias"]).any()
    assert np.asarray(p["lfm_h2_moe_router_weight"]).any()


@pytest.mark.parametrize("change", [
    {"ops": ("conv", "attention"), "conv_kernel": 1},
    {"ops": ("conv", "window")}, {"bias": True}, {"norm": "layernorm"},
    {"attention": "mha"}, {"kv_heads": 0}])
def test_check_block_spec_refuses(cfg, change):
    blk = cfg.block_spec()._replace(ops=("conv", "attention"))
    gd.check_block_spec(blk, 2)
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk._replace(**change), 2)
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk, 3)            # ops name 2 layers


# ------------------------------------------------------------------ #
# engine through pool and state against the reference's full forward
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_engine_through_pool_and_state_matches_reference(params, cfg, fast):
    """Chunked prefill (30 tokens in chunks of 8: four q-blocks carry the
    conv state), then decode; five requests on four slots, so one takes a
    slot another has used."""
    eng = engine(params, cfg, fast_path=fast)
    # the pool holds the 2 attention layers alone, rows of 2 K/V heads
    # of 8 padded to the 128 lanes; the state the 4 conv layers
    assert eng.kv.cache_k.shape == (2, eng.kv.n_blocks, 4, 128)
    assert eng.kv.cache_v.shape == eng.kv.cache_k.shape
    assert eng.kv.state.shape == (4, 4, 2, 64)
    assert eng.kv.stateful and not eng.kv.prefix_share
    out = serve(eng, [(19, 6), (7, 9), (30, 5), (3, 4), (21, 7)])
    assert eng.prefill_chunks >= 12
    for r in out.values():
        assert gap(params, cfg, r) <= TOL, r.request_id
    assert eng.kv.state_resets == 5
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in [(19, 6), (7, 9), (30, 5), (3, 4),
                                      (21, 7)])
    assert snap["moe_assignments"] == rows * 2 * 4 == sum(snap["moe_load"])
    assert snap["wave_rows_live"] == rows
    # rows computed: a chunk wave's ``wave_rows`` (at 4 slots x q 8 the
    # padded block's 32: tests/test_packed_wave.py has the engines whose
    # chunk waves pack), a decode wave's 4
    assert rows <= snap["wave_rows_computed"] <= 32 * eng.steps
    assert snap["wave_rows_computed"] < 2.5 * rows
    assert snap["chunks_deferred"] == 0
    assert snap["attn_ctx_tokens"] > 0 and snap["attn_score_pairs"] > 0
    assert eng.kv.free_blocks == eng.kv.capacity_blocks   # all released
    assert eng.kv.stats()["state_bytes"] == 4 * 4 * 2 * 64 * 4


def test_engine_logits_match_reference_row_for_row(params, cfg):
    """The wave's own logits, every row of three chunks and a decode
    step, against the reference's: not only the chosen token's."""
    blk = cfg.block_spec()
    cfg_tuple = ("lfm", 6, 8, 8, 64, blk)
    kv = PagedKVManager(layers=2, heads=2, head_dim=8, slots=2,
                        max_seq_len=64, dtype=jnp.float32, block=4,
                        state_shape=(4, 2, 64))
    seq = np.random.default_rng(5).integers(0, 257, 23).astype(np.int32)
    slot, _ = kv.alloc("a", seq, 32)
    want, _ = ref.forward(params, cfg, seq)
    ck, cv, state = kv.cache_k, kv.cache_v, kv.state
    got = []
    for off, n in ((0, 8), (8, 8), (16, 6), (22, 1)):
        tokens = np.zeros((2, 8 if n > 1 else 1), np.int32)
        tokens[slot, :n] = seq[off:off + n]
        pos = np.zeros(2, np.int32)
        q_len = np.zeros(2, np.int32)
        pos[slot], q_len[slot] = off, n
        logits, ck, cv, state = mixed_wave(
            params, cfg_tuple, ck, cv, pos, tokens, q_len,
            np.zeros(2, np.int32), np.zeros(2, bool),
            window=tokens.shape[1], block_tables=jnp.asarray(kv.tables),
            state=state)
        got.append(np.asarray(logits)[slot, :n])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                               atol=TOL)


# ------------------------------------------------------------------ #
# the conv operator alone
# ------------------------------------------------------------------ #

def conv_reference(params, cfg, us, h):
    """One conv layer of the reference over whole sequences [B, S, d]."""
    K = cfg.conv_L_cache
    u = gd._rms(h, params[f"{us}_ln1_scale"], cfg.norm_eps)
    b, c, x = jnp.split(u @ params[f"{us}_conv_in_weight"], 3, -1)
    z = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    S = h.shape[1]
    y = sum(params[f"{us}_conv_weight"][j] * z[:, j:j + S] for j in range(K))
    return h + (c * y) @ params[f"{us}_conv_out_weight"]


def test_conv_operator_across_chunks_dead_rows_and_a_reused_slot(params,
                                                                 cfg):
    blk = cfg.block_spec()
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(3, 11, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = conv_reference(params, cfg, "lfm_h1", h)
        state = jnp.zeros((4, 3, 2, 64), jnp.float32)
        marker = state.at[0].set(7.0).at[2].set(-3.0)   # other layers'
        state = marker
        si = blk.op_index(1)
        assert si == 1
        # slot 0 in q-blocks of 4, 4, 3; slot 1 of 1, 4 (3 dead), 4, 2
        # (2 dead); slot 2 dead throughout
        plan = {0: [4, 4, 3, 0], 1: [1, 4, 4, 2], 2: [0, 0, 0, 0]}
        at = {0: 0, 1: 0, 2: 0}
        got = {0: [], 1: [], 2: []}
        for step in range(4):
            block = np.zeros((3, 4, 64), np.float32)
            q_len = np.array([plan[b][step] for b in range(3)], np.int32)
            for b in range(3):
                block[b, :q_len[b]] = h[b, at[b]:at[b] + q_len[b]]
                # a dead row holds anything
                block[b, q_len[b]:] = 99.0
            out, state = gd._conv_operator(
                params, "lfm_h1", blk, jnp.asarray(block), state, si,
                jnp.asarray(q_len))
            for b in range(3):
                got[b].append(np.asarray(out)[b, :q_len[b]])
                at[b] += int(q_len[b])
        for b in (0, 1):
            np.testing.assert_allclose(np.concatenate(got[b]),
                                       np.asarray(want)[b], atol=1e-5)
        # the dead slot's rows and the other layers' never moved
        assert not np.asarray(state)[si, 2].any()
        np.testing.assert_array_equal(np.asarray(state)[[0, 2, 3]],
                                      np.asarray(marker)[[0, 2, 3]])
        # a reused slot: the manager zeroes its rows, and the next
        # sequence's first rows see no history
        kv = PagedKVManager(layers=2, heads=2, head_dim=8, slots=4,
                            max_seq_len=16, dtype=jnp.float32, block=4,
                            state_shape=(4, 2, 64))
        kv.state = kv.state + 5.0
        slot, _ = kv.alloc("x", [1, 2, 3], 8)
        assert not np.asarray(kv.state)[:, slot].any()
        others = [s for s in range(4) if s != slot]
        assert (np.asarray(kv.state)[:, others] == 5.0).all()
        assert kv.state_resets == 1


# ------------------------------------------------------------------ #
# the grouped kernel, interpreted, against the gather-then-mask oracle
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("q_block", [1, 24])
def test_grouped_rows_kernel_matches_the_oracle(groups, q_block):
    """2 K/V heads of 64 (one lane chunk), ``groups`` query heads each;
    a chunk slot with a dead tail, a decode-like slot, a dead slot; a
    table of 40 pages so that the page loop runs three groups."""
    rng = np.random.default_rng(groups * 10 + q_block)
    B, Hkv, Dh, bs, T, L = 3, 2, 64, 4, 40, 2
    H = Hkv * groups
    N = B * T + 1
    W = kv_row_width(Hkv, Dh)
    pool = [jnp.asarray(rng.normal(size=(L, N, bs, Hkv, Dh)), jnp.float32)
            for _ in range(2)]
    rows = [kv_rows(p, W) for p in pool]
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(B, T), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, q_block, H, Dh)), jnp.float32)
    q_len = jnp.asarray([min(q_block, 17), 1, 0], jnp.int32)
    lens = jnp.asarray([150, 9, 0], jnp.int32)
    got = ra.ragged_paged_attention(q, rows[0], rows[1], lens, q_len, tables,
                                    layer=1, groups=groups, interpret=True)
    # the oracle reads [.., H, Dh] pools: every query head its own copy
    # of the K/V head it reads
    want = ra.ragged_paged_reference(
        q, jnp.repeat(pool[0][1], groups, axis=2),
        jnp.repeat(pool[1][1], groups, axis=2), lens, q_len, tables)
    assert got.shape == (B, q_block, H, Dh)
    for b in range(B):
        n = int(q_len[b])
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5)
    assert not np.asarray(got[2]).any()


def test_grouped_rows_layout_round_trips():
    q = jnp.arange(2 * 16 * 8 * 4, dtype=jnp.float32).reshape(2, 16, 8, 4)
    rows = ra._grouped_rows(q, 128, 8, 4)
    assert rows.shape == (2, 64, 128)
    # tile 1, member 2, query 3 of the tile: query head 2, 6 in K/V
    # heads 0, 1's lanes
    np.testing.assert_array_equal(rows[0, 32 + 16 + 3, :4], q[0, 11, 2])
    np.testing.assert_array_equal(rows[0, 32 + 16 + 3, 4:8], q[0, 11, 6])
    np.testing.assert_array_equal(ra._ungrouped_rows(rows, 8, 4, 8, 4), q)
    with pytest.raises(ValueError, match="not 3 a K/V head"):
        ra.ragged_paged_attention(q, None, None, None, None, None, groups=3)


# ------------------------------------------------------------------ #
# the comparison is tight: each omission fails it
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def answers(params, cfg):
    return serve(engine(params, cfg),
                 [(19, 24), (7, 30), (30, 20), (12, 28)], seed=11)


def test_reference_comparison_passes_whole(params, cfg, answers):
    assert max(gap(params, cfg, r) for r in answers.values()) <= TOL


@pytest.mark.parametrize("omit", ref.OMISSIONS)
def test_reference_comparison_fails_each_omission(params, cfg, answers,
                                                  omit):
    """Leaving out the conv taps' history, the per-head q/k norm, the
    selection bias or the last layer moves some chosen token's logit
    under its row's largest by far more than the tolerance."""
    worst = max(gap(params, cfg, r, omit=(omit,)) for r in answers.values())
    assert worst > 50 * TOL, (omit, worst)


def test_reference_refuses_an_unknown_omission(params, cfg):
    with pytest.raises(ValueError, match="unknown omissions"):
        ref.forward(params, cfg, [1, 2, 3], omit=("gate",))


# ------------------------------------------------------------------ #
# what the state makes wrong is refused by name
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("kw,message", [
    ({"prefix_share": True}, "prefix-cache hit"),
    ({"spec": 2}, "speculation"),
    ({"kv_quant": "int8"}, "int8 KV cache"),
])
def test_engine_refuses_by_name(params, cfg, kw, message):
    with pytest.raises(ValueError, match=message):
        engine(params, cfg, **kw)


def test_manager_with_state_refuses_rollback_and_the_wire(params, cfg):
    eng = engine(params, cfg)
    eng.submit(Request(np.arange(9, dtype=np.int32), 3, request_id="a"))
    eng.step()
    slot = eng.kv.live()[0]
    with pytest.raises(ValueError, match="truncate.*slot-indexed state"):
        eng.kv.truncate(slot, 4)
    with pytest.raises(ValueError, match="export_blocks.*no snapshots"):
        eng.kv.export_blocks(slot)
    with pytest.raises(ValueError, match="import_blocks"):
        eng.kv.import_blocks({"layout": "paged"}, "b")
    assert eng.kv.export_prefix([1, 2, 3]) is None    # sharing is off
    with pytest.raises(ValueError, match="kv_tiers.*slot-indexed state"):
        TieredKVStore().attach("r0", eng.kv)
    with pytest.raises(ValueError, match="int8 pool beside slot-indexed"):
        PagedKVManager(layers=2, heads=2, head_dim=8, slots=2,
                       max_seq_len=64, dtype=jnp.int8,
                       state_shape=(4, 2, 64))
    eng.run()


# ------------------------------------------------------------------ #
# the accepted cells' programs (tests/test_program_digests.py pins them)
# ------------------------------------------------------------------ #

def wave_programs(sds, attn, window=1, slots=4):
    """{name: lowered mixed step} of a small GPT-2 and a small latent
    configuration at two q-block buckets x has_fresh; ``sds(shape,
    dtype)`` makes the abstract arguments.  ``window`` is the engine's
    sampling window (over 1: an engine that speculates); at 16 ``slots``
    the Q 32 x has_fresh programs are packed (``gd.wave_rows``)."""
    def w(*s):
        return sds(s, jnp.bfloat16)

    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    L, H, DH, hid, V = 2, 4, 64, 256, 512
    p = {"gpt_wte_table": w(V, hid), "gpt_wpe": w(128, hid),
         "gpt_ln_f_scale": w(hid), "gpt_ln_f_bias": w(hid)}
    for i in range(L):
        us = f"gpt_h{i}"
        for leaf, (a, b) in [("attn_q", (1, 1)), ("attn_k", (1, 1)),
                             ("attn_v", (1, 1)), ("attn_proj", (1, 1)),
                             ("ffn_wi", (1, 4)), ("ffn_wo", (4, 1))]:
            p[f"{us}_{leaf}_weight"] = w(a * hid, b * hid)
            p[f"{us}_{leaf}_bias"] = w(b * hid)
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = w(hid)
            p[f"{us}_{ln}_bias"] = w(hid)
    pool = sds((L, N, BS, kv_row_width(H, DH)), jnp.bfloat16)
    cases = {"gpt2": (p, ("gpt", L, H, DH, 128), pool, pool)}
    c = LatentMoEConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=64, kv_lora_rank=96,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32,
        intermediate_size=256, moe_intermediate_size=128,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1, rope_theta=1e6,
        rms_norm_eps=1e-5, max_position_embeddings=128)
    lp = {k: sds(s, jnp.float32 if "router" in k else jnp.bfloat16)
          for k, s in c.param_shapes("glm").items()}
    blk = c.block_spec()
    cases["latent"] = (lp, ("glm", 3, 4, 64, 128, blk),
                       sds((3, N, BS, blk.latent.row_width), jnp.bfloat16),
                       None)
    fn = gd.serve_mixed_paged_fn(True, attn, window)
    out = {}
    for name, (params, cfg_tuple, ck, cv) in cases.items():
        for Q in (1, 32):
            for fresh in (False, True):
                out[f"{name}.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                    params, cfg_tuple, ck, cv, i32(B, T), i32(B), i32(B, Q),
                    i32(B), i32(B), sds((B,), jnp.bool_),
                    sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                    attn=attn, window=window, has_fresh=fresh)
    return out


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program,scope", [
    ("gpt2.Q1.fresh0", "wave_decode"), ("gpt2.Q32.fresh1", "wave_chunk"),
    ("latent.Q1.fresh0", "wave_decode"), ("latent.Q32.fresh1", "wave_chunk"),
    ("gpt2.Q32.verify", "wave_verify")])
def test_a_waves_program_is_traced_under_its_kind(program, scope):
    """ISSUE 40: ONE outer scope names the wave's program in the device
    trace (both are ``jit__serve_mixed_paged`` on the modules line).
    Every name stack of the lowered wave that passes through one of the
    wave's parts starts under it; sampling stays outside; and it is
    metadata alone (without debug info the text is the parent's:
    tests/test_program_digests.py)."""
    import re
    if program.endswith(".verify"):
        lowered = wave_programs(jax.ShapeDtypeStruct, "masked",
                                window=3)["gpt2.Q32.fresh0"]
    else:
        lowered = wave_programs(jax.ShapeDtypeStruct, "masked")[program]
    stacks = [s for s in re.findall(r'loc\("([^"]*)"',
                                    lowered.as_text(debug_info=True))
              if s.startswith("jit(_serve_mixed_paged)/")]
    parts = ("embed", "attn_qkv", "mla_qkv", "kv_write", "attention",
             "attn_out", "mlp", "moe_route", "moe_experts", "lm_head")
    inside = [s for s in stacks if any(f"/{p}/" in s + "/" for p in parts)]
    assert len(inside) > 50
    assert all(s.split("/")[1] == scope for s in inside)
    waves = {c for s in stacks for c in s.split("/") if c.startswith("wave_")}
    assert waves == {scope}
    sampled = [s for s in stacks if "/sample/" in s + "/"]
    assert sampled and all(s.split("/")[1] == "sample" for s in sampled)


def test_hybrid_wave_carries_its_scopes(params, cfg):
    cfg_tuple = ("lfm", 6, 8, 8, 64, cfg.block_spec())
    B, Q, T = 2, 4, 4
    pool = jnp.zeros((2, 9, 16, 128), jnp.float32)
    fn = gd.serve_mixed_paged_fn(False, "masked", 1)
    text = fn.func.lower(
        params, cfg_tuple, pool, pool, jnp.zeros((B, T), jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.zeros((B, Q), jnp.int32),
        jnp.full(B, Q, jnp.int32), jnp.full(B, Q - 1, jnp.int32),
        jnp.ones(B, bool), jnp.zeros(B, jnp.float32),
        jnp.zeros(B, jnp.int32), jnp.zeros((B, 2), jnp.uint32),
        attn="masked", has_fresh=True, window=1,
        state=jnp.zeros((4, B, 2, 64), jnp.float32)).as_text(debug_info=True)
    assert "jit__serve_mixed_paged" in text
    for scope in ("embed", "conv_in", "conv_mix", "conv_out", "state_write",
                  "attn_qkv", "kv_write", "attention", "attn_out",
                  "moe_route", "moe_experts", "mlp", "lm_head", "sample"):
        assert f"/{scope}" in text, scope
    for scope in ("mla_qkv", "mla_absorb", "moe_shared"):
        assert scope not in text


# ------------------------------------------------------------------ #
# the converter
# ------------------------------------------------------------------ #

def test_convert_lfm2_moe_on_a_synthetic_state_dict(params, cfg):
    """Our leaves laid out as the checkpoint has them ([out, in] Linear
    weights, a [D, 1, K] depthwise conv, an expert a module) convert back
    to themselves, and to the same logits."""
    P = {k: np.asarray(v) for k, v in params.items()}
    sd = {"model.embed_tokens.weight": P["lfm_wte_table"],
          "model.embedding_norm.weight": P["lfm_ln_f_scale"]}
    names = (("gate", "w1"), ("up", "w3"), ("down", "w2"))
    for i, op in enumerate(cfg.operators()):
        us, hfk = f"lfm_h{i}", f"model.layers.{i}"
        sd[f"{hfk}.operator_norm.weight"] = P[f"{us}_ln1_scale"]
        sd[f"{hfk}.ffn_norm.weight"] = P[f"{us}_ln2_scale"]
        if op == "conv":
            sd[f"{hfk}.conv.in_proj.weight"] = P[f"{us}_conv_in_weight"].T
            sd[f"{hfk}.conv.conv.weight"] = P[f"{us}_conv_weight"].T[:, None]
            sd[f"{hfk}.conv.out_proj.weight"] = P[f"{us}_conv_out_weight"].T
        else:
            for nm in ("q", "k", "v"):
                sd[f"{hfk}.self_attn.{nm}_proj.weight"] = \
                    P[f"{us}_attn_{nm}_weight"].T
            sd[f"{hfk}.self_attn.out_proj.weight"] = \
                P[f"{us}_attn_proj_weight"].T
            sd[f"{hfk}.self_attn.q_layernorm.weight"] = \
                P[f"{us}_attn_q_norm_scale"]
            sd[f"{hfk}.self_attn.k_layernorm.weight"] = \
                P[f"{us}_attn_k_norm_scale"]
        ff = f"{hfk}.feed_forward"
        if i < cfg.num_dense_layers:
            for ours, theirs in names:
                sd[f"{ff}.{theirs}.weight"] = P[f"{us}_ffn_{ours}_weight"].T
            continue
        sd[f"{ff}.gate.weight"] = P[f"{us}_moe_router_weight"].T
        sd[f"{ff}.expert_bias"] = P[f"{us}_moe_router_bias"]
        for ours, theirs in names:
            for e in range(cfg.n_routed_experts):
                sd[f"{ff}.experts.{e}.{theirs}.weight"] = \
                    P[f"{us}_moe_experts_{ours}"][e].T
    assert sd["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    got = hf.convert_lfm2_moe(sd, cfg)
    assert set(got) == set(cfg.param_shapes("lfm"))
    for k, shape in cfg.param_shapes("lfm").items():
        assert got[k].shape == shape, k
        np.testing.assert_array_equal(got[k], P[k], err_msg=k)
    seq = np.arange(12) % 257
    np.testing.assert_allclose(ref.forward(got, cfg, seq)[0],
                               ref.forward(params, cfg, seq)[0], atol=1e-6)
    # a checkpoint without the buffer: a zero bias
    for k in [k for k in sd if k.endswith("expert_bias")]:
        del sd[k]
    assert not hf.convert_lfm2_moe(sd, cfg)["lfm_h3_moe_router_bias"].any()
