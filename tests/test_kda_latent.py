"""The ``Ling-3.0-flash`` decoder (``KDALatentConfig``: delta-rule layers
beside latent attention in ONE block and ONE manager, a query without its
low-rank step, a group-limited sigmoid router over a held share of the
experts, an untied head over held rows) on the serving path, at a small
size on the CPU (ISSUE 58): hidden 32, 2 heads of 16, groups of 3 layers
(KDA, KDA, MLA, KDA), 16 experts in 4 groups of which 2 are kept, top 2,
4 of them held, 96 of 128 table rows held, paged block 4 and chunks of 8.
Every comparison is of LOGITS against the plain reference's full forward
(``models/reference_kda_latent.py``), never of tokens alone.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import kda_decode as kd
from hetu_tpu.models import reference_kda_latent as ref
from hetu_tpu.models.kda_latent import (
    KDALatentConfig, init_kda_latent_params)
from hetu_tpu.models.moe_decode import (
    RoutedSpec, group_limited, route, routed_ffn)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager

from jitted import reference  # noqa: E402

NAME = "lng"
SMALL = dict(
    vocab_size=128, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=2, num_key_value_heads=2, head_dim=16,
    q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=48,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
    num_experts=16, num_experts_per_tok=2, first_k_dense_replace=1,
    n_group=4, topk_group=2, routed_scaling_factor=2.5,
    norm_topk_prob=True, moe_router_enable_expert_bias=True,
    score_function="sigmoid", rope_theta=100, rotary_dim=8,
    partial_rotary_factor=0.5, rms_norm_eps=1e-6, layer_group_size=3,
    num_kv_heads_for_linear_attn=0, group_norm_size=1, linear_silu=True,
    use_mla_nope=False, short_conv_kernel_size=4, use_nGPT=False,
    scale_router_input=False, value_norm=False, up_proj_norm=False,
    use_qk_norm=True, gated_attention_proj_granularity_type="head_wise",
    mtp_use_kda=False, no_kda_lora=True, use_kda_lora=False,
    kda_safe_gate=True, kda_lower_bound=-5, max_position_embeddings=256,
    expert_swiglu_limit_list=[0, 0, 0, 0, 4],
    share_expert_swiglu_limit_list=[0, 0, 0, 0, 5],
    image_patch_token=127)
HELD, ROWS = (4, 4), (0, 96)
# float32 weights, pools and states on both sides: what is left is the
# order of the sums
TOL = 2e-4
SIZES = [(5, 6), (12, 9), (30, 5), (61, 8), (21, 7), (17, 4)]
# decays that MOVE inside a test's few dozen positions: the family's
# draw (a memory of twenty tokens and up) would leave them at 1
DT = (0.05, 2.0)


@functools.lru_cache(maxsize=None)
def built(held=HELD, **over):
    cfg = KDALatentConfig.from_hf(SMALL, held_experts=held,
                                  vocab_rows=ROWS, **over)
    return cfg, init_kda_latent_params(cfg, name=NAME, seed=3, dt_range=DT)


def engine(cfg=None, params=None, **kw):
    if cfg is None:
        cfg, params = built()
    kw = dict(dict(slots=4, max_seq_len=128, kv_block=4, prefill_chunk=8,
                   fast_path=False), **kw)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 96, n).astype(np.int32), m,
                    request_id=f"r{i}") for i, (n, m) in enumerate(sizes)]
    return eng.run(reqs)


def gap(result, wrong=()):
    """The widest gap between a row's largest reference logit and the
    reference logit of the token the engine chose."""
    cfg, params = built()
    seq = np.asarray(result.tokens, np.int32)
    lg = reference(ref.forward, params, cfg, seq[:-1], name=NAME,
                   wrong=tuple(wrong))
    rows = np.asarray(lg)[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max())


@pytest.fixture(scope="module")
def served():
    """Six requests on four slots through the masked path: prompts under
    a chunk (5), of several (30, 61), decoding beside chunks."""
    eng = engine()
    mark = eng.metrics.mark()
    out = serve(eng, SIZES)
    return eng, out, eng.metrics.snapshot(since=mark)


# ------------------------------------------------------------------ #
# the config class and the block spec
# ------------------------------------------------------------------ #

def test_config_reads_the_sources_keys():
    cfg, params = built()
    blk = cfg.block_spec()
    assert blk.ops == ("kda", "kda", "latent_attention", "kda")
    assert (blk.norm, blk.residual, blk.attention, blk.head) == (
        "rmsnorm", "sequential", "latent", "untied")
    assert blk.latent == gd.LatentSpec(0, 16, 16, 8, 16, gate=True)
    assert blk.kda == kd.KDASpec(2, 16, 4, -5.0)
    assert blk.routed == RoutedSpec(16, 2, 2.5, True, 1, held_first=4,
                                    held=4, n_group=4, topk_group=2)
    assert (blk.op_layers(4, "pool"), blk.op_layers(4, "state"),
            blk.op_layers(4, "kda")) == (1, 3, 3)
    assert (blk.op_index(2), blk.op_index(3)) == (0, 2)
    # three layers' conv tails (the pool's dtype), then their states
    assert blk.state_shapes(4, 32) == (((1, 3, 96), None),) * 3 + (
        ((1, 2, 16, 16), jnp.dtype("float32")),) * 3
    gd.check_block_spec(blk, 4)
    hash(blk)                                  # jit-static
    assert cfg.vocab_size == 96 and cfg.published_vocab_size == 128
    shapes = cfg.param_shapes(NAME)
    assert shapes["lng_h0_kda_qkv_weight"] == (32, 96)
    assert shapes["lng_h0_kda_conv_weight"] == (4, 96)
    assert shapes["lng_h2_attn_q_weight"] == (32, 2 * 24)
    assert "lng_h2_attn_q_a_weight" not in shapes
    assert shapes["lng_h3_moe_router_weight"] == (32, 16)
    assert shapes["lng_h3_moe_experts_down"] == (4, 16, 32)
    assert "lng_h0_moe_router_weight" not in shapes        # dense
    assert params["lng_h1_kda_A_log"].dtype == jnp.float32
    assert params["lng_h1_moe_router_bias"].dtype == jnp.float32


REFUSED = [
    {"score_function": "softmax"}, {"use_qk_norm": False},
    {"linear_silu": False}, {"kda_safe_gate": False},
    {"no_kda_lora": False}, {"use_kda_lora": True}, {"use_mla_nope": True},
    {"use_nGPT": True}, {"scale_router_input": True}, {"value_norm": True},
    {"up_proj_norm": True}, {"group_norm_size": 2},
    {"gated_attention_proj_granularity_type": "element_wise"},
    {"tie_word_embeddings": True}, {"rope_scaling": {"type": "yarn"}},
    {"attention_bias": True}, {"hidden_act": "gelu"},
    {"num_key_value_heads": 1}, {"num_kv_heads_for_linear_attn": 1},
    {"rotary_dim": 16}, {"partial_rotary_factor": 1.0},
    {"expert_swiglu_limit_list": [0, 0, 0, 4]},
    {"share_expert_swiglu_limit_list": [0, 5, 0, 0]},
    # sizes that do not fit
    {"layer_group_size": 5}, {"layer_group_size": 1},
    {"qk_rope_head_dim": 7, "rotary_dim": 7, "partial_rotary_factor": None},
    {"num_experts_per_tok": 17}, {"first_k_dense_replace": 5},
    {"topk_group": 5}, {"n_group": 3}, {"num_experts_per_tok": 9},
    {"short_conv_kernel_size": 1}, {"kda_lower_bound": -6},
    {"kda_lower_bound": 0}]


@pytest.mark.parametrize("change", REFUSED, ids=lambda c: ",".join(
    f"{k}={v}" for k, v in c.items())[:48])
def test_class_raises_by_name(change):
    with pytest.raises(ValueError, match="KDALatentConfig") as e:
        KDALatentConfig.from_hf(dict(SMALL, **change))
    if "cannot run" in str(e.value):
        assert next(iter(change)) in str(e.value)


@pytest.mark.parametrize("held, rows", [((14, 4), None), ((0, 0), None),
                                        (None, (100, 40))])
def test_class_refuses_a_share_that_does_not_fit(held, rows):
    with pytest.raises(ValueError, match="sizes do not fit"):
        KDALatentConfig.from_hf(SMALL, held_experts=held, vocab_rows=rows)


def test_clamp_past_the_served_layers_is_not_read():
    # (SMALL's lists carry a nonzero fifth entry: four layers are served)
    assert built()[0].num_hidden_layers == 4


def spec_with(**over):
    return built()[0].block_spec()._replace(**over)


@pytest.mark.parametrize("blk", [
    # a delta-rule layer of the grouped-query block beside anything but
    # plain attention (beside it: ``tests/test_kda_gqa.py``, ISSUE 62)
    lambda: gd.BlockSpec(norm="rmsnorm", positions="rope", attention="gqa",
                         bias=False, kv_heads=2, window=4,
                         ops=("kda", "window_attention"),
                         kda=kd.KDASpec(2, 16), ffn="swiglu"),
    # ... named without its spec, and a spec without its layers
    lambda: spec_with(kda=None),
    lambda: spec_with(ops=("latent_attention",) * 4),
    # ... with no latent operator beside it
    lambda: spec_with(ops=("kda",) * 4),
    # ... whose sub-blocks leave float32
    lambda: spec_with(kda=kd.KDASpec(2, 16, lower_bound=-6.0)),
    lambda: spec_with(kda=kd.KDASpec(2, 16, conv_kernel=1)),
    # an indexer needs the low-rank query
    lambda: spec_with(latent=gd.LatentSpec(
        0, 16, 16, 8, 16, index=gd.IndexSpec(2, 16, 4, 8))),
    # group-limited choice: groups that divide, enough experts kept
    lambda: spec_with(routed=RoutedSpec(16, 2, n_group=3, topk_group=2)),
    lambda: spec_with(routed=RoutedSpec(16, 2, n_group=4, topk_group=5)),
    lambda: spec_with(routed=RoutedSpec(16, 9, n_group=4, topk_group=2)),
    lambda: spec_with(routed=RoutedSpec(16, 2, scoring="softmax",
                                        n_group=4, topk_group=2)),
])
def test_check_block_spec_refuses(blk):
    with pytest.raises(ValueError, match="the mixed wave runs") as e:
        gd.check_block_spec(blk(), 4)
    # the text is built from the tables
    for op in gd.OPERATORS:
        assert op in str(e.value)


# ------------------------------------------------------------------ #
# the engine against the reference's full forward
# ------------------------------------------------------------------ #

def test_engine_serves_the_reference(served):
    eng, out, snap = served
    assert eng.kv.latent and eng.kv.stateful and eng.kv.pool_layers == 1
    assert len(eng.kv.states) == 6
    for i, (n, m) in enumerate(SIZES):
        r = out[f"r{i}"]
        assert len(r.tokens) == n + m
        assert gap(r) < TOL, (i, n, m)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_comparison_notices_what_is_left_out(served, wrong):
    _, out, _ = served
    # (with two groups kept and two experts chosen, "group_max" keeps
    # the two largest experts' groups: plain top-k's choice)
    assert max(gap(out[f"r{i}"], (wrong,)) for i in range(6)) > 50 * TOL


def test_counters_in_the_windowed_snapshot(served):
    _, _, snap = served
    prompt_rows = sum(n for n, _ in SIZES)
    # every prompt row rides a q-block of 8 or a tail: rows in q-blocks
    # wider than one, x 3 layers; every answer token but a request's
    # first is a one-row step (17 = 2 x 8 + 1: its last chunk is one row)
    assert snap["kda_chunk_rows"] == 3 * (prompt_rows - 1)
    assert snap["kda_slot_steps"] == 3 * (sum(m - 1 for _, m in SIZES) + 1)


def test_fast_path_serves_the_reference():
    eng = engine(fast_path=True)
    out = serve(eng, SIZES[1:4], seed=5)
    for r in out.values():
        assert gap(r) < TOL


def state_error(eng, result, cfg, params):
    """The widest relative error, over the KDA layers, of the state the
    request left in its slot against the reference's."""
    seq = jnp.asarray(np.asarray(result.tokens, np.int32)[:-1])
    _, want = ref.forward(params, cfg, seq, name=NAME, states=True)
    want = np.asarray(want)
    n = len(eng.kv.states) // 2
    got = np.stack([np.asarray(eng.kv.states[n + i][0], np.float32)
                    for i in range(n)])                    # [n, slots, ..]
    slot = int(np.argmin([np.abs(got[0, s] - want[0]).max()
                          for s in range(got.shape[1])]))
    return max(float(np.linalg.norm(got[i, slot] - want[i])
                     / np.linalg.norm(want[i])) for i in range(n))


def test_state_agrees_and_a_bfloat16_state_does_not():
    cfg, params = built()
    eng = engine(slots=1)
    r = serve(eng, [(45, 12)], seed=2)["r0"]
    assert state_error(eng, r, cfg, params) < 1e-4
    low, _ = built(state_dtype="bfloat16")
    assert low.block_spec().kda.state_dtype == "bfloat16"
    eng = engine(low, params, slots=1)
    assert eng.kv.states[-1].dtype == jnp.bfloat16
    r = serve(eng, [(45, 12)], seed=2)["r0"]
    assert state_error(eng, r, cfg, params) > 1e-3


# ------------------------------------------------------------------ #
# the chunked form against the recurrence, step by step
# ------------------------------------------------------------------ #

def draw(Q, B=2, H=2, D=16, seed=0, bound=True):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, Q, H, D)).astype(np.float32)
               for _ in range(3))
    q, k = np.asarray(kd.l2norm(jnp.asarray(q))), np.asarray(
        kd.l2norm(jnp.asarray(k)))
    # decays AT the bound on half the channels, near 1 on the others
    g = -5.0 * rng.uniform(size=(B, Q, H, D)).astype(np.float32) ** 0.25
    if bound:
        g = np.where(rng.uniform(size=g.shape) < 0.5, -5.0, g * 0.02)
    beta = rng.uniform(0.1, 1.0, size=(B, Q, H)).astype(np.float32)
    S = rng.normal(size=(B, H, D, D)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g.astype(np.float32),
                                          beta, S))


def stepwise(q, k, v, g, beta, S):
    ys = []
    for t in range(q.shape[1]):
        y, S = kd.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("Q", [1, 15, 16, 17, 64, 65, 200])
def test_chunked_form_is_the_recurrence(Q):
    args = draw(Q, seed=Q)
    want_y, want_S = jax.jit(stepwise)(*args)
    got_y, got_S = jax.jit(kd.kda_chunked)(*args)
    assert np.isfinite(np.asarray(got_y)).all()
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_S, want_S, atol=2e-4, rtol=2e-4)


def test_dropping_the_correction_is_another_state():
    q, k, v, g, beta, S = draw(40, bound=False)
    _, want = stepwise(q, k, v, g * 0.01, beta, S)
    # gated linear attention: S' + beta k v^T
    glr = S
    for t in range(40):
        glr = glr * jnp.exp(g[:, t] * 0.01)[..., None] + (
            beta[:, t, :, None] * k[:, t])[..., None] * v[:, t][:, :, None]
    assert float(jnp.abs(glr - want).max()) > 0.1


def test_dead_rows_and_dead_slots_leave_the_state_bit_for_bit():
    sp = kd.KDASpec(2, 16)
    B, Q = 4, 32
    q, k, v, g, beta, S = draw(Q, B=B, seed=9)
    state = (jnp.zeros((1, B, 3, 96)), S[None])
    # slot 0 dead, slot 1 one row, slot 2 a q-block of 20, slot 3 one
    # whose rows past 7 hold garbage
    q_len = jnp.asarray([0, 1, 20, 7])
    mix = jax.jit(lambda *a: kd.kda_mixer(sp, *a[:-1], 0, a[-1]))
    y, out = mix(q, k, v, g, beta, state, q_len)
    mats = np.asarray(out[1][0])
    np.testing.assert_array_equal(mats[0], np.asarray(S[0]))
    assert out[0] is state[0] or np.array_equal(out[0], state[0])
    for b, n in ((1, 1), (2, 20), (3, 7)):
        want_y, want_S = stepwise(*(a[b:b + 1, :n] for a in
                                    (q, k, v, g, beta)), S[b:b + 1])
        np.testing.assert_allclose(y.reshape(B, Q, -1)[b, :n],
                                   want_y.reshape(n, -1), atol=2e-4)
        np.testing.assert_allclose(mats[b], want_S[0], atol=2e-4)
    # the dead rows' contents move nothing
    noisy = [a.at[3, 7:].set(7.0) for a in (q, k, v)]
    y2, out2 = mix(*noisy, g, beta, state, q_len)
    np.testing.assert_array_equal(np.asarray(out2[1]), np.asarray(out[1]))
    np.testing.assert_array_equal(np.asarray(y2).reshape(B, Q, -1)[3, :7],
                                  np.asarray(y).reshape(B, Q, -1)[3, :7])


# ------------------------------------------------------------------ #
# the router and the held share
# ------------------------------------------------------------------ #

def test_group_limited_choice_differs_from_plain_topk_by_hand():
    # 8 experts in 4 groups of 2, 2 groups kept, top 3.  Group sums:
    # (0.9 + 0.1, 0.5 + 0.45, 0.6 + 0.3, 0.2 + 0.2) = 1.0, 0.95, 0.9, 0.4:
    # groups 0 and 1 are kept, so expert 4 (0.6, third overall) is out
    pick = jnp.asarray([[0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.2, 0.2]])
    lim = group_limited(pick, 4, 2)
    assert np.isneginf(np.asarray(lim)[0, 4:]).all()
    np.testing.assert_array_equal(np.asarray(lim)[0, :4],
                                  np.asarray(pick)[0, :4])
    assert sorted(np.asarray(jax.lax.top_k(lim, 3)[1])[0]) == [0, 2, 3]
    assert sorted(np.asarray(jax.lax.top_k(pick, 3)[1])[0]) == [0, 2, 4]
    # by the LARGEST one the groups would be 0 and 2
    # through ``route``: logits whose sigmoid is ``pick``, no bias
    x = jnp.eye(8, dtype=jnp.float32)[:1]
    w = jnp.zeros((8, 8)).at[0].set(jnp.log(pick[0] / (1 - pick[0])))
    sel, wt = route(x, w, None, RoutedSpec(8, 3, n_group=4, topk_group=2))
    assert sorted(np.asarray(sel)[0]) == [0, 2, 3]
    np.testing.assert_allclose(np.asarray(wt).sum(), 1.0, atol=1e-6)
    sel, _ = route(x, w, None, RoutedSpec(8, 3))
    assert sorted(np.asarray(sel)[0]) == [0, 2, 4]


def test_route_agrees_with_the_reference_router():
    cfg, params = built()
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    us = "lng_h3"
    sel, w = route(x, params[f"{us}_moe_router_weight"],
                   params[f"{us}_moe_router_bias"], cfg.routed_spec())
    with jax.default_matmul_precision("highest"):
        chosen, want = ref.route(params, us, cfg, x)
    got = np.zeros((64, 16), np.float32)
    np.put_along_axis(got, np.asarray(sel), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    # it is a choice plain top-k would not have made somewhere
    _, plain = ref.route(params, us, cfg, x, wrong=("plain_topk",))
    assert (np.asarray(plain) != np.asarray(want)).any()


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    whole_cfg, whole = built(held=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    us = "lng_h2"
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.ffn_parts(whole, us, whole_cfg, x)
    total = 0.0
    for rank in range(4):
        held = (4 * rank, 4)
        cfg = KDALatentConfig.from_hf(SMALL, held_experts=held,
                                      vocab_rows=ROWS)
        part = dict(whole)
        for leaf in ("gate", "up", "down"):
            part[f"{us}_moe_experts_{leaf}"] = whole[
                f"{us}_moe_experts_{leaf}"][4 * rank:4 * rank + 4]
        y = routed_ffn(part, us, x, cfg.routed_spec())
        # what every chip computes alike is counted once
        total = total + (y - shared)
        # and a share alone is the reference's share
        with jax.default_matmul_precision("highest"):
            mine, _ = ref.ffn_parts(part, us, cfg, x, held)
        np.testing.assert_allclose(y - shared, mine, atol=1e-4)
    np.testing.assert_allclose(total + shared, routed + shared, atol=2e-4)


# ------------------------------------------------------------------ #
# one manager: a latent pool AND a set of slot states
# ------------------------------------------------------------------ #

def manager(**kw):
    blk = built()[0].block_spec()
    return PagedKVManager(**dict(dict(
        layers=1, heads=2, head_dim=16, slots=2, max_seq_len=32, block=4,
        row_shape=(blk.latent.row_width,),
        state_shapes=blk.state_shapes(4, 32)), **kw))


def test_one_manager_holds_the_pool_and_the_states():
    kv = manager()
    assert kv.latent and kv.stateful and kv.cache_v is None
    assert kv.cache_k.shape == (1, 17, 4, 128)
    assert [s.shape for s in kv.states] == [(1, 2, 3, 96)] * 3 + [
        (1, 2, 2, 16, 16)] * 3
    assert kv.state_bytes == sum(s.nbytes for s in kv.states)
    assert not kv.prefix_share
    # a claimed slot's states are zeroed, the other slot's stay
    kv.state = tuple(jnp.ones_like(s) for s in kv.states)
    slot, cached = kv.alloc("a", list(range(6)), 10)
    assert slot is not None and cached == 0 and kv.state_resets == 1
    other = 1 - slot
    for s in kv.states:
        assert float(jnp.abs(s[:, slot]).max()) == 0.0
        assert float(s[:, other].min()) == 1.0
    assert kv.n_table[slot] == 3                    # 10 positions, block 4
    kv.release(slot)
    assert kv.free_slots == 2 and kv.free_blocks == 16


@pytest.mark.parametrize("what", ["truncate", "export", "import", "prefix"])
def test_what_the_state_refuses_stays_refused(what):
    if what == "prefix":
        with pytest.raises(ValueError, match="prefix_share"):
            manager(prefix_share=True)
        return
    kv = manager()
    slot, _ = kv.alloc("a", list(range(6)), 10)
    kv.advance(slot, 6)
    with pytest.raises(ValueError, match="slot-indexed state|latent rows"):
        if what == "truncate":
            kv.truncate(slot, 2)
        elif what == "export":
            kv.export_blocks(slot)
        else:
            kv.import_blocks({"length": 4}, "b")


# ------------------------------------------------------------------ #
# the lowered programs (``tests/test_program_digests.py`` keeps a case
# a program)
# ------------------------------------------------------------------ #

def kda_latent_programs(sds, attn, qs=(1, 32), slots=4):
    """{name: lowered mixed step} of the small four-layer model at
    widths of whole lane tiles (heads of 64, a latent row of 128), so
    that the latent kernel lowers for the chip: the pool, then the
    manager's set of six states, donated."""
    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    c = KDALatentConfig.from_hf(dict(
        SMALL, hidden_size=256, num_attention_heads=4, num_key_value_heads=4,
        head_dim=64, kv_lora_rank=64, qk_nope_head_dim=64,
        qk_rope_head_dim=64, rotary_dim=64, partial_rotary_factor=1.0,
        v_head_dim=64, moe_intermediate_size=128), held_experts=HELD,
        vocab_rows=ROWS)
    blk = c.block_spec()
    p = {k: sds(s, jnp.float32 if k.endswith(
        ("router_weight", "router_bias", "dt_bias", "A_log"))
        else jnp.bfloat16) for k, s in c.param_shapes(NAME).items()}
    pool = sds((1, N, BS, 128), jnp.bfloat16)
    state = tuple(sds((shape[0], B) + shape[1:], dtype or jnp.bfloat16)
                  for shape, dtype in blk.state_shapes(4, 256))
    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"kda_latent.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (NAME, 4, 4, 64, 128, blk), pool, None, i32(B, T),
                i32(B), i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
                sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                attn=attn, window=1, has_fresh=fresh, state=state)
    return out


def test_the_wave_traces_the_new_scopes_for_this_spec_alone():
    text = kda_latent_programs(jax.ShapeDtypeStruct, "masked", qs=(32,))[
        "kda_latent.Q32.fresh1"].as_text(debug_info=True)
    for scope in ("kda_qkvg", "kda_conv", "kda_scan", "state_write",
                  "kda_out", "moe_group_select", "mla_qkv", "mla_absorb",
                  "mla_gate", "kv_write", "attention", "attn_out",
                  "moe_route", "moe_experts", "moe_shared", "lm_head"):
        assert f"/{scope}" in text, scope
    assert "attn_q_a" not in text
    from test_sparse_latent import sparse_latent_programs
    other = sparse_latent_programs(jax.ShapeDtypeStruct, "masked", qs=(32,))[
        "sparse_latent.Q32.fresh1"].as_text(debug_info=True)
    for scope in ("/kda_qkvg", "/kda_scan", "/moe_group_select"):
        assert scope not in other
