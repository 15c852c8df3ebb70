"""The ``nemotron_h`` family (``nemotron_h.NemotronHConfig``) on the mixed
wave: layers that are a Mamba-2 mixer, an attention or a latent routed
FFN ALONE, the expert layers holding a SHARE of their experts; the
engine's logits and final states through the paged pool and the slot
states against ``reference_nemotron_h``'s full forward, float32 both
sides on the CPU.

Tolerance: 1e-4 of the logits' spread, absolute (``test_ssm_hybrid``'s:
both sides are float32 and differ in the order of their sums)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import moe_decode as md
from hetu_tpu.models import nemotron_h as nh
from hetu_tpu.models import reference_nemotron_h as ref
from hetu_tpu.serving import Request, ServingEngine

TOL = 1e-4
NAME = "nmh"
PATTERN = "MEMEMEM*EME"
HELD = (4, 4)            # experts [4, 8) of 16: the second quarter

SMALL = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=11,
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, n_routed_experts=16,
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, n_group=1, topk_group=1, layer_norm_epsilon=1e-5,
    max_position_embeddings=256, mlp_hidden_act="relu2",
    mamba_hidden_act="silu", use_conv_bias=True, rope_theta=10000,
    partial_rotary_factor=1, num_nextn_predict_layers=1,
    mtp_hybrid_override_pattern="*E", model_type="nemotron_h")


@pytest.fixture(scope="module")
def cfg():
    return nh.NemotronHConfig.from_hf(SMALL, held_experts=HELD)


@pytest.fixture(scope="module")
def params(cfg):
    return nh.init_nemotron_h_params(cfg, NAME, seed=3)


@pytest.fixture(scope="module")
def whole():
    """The same model holding every expert, and its weights."""
    c = nh.NemotronHConfig.from_hf(SMALL)
    return c, nh.init_nemotron_h_params(c, NAME, seed=5)


def engine(params, cfg, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("kv_block", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("fast_path", False)
    return ServingEngine(params, cfg, **kw)


def forward(params, cfg, seq, **kw):
    """The reference over ``seq`` for the share ``cfg`` holds: (logits,
    states, margin) as numpy."""
    lg, st, mg = ref.forward(params, cfg, seq, NAME, held=cfg.held_experts,
                             **kw)
    return np.asarray(lg), np.asarray(st), np.asarray(mg)


# ------------------------------------------------------------------ #
# the spec: one-part layers, and what each keeps
# ------------------------------------------------------------------ #

def test_block_spec_states_every_layer_as_what_it_is(cfg):
    blk = cfg.block_spec()
    gd.check_block_spec(blk, 11)
    ops = {"M": "ssm", "*": "attention", "E": "none"}
    ffns = {"M": "none", "*": "none", "E": "routed"}
    assert [blk.op_kind(i) for i in range(11)] == [ops[c] for c in PATTERN]
    assert [blk.ffn_kind(i) for i in range(11)] == [ffns[c] for c in PATTERN]
    assert blk.positions == "none" and blk.mup is None
    assert blk.routed_layers(11) == 5
    rt = blk.routed
    assert (rt.num_experts, rt.top_k, rt.held_first, rt.held) == (16, 4, 4, 4)
    assert (rt.latent, rt.expert, rt.held_experts) == (32, "relu2", 4)
    assert rt.holds_a_share
    # every expert held: ``held`` 0, as the accepted specs state it
    assert nh.NemotronHConfig.from_hf(SMALL).routed_spec().held == 0


def test_holds_and_op_index_over_layers_that_keep_nothing(cfg):
    blk = cfg.block_spec()
    assert [blk.holds(i, "state") for i in range(11)] == \
        [c == "M" for c in PATTERN]
    assert [blk.holds(i, "pool") for i in range(11)] == \
        [c == "*" for c in PATTERN]
    assert not any(blk.holds(i, "window") for i in range(11))
    # an expert layer keeps nothing: no index into anything
    assert [blk.op_index(i) for i in range(11)] == \
        [0, None, 1, None, 2, None, 3, 0, None, 4, None]
    assert [blk.op_layers(11, w) for w in ("pool", "window", "state")] == \
        [1, 0, 5]
    assert [blk.op_layers(11, o) for o in ("ssm", "attention", "none")] == \
        [5, 1, 5]
    shapes = blk.state_shapes(11, 64)
    assert len(shapes) == 10
    assert shapes[0] == ((1, 3, 32 + 2 * 2 * 16), None)
    assert shapes[5] == ((1, 4, 8, 16), jnp.float32)
    # the accepted specs answer as they did
    assert gd.GPT2_BLOCK.op_index(3) == 3 and gd.GPT2_BLOCK.holds(0, "pool")


def test_param_shapes_have_one_norm_a_layer_and_held_experts(cfg):
    shapes = cfg.param_shapes(NAME)
    for i, c in enumerate(PATTERN):
        norms = [k for k in shapes if k.startswith(f"{NAME}_h{i}_ln")]
        assert norms == [f"{NAME}_h{i}_{'ln2' if c == 'E' else 'ln1'}_scale"]
    assert shapes[f"{NAME}_h1_moe_experts_up"] == (4, 32, 48)
    assert shapes[f"{NAME}_h1_moe_experts_down"] == (4, 48, 32)
    assert shapes[f"{NAME}_h1_moe_router_weight"] == (64, 16)
    assert shapes[f"{NAME}_h1_moe_shared_up_weight"] == (64, 96)
    assert not any("gate" in k or "ffn" in k or "wpe" in k for k in shapes)


@pytest.mark.parametrize("change", [
    dict(attention="latent", latent=gd.LatentSpec(16, 16, 8, 8, 8)),
    dict(ssm=None),
    dict(ops=("none",) * 11, ffns=("none",) * 11),
    dict(ffns=("routed",) * 10),
    dict(positions="learned"),
    dict(ops=("ssm",) * 5 + ("conv",) * 6, conv_kernel=3),
    dict(routed=None),
    dict(ffns=("swiglu",) * 11),
], ids=["latent_beside_state", "no_ssm_spec", "layers_of_nothing",
        "short_ffns", "learned_positions", "two_kinds_of_state",
        "no_routed_spec", "routed_spec_unused"])
def test_check_block_spec_still_raises(cfg, change):
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(cfg.block_spec()._replace(**change), 11)


@pytest.mark.parametrize("change", [
    dict(expert="gelu"), dict(held=17), dict(held_first=14, held=4),
    dict(latent=-1), dict(scoring="tanh")])
def test_check_block_spec_refuses_a_routed_spec_it_cannot_run(cfg, change):
    blk = cfg.block_spec()
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(
            blk._replace(routed=blk.routed._replace(**change)), 11)


def test_the_error_text_enumerates_from_the_tables(cfg):
    with pytest.raises(ValueError) as e:
        gd.check_block_spec(cfg.block_spec()._replace(ssm=None), 11)
    text = str(e.value)
    for word in tuple(gd.OPERATORS) + gd.FFN_KINDS + md.SCORINGS \
            + md.EXPERT_FORMS + gd.ROPE_KINDS:
        assert word in text, word


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "MEM-MEM*EME"), ("mlp_bias", True),
    ("n_group", 2), ("mlp_hidden_act", "silu"), ("sliding_window", 128),
    ("tie_word_embeddings", True), ("num_hidden_layers", 10),
    ("residual_in_fp32", True), ("use_conv_bias", False)])
def test_config_refuses_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match="NemotronHConfig"):
        nh.NemotronHConfig.from_hf(dict(SMALL, **{key: value}))


@pytest.mark.parametrize("held", [(0, 0), (14, 4), (-1, 4)])
def test_config_refuses_a_share_outside_the_experts(held):
    with pytest.raises(ValueError, match="held"):
        nh.NemotronHConfig.from_hf(SMALL, held_experts=held)


def test_recurrence_constants_and_router_stay_float32(cfg):
    p = nh.init_nemotron_h_params(cfg, NAME, seed=1, dtype=jnp.bfloat16)
    for k, v in p.items():
        f32 = k.endswith(("_ssm_dt_bias", "_ssm_A_log", "_ssm_D")) \
            or "_moe_router_" in k
        assert v.dtype == (jnp.float32 if f32 else jnp.bfloat16), k
    dt = jax.nn.softplus(p[f"{NAME}_h0_ssm_dt_bias"])
    assert float(dt.min()) >= 0.001 * 0.999 and float(dt.max()) <= 0.1001


# ------------------------------------------------------------------ #
# the wave against the reference: logits and final states
# ------------------------------------------------------------------ #

def serve(eng, sizes, seed=1):
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(sizes):
        eng.submit(Request(rng.integers(0, 211, n).astype(np.int32), m,
                           request_id=f"q{i}"))
    return eng.run()


def gap(params, cfg, result):
    """The widest (largest logit - served token's logit) over the
    answer's rows, in units of the logits' spread."""
    seq = np.asarray(result.tokens, np.int32)
    lg, _, _ = forward(params, cfg, seq[:-1])
    rows = lg[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max() / lg.std())


@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_engine_serves_slots_out_of_step(params, cfg, fast):
    eng = engine(params, cfg, fast_path=fast, prefill_chunk=8)
    assert len(eng.kv.states) == 10
    assert all(t.shape == (1, 4, 3, 96) for t in eng.kv.states[:5])
    assert all(m.shape == (1, 4, 4, 8, 16) and m.dtype == jnp.float32
               for m in eng.kv.states[5:])
    assert eng.kv.cache_k.shape[0] == 1         # one layer holds pages
    # six requests on four slots: out of step (prompts of two chunks of
    # 8, or of a chunk of 8 and a rest of 4: two bucket sizes; answers
    # of 4 or 8), two slots reused; every sequence 20 long, so that the
    # reference's scan is compiled once
    sizes = [(16, 4), (12, 8), (16, 4), (12, 8), (16, 4), (12, 8)]
    out = serve(eng, sizes)
    assert len(out) == 6 and eng.kv.state_resets == 6
    for r in out.values():
        assert gap(params, cfg, r) <= TOL, r.request_id
    assert eng.prefill_chunks == 12          # two a prompt
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in sizes)
    assert snap["ssm_rows"] == rows * 5
    assert snap["ssm_slot_steps"] % 5 == 0 and snap["ssm_slot_steps"] > 0
    assert snap["attn_ctx_tokens"] > 0
    # every row routes 4 assignments in each of 5 layers; a quarter of
    # the experts is held, and about a quarter of them land
    assert snap["moe_assignments_routed"] == rows * 4 * 5
    assert 0 < snap["moe_assignments"] < snap["moe_assignments_routed"]
    assert sum(snap["moe_load"]) == snap["moe_assignments"]
    assert len(snap["moe_load"]) == 4
    assert 0.1 < snap["moe_assignments"] / snap["moe_assignments_routed"] \
        < 0.45


def test_a_reused_slot_serves_as_a_fresh_engine_does(params, cfg):
    prompt = np.random.default_rng(4).integers(0, 211, 16).astype(np.int32)
    used = engine(params, cfg, slots=1)
    serve(used, [(32, 4)], seed=9)
    used.submit(Request(prompt, 4, request_id="again"))
    second = used.run()["again"]
    fresh = engine(params, cfg, slots=1)
    fresh.submit(Request(prompt, 4, request_id="again"))
    first = fresh.run()["again"]
    assert list(second.tokens) == list(first.tokens)
    assert gap(params, cfg, second) <= TOL
    for a, b in zip(used.kv.states, fresh.kv.states):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # the state the drain left is the reference's after the last token
    # the engine consumed (all but the last it produced)
    _, states, _ = forward(params, cfg, np.asarray(second.tokens[:-1]))
    mats = np.concatenate([np.asarray(s) for s in used.kv.states[5:]])
    np.testing.assert_allclose(mats[:, 0], states,
                               atol=TOL * np.abs(states).max())


@pytest.fixture(scope="module")
def sound(params, cfg):
    seq = np.random.default_rng(8).integers(0, 211, 24).astype(np.int32)
    return (seq,) + forward(params, cfg, seq)[:2]


@pytest.mark.parametrize("omit", ref.OMISSIONS)
def test_the_comparison_notices_each_omission(params, cfg, sound, omit):
    seq, want, states = sound
    got, other, _ = forward(params, cfg, seq, omit=omit, carry_at=16)
    moved = np.abs(got - want).max() / want.std()
    if omit == "state_bf16":
        # rounding the state moves the state; the logits hardly
        moved = np.abs(other - states).max() / np.abs(states).max()
        assert moved > 1e-4
    else:
        assert moved > 100 * TOL, (omit, moved)


def test_seeded_weights_leave_no_part_vanishing(params, cfg):
    stats = {}
    seq = np.random.default_rng(2).integers(0, 211, 48).astype(np.int32)
    ref.forward(params, cfg, seq, NAME, held=cfg.held_experts, stats=stats)
    assert [s["kind"] for s in stats["layers"]] == list(PATTERN)
    for s in stats["layers"]:
        assert 0.02 < s["part"] / s["residual"] < 2.0, s
    assert 0.3 < stats["logits"] < 5.0


# ------------------------------------------------------------------ #
# an expert layer told which experts it holds
# ------------------------------------------------------------------ #

def layer_inputs(c, rows=24, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, c.hidden_size),
                             jnp.float32)


def share_of(p, us, first, count):
    """The parameters of a layer that holds experts ``[first, first +
    count)``: the expert leaves cut, everything else as it is."""
    cut = dict(p)
    for leaf in ("up", "down"):
        k = f"{us}_moe_experts_{leaf}"
        cut[k] = p[k][first:first + count]
    return cut


def test_four_shares_add_up_to_the_uncut_layer(whole):
    """Guide section 4's share test: the four shares' routed parts, with
    the latent projections, the router and the shared expert counted
    once, are the uncut reference's whole layer."""
    c, p = whole
    us = f"{NAME}_h1"
    u = layer_inputs(c)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(p, us, c, u)
        spec = c.routed_spec()
        total = 0.0
        for r in range(4):
            rt = spec._replace(held_first=4 * r, held=4)
            y = md.routed_ffn(share_of(p, us, 4 * r, 4), us, u, rt)
            total = total + (y - want["shared"])
            # and each share is the reference's for that share
            one = ref.expert_layer(p, us, c, u, held=(4 * r, 4))
            np.testing.assert_allclose(y, one["out"], atol=2e-5)
            assert float(jnp.abs(one["routed"]).max()) > 0
        np.testing.assert_allclose(total + want["shared"], want["out"],
                                   atol=5e-5)
        # the layer that holds them all says the same
        np.testing.assert_allclose(md.routed_ffn(p, us, u, spec),
                                   want["out"], atol=5e-5)


def test_a_share_at_a_decode_waves_rows_runs_the_kernel_like_the_reference(
        whole):
    """ISSUE 49: 32 rows x top-4 are 128 sorted rows, ONE whole row tile,
    of which a quarter can land on the 4 experts held (8 a group, most of
    the tile past the groups' sum): the squared-ReLU layer's two products
    run through ``kernels/grouped_matmul`` (interpreted) and give the
    reference's share."""
    c, p = whole
    us = f"{NAME}_h1"
    u = layer_inputs(c, rows=32, seed=9)
    rt = c.routed_spec()._replace(held_first=8, held=4)
    cut = share_of(p, us, 8, 4)
    assert md.takes_kernel(32 * rt.top_k)
    text = str(jax.make_jaxpr(lambda x: md.routed_ffn(cut, us, x, rt))(u))
    assert text.count("pallas_call") == 2 and "ragged_dot" not in text
    stats = {}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(p, us, c, u, held=(8, 4))
        y = md.routed_ffn(cut, us, u, rt, stats=stats)
    assert 0 < int(stats["load"].sum()) < int(stats["routed"]) == 128
    np.testing.assert_allclose(y, want["out"], atol=2e-5)
    assert float(jnp.abs(want["routed"]).max()) > 0


def test_every_expert_held_is_todays_routed_ffn_bit_for_bit():
    """``held == E`` on a gated spec: the same lowered text and the same
    bits as a spec that says nothing of a share."""
    E, k, D, F, T = 8, 2, 32, 48, 24
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    us = "m_h0"
    p = {f"{us}_moe_router_weight": jax.random.normal(keys[0], (D, E)),
         f"{us}_moe_router_bias": 0.1 * jax.random.normal(keys[1], (E,)),
         f"{us}_moe_experts_gate": 0.2 * jax.random.normal(keys[2], (E, D, F)),
         f"{us}_moe_experts_up": 0.2 * jax.random.normal(keys[3], (E, D, F)),
         f"{us}_moe_experts_down": 0.2 * jax.random.normal(keys[4],
                                                           (E, F, D))}
    x = jax.random.normal(keys[5], (T, D))
    valid = jnp.arange(T) % 5 != 0
    today = md.RoutedSpec(num_experts=E, top_k=k, scale=1.5)
    told = today._replace(held_first=0, held=E)
    assert not told.holds_a_share and told.held_experts == E

    def run(spec):
        stats = {}
        fn = jax.jit(lambda p, x: md.routed_ffn(p, us, x, spec, valid,
                                                stats))
        return fn(p, x), fn.lower(p, x).as_text(), stats

    a, text_a, stats_a = run(today)
    b, text_b, stats_b = run(told)
    assert text_a == text_b
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "routed" not in stats_a and "routed" not in stats_b


def test_a_row_whose_experts_are_all_absent_gets_the_shared_expert(whole):
    c, p = whole
    us = f"{NAME}_h3"
    u = layer_inputs(c, rows=64, seed=3)
    sel, _ = md.route(u, p[f"{us}_moe_router_weight"],
                      p[f"{us}_moe_router_bias"], c.routed_spec())
    sel = np.asarray(sel)
    # the share none of whose experts the first row chose
    first = next(f for f in range(0, 16, 2)
                 if not np.isin(sel[0], [f, f + 1]).any())
    rt = c.routed_spec()._replace(held_first=first, held=2)
    stats = {}
    y = md.routed_ffn(share_of(p, us, first, 2), us, u, rt, stats=stats)
    shared = ref.expert_layer(p, us, c, u)["shared"]
    absent = ~np.isin(sel, [first, first + 1]).any(axis=1)
    assert absent[0] and not absent.all()
    np.testing.assert_allclose(np.asarray(y)[absent],
                               np.asarray(shared)[absent], atol=1e-5)
    assert np.abs(np.asarray(y - shared)[~absent]).max() > 1e-3
    # the load counts what landed; ``routed`` everything
    assert int(stats["routed"]) == 64 * 4
    assert int(stats["load"].sum()) == int(
        np.isin(sel, [first, first + 1]).sum())


def test_weights_are_normalised_over_all_the_chosen(whole):
    """A share's routed part is NOT what a layer of its experts alone
    would compute: the sum under the weights runs over all 4 chosen."""
    c, p = whole
    us = f"{NAME}_h1"
    u = layer_inputs(c, seed=4)
    right = ref.expert_layer(p, us, c, u, held=(0, 8))
    wrong = ref.expert_layer(p, us, c, u, held=(0, 8), omit="norm_held")
    y = md.routed_ffn(share_of(p, us, 0, 8), us, u,
                      c.routed_spec()._replace(held_first=0, held=8))
    np.testing.assert_allclose(y, right["out"], atol=2e-5)
    assert float(jnp.abs(wrong["out"] - right["out"]).max()) > 1e-2


@pytest.mark.parametrize("rows,spec,kernel", [
    # a decode wave of 64 slots x 22: 1,408 sorted rows, 352 landing on
    # 128 experts, 2.75 a group: the kernel since PR 49 (its sweep read
    # 1.04 ms a layer there against ``ragged_dot``'s 2.78)
    (1408, (512, 128), True),
    # a packed chunk wave of 1,024 rows x 22: 5,632 landing, 44 a group
    (22528, (512, 128), True),
    # every expert held
    (1024, (64, 64), True), (128, (64, 64), True), (1000, (8, 8), False),
], ids=["share_decode", "share_chunk", "whole_chunk", "whole_decode",
        "no_whole_tiles"])
def test_takes_kernel_is_asked_with_the_rows_that_land_here(rows, spec,
                                                            kernel):
    """Since PR 49 the rule reads the sorted rows alone, whatever share
    of them can land here: the kernel's steps follow the groups that
    have rows, and a row tile past the groups' sum has none."""
    _, held = spec
    assert md.takes_kernel(rows) is kernel
    load = jnp.zeros((held,), jnp.int32).at[held // 2].set(3)
    tiles = md.kernel_tiles(load, rows)
    assert (tiles is not None) is kernel
    if kernel:
        # one group has rows: one live step of the static grid
        assert [int(n) for n in tiles.steps] == [1, 1]


@pytest.mark.parametrize("T,kernel", [(64, True), (1024, True), (5, False)],
                         ids=["decode_wave", "chunk_wave", "no_whole_tiles"])
def test_a_layer_that_holds_a_share_traces_the_product_the_rule_names(
        T, kernel):
    """Both products of a layer follow the rule's ONE answer for its
    sorted rows: 64 slots x 22 are 1,408, eleven row tiles of which
    three can be expected live (352 landing on 128), and since PR 49
    the kernel's; 5 rows x 22 are no whole tiles."""
    spec = md.RoutedSpec(num_experts=512, top_k=22, scale=5.0, held=128,
                         latent=64, expert="relu2", n_shared=1)
    us, D = "m", 32
    z = jnp.zeros
    p = {f"{us}_moe_router_weight": z((D, 512)),
         f"{us}_moe_router_bias": z((512,)),
         f"{us}_moe_latent_in_weight": z((D, 64)),
         f"{us}_moe_latent_out_weight": z((64, D)),
         f"{us}_moe_experts_up": z((128, 64, 16)),
         f"{us}_moe_experts_down": z((128, 16, 64)),
         f"{us}_moe_shared_up_weight": z((D, 8)),
         f"{us}_moe_shared_down_weight": z((8, D))}
    text = str(jax.make_jaxpr(
        lambda x: md.routed_ffn(p, us, x, spec))(z((T, D))))
    assert (text.count("pallas_call") == 2) is kernel
    assert ("ragged_dot" in text) is not kernel


def test_squared_relu_is_the_kernels_epilogue_and_the_compilers_pass():
    """The two tilings of ``grouped_matmul`` agree on ``relu(x W) ** 2``
    (the Pallas kernel interpreted: its epilogue on the accumulator)."""
    G, K, N, M = 4, 32, 48, 256
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(keys[0], (M, K), jnp.float32)
    rhs = jax.random.normal(keys[1], (G, K, N), jnp.float32)
    sizes = jnp.asarray([60, 0, 100, 40], jnp.int32)     # 56 rows nobody's
    tiles = md.kernel_tiles(sizes, M)
    assert tiles is not None
    with jax.default_matmul_precision("highest"):
        by_kernel = md.grouped_matmul(lhs, rhs, sizes, tiles=tiles,
                                      act="relu2")
        by_dot = jnp.square(jax.nn.relu(
            jax.lax.ragged_dot(lhs, rhs, sizes)))
    np.testing.assert_allclose(np.asarray(by_kernel)[:200],
                               np.asarray(by_dot)[:200], rtol=1e-5,
                               atol=1e-5)
    from hetu_tpu.kernels.grouped_matmul import grouped_matmul_tiled
    with pytest.raises(ValueError, match="relu2"):
        grouped_matmul_tiled(lhs, rhs, tiles, up=rhs, act="relu2")


# ------------------------------------------------------------------ #
# counters, events, hetu_trace and hetu_top
# ------------------------------------------------------------------ #

def test_a_served_wave_stamps_held_beside_routed(params, cfg):
    from hetu_tpu.telemetry import top, trace
    eng = engine(params, cfg)
    events = []
    eng.metrics.event = lambda kind, **f: events.append(
        dict(f, event=kind))
    serve(eng, [(20, 4), (9, 5)])
    steps = [e for e in events if e["event"] == "serve_step"]
    assert steps and all("moe_held" in e for e in steps)
    for e in steps:
        assert e["moe_routed"] == e["moe_tokens"] * 4 * 5
        assert 0 <= e["moe_held"] <= e["moe_routed"]
    assert trace.check_moe_attribution(steps) == []
    bad = dict(steps[0], moe_held=steps[0]["moe_routed"] + 1)
    assert "held experts" in trace.check_moe_attribution([bad])[0]
    panel = top.summarize(steps)["moe"]
    held = sum(e["moe_held"] for e in steps)
    routed = sum(e["moe_routed"] for e in steps)
    assert panel["held_share"] == round(held / routed, 4)
    assert f"held {panel['held_share']:.4f}" in top.render(
        top.summarize(steps))


def test_an_engine_that_holds_every_expert_counts_routed_as_landed(whole):
    c, p = whole
    eng = engine(p, c)
    serve(eng, [(12, 3)])
    snap = eng.metrics.snapshot()
    assert snap["moe_assignments"] == snap["moe_assignments_routed"] \
        == (12 + 3 - 1) * 4 * 5
    assert len(snap["moe_load"]) == 16


def test_the_latent_projections_have_their_own_scopes():
    low = nemotron_programs(jax.ShapeDtypeStruct, "masked", qs=(1,))[
        "nemotron.Q1.fresh0"]
    text = low.as_text(debug_info=True)
    for scope in ("moe_latent_in", "moe_latent_out", "moe_route",
                  "moe_experts", "moe_shared", "ssm_in", "ssm_conv",
                  "ssm_scan", "state_write", "ssm_out", "attention",
                  "lm_head", "wave_decode"):
        assert scope in text, scope


# ------------------------------------------------------------------ #
# the programs ``test_program_digests`` pins
# ------------------------------------------------------------------ #

def nemotron_programs(sds, attn, qs=(1, 32), slots=4, ssm_state=16):
    """{name: lowered mixed step} of a small ``nemotron_h`` configuration
    whose expert layers hold a quarter of 16 experts; ``sds(shape,
    dtype)`` makes the abstract arguments.  At 32 ``slots`` a decode
    wave's 32 x top-4 sorted rows are one whole row tile; at an
    ``ssm_state`` of 128 columns the mixers' one-row slots take
    ``kernels/ssm_step``."""
    from hetu_tpu.kv_layout import kv_row_width

    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    c = nh.NemotronHConfig.from_hf(dict(
        SMALL, hidden_size=256, num_attention_heads=4, head_dim=64,
        mamba_num_heads=4, mamba_head_dim=32, moe_latent_size=128,
        moe_intermediate_size=128, moe_shared_expert_intermediate_size=256,
        vocab_size=512, max_position_embeddings=128,
        ssm_state_size=ssm_state), held_experts=HELD)
    blk = c.block_spec()
    p = {k: sds(s, jnp.float32 if "router" in k or k.endswith(
        ("_ssm_dt_bias", "_ssm_A_log", "_ssm_D")) else jnp.bfloat16)
        for k, s in c.param_shapes(NAME).items()}
    state = tuple(
        sds((sh[0], B) + tuple(sh[1:]), jnp.bfloat16 if dt is None else dt)
        for sh, dt in blk.state_shapes(11, 256))
    pool = sds((1, N, BS, kv_row_width(2, 64)), jnp.bfloat16)
    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"nemotron.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, ("nmh", 11, 4, 64, 128, blk), pool, pool, i32(B, T),
                i32(B), i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
                sds((B,), jnp.float32), i32(B),
                sds((B, 2), jnp.uint32), attn=attn, window=1,
                has_fresh=fresh, state=state)
    return out
