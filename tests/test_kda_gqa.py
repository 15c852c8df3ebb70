"""The ``solar_open2`` decoder (``KDAGQAConfig``: the published delta rule,
unbounded decay, low-rank projections, a gate a column, beta in (0, 2),
beside gated position-free grouped-query attention in ONE block and ONE
manager, a plain top-k sigmoid router over a held share of the experts in
EVERY layer, an untied head over held rows) on the serving path, at a
small size on the CPU (ISSUE 62): hidden 32, 4 heads of 16 over 2 K/V
heads, four layers (GQA, KDA, KDA, KDA), 16 experts, top 2, 2 of them
held, 96 of 128 table rows held, paged block 4 and chunks of 8.  Every
comparison is of LOGITS against the plain reference's full forward
(``models/reference_kda_gqa.py``), never of tokens alone.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import kda_decode as kd
from hetu_tpu.models import reference_kda_gqa as ref
from hetu_tpu.models.kda_gqa import KDAGQAConfig, init_kda_gqa_params
from hetu_tpu.models.moe_decode import RoutedSpec, route, routed_ffn
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager

from jitted import reference  # noqa: E402

NAME = "slr"
SMALL = dict(
    model_type="solar_open2", partial_rotary_factor=1,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
    head_dim=16, num_key_value_heads=2, vocab_size=128,
    intermediate_size=48, moe_intermediate_size=16, rms_norm_eps=1e-5,
    rope_theta=10000, tie_word_embeddings=False,
    max_position_embeddings=256, first_k_dense_replace=0, use_rope=False,
    gqa_interval=3, gqa_layers=[0], use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    n_routed_experts=16, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, num_experts_per_tok=2)
HELD, ROWS = (2, 2), (0, 96)
# float32 weights, pools and states on both sides: what is left is the
# order of the sums
TOL = 2e-4
SIZES = [(5, 6), (12, 9), (30, 5), (61, 8), (21, 7), (17, 4)]
# decays that MOVE inside a test's few dozen positions: the family's
# draw (a memory of twenty tokens and up) would leave them at 1
DT = (0.05, 2.0)


@functools.lru_cache(maxsize=None)
def built(held=HELD, **over):
    cfg = KDAGQAConfig.from_hf(SMALL, held_experts=held, vocab_rows=ROWS,
                               **over)
    return cfg, init_kda_gqa_params(cfg, name=NAME, seed=3, dt_range=DT)


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
    a chunk (5), of several (30, 61), decoding beside chunks; the fifth
    and sixth take slots the first ones gave back."""
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
    assert blk.ops == ("attention", "kda", "kda", "kda")
    assert (blk.norm, blk.residual, blk.attention, blk.head,
            blk.positions, blk.kv_heads, blk.attn_gate) == (
        "rmsnorm", "sequential", "gqa", "untied", "none", 2, True)
    assert blk.kda == kd.KDASpec(4, 16, 4, decay="softplus", rank=16,
                                 gate_by="channel", beta_scale=2.0)
    assert blk.kda.unbounded and blk.kda.fits()
    assert blk.routed == RoutedSpec(16, 2, 1.0, True, 1, held_first=2,
                                    held=2)
    assert (blk.op_layers(4, "pool"), blk.op_layers(4, "state"),
            blk.op_layers(4, "kda")) == (1, 3, 3)
    assert (blk.op_index(0), blk.op_index(1), blk.op_index(3)) == (0, 0, 2)
    assert all(blk.ffn_kind(i) == "routed" for i in range(4))
    # three layers' conv tails (the pool's dtype), then their states
    assert blk.state_shapes(4, 32) == (((1, 3, 192), None),) * 3 + (
        ((1, 4, 16, 16), jnp.dtype("float32")),) * 3
    gd.check_block_spec(blk, 4)
    hash(blk)                                  # jit-static
    assert cfg.vocab_size == 96 and cfg.published_vocab_size == 128
    shapes = cfg.param_shapes(NAME)
    assert shapes["slr_h1_kda_qkv_weight"] == (32, 192)
    assert shapes["slr_h1_kda_f_a_weight"] == (32, 16)
    assert shapes["slr_h1_kda_f_b_weight"] == (16, 64)
    assert shapes["slr_h1_kda_gate_b_weight"] == (16, 64)
    assert shapes["slr_h1_kda_gate_bias"] == (64,)
    assert "slr_h1_kda_f_weight" not in shapes
    assert shapes["slr_h0_attn_q_weight"] == (32, 64)
    assert shapes["slr_h0_attn_k_weight"] == (32, 32)
    assert shapes["slr_h0_attn_gate_weight"] == (32, 64)
    assert shapes["slr_h0_moe_router_weight"] == (32, 16)   # every layer
    assert shapes["slr_h3_moe_experts_down"] == (2, 16, 32)
    assert params["slr_h1_kda_A_log"].dtype == jnp.float32
    assert params["slr_h1_moe_router_bias"].dtype == jnp.float32
    full = KDAGQAConfig.from_hf(dict(SMALL, kda_use_full_proj=True))
    assert full.param_shapes(NAME)["slr_h1_kda_gate_weight"] == (32, 64)


REFUSED = [
    {"use_rope": True}, {"tie_word_embeddings": True},
    {"first_k_dense_replace": 1}, {"norm_topk_prob": False},
    {"score_function": "softmax"}, {"scoring_func": "softmax"},
    {"n_group": 4}, {"topk_group": 2}, {"rope_scaling": {"type": "yarn"}},
    {"attention_bias": True}, {"hidden_act": "gelu"}, {"use_qk_norm": True},
    {"kda_safe_gate": True}, {"sliding_window": 64},
    {"linear_attn_config": dict(SMALL["linear_attn_config"],
                                num_kv_heads=2)},
    {"linear_attn_config": dict(SMALL["linear_attn_config"], num_heads=2)},
    {"linear_attn_config": dict(SMALL["linear_attn_config"], head_dim=32)},
    {"gqa_layers": [4]}, {"gqa_layers": []}, {"gqa_layers": [0, 1, 2, 3]},
    {"gqa_layers": [0, 0]},
    # sizes that do not fit
    {"num_key_value_heads": 3}, {"num_experts_per_tok": 17},
    {"linear_attn_config": dict(SMALL["linear_attn_config"],
                                short_conv_kernel_size=1)}]


@pytest.mark.parametrize("change", REFUSED, ids=lambda c: ",".join(
    f"{k}={v}" for k, v in c.items())[:48])
def test_class_raises_by_name(change):
    with pytest.raises(ValueError, match="KDAGQAConfig") as e:
        KDAGQAConfig.from_hf(dict(SMALL, **change))
    if "cannot run" in str(e.value):
        assert next(iter(change)) in str(e.value)


@pytest.mark.parametrize("held, rows", [((15, 2), None), ((0, 0), None),
                                        (None, (100, 40))])
def test_class_refuses_a_share_that_does_not_fit(held, rows):
    with pytest.raises(ValueError, match="sizes do not fit"):
        KDAGQAConfig.from_hf(SMALL, held_experts=held, vocab_rows=rows)


def spec_with(**over):
    return built()[0].block_spec()._replace(**over)


def test_check_block_spec_takes_kda_beside_attention():
    gd.check_block_spec(spec_with(), 4)
    gd.check_block_spec(spec_with(attn_gate=False), 4)
    gd.check_block_spec(spec_with(positions="rope"), 4)
    gd.check_block_spec(spec_with(ops=("kda", "attention") * 2), 4)


@pytest.mark.parametrize("blk", [
    # two kinds of slot state in one spec: the manager holds one set
    lambda: spec_with(ops=("attention", "kda", "conv", "kda"),
                      conv_kernel=3),
    # no attention layer beside the delta rule, or another operator
    lambda: spec_with(ops=("kda",) * 4),
    lambda: spec_with(ops=("attention", "kda", "window_attention", "kda"),
                      window=8),
    # named without its spec, and a spec without its layers
    lambda: spec_with(kda=None),
    lambda: spec_with(ops=("attention",) * 4),
    # values the mixer does not run
    lambda: spec_with(kda=built()[0].kda._replace(decay="relu")),
    lambda: spec_with(kda=built()[0].kda._replace(gate_by="row")),
    lambda: spec_with(kda=built()[0].kda._replace(beta_scale=3.0)),
    lambda: spec_with(kda=built()[0].kda._replace(rank=-1)),
    # the gate a column goes with the sequential residual
    lambda: spec_with(ops=("attention",) * 4, kda=None,
                      residual="parallel"),
])
def test_check_block_spec_refuses(blk):
    with pytest.raises(ValueError, match="the mixed wave runs") as e:
        gd.check_block_spec(blk(), 4)
    for op in gd.OPERATORS:
        assert op in str(e.value)


def test_the_latent_block_has_no_column_gate():
    from test_kda_latent import built as ling
    with pytest.raises(ValueError, match="the mixed wave runs"):
        gd.check_block_spec(
            ling()[0].block_spec()._replace(attn_gate=True), 4)


# ------------------------------------------------------------------ #
# the engine against the reference's full forward
# ------------------------------------------------------------------ #

def test_engine_serves_the_reference(served):
    eng, out, snap = served
    assert not eng.kv.latent and eng.kv.stateful
    assert eng.kv.pool_layers == 1 and eng.kv.cache_v is not None
    assert len(eng.kv.states) == 6
    # six requests on four slots: slots were given back and claimed again
    assert eng.kv.state_resets == 6 and eng.kv.free_slots == 4
    for i, (n, m) in enumerate(SIZES):
        r = out[f"r{i}"]
        assert len(r.tokens) == n + m
        assert gap(r) < TOL, (i, n, m)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_comparison_notices_what_is_left_out(served, wrong):
    _, out, _ = served
    assert max(gap(out[f"r{i}"], (wrong,)) for i in range(6)) > 50 * TOL


def test_counters_in_the_windowed_snapshot(served):
    _, _, snap = served
    prompt_rows = sum(n for n, _ in SIZES)
    # (``tests/test_kda_latent.py`` has the arithmetic: 17 = 2 x 8 + 1)
    assert snap["kda_chunk_rows"] == 3 * (prompt_rows - 1)
    assert snap["kda_slot_steps"] == 3 * (sum(m - 1 for _, m in SIZES) + 1)
    assert snap["attn_ctx_tokens"] > 0
    assert snap["moe_assignments_routed"] > snap["moe_assignments"] > 0


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
# the chunked form with a decay free of any bound
# ------------------------------------------------------------------ #

def draw(Q, B=2, H=2, D=16, seed=0, step=-30.0):
    """Rows whose every channel decays by ``step`` a row over WHOLE
    sub-blocks (rows 16-31 and 48-63 where there are any) and by up to
    ``step`` elsewhere; beta in (0, 2)."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, Q, H, D)).astype(np.float32)
               for _ in range(3))
    q, k = np.asarray(kd.l2norm(jnp.asarray(q))), np.asarray(
        kd.l2norm(jnp.asarray(k)))
    g = step * rng.uniform(size=(B, Q, H, D)).astype(np.float32) ** 4
    at = (np.arange(Q) // kd.SUB) % 2 == 1
    g[:, at] = step
    beta = rng.uniform(0.1, 2.0, size=(B, Q, H)).astype(np.float32)
    S = rng.normal(size=(B, H, D, D)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta, S))


def stepwise(q, k, v, g, beta, S):
    ys = []
    for t in range(q.shape[1]):
        y, S = kd.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("Q", [1, 15, 16, 17, 33, 64, 65, 200])
def test_level_by_level_form_is_the_recurrence_at_minus_30_a_step(Q):
    args = draw(Q, seed=Q)
    want_y, want_S = jax.jit(stepwise)(*args)
    got_y, got_S = jax.jit(
        lambda *a: kd.kda_chunked(*a, exact=True))(*args)
    assert np.isfinite(np.asarray(got_y)).all()
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_S, want_S, atol=2e-4, rtol=2e-4)


def test_the_sub_block_form_does_not_survive_it():
    """The bounded gate's factoring (a column's decay up to its own
    sub-block's beginning) overflows where the level-by-level one does
    not: why ``KDASpec.unbounded`` chooses."""
    args = draw(64, seed=1)
    got_y, _ = jax.jit(kd.kda_chunked)(*args)
    assert not np.isfinite(np.asarray(got_y)).all()


@pytest.mark.parametrize("c", [16, 48, 64])
def test_every_pair_meets_at_one_level_with_sums_of_g_alone(c):
    rows, cols, pairs = kd._levels(c)
    assert pairs.sum(0).tolist() == np.tril(np.ones((c, c), int),
                                            -1).tolist()
    # a pair's two factors sum g over (j, i]: nothing else, nothing twice
    for lv, i, j in zip(*np.nonzero(pairs)):
        want = np.zeros(c, int)
        want[j + 1:i + 1] = 1
        assert (rows[lv, i].astype(int) + cols[lv, j]).tolist() \
            == want.tolist()


def test_beta_over_one_turns_the_state_over():
    """``I - beta k k^T`` with beta in (1, 2) has a NEGATIVE eigenvalue:
    what the state holds along ``k`` changes sign in one step, which a
    beta in (0, 1) never does."""
    k = jnp.zeros((1, 1, 16)).at[0, 0, 3].set(1.0)
    S = jnp.zeros((1, 1, 16, 16)).at[0, 0, 3].set(1.0)
    zero = jnp.zeros((1, 1, 16))
    for beta, sign in ((1.8, -1.0), (0.8, 1.0)):
        _, out = kd.kda_step(k, k, zero, zero, jnp.full((1, 1), beta), S)
        np.testing.assert_allclose(out[0, 0, 3], sign * abs(1 - beta)
                                   * np.ones(16), atol=1e-6)
    # and the chunked form carries the sign through a q-block
    q, kk, v, g, beta, S0 = draw(40, seed=3, step=-0.01)
    beta = jnp.full_like(beta, 1.9)
    want_y, want_S = stepwise(q, kk, v * 0, g, beta, S0)
    _, got_S = kd.kda_chunked(q, kk, v * 0, g, beta, S0, exact=True)
    np.testing.assert_allclose(got_S, want_S, atol=2e-4)
    assert float((np.sign(want_S) != np.sign(S0)).mean()) > 0.3


# ------------------------------------------------------------------ #
# the router and the held share
# ------------------------------------------------------------------ #

def test_route_agrees_with_the_reference_router():
    cfg, params = built()
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    us = "slr_h3"
    sel, w = route(x, params[f"{us}_moe_router_weight"],
                   params[f"{us}_moe_router_bias"], cfg.routed_spec())
    with jax.default_matmul_precision("highest"):
        chosen, want = ref.route(params, us, cfg, x)
    got = np.zeros((64, 16), np.float32)
    np.put_along_axis(got, np.asarray(sel), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-5)


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    whole_cfg, whole = built(held=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    us = "slr_h2"
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.ffn_parts(whole, us, whole_cfg, x)
    total = 0.0
    for rank in range(8):
        held = (2 * rank, 2)
        cfg = KDAGQAConfig.from_hf(SMALL, held_experts=held,
                                   vocab_rows=ROWS)
        part = dict(whole)
        for leaf in ("gate", "up", "down"):
            part[f"{us}_moe_experts_{leaf}"] = whole[
                f"{us}_moe_experts_{leaf}"][2 * rank:2 * rank + 2]
        y = routed_ffn(part, us, x, cfg.routed_spec())
        # what every chip computes alike is counted once
        total = total + (y - shared)
        # and a share alone is the reference's share
        with jax.default_matmul_precision("highest"):
            mine, _ = ref.ffn_parts(part, us, cfg, x, held)
        np.testing.assert_allclose(y - shared, mine, atol=1e-4)
    np.testing.assert_allclose(total + shared, routed + shared, atol=2e-4)


# ------------------------------------------------------------------ #
# one manager: a K/V pool AND the delta rule's set of slot states
# ------------------------------------------------------------------ #

def manager(**kw):
    blk = built()[0].block_spec()
    return PagedKVManager(**dict(dict(
        layers=1, heads=2, head_dim=16, slots=3, max_seq_len=32, block=4,
        state_shapes=blk.state_shapes(4, 32)), **kw))


def test_one_manager_holds_the_kv_pool_and_the_states():
    kv = manager()
    assert not kv.latent and kv.stateful
    assert kv.n_slots == 3                     # exact, not rounded to 4
    assert kv.cache_k.shape == kv.cache_v.shape
    assert kv.cache_k.shape[:3] == (1, 3 * 8 + 1, 4)
    assert [s.shape for s in kv.states] == [(1, 3, 3, 192)] * 3 + [
        (1, 3, 4, 16, 16)] * 3
    assert [s.dtype for s in kv.states[3:]] == [jnp.float32] * 3
    assert kv.state_bytes == sum(s.nbytes for s in kv.states)
    assert not kv.prefix_share
    # a claimed slot's states are zeroed, the other slots' stay
    kv.state = tuple(jnp.ones_like(s) for s in kv.states)
    slot, cached = kv.alloc("a", list(range(6)), 10)
    assert slot is not None and cached == 0 and kv.state_resets == 1
    for s in kv.states:
        assert float(jnp.abs(s[:, slot]).max()) == 0.0
        assert float(jnp.delete(s, slot, axis=1).min()) == 1.0
    assert kv.n_table[slot] == 3                    # 10 positions, block 4
    kv.release(slot)
    assert kv.free_slots == 3 and kv.free_blocks == 24


@pytest.mark.parametrize("what", ["truncate", "export", "import", "prefix"])
def test_what_the_state_refuses_stays_refused(what):
    if what == "prefix":
        with pytest.raises(ValueError, match="prefix_share"):
            manager(prefix_share=True)
        return
    kv = manager()
    slot, _ = kv.alloc("a", list(range(6)), 10)
    kv.advance(slot, 6)
    with pytest.raises(ValueError, match="slot-indexed state"):
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

def kda_gqa_programs(sds, attn, qs=(1, 64), slots=4):
    """{name: lowered mixed step} of the small four-layer model at
    widths of whole lane tiles (heads of 128, so that the chunk program
    takes ``kda_chunk_scan`` with ``exact`` and the K/V kernels lower
    for the chip): the K/V pool pair, then the manager's set of six
    states, donated."""
    def i32(*s):
        return sds(s, jnp.int32)

    B, T, N, BS = slots, 8, 33, 16
    c = KDAGQAConfig.from_hf(dict(
        SMALL, hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, moe_intermediate_size=128,
        linear_attn_config=dict(SMALL["linear_attn_config"], head_dim=128,
                                num_heads=2)),
        held_experts=HELD, vocab_rows=ROWS)
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
            out[f"kda_gqa.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (NAME, 4, 2, 128, 128, blk), pool, pool, i32(B, T),
                i32(B), i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
                sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                attn=attn, window=1, has_fresh=fresh, state=state)
    return out


def test_the_wave_traces_the_new_scopes_for_this_spec_alone():
    text = kda_gqa_programs(jax.ShapeDtypeStruct, "masked", qs=(64,))[
        "kda_gqa.Q64.fresh1"].as_text(debug_info=True)
    for scope in ("kda_qkvg", "kda_conv", "kda_scan", "state_write",
                  "kda_out", "attn_qkv", "kv_write", "attention",
                  "gqa_gate", "attn_out", "moe_route", "moe_experts",
                  "moe_shared", "lm_head"):
        assert f"/{scope}" in text, scope
    assert "kda_chunk_scan" in text            # heads of 128: the kernel
    from test_kda_latent import kda_latent_programs
    other = kda_latent_programs(jax.ShapeDtypeStruct, "masked", qs=(32,))[
        "kda_latent.Q32.fresh1"].as_text(debug_info=True)
    assert "/gqa_gate" not in other
