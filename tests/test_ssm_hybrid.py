"""The state-space hybrid block (``ssm_decode.SSMHybridConfig``, the
``falcon_h1`` family) on the mixed wave: the engine's logits through the
paged pool and the slot states against ``reference_ssm_hybrid``'s full
forward, float32 both sides on the CPU.

Tolerance: 1e-4 of the logits' spread (their standard deviation is of
order 1 at these seeded weights), absolute.  Both sides are float32; what
differs is the order of the sums (the chunked form's products and one
state update a chunk against a step a position; the paged softmax against
a dense one; a packed wave's gathers), each a relative 1e-6 or so a
product, through 4 layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_ssm_hybrid as ref
from hetu_tpu.models import ssm_decode as sd
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager

from jitted import mixed_wave, reference

TOL = 1e-4
NAME = "fh1"

SMALL = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    rope_theta=1e11, rms_norm_eps=1e-5, max_position_embeddings=256,
    embedding_multiplier=5.5, attention_in_multiplier=0.9,
    attention_out_multiplier=0.04, key_multiplier=0.3,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.09,
    ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.36],
    mlp_multipliers=[0.18, 0.05], lm_head_multiplier=0.02,
    model_type="falcon_h1")


@pytest.fixture(scope="module")
def cfg():
    return sd.SSMHybridConfig.from_hf(SMALL)


@pytest.fixture(scope="module")
def params(cfg):
    return sd.init_ssm_hybrid_params(cfg, NAME, seed=3)


def engine(params, cfg, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("kv_block", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("fast_path", False)
    return ServingEngine(params, cfg, **kw)


def mixed_step(params, cfg_tuple, kv, plan, last_only=False):
    """One ``_mixed_step`` over the manager's pool and states for
    ``plan`` {slot: (tokens, pos)}; returns {slot: logits [n, V]}, every
    row's, or with ``last_only`` (a sampling window of 1, as the engine's:
    a chunk wave then packs) the last row's alone."""
    B = kv.n_slots
    width = max(len(t) for t, _ in plan.values())
    Q = gd._pow2(width)
    tokens = np.zeros((B, Q), np.int32)
    pos = np.zeros(B, np.int32)
    q_len = np.zeros(B, np.int32)
    for s, (t, p) in plan.items():
        tokens[s, :len(t)] = t
        pos[s], q_len[s] = p, len(t)
    first = np.maximum(q_len - 1, 0) if last_only else np.zeros(B, np.int32)
    logits, kv.cache_k, kv.cache_v, kv.state = mixed_wave(
        params, cfg_tuple, kv.cache_k, kv.cache_v, pos, tokens, q_len,
        first, np.zeros(B, bool), window=1 if last_only else Q,
        block_tables=jnp.asarray(kv.tables), has_fresh=Q > 1,
        state=kv.state)
    n = (lambda t: 1) if last_only else len
    return {s: np.asarray(logits)[s, :n(t)] for s, (t, _) in plan.items()}


def manager(cfg, slots=4, dtype=jnp.float32):
    blk = cfg.block_spec()
    L = cfg.num_hidden_layers
    return PagedKVManager(
        layers=blk.op_layers(L, "pool"), heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, slots=slots, max_seq_len=128, dtype=dtype,
        block=4, state_shapes=blk.state_shapes(L, cfg.hidden_size))


def tuple_of(cfg, kv):
    return (NAME, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.head_dim, kv.s_max, cfg.block_spec())


# ------------------------------------------------------------------ #
# the manager's set of slot states
# ------------------------------------------------------------------ #

def state_manager(**kw):
    """Two members of unlike rank and dtype beside a float32 pool."""
    return PagedKVManager(
        layers=2, heads=2, head_dim=8, slots=4, max_seq_len=32, block=4,
        state_shapes=(((2, 3, 24), None), ((2, 4, 8, 16), jnp.bfloat16)),
        **kw)


def test_manager_zeroes_every_member_of_a_claimed_slot():
    from hetu_tpu import telemetry
    kv = state_manager()
    tails, mats = kv.states
    assert tails.shape == (2, 4, 3, 24) and tails.dtype == jnp.float32
    assert mats.shape == (2, 4, 4, 8, 16) and mats.dtype == jnp.bfloat16
    assert kv.state_bytes == tails.nbytes + mats.nbytes \
        == kv.stats()["state_bytes"]
    assert telemetry.snapshot()["gauges"]["serve.state.bytes"] \
        == kv.state_bytes
    kv.state = tuple(jnp.ones_like(s) for s in kv.states)
    slot, _ = kv.alloc("a", np.arange(5, dtype=np.int32), 8)
    other = [s for s in range(4) if s != slot]
    for member in kv.states:
        member = np.asarray(member.astype(jnp.float32))
        assert not member[:, slot].any() and member[:, other].all()
    assert kv.state_resets == 1 and isinstance(kv.state, tuple)


@pytest.mark.parametrize("what", ["prefix_share", "truncate",
                                  "export_blocks", "import_blocks",
                                  "both_arguments"])
def test_manager_with_a_state_set_refuses_by_name(what):
    if what == "prefix_share":
        with pytest.raises(ValueError, match="prefix_share.*slot-indexed"):
            state_manager(prefix_share=True)
        return
    if what == "both_arguments":
        with pytest.raises(ValueError, match="state_shape OR state_shapes"):
            state_manager(state_shape=(2, 3, 24))
        return
    kv = state_manager()
    assert kv.stateful and not kv.prefix_share
    slot, _ = kv.alloc("a", np.arange(9, dtype=np.int32), 12)
    with pytest.raises(ValueError, match=f"{what}"):
        if what == "truncate":
            kv.truncate(slot, 4)
        elif what == "export_blocks":
            kv.export_blocks(slot)
        else:
            kv.import_blocks({"layout": "paged"}, "b")


def test_one_member_set_is_the_short_convolutions_state():
    """``state_shape=`` (PR 34) is the one-member case of the same code:
    the step is handed the array itself, not a tuple."""
    kv = PagedKVManager(layers=2, heads=2, head_dim=8, slots=4,
                        max_seq_len=32, block=4, state_shape=(3, 2, 16))
    assert len(kv.states) == 1 and kv.state is kv.states[0]
    assert kv.state.shape == (3, 4, 2, 16)
    kv.state = kv.state + 1.0
    slot, _ = kv.alloc("a", np.arange(5, dtype=np.int32), 8)
    assert not np.asarray(kv.state)[:, slot].any()
    assert PagedKVManager(layers=2, heads=2, head_dim=8, slots=4,
                          max_seq_len=32, block=4).state is None


# ------------------------------------------------------------------ #
# the spec
# ------------------------------------------------------------------ #

def test_block_spec_and_the_shapes_it_asks_for(cfg):
    blk = cfg.block_spec()
    gd.check_block_spec(blk, 4)
    assert blk.ops == ("attention+ssm",) * 4 and blk.head_dim == 16
    assert [blk.op_index(i, "pool") for i in range(4)] == [0, 1, 2, 3]
    assert [blk.op_index(i, "state") for i in range(4)] == [0, 1, 2, 3]
    assert blk.op_layers(4, "pool") == blk.op_layers(4, "state") == 4
    assert gd.head_dim_of(cfg) == 16
    # a layer's conv tail and matrix state are arrays of their own
    shapes = blk.state_shapes(4, 64)
    assert shapes[:4] == (((1, 3, 32 + 2 * 2 * 16), None),) * 4
    assert shapes[4:] == (((1, 4, 8, 16), jnp.float32),) * 4
    assert cfg.ssm.proj_width == 2 * 32 + 2 * 2 * 16 + 4


@pytest.mark.parametrize("change", [
    dict(ssm=None), dict(ops=("attention+ssm", "conv", "attention",
                              "attention"), conv_kernel=3),
    dict(ops=("attention+ssm",) * 3), dict(attention="latent"),
    dict(bias=True)],
    ids=["no_ssm_spec", "conv_beside_ssm", "three_ops_four_layers",
         "latent", "bias"])
def test_check_block_spec_still_raises(cfg, change):
    blk = cfg.block_spec()._replace(**change)
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk, 4)


@pytest.mark.parametrize("key,value", [
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("attn_layer_indices", [0, 2]), ("mamba_d_ssm", 40),
    ("ssm_multipliers", [1.0, 1.0])])
def test_config_refuses_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match="SSMHybridConfig"):
        sd.SSMHybridConfig.from_hf(dict(SMALL, **{key: value}))


def test_engine_refuses_other_paths_by_name(params, cfg):
    for kw, what in ((dict(spec=2), "speculation"),
                     (dict(kv_quant="int8"), "int8")):
        with pytest.raises(ValueError, match=what):
            engine(params, cfg, **kw)


# ------------------------------------------------------------------ #
# (d) the operator alone: chunked form against the recurrence
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("Q,chunk", [(8, 8), (16, 8), (12, 8), (4, 8),
                                     (32, 16)])
def test_chunked_form_equals_the_recurrence(Q, chunk):
    rng = np.random.default_rng(Q * 100 + chunk)
    B, H, P, N, G = 3, 4, 8, 16, 2
    x = jnp.asarray(rng.normal(size=(B, Q, H, P)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, Q, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, Q, G, N)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, size=(B, Q, H)), jnp.float32)
    # slot 1's q-block is shorter (dead rows have dt 0), slot 2 is dead
    q_len = np.array([Q, Q - 3, 0])
    dt = jnp.where(np.arange(Q)[None, :, None] < q_len[:, None, None], dt, 0)
    A = -jnp.asarray(rng.uniform(1, 16, size=H), jnp.float32)
    S0 = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, S = sd.ssd_chunked(x, dt, A, Bm, Cm, S0, chunk)
        want, Sw = [], S0
        for t in range(Q):
            yt, Sw = sd.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t],
                                 Sw)
            want.append(yt)
    want = np.stack([np.asarray(w) for w in want], axis=1)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(y)[b, :q_len[b]],
                                   want[b, :q_len[b]], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sw), rtol=1e-4,
                               atol=1e-5)
    # the dead slot's state did not move, bit for bit
    np.testing.assert_array_equal(np.asarray(S)[2], np.asarray(S0)[2])


# ------------------------------------------------------------------ #
# (a) one sequence: one chunk, three chunks, a ragged chunk, decode
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("chunks", [(16,), (8, 8, 7), (11,), (5, 13, 3)],
                         ids=["one_chunk", "three_chunks", "no_multiple",
                              "ragged"])
def test_prefill_in_chunks_then_decode_matches_reference(params, cfg,
                                                         chunks):
    kv = manager(cfg)
    cfg_tuple = tuple_of(cfg, kv)
    P, n_dec = sum(chunks), 16
    seq = np.random.default_rng(P).integers(0, 211, P + n_dec).astype(
        np.int32)
    want = np.asarray(ref.forward(params, cfg, seq, NAME))
    slot, _ = kv.alloc("a", seq[:P], P + n_dec)
    got, off = [], 0
    for n in chunks + (1,) * n_dec:
        out = mixed_step(params, cfg_tuple, kv, {slot: (seq[off:off + n],
                                                        off)})
        got.append(out[slot])
        off += n
    got = np.concatenate(got)
    np.testing.assert_allclose(got, want, atol=TOL * want.std())


# ------------------------------------------------------------------ #
# (b) a packed wave: decode rows, two slots' chunks, a dead slot
# ------------------------------------------------------------------ #

def test_packed_wave_of_decode_rows_two_chunks_and_a_dead_slot(params, cfg,
                                                               monkeypatch):
    # pack at this size: 8 slots x q 16 = 128 padded rows, 64 packed
    monkeypatch.setattr(gd, "_PACKED_ROWS_FLOOR", 32)
    kv = manager(cfg, slots=8)
    cfg_tuple = tuple_of(cfg, kv)
    rng = np.random.default_rng(11)
    seqs = {s: rng.integers(0, 211, 40).astype(np.int32) for s in range(6)}
    want = {s: np.asarray(ref.forward(params, cfg, seqs[s], NAME))
            for s in seqs}
    slots = {s: kv.alloc(f"r{s}", seqs[s][:20], 40)[0] for s in seqs}
    at = dict.fromkeys(seqs, 0)
    got = {s: [] for s in seqs}

    def wave(plan):
        out = mixed_step(params, cfg_tuple, kv, {
            slots[s]: (seqs[s][at[s]:at[s] + n], at[s])
            for s, n in plan.items()}, last_only=True)
        for s, n in plan.items():
            at[s] += n
            got[s].append((at[s] - 1, out[slots[s]][0]))

    # six q-blocks wider than a row: two passes of the mixer's lanes
    assert sd.WIDE_LANES == 4
    wave({0: 9, 1: 12, 2: 16, 3: 7, 4: 5, 5: 6})
    wave({0: 1, 1: 1, 2: 1, 3: 1})        # a decode wave (q-block 1)
    # decode rows (0-3), two slots' chunks (4, 5) and two dead slots
    assert gd.wave_rows(cfg_tuple, 8, 1, 16) == 64 < 8 * 16
    wave({0: 1, 1: 1, 2: 1, 3: 1, 4: 16, 5: 11})
    before = [np.asarray(s) for s in kv.states]
    wave({0: 1, 1: 1, 4: 13, 5: 1})       # 2 and 3 sit this one out
    idle = sorted({slots[2], slots[3]}
                  | set(range(8)) - set(slots.values()))
    assert len(idle) == 4
    for a, b in zip(before, kv.states):
        np.testing.assert_array_equal(a[:, idle], np.asarray(b)[:, idle])
    wave({s: 1 for s in range(6)})
    for s in seqs:
        for row, lg in got[s]:
            np.testing.assert_allclose(lg, want[s][row],
                                       atol=TOL * want[s].std(),
                                       err_msg=f"slot {s} row {row}")


# ------------------------------------------------------------------ #
# the engine: requests on fewer slots, and (c) a reused slot
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
    lg = np.asarray(reference(ref.forward, params, cfg, seq[:-1], NAME))
    rows = lg[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max() / lg.std())


@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_engine_serves_through_pool_and_states(params, cfg, fast):
    eng = engine(params, cfg, fast_path=fast)
    assert len(eng.kv.states) == 8
    tails, mats = eng.kv.states[:4], eng.kv.states[4:]
    assert all(t.shape == (1, 4, 3, 96) and t.dtype == jnp.float32
               for t in tails)
    assert all(m.shape == (1, 4, 4, 8, 16) and m.dtype == jnp.float32
               for m in mats)
    assert eng.kv.cache_k.shape[0] == 4          # every layer holds pages
    sizes = [(19, 6), (7, 9), (45, 5), (3, 4), (33, 7), (16, 3)]
    out = serve(eng, sizes)
    assert len(out) == 6 and eng.kv.state_resets == 6
    for r in out.values():
        assert gap(params, cfg, r) <= TOL, r.request_id
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in sizes)
    assert snap["ssm_rows"] == rows * 4
    assert snap["ssm_slot_steps"] % 4 == 0 and snap["ssm_slot_steps"] > 0
    assert snap["attn_ctx_tokens"] > 0 and snap["attn_score_pairs"] > 0
    assert eng.kv.stats()["state_bytes"] == sum(
        s.nbytes for s in tails + mats)


def test_a_reused_slot_serves_as_a_fresh_engine_does(params, cfg):
    """One slot, two requests in turn: the second's tokens and logits
    equal those of an engine that never served the first."""
    prompt = np.random.default_rng(4).integers(0, 211, 21).astype(np.int32)
    used = engine(params, cfg, slots=1)
    serve(used, [(30, 8)], seed=9)
    used.submit(Request(prompt, 10, request_id="again"))
    second = used.run()["again"]
    fresh = engine(params, cfg, slots=1)
    fresh.submit(Request(prompt, 10, request_id="again"))
    first = fresh.run()["again"]
    assert list(second.tokens) == list(first.tokens)
    assert gap(params, cfg, second) <= TOL
    for a, b in zip(used.kv.states, fresh.kv.states):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ------------------------------------------------------------------ #
# (f) the multipliers, and what the comparison notices
# ------------------------------------------------------------------ #

MULTIPLIERS = {
    "embedding_multiplier": 3.0, "attention_in_multiplier": 0.5,
    "attention_out_multiplier": 0.1, "key_multiplier": 0.9,
    "ssm_in_multiplier": 0.5, "ssm_out_multiplier": 0.2,
    "ssm_multipliers": [0.7, 0.1, 0.4, 0.2, 0.9],
    "mlp_multipliers": [0.4, 0.1], "lm_head_multiplier": 0.05}


@pytest.mark.parametrize("key", sorted(MULTIPLIERS))
def test_each_multiplier_moves_the_logits_as_the_reference(params, key):
    """The SAME weights under another value of one multiplier: the
    wave's logits follow the reference's, and both moved."""
    base = sd.SSMHybridConfig.from_hf(SMALL)
    cfg = sd.SSMHybridConfig.from_hf(dict(SMALL, **{key: MULTIPLIERS[key]}))
    seq = np.random.default_rng(2).integers(0, 211, 20).astype(np.int32)
    want = np.asarray(ref.forward(params, cfg, seq, NAME))
    moved = np.abs(want - np.asarray(ref.forward(params, base, seq, NAME)))
    assert moved.max() > 100 * TOL * want.std()
    kv = manager(cfg)
    slot, _ = kv.alloc("a", seq, 32)
    cfg_tuple = tuple_of(cfg, kv)
    got = np.concatenate([
        mixed_step(params, cfg_tuple, kv, {slot: (seq[o:o + n], o)})[slot]
        for o, n in ((0, 8), (8, 11), (19, 1))])
    np.testing.assert_allclose(got, want, atol=TOL * want.std())


@pytest.mark.parametrize("omit", ref.OMISSIONS)
def test_the_comparison_notices_each_omission(params, cfg, omit):
    seq = np.random.default_rng(6).integers(0, 211, 40).astype(np.int32)
    want = np.asarray(ref.forward(params, cfg, seq, NAME))
    other = np.asarray(ref.forward(params, cfg, seq, NAME, omit=omit,
                                   carry_at=16))
    # rounding the state moves a 40-token sequence's logits least: 29
    # tolerances; every other omission moves them by hundreds
    least = 10 if omit == "state_bf16" else 50
    assert np.abs(want - other).max() > least * TOL * want.std(), omit


def test_seeded_weights_leave_no_branch_vanishing(params, cfg):
    stats = {}
    seq = np.random.default_rng(8).integers(0, 211, 48).astype(np.int32)
    ref.forward(params, cfg, seq, NAME, stats=stats)
    for layer in stats["layers"]:
        for branch in ("attention", "ssm", "mlp"):
            assert layer[branch] > 0.05 * layer["residual"], (branch, layer)
        assert layer["scores"] > 0.2
    assert 0.3 < stats["logits"] < 3.0


def test_recurrence_constants_stay_float32(cfg):
    p = sd.init_ssm_hybrid_params(cfg, NAME, seed=1, dtype=jnp.bfloat16)
    for k, v in p.items():
        want = jnp.float32 if k.endswith(sd.F32_LEAVES) else jnp.bfloat16
        assert v.dtype == want, k
    dt = jax.nn.softplus(p[f"{NAME}_h0_ssm_dt_bias"])
    assert float(dt.min()) >= 0.001 * 0.999 and float(dt.max()) <= 0.1001
    a = jnp.exp(p[f"{NAME}_h0_ssm_A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0


# ------------------------------------------------------------------ #
# (e) five query heads a K/V head at head 128, the kernel interpreted
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("q_block", [1, 24])
def test_five_query_heads_a_kv_head_at_head_128(q_block):
    """4 K/V heads of 128 (a pooled row of 512 lanes), 5 query heads
    each: the first group count that is no power of two.  A chunk slot
    with a dead tail, a decode-like slot, a dead slot; a table of 40
    pages so that the page loop runs three groups."""
    from hetu_tpu.kernels import ragged_attention as ra
    from hetu_tpu.serving.kv_manager import kv_row_width, kv_rows
    rng = np.random.default_rng(50 + q_block)
    B, Hkv, Dh, bs, T, L, groups = 3, 4, 128, 4, 40, 2, 5
    H = Hkv * groups
    N = B * T + 1
    W = kv_row_width(Hkv, Dh)
    assert W == 512
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
    want = ra.ragged_paged_reference(
        q, jnp.repeat(pool[0][1], groups, axis=2),
        jnp.repeat(pool[1][1], groups, axis=2), lens, q_len, tables)
    assert got.shape == (B, q_block, H, Dh)
    for b in range(B):
        n = int(q_len[b])
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=5e-5)
    assert not np.asarray(got[2]).any()


# ------------------------------------------------------------------ #
# hetu_trace --check: slot steps = live slots x state-space layers
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("fields,problem", [
    (dict(ssm_slot_steps=12, ssm_live_slots=3, ssm_layers=4, ssm_rows=80),
     None),
    (dict(ssm_slot_steps=11, ssm_live_slots=3, ssm_layers=4), "counts 11"),
    (dict(ssm_slot_steps=12, ssm_layers=4), "without integer"),
    (dict(moe_tokens=5), None)],
    ids=["holds", "miscounted", "companion_missing", "exempt"])
def test_trace_check_holds_a_step_to_its_live_slots(fields, problem):
    from hetu_tpu.telemetry.trace import check_ssm_attribution
    found = check_ssm_attribution(
        [{"event": "serve_step", "step": 7, **fields},
         {"event": "serve_finish", "ssm_slot_steps": 1}])
    assert (found == []) if problem is None \
        else (len(found) == 1 and problem in found[0])


def test_record_ssm_counts_what_the_check_reads():
    from hetu_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    mark = m.mark()
    rec = m.record_state_scan("ssm", live_slots=3, rows=19,
                              chunk_pairs=40, layers=4)
    assert rec == {"slot_steps": 12, "rows": 76, "live_slots": 3,
                   "layers": 4}
    snap = m.snapshot(since=mark)
    assert (snap["ssm_slot_steps"], snap["ssm_rows"],
            snap["ssm_chunk_pairs"]) == (12, 76, 160)
    assert snap["ssm_kernel_slot_steps"] == 0


@pytest.mark.parametrize("d_state,want", [(128, 6), (16, 0)],
                         ids=["whole_lane_tiles", "narrow_state"])
def test_a_wave_counts_the_slots_the_step_kernel_took(d_state, want):
    """``serve.ssm.kernel_slot_steps`` / ``ssm_kernel_slot_steps`` (ISSUE
    50): a wave of 3 one-row slots, 1 wide and 1 dead over 2 layers, as
    the engine reads its own wave: the one-row slots x layers where the
    mixer's shape rule hands their step to ``kernels/ssm_step``, 0 where
    it keeps ``ssd_step``; the live slots' steps either way."""
    from hetu_tpu import telemetry
    c = sd.SSMHybridConfig.from_hf(dict(
        SMALL, num_hidden_layers=2, mamba_d_state=d_state))
    eng = engine(sd.init_ssm_hybrid_params(c, NAME, seed=1), c, slots=5)
    before = telemetry.snapshot()["counters"].get(
        "serve.ssm.kernel_slot_steps", 0)
    mark = eng.metrics.mark()
    rec = eng._wave_record({"q_len": np.array([1, 7, 1, 0, 1]),
                            "pos": np.array([9, 0, 3, 0, 5]), "q": 8}, 40)
    assert rec["ssm"]["slot_steps"] == 4 * 2
    for snap in (eng.metrics.snapshot(since=mark), eng.metrics.snapshot()):
        assert snap["ssm_kernel_slot_steps"] == want
        assert snap["ssm_slot_steps"] == 8
    assert telemetry.snapshot()["counters"].get(
        "serve.ssm.kernel_slot_steps", 0) - before == want
