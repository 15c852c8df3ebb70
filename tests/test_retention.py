"""The power-retention decoder (``retention_decode.RetentionConfig``, the
``brumby`` family) on the mixed wave (ISSUE 44): the engine's logits
through the slot states (and NO K/V pool) against
``reference_retention``'s full forward in the attention form, float32
both sides on the CPU, at a tiny size: hidden 64, 4 query heads over 2
K/V heads of 16 (``D`` 136), 3 layers, vocabulary 256.

Tolerance: 2e-4 of the logits' spread (their standard deviation is 1 at
these seeded weights), absolute.  Both sides are float32; what differs
is the order of the sums (a recurrence over ``phi(k) v^T`` and one
state update a chunk against a quadratic form over ``(q . k)^2``), each
a relative 1e-6 or so a product, through 3 layers.  A state KEPT in
bfloat16 moves the logits by a hundred times that (``test_a_bfloat16_
state_fails_the_margin``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_retention as ref
from hetu_tpu.models import retention_decode as rd
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager
from hetu_tpu.serving.metrics import ServingMetrics
from hetu_tpu.telemetry import top
from hetu_tpu.telemetry.trace import (
    check_ret_attribution, check_ssm_attribution)

from jitted import mixed_wave, reference

TOL = 2e-4
NAME = "bru"
V = 256

SMALL = dict(
    vocab_size=V, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, rope_theta=1e6, rms_norm_eps=1e-6,
    max_position_embeddings=512, attention_bias=False,
    tie_word_embeddings=False, sliding_window=None,
    use_sliding_window=False, rope_scaling=None, max_window_layers=3,
    hidden_act="silu", model_type="brumby", retention_chunk=8)
# memories of 4-64 steps: the test's sequences are tens of tokens long
MEMORY = (4.0, 64.0)


@pytest.fixture(scope="module")
def cfg():
    return rd.RetentionConfig.from_hf(SMALL)


@pytest.fixture(scope="module")
def params(cfg):
    return rd.init_retention_params(cfg, NAME, seed=3, memory_range=MEMORY)


def engine(params, cfg, **kw):
    kw = dict(dict(slots=3, max_seq_len=128, prefill_chunk=16,
                   fast_path=False), **kw)
    return ServingEngine(params, cfg, **kw)


def manager(cfg, slots=4, **kw):
    blk = cfg.block_spec()
    L = cfg.num_hidden_layers
    return PagedKVManager(
        layers=blk.op_layers(L, "pool"), heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, slots=slots, max_seq_len=128,
        pos_cap=cfg.max_position_embeddings, dtype=jnp.float32,
        state_shapes=blk.state_shapes(L, cfg.hidden_size), **kw)


def tuple_of(cfg, kv):
    return (NAME, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.head_dim, kv.s_max, cfg.block_spec())


def mixed_step(params, cfg_tuple, kv, plan, last_only=False):
    """One ``_mixed_step`` over the manager's states for ``plan`` {slot:
    (tokens, pos)}; returns {slot: logits [n, V]}, every row's, or with
    ``last_only`` (a sampling window of 1, as the engine's: a chunk wave
    then packs) the last row's alone."""
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
    logits, ck, cv, kv.state = mixed_wave(
        params, cfg_tuple, kv.cache_k, kv.cache_v, pos, tokens, q_len,
        first, np.zeros(B, bool), window=1 if last_only else Q,
        block_tables=jnp.asarray(kv.tables), has_fresh=Q > 1,
        state=kv.state)
    assert ck is None and cv is None
    n = (lambda t: 1) if last_only else len
    return {s: np.asarray(logits)[s, :n(t)] for s, (t, _) in plan.items()}


# ------------------------------------------------------------------ #
# the embedding and the three forms
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(d):
    rng = np.random.default_rng(d)
    a = rng.normal(size=(7, d)).astype(np.float32)
    b = rng.normal(size=(7, d)).astype(np.float32)
    pa, pb = rd.sympow2(a), rd.sympow2(b)
    assert pa.shape == (7, d * (d + 1) // 2) and pa.dtype == jnp.float32
    want = (a.astype(np.float64) * b).sum(-1) ** 2
    np.testing.assert_allclose((np.asarray(pa, np.float64)
                                * np.asarray(pb)).sum(-1), want, rtol=1e-5)
    assert rd.RetentionSpec(8, d).state == pa.shape[-1]


def attention_form(q, k, v, lg):
    """[Q, g, m, d] float64: the weights (q_t . k_j)^2 under the gates
    between j and t, a row normalised by their sum."""
    cum = np.cumsum(lg, axis=0)                            # [Q, g]
    s = np.einsum("tgmd,jgd->gmtj", q, k) ** 2
    decay = np.exp(np.where(np.tril(np.ones((len(q),) * 2, bool)),
                            cum.T[:, :, None] - cum.T[:, None, :], -np.inf))
    a = s * decay[:, None]
    return np.einsum("gmtj,jgd->tgmd", a, v) \
        / a.sum(-1).transpose(2, 0, 1)[..., None]


@pytest.mark.parametrize("Q,chunk", [(8, 8), (16, 8), (12, 8), (4, 8),
                                     (32, 16)])
def test_step_form_equals_chunked_form_equals_attention_form(Q, chunk):
    rng = np.random.default_rng(Q * 100 + chunk)
    B, g, m, d = 3, 2, 2, 16
    q = rng.normal(size=(B, Q, g, m, d)).astype(np.float32)
    k = rng.normal(size=(B, Q, g, d)).astype(np.float32)
    v = rng.normal(size=(B, Q, g, d)).astype(np.float32)
    lg = np.log(rng.uniform(0.6, 0.999, size=(B, Q, g))).astype(np.float32)
    # slot 1's q-block is shorter (dead rows: lg 0, k 0), slot 2 is dead
    q_len = np.array([Q, Q - 3, 0])
    live = np.arange(Q)[None, :] < q_len[:, None]
    # (a row's own key is in sight of its query: a sequence's first rows
    # divide by (q_t . k_t)^2 alone, which the state form knows to 1e-7 of
    # |q|^2 |k|^2, so a query at right angles to its key reads noise)
    q = q + k[:, :, :, None, :]
    k = np.where(live[..., None, None], k, 0)
    lg = np.where(live[..., None], lg, 0)
    D = d * (d + 1) // 2
    zero = (jnp.zeros((B, g, D, d)), jnp.zeros((B, g, D)))
    with jax.default_matmul_precision("highest"):
        y, S, z = rd.retention_chunked(*map(jnp.asarray, (q, k, v, lg)),
                                       *zero, chunk)
        want, (Sw, zw) = [], zero
        for t in range(Q):
            yt, Sw, zw = rd.retention_step(q[:, t], k[:, t], v[:, t],
                                           lg[:, t], Sw, zw)
            want.append(np.asarray(yt))
    want = np.stack(want, axis=1)
    for b in range(2):
        n = q_len[b]
        np.testing.assert_allclose(np.asarray(y)[b, :n], want[b, :n],
                                   rtol=2e-4, atol=2e-5)
        plain = attention_form(*(a[b, :n].astype(np.float64)
                                 for a in (q, k, v, lg)))
        np.testing.assert_allclose(want[b, :n], plain, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sw), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zw), rtol=1e-4,
                               atol=1e-4)
    # the dead slot's state did not move, bit for bit, from a carry
    carry = (jnp.asarray(rng.normal(size=(B, g, D, d)), jnp.float32),
             jnp.asarray(rng.normal(size=(B, g, D)), jnp.float32))
    _, S2, z2 = rd.retention_chunked(*map(jnp.asarray, (q, k, v, lg)),
                                     *carry, chunk)
    np.testing.assert_array_equal(np.asarray(S2)[2], np.asarray(carry[0])[2])
    np.testing.assert_array_equal(np.asarray(z2)[2], np.asarray(carry[1])[2])


# ------------------------------------------------------------------ #
# one sequence through the wave: chunks of several widths, then decode
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("chunks", [(16,), (8, 8, 7), (11,), (5, 13, 3),
                                    (32, 9)],
                         ids=["one_chunk", "three_chunks", "no_multiple",
                              "ragged", "wider_than_a_chunk"])
def test_prefill_in_chunks_then_decode_matches_reference(params, cfg,
                                                         chunks):
    kv = manager(cfg)
    cfg_tuple = tuple_of(cfg, kv)
    P, n_dec = sum(chunks), 12
    seq = np.random.default_rng(P).integers(0, V, P + n_dec).astype(np.int32)
    want = np.asarray(ref.forward(params, cfg, seq, NAME))
    slot, _ = kv.alloc("a", seq[:P], P + n_dec)
    got, off = [], 0
    for n in chunks + (1,) * n_dec:
        out = mixed_step(params, cfg_tuple, kv, {slot: (seq[off:off + n],
                                                        off)})
        got.append(out[slot])
        off += n
    np.testing.assert_allclose(np.concatenate(got), want,
                               atol=TOL * want.std())


def test_packed_wave_of_decode_rows_chunks_and_dead_slots(params, cfg,
                                                          monkeypatch):
    """Decode rows, more wide slots than the mixer's lanes, dead slots
    and a dead row's state, in packed waves of 8 slots."""
    monkeypatch.setattr(gd, "_PACKED_ROWS_FLOOR", 32)
    kv = manager(cfg, slots=8)
    cfg_tuple = tuple_of(cfg, kv)
    rng = np.random.default_rng(11)
    seqs = {s: rng.integers(0, V, 40).astype(np.int32) for s in range(6)}
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

    # six q-blocks wider than a row over 8 slots: gcd(3, 8) = 1 lane a
    # pass, six passes (the cell's 24 slots take three lanes)
    assert rd.WIDE_LANES == 3
    wave({0: 9, 1: 12, 2: 16, 3: 7, 4: 5, 5: 6})
    wave({0: 1, 1: 1, 2: 1, 3: 1})        # a decode wave (q-block 1)
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
# the manager without a pool layer
# ------------------------------------------------------------------ #

def test_manager_without_pool_layers_admits_by_slot(cfg):
    kv = manager(cfg, slots=3)
    # the slot count is exact, there is no pool and a token takes no block
    assert kv.n_slots == 3 and kv.cache_k is None and kv.cache_v is None
    assert kv.blocks_needed(10_000) == 0 and kv.capacity_blocks == 0
    assert kv.tables.shape == (3, 1) and kv.cache_bytes == 0
    assert kv.s_max == 128 and kv.stateful and not kv.prefix_share
    D = 16 * 17 // 2
    assert [tuple(s.shape) for s in kv.states] == \
        [(1, 3, 2, D, 16)] * 3 + [(1, 3, 2, D)] * 3
    assert all(s.dtype == jnp.float32 for s in kv.states)
    assert kv.state_bytes == 3 * 3 * 2 * (D * 16 + D) * 4 \
        == kv.stats()["state_bytes"]
    # admission by slot alone, whatever the lengths
    got = [kv.alloc(f"r{i}", np.arange(100), 128)[0] for i in range(4)]
    assert sorted(got[:3]) == [0, 1, 2] and got[3] is None
    assert kv.state_resets == 3 and kv.free_slots == 0
    kv.advance(got[0], 100)
    kv.release(got[0])
    assert kv.alloc("again", np.arange(5), 20)[0] == got[0]
    with pytest.raises(ValueError, match="exceeds S_max"):
        kv.alloc("long", np.arange(5), 129)


def test_a_claimed_slot_starts_from_a_zero_state(cfg):
    kv = manager(cfg, slots=2)
    kv.state = tuple(jnp.ones_like(s) for s in kv.states)
    slot, _ = kv.alloc("r", np.arange(4), 8)
    for s in kv.states:
        a = np.asarray(s)
        assert not a[:, slot].any() and a[:, 1 - slot].all()


@pytest.mark.parametrize("what", ["prefix_share", "truncate",
                                  "export_blocks", "import_blocks",
                                  "pool_blocks", "window_layers",
                                  "no_state"])
def test_manager_without_pool_layers_refuses_by_name(cfg, what):
    if what in ("prefix_share", "pool_blocks", "window_layers"):
        extra = {"prefix_share": {"prefix_share": True},
                 "pool_blocks": {"pool_blocks": 9},
                 "window_layers": {"window_layers": 1, "window": 4}}[what]
        with pytest.raises(ValueError, match=what.split("_")[0]):
            manager(cfg, **extra)
        return
    if what == "no_state":
        with pytest.raises(ValueError, match="no slot state"):
            PagedKVManager(layers=0, heads=2, head_dim=16, slots=2,
                           max_seq_len=32)
        return
    kv = manager(cfg)
    slot, _ = kv.alloc("r", np.arange(6), 12)
    kv.advance(slot, 6)
    call = {"truncate": lambda: kv.truncate(slot, 3),
            "export_blocks": lambda: kv.export_blocks(slot),
            "import_blocks": lambda: kv.import_blocks("x", {})}[what]
    with pytest.raises(ValueError, match="slot-indexed state"):
        call()


# ------------------------------------------------------------------ #
# the block spec
# ------------------------------------------------------------------ #

def test_block_spec_and_the_shapes_it_asks_for(cfg):
    blk = cfg.block_spec()
    assert blk.ops == ("retention",) * 3 and blk.qk_norm
    assert blk.retention == rd.RetentionSpec(2, 16, 8, "float32", 2)
    assert "retention" in gd.OPERATORS
    gd.check_block_spec(blk, 3)
    assert [blk.holds(0, w) for w in ("pool", "window", "state")] == \
        [False, False, True]
    assert blk.op_layers(3, "pool") == 0 and blk.op_layers(3, "state") == 3
    assert [blk.op_index(i) for i in range(3)] == [0, 1, 2]
    assert blk.state_shapes(3, 64) == blk.retention.state_shapes(3)
    # beside plain attention layers: a pool for those, state for these
    mixed = blk._replace(ops=("attention", "retention", "attention"))
    gd.check_block_spec(mixed, 3)
    assert mixed.op_layers(3, "pool") == 2 and mixed.op_index(1) == 0
    assert len(mixed.state_shapes(3, 64)) == 2


@pytest.mark.parametrize("change", [
    dict(retention=None), dict(ops=("retention", "conv", "retention"),
                               conv_kernel=3),
    dict(ops=("retention",) * 2), dict(kv_heads=4), dict(head_dim=8),
    dict(retention=rd.RetentionSpec(2, 16, 8, "float32", 3)),
    dict(mup=gd.MuP()), dict(ops=("attention",) * 3)],
    ids=["no_spec", "beside_conv", "too_few_layers", "other_kv_heads",
         "other_head", "degree_3", "multipliers", "spec_without_layers"])
def test_check_block_spec_still_raises_by_name(cfg, change):
    with pytest.raises(ValueError, match="retention") as e:
        gd.check_block_spec(cfg.block_spec()._replace(**change), 3)
    assert "it cannot run" in str(e.value)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("sliding_window", 128), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn"}), ("retention_degree", 3)])
def test_config_refuses_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match=key):
        rd.RetentionConfig.from_hf(dict(SMALL, **{key: value}))


def test_engine_refuses_other_paths_by_name(params, cfg):
    for kw, what in ((dict(spec=2), "speculation"),
                     (dict(kv_quant="int8"), "int8"),
                     (dict(pool_blocks=64), "pool_blocks")):
        with pytest.raises(ValueError, match=what):
            engine(params, cfg, **kw)
    with pytest.raises(ValueError, match="prefix_share"):
        engine(params, cfg, prefix_share=True)


def test_the_gates_bias_is_float32_and_spans_the_memories(cfg):
    p = rd.init_retention_params(cfg, NAME, seed=5, dtype=jnp.bfloat16)
    for i in range(3):
        b = p[f"{NAME}_h{i}_ret_gate_bias"]
        assert b.dtype == jnp.float32 and b.shape == (2,)
        memory = 1.0 / (1.0 - np.asarray(jax.nn.sigmoid(b), np.float64))
        assert np.all((memory >= 15.9) & (memory <= 16400))
        assert p[f"{NAME}_h{i}_ret_gate_weight"].dtype == jnp.bfloat16
    assert all(k.endswith(rd.F32_LEAVES) == (v.dtype == jnp.float32)
               for k, v in p.items())


# ------------------------------------------------------------------ #
# the engine
# ------------------------------------------------------------------ #

def serve(eng, sizes, seed=1):
    rng = np.random.default_rng(seed)
    for i, (n, m) in enumerate(sizes):
        eng.submit(Request(rng.integers(0, V, n).astype(np.int32), m,
                           request_id=f"q{i}"))
    return eng.run()


def gap(params, cfg, result, omit=None):
    """The widest (largest logit - served token's logit) over the
    answer's rows, in units of the logits' spread."""
    seq = np.asarray(result.tokens, np.int32)
    lg = np.asarray(reference(ref.forward, params, cfg, seq[:-1], NAME,
                              omit=omit))
    rows = lg[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max() / lg.std())


SIZES = [(19, 6), (7, 9), (45, 5), (3, 4), (33, 7), (16, 3)]


@pytest.fixture(scope="module")
def served(params, cfg):
    eng = engine(params, cfg)
    return eng, serve(eng, SIZES)


def test_engine_serves_through_the_states_and_no_pool(params, cfg, served):
    eng, out = served
    assert eng.kv.n_slots == 3 and eng.kv.cache_k is None
    assert len(eng.kv.states) == 6 and eng.paged
    assert len(out) == 6 and eng.kv.state_resets == 6
    for r in out.values():
        assert r.n_generated == dict(
            (f"q{i}", m) for i, (_, m) in enumerate(SIZES))[r.request_id]
        assert gap(params, cfg, r) <= TOL, r.request_id
    snap = eng.metrics.snapshot()
    rows = sum(n + m - 1 for n, m in SIZES)
    assert snap["ret_rows"] == rows * 3
    assert snap["ret_slot_steps"] % 3 == 0 and snap["ret_slot_steps"] > 0
    assert snap["ret_chunk_pairs"] > 0 and snap["ssm_slot_steps"] == 0
    # heads of 16: the wide slots' chunked form stays in XLA's operations
    assert snap["ret_kernel_slot_steps"] == 0
    # an engine without attention counts none, and runs no kernel
    assert snap["attn_ctx_tokens"] == snap["attn_score_pairs"] == 0
    assert snap["attn_tiles_live"] == snap["attn_tiles_short"] == 0


def test_a_kernel_engine_is_the_same_engine(params, cfg):
    """``fast_path=True`` (the TPU's default) changes nothing: the wave
    calls no Pallas kernel."""
    fast = engine(params, cfg, fast_path=True)
    out = serve(fast, SIZES[:3])
    slow = serve(engine(params, cfg), SIZES[:3])
    assert all(list(out[k].tokens) == list(slow[k].tokens) for k in out)
    snap = fast.metrics.snapshot()
    assert snap["attn_tiles_live"] == 0
    B = 3
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)    # noqa: E731
    text = fast._mixed.func.lower(
        fast.params, fast.cfg_tuple, None, None, i32(B, 1), i32(B),
        i32(B, 16), i32(B), i32(B), jax.ShapeDtypeStruct((B,), jnp.bool_),
        jax.ShapeDtypeStruct((B,), jnp.float32), i32(B),
        jax.ShapeDtypeStruct((B, 2), jnp.uint32), attn="ragged", window=1,
        has_fresh=True, state=fast.kv.state).as_text()
    assert "tpu_custom_call" not in text


def test_a_reused_slot_serves_as_a_fresh_engine_does(params, cfg):
    prompt = np.random.default_rng(4).integers(0, V, 21).astype(np.int32)
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


def logits_error(params, cfg, seq, P, **config):
    """The widest |served logit - reference logit| over prefill in chunks
    of 8 and decoding of ``seq``, in units of the logits' spread, for the
    configuration ``SMALL`` with ``config`` over it."""
    c = rd.RetentionConfig.from_hf(dict(SMALL, **config))
    kv = manager(c)
    want = np.asarray(ref.forward(params, cfg, seq, NAME))
    slot, _ = kv.alloc("a", seq[:P], len(seq))
    got, off = [], 0
    for n in (8,) * (P // 8) + (1,) * (len(seq) - P):
        got.append(mixed_step(params, tuple_of(c, kv), kv,
                              {slot: (seq[off:off + n], off)})[slot])
        off += n
    return float(np.abs(np.concatenate(got) - want).max() / want.std()), kv


def test_a_bfloat16_state_fails_the_margin(params, cfg):
    """The state KEPT in bfloat16 (rounded at every write: each chunk,
    each decoded token) moves the logits by far more than the margin
    the float32 state keeps."""
    seq = np.random.default_rng(8).integers(0, V, 96).astype(np.int32)
    sound, kv = logits_error(params, cfg, seq, 64)
    assert sound <= TOL and kv.states[0].dtype == jnp.float32
    rounded, kv = logits_error(params, cfg, seq, 64, state_dtype="bfloat16")
    assert kv.states[0].dtype == kv.states[-1].dtype == jnp.bfloat16
    assert rounded > 20 * TOL, (sound, rounded)


@pytest.mark.parametrize("omit", ref.OMISSIONS)
def test_the_comparison_notices_each_omission(params, cfg, served, omit):
    _, out = served
    assert max(gap(params, cfg, r, omit) for r in out.values()) > 50 * TOL


def test_seeded_weights_leave_no_branch_vanishing(params, cfg):
    stats = {}
    seq = np.random.default_rng(2).integers(0, V, 48).astype(np.int32)
    ref.forward(params, cfg, seq, NAME, stats=stats)
    for layer in stats["layers"]:
        assert 0.2 < layer["retention"] / layer["residual"] < 2.0
        assert 0.2 < layer["mlp"] / layer["residual"] < 2.0
    assert 0.5 < stats["logits"] < 2.0


def test_beside_attention_layers_chunking_changes_nothing(params, cfg):
    """A spec that mixes retention with plain attention layers (no cell
    asks for one): the pool holds the attention layers, the state the
    retention layer, and prefill in chunks equals prefill in one."""
    class Mixed(rd.RetentionConfig):
        def block_spec(self):
            return super().block_spec()._replace(
                ops=("attention", "retention", "attention"))
    c = Mixed.from_hf(SMALL)
    seq = np.random.default_rng(6).integers(0, V, 28).astype(np.int32)
    outs = []
    for chunks in ((24,), (8, 8, 8), (5, 16, 3)):
        kv = PagedKVManager(
            layers=2, heads=2, head_dim=16, slots=2, max_seq_len=64,
            dtype=jnp.float32, block=4,
            state_shapes=c.block_spec().state_shapes(3, 64))
        assert kv.cache_k.shape[0] == 2 and len(kv.states) == 2
        t = tuple_of(c, kv)
        slot, _ = kv.alloc("a", seq[:24], 28)
        got, off = [], 0
        for n in chunks + (1,) * 4:
            B = kv.n_slots
            Q = gd._pow2(n)
            tokens = np.zeros((B, Q), np.int32)
            tokens[slot, :n] = seq[off:off + n]
            pos, q_len = np.zeros(B, np.int32), np.zeros(B, np.int32)
            pos[slot], q_len[slot] = off, n
            lg, kv.cache_k, kv.cache_v, kv.state = mixed_wave(
                params, t, kv.cache_k, kv.cache_v, pos, tokens, q_len,
                np.zeros(B, np.int32), np.zeros(B, bool), window=Q,
                block_tables=jnp.asarray(kv.tables), has_fresh=Q > 1,
                state=kv.state)
            got.append(np.asarray(lg)[slot, :n])
            kv.advance(slot, n)
            off += n
        outs.append(np.concatenate(got))
    assert np.isfinite(outs[0]).all() and outs[0].std() > 0.1
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], atol=TOL * outs[0].std())


def test_beside_a_gpt2_engine_in_one_process(params, cfg):
    from hetu_tpu.models import GPTConfig
    from benchmarks.runners import serve as serve_runner
    g = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=2, max_position_embeddings=64,
                  seq_len=64, dropout_rate=0.0)
    gp = serve_runner.init_params(g, 0, jnp.float32)
    gpt = ServingEngine(gp, g, slots=2, fast_path=False)
    ret = engine(params, cfg)
    prompt = np.arange(9, dtype=np.int32)
    gpt.submit(Request(prompt, 4, request_id="g"))
    ret.submit(Request(prompt, 4, request_id="r"))
    while gpt.pending or ret.pending:
        gpt.step()
        ret.step()
    alone = engine(params, cfg)
    alone.submit(Request(prompt, 4, request_id="r"))
    assert gpt.kv.cache_k is not None and ret.kv.cache_k is None
    assert gpt.metrics.snapshot()["ret_slot_steps"] == 0
    assert gpt.metrics.snapshot()["attn_ctx_tokens"] > 0
    assert list(alone.run()["r"].tokens)[:9] == list(prompt)


# ------------------------------------------------------------------ #
# hetu_trace --check and hetu_top
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("check,kind", [(check_ret_attribution, "ret"),
                                        (check_ssm_attribution, "ssm")])
@pytest.mark.parametrize("fields,problem", [
    (dict(slot_steps=18, live_slots=6, layers=3), None),
    (dict(slot_steps=17, live_slots=6, layers=3), "6 live slot(s) x 3"),
    (dict(slot_steps=18, live_slots=6), "without integer"),
    (dict(slot_steps=18, live_slots="6", layers=3), "without integer")])
def test_trace_check_holds_a_step_to_its_live_slots(check, kind, fields,
                                                    problem):
    event = {"event": "serve_step", "step": 7,
             **{f"{kind}_{k}": v for k, v in fields.items()}}
    found = check([event, {"event": "serve_finish",
                           f"{kind}_slot_steps": 1}])
    assert (found == []) if problem is None else (
        len(found) == 1 and problem in found[0]
        and found[0].startswith(f"{kind}-attribution"))
    other = check_ssm_attribution if kind == "ret" else check_ret_attribution
    assert other([event]) == []


def test_the_engines_stream_passes_the_check_and_top_renders_state(
        params, cfg, tmp_path):
    log = tmp_path / "serve.jsonl"
    eng = engine(params, cfg, log_path=str(log))
    serve(eng, SIZES[:4])
    events = [json.loads(line) for line in log.read_text().splitlines()]
    steps = [e for e in events if e.get("event") == "serve_step"]
    assert steps and all(
        e["ret_slot_steps"] == e["ret_live_slots"] * 3 and e["ret_layers"] == 3
        and "ssm_slot_steps" not in e for e in steps)
    assert check_ret_attribution(events) == []
    events.append({"event": "gauge", "kind": "gauge",
                   "name": "serve.state.bytes",
                   "value": eng.kv.state_bytes})
    stats = top.summarize(events)
    assert stats["state"]["slot_steps_per_sec"] is None \
        or stats["state"]["slot_steps_per_sec"] > 0
    frame = top.render(stats)
    assert "state     bytes" in frame and "slot_steps/s" in frame
    plain = [{k: v for k, v in e.items() if not k.startswith("ret_")}
             for e in events if e.get("name") != "serve.state.bytes"]
    assert "state     bytes" not in top.render(top.summarize(plain))


def test_record_state_scan_counts_what_the_check_reads():
    m = ServingMetrics()
    mark = m.mark()
    rec = m.record_state_scan("ret", live_slots=5, rows=260, chunk_pairs=300,
                              layers=6)
    assert rec == {"slot_steps": 30, "rows": 1560, "live_slots": 5,
                   "layers": 6}
    snap = m.snapshot(since=mark)
    assert (snap["ret_slot_steps"], snap["ret_rows"],
            snap["ret_chunk_pairs"]) == (30, 1560, 1800)
    assert snap["ssm_slot_steps"] == 0


@pytest.mark.parametrize("kind,kernel_slots,want", [
    ("ret", 3, 18), ("ret", 0, 0), ("ssm", 3, 0), ("ssm", 0, 0)],
    ids=["wide_slots_x_layers", "one_row_slots", "ssm_kind",
         "ssm_kind_none"])
def test_record_state_scan_counts_the_slots_the_kernel_took(kind,
                                                            kernel_slots,
                                                            want):
    """``serve.ret.kernel_slot_steps`` / ``ret_kernel_slot_steps`` (ISSUE
    45): wide slots x layers of a retention wave whose program takes
    ``kernels/retention_scan``; 0 for a wave of one-row slots and for
    the state-space kind, whose kernel's slots are a counter of their
    own (``serve.ssm.kernel_slot_steps``, ``tests/test_ssm_hybrid.py``)."""
    from hetu_tpu import telemetry
    m = ServingMetrics()
    mark = m.mark()
    before = telemetry.snapshot()["counters"].get(
        "serve.ret.kernel_slot_steps", 0)
    rec = m.record_state_scan(kind, live_slots=24, rows=789, chunk_pairs=0,
                              layers=6, kernel_slots=kernel_slots)
    assert rec["slot_steps"] == 144
    assert m.snapshot(since=mark)["ret_kernel_slot_steps"] == want
    assert m.snapshot()["ret_kernel_slot_steps"] == want
    assert telemetry.snapshot()["counters"].get(
        "serve.ret.kernel_slot_steps", 0) - before == want


# ------------------------------------------------------------------ #
# the accepted cells' programs (tests/test_program_digests.py pins them)
# ------------------------------------------------------------------ #

def window_programs(sds, attn="masked", qs=(1, 8), slots=4):
    from hetu_tpu.models.moe_decode import HybridMoEConfig
    from test_window_moe import NAME as MEL, SMALL as MELLUM
    i32 = lambda *s: sds(s, jnp.int32)                     # noqa: E731
    c = HybridMoEConfig.from_hf(MELLUM)
    B, T, BS = slots, 16, 4
    p = {k: sds(s, jnp.float32) for k, s in c.param_shapes(MEL).items()}
    pool = sds((1, 33, BS, 128), jnp.float32)
    win = sds((3, 25, BS, 128), jnp.float32)
    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"mellum2.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (MEL, 4, 8, 16, 64, c.block_spec()), pool, pool,
                i32(B, T), i32(B), i32(B, Q), i32(B), i32(B),
                sds((B,), jnp.bool_), sds((B,), jnp.float32), i32(B),
                sds((B, 2), jnp.uint32), attn=attn, window=1,
                has_fresh=fresh, win=(win, win), ring=i32(B, 6))
    return out


def retention_programs(sds, qs=(1, 32)):
    """{name: lowered mixed step} of this file's small power-retention
    model: no pool, six states a slot."""
    i32 = lambda *s: sds(s, jnp.int32)                     # noqa: E731
    c = rd.RetentionConfig.from_hf(SMALL)
    blk = c.block_spec()
    B = 4
    p = {k: sds(s, jnp.float32) for k, s in c.param_shapes(NAME).items()}
    state = tuple(sds((sh[0], B) + tuple(sh[1:]), dt)
                  for sh, dt in blk.state_shapes(3, 64))
    fn = gd.serve_mixed_paged_fn(True, "masked", 1)
    out = {}
    for Q in qs:
        for fresh in (False, True):
            out[f"brumby.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                p, (NAME, 3, 4, 16, 512, blk), None, None, i32(B, 1),
                i32(B), i32(B, Q), i32(B), i32(B), sds((B,), jnp.bool_),
                sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                attn="masked", window=1, has_fresh=fresh, state=state)
    return out


def test_a_retention_wave_builds_no_mask_and_takes_no_pool():
    """The program of a spec without a pool layer: no array over
    positions, the states donated and handed back, the pool pair None."""
    lowered = retention_programs(jax.ShapeDtypeStruct,
                                 qs=(8,))["brumby.Q8.fresh1"]
    leaves = jax.tree_util.tree_leaves(lowered.out_info)
    assert len(leaves) == 2 + 6            # sampled, keys, the six states
    assert "x512x" not in lowered.as_text()      # nothing spans S_max
