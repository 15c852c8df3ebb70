"""The decoder of sliding-window and full grouped-query layers over a
softmax-routed FFN (``HybridMoEConfig`` with ``model_type`` "mellum": the
Mellum 2 family) on the serving path, at a small size on the CPU (ISSUE
42): hidden 48, 8 query heads of 16 over 2 K/V heads, window 12, 8
experts top-2, layers s s s F, vocabulary 257, paged block 4 and chunks
of 8, so that the ring (6 blocks) wraps after 24 positions.  Every
comparison is of LOGITS against the plain reference's full forward
(``models/reference_window_moe.py``), never of tokens alone.
"""

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.kernels import ragged_attention as ra
from hetu_tpu.kv_layout import kv_row_width, kv_rows
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import reference_window_moe as ref
from hetu_tpu.models.moe_decode import (
    HybridMoEConfig, RoutedSpec, init_hybrid_moe_params, route)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.serving.kv_manager import PagedKVManager
from hetu_tpu.serving.kv_tiers import TieredKVStore

# the described v5e chip and its shape-with-sharding factory
from test_chip_compile import (  # noqa: E402,F401
    no_compile_cache, sds, topo)
from jitted import mixed_wave, reference  # noqa: E402

NAME = "mel"
PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}
SMALL = dict(
    vocab_size=257, hidden_size=48, num_hidden_layers=4, head_dim=16,
    num_attention_heads=8, num_key_value_heads=2,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, rms_norm_eps=1e-6, sliding_window=12,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 1.1386},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}},
    tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
    use_sliding_window=True, max_position_embeddings=256,
    model_type="mellum")
# float32 weights and float32 pools on both sides: what is left is the
# order of the sums (grouped against dense expert matmuls, online against
# whole softmax): 1e-5 of logits whose standard deviation is 1.4
TOL = 2e-4
SIZES = [(5, 6), (12, 9), (30, 5), (61, 20), (21, 7), (90, 12)]


@pytest.fixture(scope="module")
def cfg():
    return HybridMoEConfig.from_hf(SMALL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_hybrid_moe_params(cfg, name=NAME, seed=3, scale=0.2)


def engine(params, cfg, **kw):
    kw = dict(dict(slots=4, max_seq_len=128, kv_block=4, prefill_chunk=8,
                   fast_path=False), **kw)
    return ServingEngine(params, cfg, **kw)


def serve(eng, sizes, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 257, n).astype(np.int32), m,
                    request_id=f"r{i}") for i, (n, m) in enumerate(sizes)]
    return eng.run(reqs)


def gap(params, result, wrong=()):
    """The widest gap between a row's largest reference logit and the
    reference logit of the token the engine chose."""
    seq = np.asarray(result.tokens, np.int32)
    # the sound reference as one program a length; a fault's operator by
    # operator, which the faults share (a program a fault and a length
    # would be thirty of them)
    forward = ref.forward if wrong else functools.partial(reference,
                                                          ref.forward)
    lg, _ = forward(params, SMALL, seq[:-1], name=NAME, wrong=wrong)
    rows = np.asarray(lg)[result.prompt_len - 1:]
    chosen = rows[np.arange(len(rows)), seq[result.prompt_len:]]
    return float((rows.max(-1) - chosen).max())


@pytest.fixture(scope="module")
def served(params, cfg):
    """Six requests on four slots through the masked path: prompts
    below (5), at (12) and several rings past (61, 90) the window."""
    eng = engine(params, cfg)
    return eng, serve(eng, SIZES)


# ------------------------------------------------------------------ #
# the config class and the block spec
# ------------------------------------------------------------------ #

def test_config_reads_the_sources_keys(cfg):
    blk = cfg.block_spec()
    assert blk.ops == ("window_attention",) * 3 + ("attention",)
    assert (blk.attention, blk.kv_heads, blk.qk_norm, blk.bias) == (
        "gqa", 2, False, False)
    assert (blk.window, blk.head_dim, blk.head) == (12, 16, "untied")
    assert blk.routed == RoutedSpec(8, 2, 1.0, True, 0, "softmax")
    assert [blk.op_index(i) for i in range(4)] == [0, 1, 2, 0]
    assert [blk.holds(i, "pool") for i in range(4)] == [False] * 3 + [True]
    assert [blk.holds(i, "window") for i in range(4)] == [True] * 3 + [False]
    assert (blk.op_layers(4, "pool"), blk.op_layers(4, "window"),
            blk.op_layers(4, "state")) == (1, 3, 0)
    assert blk.state_shapes(4, 48) is None
    # rotary parameters by operator, frequencies on the host
    inv_w, f_w = blk.rope_of(0)
    inv_f, f_f = blk.rope_of(3)
    assert f_w == 1.0 and f_f == 1.1386 and len(inv_w) == len(inv_f) == 8
    assert inv_w != inv_f
    gd.check_block_spec(blk, 4)
    hash(blk)                                  # jit-static
    shapes = cfg.param_shapes(NAME)
    assert shapes["mel_h0_attn_q_weight"] == (48, 128)
    assert shapes["mel_h0_attn_k_weight"] == (48, 32)
    assert shapes["mel_lm_head_weight"] == (48, 257)
    assert shapes["mel_h3_moe_experts_down"] == (8, 32, 48)
    assert not any("router_bias" in k or "norm_scale" in k or "ffn_" in k
                   for k in shapes)


@pytest.mark.parametrize("change,message", [
    ({"sliding_window": 0}, "sliding_window"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"model_type": "other"}, "model_type"),
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"layer_types": ["sliding_attention"] * 3 + ["linear_attention"]},
     "linear_attention"),
])
def test_config_refuses_what_it_cannot_run(change, message):
    with pytest.raises(ValueError, match=message):
        HybridMoEConfig.from_hf(dict(SMALL, **change))


def test_an_unknown_rope_type_is_refused():
    bad = dict(SMALL, rope_parameters=dict(
        SMALL["rope_parameters"],
        full_attention={"rope_type": "llama3", "rope_theta": 1e4}))
    with pytest.raises(ValueError, match="llama3"):
        HybridMoEConfig.from_hf(bad).block_spec()


@pytest.mark.parametrize("change", [
    {"window": 0}, {"ops": ("window_attention", "conv"), "conv_kernel": 3},
    {"rope_by_op": (("conv", (1.0,), 1.0),)},
    {"rope_by_op": (("attention", (1.0,), 0.0),)},
    {"routed": RoutedSpec(8, 2, scoring="tanh")},
    {"attention": "latent"}])
def test_check_block_spec_refuses(cfg, change):
    blk = cfg.block_spec()._replace(
        ops=("window_attention", "attention"))
    gd.check_block_spec(blk, 2)
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk._replace(**change), 2)
    # the message lists what exists after this PR
    with pytest.raises(ValueError, match="window_attention.*yarn.*softmax"):
        gd.check_block_spec(blk._replace(window=0), 2)
    # a window without a window layer is refused too
    with pytest.raises(ValueError, match="cannot run"):
        gd.check_block_spec(blk._replace(ops=("attention", "attention")), 2)


# ------------------------------------------------------------------ #
# YaRN's frequencies against the closed form, at the published numbers
# ------------------------------------------------------------------ #

def test_yarn_frequencies_at_the_published_numbers():
    d, theta, s = 128, 500000.0, 16.0
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    inv, factor = gd.rope_frequencies(d, **PUBLISHED_YARN)
    assert factor == 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    # ... which is also what a file without the key gets
    implied = dict(PUBLISHED_YARN)
    del implied["attention_factor"]
    assert abs(gd.rope_frequencies(d, **implied)[1] - factor) < 1e-12
    inv = np.asarray(inv)
    base = theta ** (-2 * np.arange(64) / d)
    ramp = np.clip((np.arange(64) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(
        inv, (1 - ramp) * base + ramp * base / s, rtol=1e-12)
    # below ``low`` the default frequencies, above ``high`` a sixteenth
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-12)
    assert base[26] / 16 < inv[26] < base[26]
    # the default section: theta's own, factor 1; the reference's own
    # closed form agrees with the program's
    inv0, f0 = gd.rope_frequencies(
        d, rope_type="default", rope_theta=500000)
    np.testing.assert_allclose(inv0, base, rtol=1e-12)
    assert f0 == 1.0
    rinv, rf = ref.inv_freq(d, **PUBLISHED_YARN)
    np.testing.assert_allclose(rinv, inv, rtol=1e-12)
    assert rf == factor


def test_rope_with_frequencies_and_a_factor():
    """``_rope`` with ``inv`` and ``factor``: cos and sin BOTH scaled, so
    a rotated vector's norm is ``factor`` times the plain one's and a
    score carries its square; without them the text is what it was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16), jnp.float32)
    pos = jnp.arange(10).reshape(2, 5)
    inv, factor = gd.rope_frequencies(16, **SMALL["rope_parameters"][
        "full_attention"])
    plain = gd._rope(x, pos, 10000.0)
    same = gd._rope(x, pos, 10000.0, gd.rope_frequencies(
        16, rope_theta=10000.0)[0], 1.0)
    np.testing.assert_allclose(same, plain, rtol=1e-6, atol=1e-6)
    yarn = gd._rope(x, pos, 10000.0, inv, factor)
    np.testing.assert_allclose(
        jnp.linalg.norm(yarn, axis=-1),
        factor * jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert not np.allclose(yarn, factor * plain, atol=1e-3)


# ------------------------------------------------------------------ #
# softmax routing against the reference, the normalisation included
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("norm", [True, False])
def test_softmax_route_matches_the_dense_reference(norm):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(33, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(48, 8)), jnp.float32)
    spec = RoutedSpec(8, 3, scale=1.0, norm_topk=norm, scoring="softmax")
    sel, wt = route(x, w, None, spec)                  # no bias is read
    with jax.default_matmul_precision("highest"):
        p = np.asarray(jax.nn.softmax(x @ w, -1))
    top = np.argsort(-p, axis=-1)[:, :3]
    assert (np.sort(np.asarray(sel), -1) == np.sort(top, -1)).all()
    want = np.take_along_axis(p, np.asarray(sel), -1)
    if norm:
        want = want / want.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(wt).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wt), want, rtol=1e-5)
    # a sigmoid router on the same scores chooses by s + b and weights
    # by s: another function
    _, ws = route(x, w, jnp.zeros(8), spec._replace(scoring="sigmoid"))
    assert not np.allclose(np.asarray(ws), np.asarray(wt), atol=1e-3)


# ------------------------------------------------------------------ #
# the kernel against a banded softmax
# ------------------------------------------------------------------ #

def banded_reference(q, pool_k, pool_v, lengths, q_lens, tables, layer,
                     groups, window):
    """``ragged_masked_reference`` with the band, in plain jnp."""
    B, Q, H, Dh = q.shape
    bs = pool_k.shape[2]
    T = tables.shape[1]
    Hkv = H // groups
    k = pool_k[layer][tables].reshape(B, T * bs, -1)[..., :Hkv * Dh]
    v = pool_v[layer][tables].reshape(B, T * bs, -1)[..., :Hkv * Dh]
    k = jnp.repeat(k.reshape(B, T * bs, Hkv, Dh), groups, axis=2)
    v = jnp.repeat(v.reshape(B, T * bs, Hkv, Dh), groups, axis=2)
    posq = jnp.clip((lengths - q_lens)[:, None] + jnp.arange(Q)[None, :],
                    0, jnp.maximum(lengths - 1, 0)[:, None])
    s = jnp.einsum("bqhd,bshd->bqhs", q, k) * Dh ** -0.5
    j = jnp.arange(T * bs)[None, None, None, :]
    seen = j <= posq[:, :, None, None]
    if window:
        seen &= j > posq[:, :, None, None] - window
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    out = jnp.einsum("bqhs,bshd->bqhd", p, v)
    return out * (lengths > 0)[:, None, None, None]


# block 4; a page group is 16 pages = 64 positions: 70 is not a multiple
@pytest.mark.parametrize("window", [0, 4, 16, 70])
@pytest.mark.parametrize("q_len", [1, 8], ids=["decode", "chunk"])
def test_window_kernel_against_a_banded_softmax(window, q_len):
    """Interpret mode, float32: four slots of unlike lengths in one wave
    (one dead), lengths from inside the window to three groups past it,
    under a table that lists every page once (``window`` 0 is the kernel
    there was)."""
    rng = np.random.default_rng(window * 10 + q_len)
    B, H, G, Dh, bs, T, N, L = 4, 4, 2, 16, 4, 64, 300, 2
    W = kv_row_width(H // G, Dh)
    pool_k = jnp.asarray(rng.normal(size=(L, N, bs, W)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(L, N, bs, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(N - 1)[:B * T].reshape(B, T) + 1,
                         jnp.int32)
    lengths = jnp.asarray([q_len, 37, 0, 201], jnp.int32)
    q_lens = jnp.asarray([q_len, min(q_len, 5), 0, q_len], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, q_len, H, Dh)), jnp.float32)
    got = ra.ragged_paged_attention(
        q, pool_k, pool_v, lengths, q_lens, tables, layer=1, groups=G,
        interpret=True, window=window)
    want = banded_reference(q, pool_k, pool_v, lengths, q_lens, tables, 1,
                            G, window)
    live = (jnp.arange(q_len)[None, :] < q_lens[:, None])
    np.testing.assert_allclose(
        np.where(live[:, :, None, None], got, 0),
        np.where(live[:, :, None, None], want, 0), rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(got[2]).max()) == 0.0         # the dead slot


def test_window_kernel_over_a_ring_repeated():
    """The table the step hands a window layer: a ring of 6 blocks a slot
    repeated over the logical pages.  Pages a later one has overwritten
    lie before every band, so the result is that of a table that still
    held them all."""
    rng = np.random.default_rng(7)
    B, H, G, Dh, bs, T, ring, window = 2, 4, 2, 16, 4, 32, 6, 12
    W = kv_row_width(H // G, Dh)
    n = 1 + B * T
    flat_k = jnp.asarray(rng.normal(size=(1, n, bs, W)), jnp.float32)
    flat_v = jnp.asarray(rng.normal(size=(1, n, bs, W)), jnp.float32)
    flat = jnp.arange(1, n).reshape(B, T).astype(jnp.int32)
    lengths = jnp.asarray([90, 41], jnp.int32)
    q_lens = jnp.asarray([8, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 8, H, Dh)), jnp.float32)
    want = banded_reference(q, flat_k, flat_v, lengths, q_lens, flat, 0, G,
                            window)
    # the ring pool: logical page j of slot b in block 1 + b*ring + j%ring,
    # later pages overwriting earlier ones, up to the slot's last page
    ring_k = jnp.zeros((1, 1 + B * ring, bs, W), jnp.float32)
    ring_v = jnp.zeros_like(ring_k)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // bs)):
            at = 1 + b * ring + j % ring
            ring_k = ring_k.at[0, at].set(flat_k[0, flat[b, j]])
            ring_v = ring_v.at[0, at].set(flat_v[0, flat[b, j]])
    table = (1 + jnp.arange(B)[:, None] * ring
             + jnp.arange(T)[None, :] % ring).astype(jnp.int32)
    got = ra.ragged_paged_attention(
        q, ring_k, ring_v, lengths, q_lens, table, layer=0, groups=G,
        interpret=True, window=window)
    live = (jnp.arange(8)[None, :] < q_lens[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               np.where(live, want, 0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("q_len", [1, 256], ids=["Q1", "Q256"])
def test_the_window_kernel_compiles_at_the_cells_sizes(sds, q_len):
    """Compiled for the described v5e at the published widths (32 query
    heads over 4 K/V heads of 128: rows of 512 lanes; 32 slots; a table
    of 1,024 logical pages over a ring of 81; window 1,024): the banded
    kernel is named ``ragged_paged_window`` and nothing of it is
    ``ragged_paged_mixed``; with ``window`` 0 it is the other way round."""
    B, T, H, G, DH, BS = 32, 1024, 32, 8, 128, 16
    pool = sds((9, 32 * 81 + 1, BS, kv_row_width(H // G, DH)), jnp.bfloat16)
    lens = sds((B,), jnp.int32)
    args = (sds((B, q_len, H, DH), jnp.bfloat16), pool, pool, lens, lens,
            sds((B, T), jnp.int32))
    texts = {}
    for window in (1024, 0):
        def fn(q, pk, pv, lengths, q_lens, bt):
            return ra.ragged_paged_attention(
                q, pk, pv, lengths, q_lens, bt, layer=8, interpret=False,
                groups=G, window=window)
        texts[window] = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in texts[window]
    assert "ragged_paged_window" in texts[1024]
    assert "ragged_paged_mixed" not in texts[1024]
    assert "ragged_paged_window" not in texts[0]
    assert "ragged_paged_mixed" in texts[0]


def test_the_int8_pools_kernel_has_no_window():
    q = jnp.zeros((1, 1, 2, 8), jnp.float32)
    pool = jnp.zeros((1, 3, 4, 2, 8), jnp.int8)
    sc = jnp.ones((1, 3, 4, 2), jnp.float32)
    with pytest.raises(ValueError, match="no window"):
        ra.ragged_paged_attention(
            q, pool, pool, jnp.ones(1, jnp.int32), jnp.ones(1, jnp.int32),
            jnp.zeros((1, 2), jnp.int32), k_scale=sc, v_scale=sc, window=4)


# ------------------------------------------------------------------ #
# engine through both pools against the reference's full forward
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("fast", [False, True], ids=["masked", "kernel"])
def test_engine_through_both_pools_matches_reference(params, cfg, fast,
                                                     served):
    """Chunked prefill then decode, six requests on four slots (two take
    a slot another has used), prompts below, at and several rings past
    the window, so chunk waves and decode waves of slots of unlike
    length share a wave."""
    eng, out = served if not fast else (None, None)
    if fast:
        eng = engine(params, cfg, fast_path=True)
        out = serve(eng, SIZES)
    # the pool holds the full layer alone, the window pool the three
    # window layers: rows of 2 K/V heads of 16 padded to the 128 lanes,
    # a ring of ceil((12 + 8) / 4) + 1 = 6 blocks a slot + scratch
    assert eng.kv.cache_k.shape == (1, eng.kv.n_blocks, 4, 128)
    assert eng.kv.win_k.shape == (3, 4 * 6 + 1, 4, 128)
    assert eng.kv.win_v.shape == eng.kv.win_k.shape
    assert eng.kv.ring == 6 and not eng.kv.prefix_share
    for r in out.values():
        assert gap(params, r) <= TOL, r.request_id
    assert eng.kv.free_blocks == eng.kv.capacity_blocks   # all released
    assert eng.kv.free_window_blocks == 4 * 6
    assert not eng.kv.win_tables.any()
    stats = eng.kv.stats()
    assert stats["full_bytes"] == 2 * eng.kv.n_blocks * 4 * 128 * 4
    assert stats["window_bytes"] == 2 * 3 * 25 * 4 * 128 * 4
    assert stats["cache_bytes"] == eng.kv.cache_bytes \
        == stats["full_bytes"] + stats["window_bytes"]
    assert stats["window_ring"] == 6 and stats["window_layers"] == 3


def test_engine_logits_match_reference_row_for_row(params, cfg):
    """The wave's own logits, every row of chunks and decode steps of
    TWO slots of unlike length in one wave, against the reference's: not
    only the chosen token's.  Slot 0 runs 50 positions (the ring wraps
    twice), slot 1 starts later and stays inside the window."""
    blk = cfg.block_spec()
    cfg_tuple = (NAME, 4, 8, 16, 64, blk)
    kv = PagedKVManager(layers=1, heads=2, head_dim=16, slots=2,
                        max_seq_len=64, dtype=jnp.float32, block=4,
                        window_layers=3, window=12, window_chunk=8)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 257, 50).astype(np.int32),
            rng.integers(0, 257, 11).astype(np.int32)]
    slots = [kv.alloc("a", seqs[0], 64)[0], kv.alloc("b", seqs[1], 64)[0]]
    want = [np.asarray(ref.forward(params, SMALL, s, name=NAME)[0])
            for s in seqs]
    got = [[], []]
    # (offset, rows) of slot 0 | of slot 1, wave by wave
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


# the four faults the comparison must notice, each one thing computed
# wrongly on the REFERENCE's side against the sound engine's tokens
@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_comparison_notices(params, served, wrong):
    _, out = served
    sound = max(gap(params, r) for r in out.values())
    # the fault must show in the two longest alone (several rings past
    # the window): fewer sequences to find it in, and two lengths for
    # the faulty reference to compile
    faulty = max(gap(params, out[r], wrong=(wrong,)) for r in ("r3", "r5"))
    assert sound <= TOL
    assert faulty > 500 * TOL, (wrong, faulty)


def test_the_window_never_binds_under_the_window(params, served):
    """A prompt + answer of 11 positions lies inside the window of 12:
    scored as a full layer it reads the same (the fault shows only where
    the window binds), so the sample must hold a long prompt."""
    _, out = served
    assert gap(params, out["r0"], wrong=("window_as_full",)) <= TOL
    assert gap(params, out["r3"], wrong=("window_as_full",)) > 500 * TOL


def test_reference_refuses_an_unknown_fault(params):
    with pytest.raises(ValueError, match="unknown faults"):
        ref.forward(params, SMALL, np.arange(4), name=NAME,
                    wrong=("nothing",))


# ------------------------------------------------------------------ #
# the ring
# ------------------------------------------------------------------ #

def manager(**kw):
    kw = dict(dict(layers=1, heads=2, head_dim=16, slots=4, max_seq_len=128,
                   dtype=jnp.float32, block=4, window_layers=3, window=12,
                   window_chunk=8), **kw)
    return PagedKVManager(**kw)


def test_the_ring_is_claimed_whole_and_returned():
    kv = manager()
    assert kv.ring == 6 and kv.win_tables.shape == (4, 6)
    assert kv.free_window_blocks == 24
    # a long prompt costs the window pool no more than a short one
    a, _ = kv.alloc("a", np.arange(100), 120)
    b, _ = kv.alloc("b", np.arange(3), 8)
    assert kv.window_blocks_held(a) == kv.window_blocks_held(b) == 6
    assert kv.free_window_blocks == 12
    held = set(kv.win_tables[a]) | set(kv.win_tables[b])
    assert len(held) == 12 and 0 not in held          # 0 is scratch
    # the full pool holds every position of each
    assert kv.n_table[a] == 30 and kv.n_table[b] == 2
    kv.release(a)
    assert kv.free_window_blocks == 18 and not kv.win_tables[a].any()
    kv.release(b)
    assert kv.free_window_blocks == 24
    assert kv.free_blocks == kv.capacity_blocks


def test_no_live_position_is_overwritten():
    """Every q-block a wave can write (up to ``window_chunk`` rows at any
    offset): the pages it overwrites hold only positions that its first
    row, and so every later row, can no longer see."""
    kv = manager()
    ring, bs, window = kv.ring, kv.block, kv.window
    for pos in range(0, 100):
        for q in (1, 3, 8):
            written = {p // bs for p in range(pos, pos + q)}
            entries = {j % ring for j in written}
            oldest_seen = max(pos - window + 1, 0)
            # logical pages that hold a position some row still sees and
            # that the write does not itself hold
            needed = {p // bs for p in range(oldest_seen, pos)} - written
            assert not ({j % ring for j in needed} & entries), (pos, q)


def test_advance_counts_the_blocks_it_recycles():
    kv = manager()
    s, _ = kv.alloc("a", np.arange(60), 80)
    kv.advance(s, 24)                  # 6 pages: the ring's first turn
    assert kv.window_blocks_recycled == 0
    kv.advance(s, 8)                   # pages 6, 7 overwrite 0, 1
    assert kv.window_blocks_recycled == 2
    kv.advance(s, 1)                   # page 8
    kv.advance(s, 1)                   # still page 8
    assert kv.window_blocks_recycled == 3
    assert kv.stats()["window_blocks_recycled"] == 3


@pytest.mark.parametrize("what", ["prefix_share", "truncate", "export",
                                  "import", "tiers", "kv_quant", "latent"])
def test_a_manager_with_window_layers_refuses_by_name(what):
    if what == "prefix_share":
        with pytest.raises(ValueError, match="prefix_share with window"):
            manager(prefix_share=True)
        return
    if what == "kv_quant":
        with pytest.raises(ValueError, match="window layers beside an int8"):
            manager(dtype="int8")
        return
    if what == "latent":
        # float latent rows HOLD a latent ring of the window layers' own
        # width (one pool, no second); mixed kinds and int8 still raise
        kv = manager(row_shape=(128,), window_row_shape=(256,),
                     index_shape=(16,))
        assert kv.win_k.shape[2:] == (4, 256) and kv.win_v is None
        assert kv.cache_k.shape[2:] == (4, 128)
        assert kv.cache_v.shape == kv.cache_k.shape[:3] + (16,)
        assert kv.index_bytes == kv.cache_v.nbytes
        assert kv.window_bytes == kv.win_k.nbytes
        s, _ = kv.alloc("a", np.arange(40), 60)
        kv.advance(s, 40)
        assert kv.window_blocks_held(s) == kv.ring
        with pytest.raises(ValueError, match="export_blocks.*latent rows"):
            kv.export_blocks(s)
        kv.release(s)
        assert kv.free_window_blocks == kv.n_slots * kv.ring
        with pytest.raises(ValueError, match="window layers beside"):
            manager(row_shape=(128,))
        with pytest.raises(ValueError, match="window layers beside an int8"):
            manager(row_shape=(128,), window_row_shape=(256,), dtype="int8")
        with pytest.raises(ValueError, match="index_shape goes with latent"):
            manager(index_shape=(16,))
        return
    kv = manager()
    assert not kv.prefix_share
    s, _ = kv.alloc("a", np.arange(40), 60)
    kv.advance(s, 40)
    if what == "truncate":
        with pytest.raises(ValueError, match="below the ring's oldest"):
            kv.truncate(s, 30)
        kv.truncate(s, 40)                             # nothing taken back
        # before the ring has turned a rollback uncovers nothing lost
        t, _ = kv.alloc("b", np.arange(10), 20)
        kv.advance(t, 10)
        kv.truncate(t, 6)
        assert kv.lengths[t] == 6
    elif what == "export":
        with pytest.raises(ValueError, match="export_blocks.*ring of 6"):
            kv.export_blocks(s)
    elif what == "import":
        with pytest.raises(ValueError, match="import_blocks.*ring"):
            kv.import_blocks({"layout": "paged"}, "x")
    else:
        with pytest.raises(ValueError, match="window layers cannot spill"):
            TieredKVStore(host_bytes=1 << 20).attach(0, kv)


def test_a_manager_without_window_layers_has_no_window_pool():
    kv = PagedKVManager(layers=2, heads=2, head_dim=16, slots=2,
                        max_seq_len=32, dtype=jnp.float32, block=4)
    assert kv.window_layers == 0 and kv.ring == 0
    assert kv.win_k is None and kv.win_tables is None
    assert kv.window_bytes == 0 and kv.cache_bytes == kv.full_bytes
    assert kv.free_window_blocks == 0
    s, _ = kv.alloc("a", np.arange(9), 20)
    kv.advance(s, 9)
    kv.truncate(s, 4)                                  # as ever
    assert kv.window_blocks_held(s) == 0
    with pytest.raises(ValueError, match="sees at least itself"):
        PagedKVManager(layers=1, heads=2, head_dim=16, slots=2,
                       max_seq_len=32, block=4, window_layers=1)


def test_the_engine_refuses_speculation_and_int8(params, cfg):
    with pytest.raises(ValueError, match="speculation"):
        engine(params, cfg, spec=2)
    with pytest.raises(ValueError, match="int8"):
        engine(params, cfg, kv_quant="int8")
    with pytest.raises(ValueError, match="prefix_share with window"):
        engine(params, cfg, prefix_share=True)


# ------------------------------------------------------------------ #
# counters and gauges
# ------------------------------------------------------------------ #

def test_window_counters_count_what_a_window_layer_read(params, cfg):
    """One request alone, prompt 20 in chunks of 8 then three decode
    steps: a full layer's counters as ever, the window layers' beside
    them (window 12)."""
    from hetu_tpu import telemetry
    eng = engine(params, cfg)
    serve(eng, [(20, 4)])
    snap = eng.metrics.snapshot()
    # waves: (pos 0, q 8) (8, 8) (16, 4) then decode at 20, 21, 22
    assert snap["attn_ctx_tokens"] == 8 + 16 + 20 + 21 + 22 + 23
    assert snap["attn_window_ctx_tokens"] == (
        8 + 16 + 15                      # min(filled, 12 + q - 1)
        + 12 + 12 + 12)
    pairs = lambda pos, q: sum(min(pos + j + 1, 12) for j in range(q))  # noqa
    assert snap["attn_window_score_pairs"] == (
        pairs(0, 8) + pairs(8, 8) + pairs(16, 4) + 3 * 12)
    assert snap["attn_window_score_pairs"] < snap["attn_score_pairs"]
    # 23 positions filled: pages 0..5, the ring's first turn, no more
    assert snap["window_blocks_recycled"] == 0
    mark = eng.metrics.mark()
    serve(eng, [(30, 3)])
    since = eng.metrics.snapshot(since=mark)
    # 32 positions filled = 8 pages: 2 past the ring's 6
    assert since["window_blocks_recycled"] == 2 \
        == eng.kv.window_blocks_recycled
    assert since["attn_window_ctx_tokens"] < since["attn_ctx_tokens"]
    reg = telemetry.snapshot() if hasattr(telemetry, "snapshot") else None
    if reg is not None:
        flat = json_dumps(reg)
        for name in ("serve.attn.window_ctx_tokens",
                     "serve.attn.window_score_pairs",
                     "serve.kv.window_blocks_recycled",
                     "serve.blocks_free.window", "serve.kv.window_bytes"):
            assert name in flat, name


def json_dumps(value):
    import json
    return json.dumps(value, default=str)


def test_an_engine_without_window_layers_counts_none():
    from test_hybrid_moe import SMALL as LFM
    c = HybridMoEConfig.from_hf(LFM)
    p = init_hybrid_moe_params(c, seed=3, scale=0.2)
    eng = ServingEngine(p, c, slots=2, max_seq_len=32, kv_block=4,
                        prefill_chunk=8, fast_path=False)
    eng.run([Request(np.arange(9, dtype=np.int32), 3, request_id="a")])
    snap = eng.metrics.snapshot()
    assert snap["attn_ctx_tokens"] > 0
    assert snap["attn_window_ctx_tokens"] == 0 \
        == snap["attn_window_score_pairs"] == snap["window_blocks_recycled"]
    assert eng.kv.win_k is None


@pytest.mark.parametrize("fields,problems", [
    ({"window_ring": 6, "window_held_max": 6}, 0),
    ({"window_ring": 6, "window_held_max": 0}, 0),
    ({"window_ring": 6, "window_held_max": 7}, 1),
    ({"window_ring": 6}, 1),
    ({}, 0),
], ids=["full", "empty", "over", "no-companion", "exempt"])
def test_trace_check_holds_a_slot_to_its_ring(fields, problems):
    from hetu_tpu.telemetry.trace import check_window_ring
    events = [{"event": "serve_step", "step": 3, **fields},
              {"event": "serve_admit", "window_ring": 1}]
    found = check_window_ring(events)
    assert len(found) == problems
    assert all(p.startswith("window-ring: step 3") for p in found)


def test_the_engines_steps_pass_the_ring_check_and_top_shows_the_pool(
        params, cfg):
    """A served run's own ``serve_step`` records carry the ring and the
    most blocks a slot holds; ``hetu_trace --check``'s rule passes on
    them and ``hetu_top`` renders the window pool's line.  An engine
    without window layers renders none."""
    from hetu_tpu.telemetry import top
    from hetu_tpu.telemetry.trace import check_window_ring
    eng = engine(params, cfg)
    serve(eng, [(30, 3), (5, 2)])
    seen = [dict(e) for e in eng.metrics.events]
    steps = [e for e in seen if e["event"] == "serve_step"]
    assert steps and all(e["window_ring"] == 6 for e in steps)
    assert max(e["window_held_max"] for e in steps) == 6
    assert check_window_ring(seen) == []
    gauges = [{"event": "gauge", "name": "serve.blocks_free.window",
               "value": 18},
              {"event": "gauge", "name": "serve.kv.window_bytes",
               "value": eng.kv.window_bytes}]
    frame = top.render(top.summarize(seen + gauges))
    assert "kv window blocks_free 18  ring 6  held_max" in frame
    assert str(eng.kv.window_bytes) in frame
    plain = [dict(e) for e in seen]
    for e in plain:
        e.pop("window_ring", None)
        e.pop("window_held_max", None)
    assert "kv window" not in top.render(top.summarize(plain))


# ------------------------------------------------------------------ #
# the accepted cells' programs (tests/test_program_digests.py pins them)
# ------------------------------------------------------------------ #

def hybrid_programs(sds, attn, slots=4, qs=(1, 32)):
    """{name: lowered mixed step} of a small ``lfm2_moe`` and a small
    ``falcon_h1`` configuration; ``sds(shape, dtype)`` makes the abstract
    arguments.  At 16 ``slots`` the Q 32 x has_fresh programs are packed
    (``gd.wave_rows``)."""
    B, T, N, BS = slots, 8, 33, 16
    c = HybridMoEConfig.from_hf(dict(
        vocab_size=512, hidden_size=256, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2,
        layer_types=["conv", "full_attention", "conv", "full_attention"],
        conv_L_cache=3, conv_bias=False, intermediate_size=256,
        moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
        num_dense_layers=1, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, rope_theta=1e6, norm_eps=1e-5,
        max_position_embeddings=128, model_type="lfm2_moe"))
    lp = {k: sds(s, jnp.float32 if "router" in k else jnp.bfloat16)
          for k, s in c.param_shapes("lfm").items()}
    cases = {"lfm2": (lp, ("lfm", 4, 8, 32, 128, c.block_spec()),
                      sds((2, N, BS, kv_row_width(2, 32)), jnp.bfloat16),
                      sds((2, B, 2, 256), jnp.bfloat16))}
    cases["falcon"] = falcon_case(sds, B, N, BS)
    return lower_cases(sds, attn, cases, B, T, qs)


def falcon_case(sds, B, N, BS, ssm_state=16):
    """(params, cfg tuple, pool, state) of a small ``falcon_h1``
    configuration; at an ``ssm_state`` of 128 columns the mixers'
    one-row slots take ``kernels/ssm_step``."""
    from hetu_tpu.models import ssm_decode as sd
    f = sd.SSMHybridConfig.from_hf(dict(
        vocab_size=512, hidden_size=256, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        intermediate_size=256, mamba_d_ssm=128, mamba_n_heads=4,
        mamba_d_head=32, mamba_d_state=ssm_state, mamba_n_groups=2,
        mamba_d_conv=4, mamba_chunk_size=8, rope_theta=1e11, rms_norm_eps=1e-5,
        max_position_embeddings=128, embedding_multiplier=5.5,
        attention_in_multiplier=0.9, attention_out_multiplier=0.04,
        key_multiplier=0.3, ssm_in_multiplier=0.25, ssm_out_multiplier=0.09,
        ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.36],
        mlp_multipliers=[0.18, 0.05], lm_head_multiplier=0.02,
        model_type="falcon_h1"))
    fb = f.block_spec()
    fp = {k: sds(s, jnp.bfloat16) for k, s in f.param_shapes("fh1").items()}
    fstate = tuple(
        sds((sh[0], B) + tuple(sh[1:]), jnp.bfloat16 if dt is None else dt)
        for sh, dt in fb.state_shapes(2, 256))
    return (fp, ("fh1", 2, 4, 64, 128, fb),
            sds((2, N, BS, kv_row_width(2, 64)), jnp.bfloat16), fstate)


def lower_cases(sds, attn, cases, B, T, qs=(1, 32)):
    """{name.Q.fresh: lowered mixed step} of ``cases`` {name: (params,
    cfg tuple, pool, state)}."""
    def i32(*s):
        return sds(s, jnp.int32)

    fn = gd.serve_mixed_paged_fn(True, attn, 1)
    out = {}
    for name, (p, cfg_tuple, pool, state) in cases.items():
        for Q in qs:
            for fresh in (False, True):
                out[f"{name}.Q{Q}.fresh{int(fresh)}"] = fn.func.lower(
                    p, cfg_tuple, pool, pool, i32(B, T), i32(B), i32(B, Q),
                    i32(B), i32(B), sds((B,), jnp.bool_),
                    sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
                    attn=attn, window=1, has_fresh=fresh, state=state)
    return out


def test_a_window_spec_lowers_its_own_program(cfg):
    """The window model's wave has both kernels' branches and hands the
    window pool back; nothing of it is in a program without ``win``."""
    abstract = jax.ShapeDtypeStruct
    i32 = lambda *s: abstract(s, jnp.int32)                # noqa: E731
    sds = abstract
    B, T, BS = 4, 16, 4
    p = {k: sds(s, jnp.float32) for k, s in cfg.param_shapes(NAME).items()}
    pool = sds((1, 33, BS, 128), jnp.float32)
    win = sds((3, 25, BS, 128), jnp.float32)
    fn = gd.serve_mixed_paged_fn(True, "masked", 1)
    lowered = fn.func.lower(
        p, (NAME, 4, 8, 16, 64, cfg.block_spec()), pool, pool, i32(B, T),
        i32(B), i32(B, 8), i32(B), i32(B), sds((B,), jnp.bool_),
        sds((B,), jnp.float32), i32(B), sds((B, 2), jnp.uint32),
        attn="masked", window=1, has_fresh=True, win=(win, win),
        ring=i32(B, 6))
    outs = lowered.out_info
    # sampled, pool pair, keys, (load, touched), the window pool pair
    assert len(jax.tree_util.tree_leaves(outs)) == 4 + 2 + 2
    assert jax.tree_util.tree_leaves(outs)[-1].shape == (3, 25, BS, 128)
