"""``kernels/retention_scan`` (ISSUE 45): the power-retention layer's
chunked form through ``phi`` as one Pallas kernel, interpreted on the
CPU, against ``retention_decode.retention_chunked`` (the form in XLA's
own operations, which heads narrower than a lane tile keep running);
and (ISSUE 63) the one-step form as another, against
``retention_decode.retention_step``.

Head 128 so that the kernel is what runs (its stripes are the head's
width: ``D`` 8,256), few rows and K/V heads so that it is quick.  Both
sides are float32 here: ``y`` is held to bfloat16's rounding (2^-8 of
its size, what the served rows are stored in), the states to 1e-5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels.retention_scan import (
    retention_chunk_scan, retention_step_scan)
from hetu_tpu.models import retention_decode as rd

D_HEAD = 128
D_STATE = D_HEAD * (D_HEAD + 1) // 2
SLOTS = 5
G = 2


def rows(rng, lanes, Q, m, q_len):
    """(q, k, v, lg) of ``lanes`` q-blocks ``Q`` wide with ``q_len`` live
    rows each: dead rows have lg 0 and k 0, as the mixer hands them."""
    q = rng.normal(size=(lanes, Q, G, m, D_HEAD)).astype(np.float32)
    k = rng.normal(size=(lanes, Q, G, D_HEAD)).astype(np.float32)
    v = rng.normal(size=(lanes, Q, G, D_HEAD)).astype(np.float32)
    lg = np.log(rng.uniform(0.6, 0.999, size=(lanes, Q, G))
                ).astype(np.float32)
    live = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    # (a row's own key in sight of its query: test_retention.py says why)
    q = q + k[:, :, :, None, :]
    k = np.where(live[..., None, None], k, 0)
    lg = np.where(live[..., None], lg, 0)
    return tuple(map(jnp.asarray, (q, k, v, lg)))


def states(rng, scale=1.0):
    mats = rng.normal(size=(1, SLOTS, G, D_STATE, D_HEAD)) * scale
    norms = np.abs(rng.normal(size=(1, SLOTS, G, D_STATE))) * scale
    return jnp.asarray(mats, jnp.float32), jnp.asarray(norms, jnp.float32)


def close(got, want, rel):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


def same_bits(a, b):
    return np.array_equal(np.asarray(a).view(np.uint32),
                          np.asarray(b).view(np.uint32))


@pytest.mark.parametrize("m", [1, 5], ids=["m1", "m5"])
@pytest.mark.parametrize("Q", [8, 16, 12])
def test_the_kernel_is_the_xla_body(Q, m):
    """Three lanes on slots 3, 0, 4 (the third idle), chunks of 8: a
    q-block of one chunk, of two (the second reads the state the first
    left) and one that is no whole number of chunks, a lane with dead
    rows past its ``q_len``."""
    rng = np.random.default_rng(Q * 10 + m)
    slot = jnp.array([3, 0, 4])
    q_len = np.array([Q, Q - 3, 0])
    q, k, v, lg = rows(rng, 3, Q, m, q_len)
    mats, norms = states(rng)
    want_y, want_S, want_z = rd.retention_chunked(
        q, k, v, lg, mats[0][slot], norms[0][slot], 8)
    y, mats2, norms2 = rd.retention_chunked_inplace(
        q, k, v, lg, mats, norms, slot, jnp.asarray(q_len), 8)
    for b in range(2):
        n = q_len[b]
        close(y[b, :n], want_y[b, :n], 2.0 ** -8)
        close(mats2[0, slot[b]], want_S[b], 1e-5)
        close(norms2[0, slot[b]], want_z[b], 1e-5)
    # the idle lane's slot and the slots no lane holds: bit for bit
    for s in (4, 1, 2):
        np.testing.assert_array_equal(np.asarray(mats2[0, s]),
                                      np.asarray(mats[0, s]))
        np.testing.assert_array_equal(np.asarray(norms2[0, s]),
                                      np.asarray(norms[0, s]))


@pytest.mark.parametrize("m,seed", [(1, 0), (5, 1), (2, 2)])
def test_phi_dot_phi_through_the_kernels_stripes(m, seed):
    """One key into an empty state leaves ``z = phi(k)`` in the kernel's
    stripe order, the half stripe included, to the bit ``sympow2``'s; a
    query read through it is ``(q . k)^2``."""
    rng = np.random.default_rng(seed)
    c = 8
    q, k, v, _ = rows(rng, 1, c, m, [1])
    one = jnp.ones((1, c, G), jnp.float32)
    zero = (jnp.zeros((1, SLOTS, G, D_STATE, D_HEAD), jnp.float32),
            jnp.zeros((1, SLOTS, G, D_STATE), jnp.float32))
    slot, n = jnp.array([2]), jnp.array([1])
    _, _, mats, norms = retention_chunk_scan(slot, n, q, k, v, one,
                                             one[:, 0], *zero)
    phi_k = rd.sympow2(k[0, 0])                            # [G, D]
    np.testing.assert_array_equal(np.asarray(norms[0, 2]),
                                  np.asarray(phi_k))
    close(mats[0, 2], phi_k[..., None] * v[0, 0][:, None, :], 1e-6)
    _, den, _, _ = retention_chunk_scan(slot, n, q, jnp.zeros_like(k), v,
                                        one, one[:, 0], mats, norms)
    want = np.einsum("igmd,gd->gmi", np.asarray(q[0], np.float64),
                     np.asarray(k[0, 0], np.float64)) ** 2
    # (|q|^2 |k|^2 is ~1e4 here: a query near right angles to the key
    # reads a small number off sums of that size)
    np.testing.assert_allclose(np.asarray(den[0]), want, rtol=1e-5,
                               atol=1e-2)


@pytest.mark.parametrize("q_len", [(8, 0, 0), (0, 0, 0), (0, 5, 8)],
                         ids=["tail_idle", "all_idle", "head_idle"])
def test_an_idle_lane_and_an_untouched_slot_keep_their_bits(q_len):
    rng = np.random.default_rng(sum(q_len))
    slot = jnp.array([1, 4, 2])
    q, k, v, lg = rows(rng, 3, 8, 5, q_len)
    mats, norms = states(rng, scale=1e3)
    # (negative zeros too: a decay of 1 and an increment of +0 would
    # turn them over)
    mats = mats.at[0, 4, 0, :64].set(-0.0)
    y, mats2, norms2 = rd.retention_chunked_inplace(
        q, k, v, lg, mats, norms, slot, jnp.asarray(q_len), 8)
    assert np.isfinite(np.asarray(y)).all()
    moved = {int(slot[b]) for b in range(3) if q_len[b]}
    for s in range(SLOTS):
        same = same_bits(mats2[0, s], mats[0, s]) \
            and same_bits(norms2[0, s], norms[0, s])
        assert same == (s not in moved), s


# the one-step kernel's slot sets, a q_len a slot: every slot one row;
# none (wide, dead: the kernel's grid is all idle); one-row slots mixed
# with a wide one and dead ones, the last slot and the first among the
# ones that move
Q_LENS = {"all_one": (1, 1, 1, 1, 1), "none": (8, 0, 0, 5, 0),
          "mixed": (1, 8, 0, 0, 1), "wide_first": (4, 1, 1, 0, 1)}


def step_rows(rng, m):
    q = rng.normal(size=(SLOTS, G, m, D_HEAD)).astype(np.float32)
    k = rng.normal(size=(SLOTS, G, D_HEAD)).astype(np.float32)
    v = rng.normal(size=(SLOTS, G, D_HEAD)).astype(np.float32)
    lg = np.log(rng.uniform(0.6, 0.999, size=(SLOTS, G))).astype(np.float32)
    return tuple(map(jnp.asarray, (q + k[:, :, None, :], k, v, lg)))


@pytest.mark.parametrize("m", [1, 5, 2], ids=["m1", "m5", "m2"])
@pytest.mark.parametrize("slots", ["all_one", "none", "mixed"])
def test_the_step_kernel_is_the_xla_step(slots, m):
    """``retention_step_inplace`` against ``retention_step`` as the
    mixer hands it the wave (``k`` 0 and ``lg`` 0 on the slots that do
    not move): ``y``, ``S`` and ``z`` of the one-row slots in float32,
    every other slot's state bit for bit and its ``y`` 0."""
    rng = np.random.default_rng(len(slots) * 10 + m)
    one = np.asarray(Q_LENS[slots]) == 1
    q, k, v, lg = step_rows(rng, m)
    mats, norms = states(rng)
    want_y, want_S, want_z = rd.retention_step(
        q, jnp.where(one[:, None, None], k, 0), v,
        jnp.where(one[:, None], lg, 0.0), mats[0], norms[0])
    y, mats2, norms2 = rd.retention_step_inplace(
        q, k, v, lg, mats, norms, jnp.asarray(one))
    for s in range(SLOTS):
        if one[s]:
            close(y[s], want_y[s], 1e-5)
            close(mats2[0, s], want_S[s], 1e-6)
            close(norms2[0, s], want_z[s], 1e-6)
        else:
            assert not np.asarray(y[s]).any()
            assert same_bits(mats2[0, s], mats[0, s])
            assert same_bits(norms2[0, s], norms[0, s])


@pytest.mark.parametrize("slots", ["none", "mixed", "wide_first"])
def test_a_slot_the_step_kernel_does_not_take_keeps_its_bits(slots):
    """A wide slot, a dead one and the wave with no one-row slot at all,
    states of 1e3 with negative zeros among them (a decay of 1 and an
    increment of +0 would turn them over); the kernel called as it
    stands, its lanes past the last one-row slot naming that slot
    again."""
    rng = np.random.default_rng(sum(Q_LENS[slots]))
    one = np.asarray(Q_LENS[slots]) == 1
    q, k, v, lg = step_rows(rng, 5)
    mats, norms = states(rng, scale=1e3)
    mats = mats.at[0, :, 0, :64].set(-0.0)
    n = int(one.sum())
    order = np.argsort(~one, kind="stable")
    slot = np.where(np.arange(SLOTS) < n, order, order[max(n - 1, 0)])
    num, den, mats2, norms2 = retention_step_scan(
        jnp.asarray(slot), jnp.asarray(n), q[slot], k[slot], v[slot],
        jnp.exp(lg[slot]), mats, norms)
    assert np.isfinite(np.asarray(num)).all()
    assert not np.asarray(num[n:]).any() and not np.asarray(den[n:]).any()
    for s in range(SLOTS):
        same = same_bits(mats2[0, s], mats[0, s]) \
            and same_bits(norms2[0, s], norms[0, s])
        assert same == (not one[s]), s


@pytest.mark.parametrize("head_dim,q_block,kernel", [
    (16, 8, False), (2, 256, False), (128, 1, False), (64, 256, False),
    (128, 2, True), (128, 256, True), (256, 8, True),
    # asked of the head alone: the one-row slots' step
    (128, None, True), (256, None, True), (16, None, False),
    (64, None, False)])
def test_the_shape_rule(head_dim, q_block, kernel):
    assert rd.takes_kernel(head_dim, q_block) is kernel


def mixer_case(d, Q):
    """A wave of six slots x ``Q`` rows on ``G`` K/V heads of
    ``d``: slots of Q, Q - 3, 2 and 5 rows (two passes of three lanes,
    the second with two idle lanes), one decoding slot, one dead."""
    rng = np.random.default_rng(d + Q)
    B, m, D = 6, 2, d * (d + 1) // 2
    sp = rd.RetentionSpec(G, d, chunk=4)
    q = jnp.asarray(rng.normal(size=(B, Q, G * m, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Q, G, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Q, G, d)), jnp.float32)
    lg = jnp.asarray(np.log(rng.uniform(0.6, 0.999, size=(B, Q, G))),
                     jnp.float32)
    q = q + jnp.repeat(k, m, axis=2)
    state = (jnp.asarray(rng.normal(size=(1, B, G, D, d)), jnp.float32),
             jnp.asarray(np.abs(rng.normal(size=(1, B, G, D))), jnp.float32))
    q_len = jnp.asarray([Q, 1, max(Q - 3, 1), 0, min(2, Q), min(5, Q)])
    return sp, q, k, v, lg, state, q_len


@pytest.mark.parametrize("d,Q,kernels", [(128, 8, 2), (128, 1, 1),
                                         (16, 8, 0), (16, 1, 0)])
def test_the_mixer_takes_the_kernel_by_the_rule(d, Q, kernels):
    """A head of 128: the one-step kernel in every program, the chunk
    kernel beside it where the q-block is wider than one row; a head of
    16: neither."""
    sp, q, k, v, lg, state, q_len = mixer_case(d, Q)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: rd.retention_mixer(sp, *a, 0, q_len))(q, k, v, lg, state))
    assert ("pallas_call" in jaxpr) is bool(kernels)
    assert ("retention_step_scan" in jaxpr) is (kernels > 0)
    assert ("retention_chunk_scan" in jaxpr) is (kernels > 1)


@pytest.mark.parametrize("Q", [8, 1])
def test_the_mixers_wave_is_the_same_wave_through_the_kernel(monkeypatch, Q):
    """The one-row slot through ``retention_step_scan`` and (``Q`` 8) the
    wide ones through ``retention_chunk_scan``, against the same wave in
    XLA's operations."""
    sp, q, k, v, lg, state, q_len = mixer_case(128, Q)
    y, (S, z) = rd.retention_mixer(sp, q, k, v, lg, state, 0, q_len)
    monkeypatch.setattr(rd, "takes_kernel", lambda d, Q=None: False)
    want_y, (want_S, want_z) = rd.retention_mixer(sp, q, k, v, lg, state, 0,
                                                  q_len)
    for b, n in enumerate(np.asarray(q_len)):
        if n:
            close(y[b, :n], want_y[b, :n], 2.0 ** -8)
        close(S[0, b], want_S[0, b], 1e-5)
        close(z[0, b], want_z[0, b], 1e-5)
    # the dead slot's state as it was, through either
    np.testing.assert_array_equal(np.asarray(S[0, 3]),
                                  np.asarray(state[0][0, 3]))


# ------------------------------------------------------------------ #
# the engine: a model of 128-column heads serves through the kernel
# ------------------------------------------------------------------ #

WIDE = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=2, num_key_value_heads=1, head_dim=D_HEAD,
    intermediate_size=96, rope_theta=1e6, rms_norm_eps=1e-6,
    max_position_embeddings=512, retention_chunk=8)


def test_an_engine_of_wide_heads_serves_through_the_kernel():
    """Chunks of 16 over chunks of 8 in the scan: every chunk wave's
    wide slots go through the chunk kernel (twice a q-block), its
    one-row slots and the decode waves' through the one-step kernel; the
    logits against the plain reference as ``tests/test_retention.py``
    holds its engine, and the counter: every live slot of every wave."""
    from hetu_tpu.models import reference_retention as ref
    from hetu_tpu.serving import Request, ServingEngine
    cfg = rd.RetentionConfig.from_hf(WIDE)
    params = rd.init_retention_params(cfg, "bru", seed=5,
                                      memory_range=(4.0, 64.0))
    eng = ServingEngine(params, cfg, slots=3, max_seq_len=128,
                        prefill_chunk=16, fast_path=False)
    rng = np.random.default_rng(2)
    sizes = [(21, 3), (9, 2), (34, 2)]
    for i, (n, m) in enumerate(sizes):
        eng.submit(Request(rng.integers(0, 256, n).astype(np.int32), m,
                           request_id=f"q{i}"))
    out = eng.run()
    for r in out.values():
        seq = np.asarray(r.tokens, np.int32)
        lg = np.asarray(ref.forward(params, cfg, seq[:-1], "bru"))
        at = lg[r.prompt_len - 1:]
        chosen = at[np.arange(len(at)), seq[r.prompt_len:]]
        assert float((at.max(-1) - chosen).max() / lg.std()) <= 2e-4
    snap = eng.metrics.snapshot()
    # every prompt chunk is wider than one row here (21 = 16 + 5, 9,
    # 34 = 16 + 16 + 2: six wide slot steps a layer), and the waves that
    # carry them carry the other slots' single rows
    assert snap["ret_slot_steps"] > 6 * 2
    assert snap["ret_kernel_slot_steps"] == snap["ret_slot_steps"]
    assert snap["ssm_kernel_slot_steps"] == snap["ssm_slot_steps"] == 0
