"""``kernels/kda_scan`` (ISSUE 59): the gated delta rule's chunked form as
one Pallas kernel, interpreted on the CPU, against
``kda_decode.kda_chunked`` (the form in XLA's own operations, which heads
narrower than a lane tile keep running) and against the recurrence row
after row (``kda_step``).

Head 128 so that the kernel is what runs, few heads and slots so that it
is quick.  Both sides are float32 here (what is left is the order of the
sums): the tolerance is the one ``tests/test_kda_latent.py`` holds the
chunked form to against the recurrence, 2e-4."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import kda_scan
from hetu_tpu.models import kda_decode as kd
from hetu_tpu.models.gpt_decode import _Rows

D, H, SLOTS = 128, 2, 5
TOL = 2e-4


def draw(Q, lanes=3, seed=0, decay="mixed"):
    """(q, k, v, g, beta, mats) of ``lanes`` q-blocks ``Q`` wide: ``g``
    at the bound on every channel of whole sub-blocks ("bound": the
    exponents' worst case, e^80 inside a sub-block), near 0 ("near0"),
    at the bound on half the channels and near 0 on the others, or FREE
    of any bound ("free", ISSUE 62: -30 a step on every channel of whole
    sub-blocks, e^480 where a sub-block's own reference would put it,
    with beta up to 2)."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(lanes, Q, H, D)).astype(np.float32)
               for _ in range(3))
    q, k = (np.asarray(kd.l2norm(jnp.asarray(a))) for a in (q, k))
    g = -5.0 * rng.uniform(size=(lanes, Q, H, D)).astype(np.float32) ** 0.25
    if decay == "bound":
        whole = rng.uniform(size=(lanes, -(-Q // kd.SUB), H, 1)) < 0.5
        g = np.where(np.repeat(whole, kd.SUB, axis=1)[:, :Q], -5.0, g * 0.02)
    elif decay == "free":
        whole = rng.uniform(size=(lanes, -(-Q // kd.SUB), H, 1)) < 0.5
        g = np.where(np.repeat(whole, kd.SUB, axis=1)[:, :Q], -30.0, g)
    elif decay == "near0":
        g = g * 0.002
    else:
        g = np.where(rng.uniform(size=g.shape) < 0.5, -5.0, g * 0.02)
    beta = rng.uniform(0.1, 2.0 if decay == "free" else 1.0,
                       size=(lanes, Q, H)).astype(np.float32)
    mats = rng.normal(size=(1, SLOTS, H, D, D)).astype(np.float32)
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (q, k, v, g, beta, mats))


def kda_chunk_scan(slot, q_len, q, k, v, g, beta, mats, **kw):
    """The kernel over rows [lanes, Q, H, D], handed over as the wave's
    rows lie: a head's columns side by side."""
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))        # noqa: E731
    return kda_scan.kda_chunk_scan(slot, q_len, flat(q), flat(k), flat(v),
                                   flat(g), beta, mats, **kw)


def dead(q_len, Q, k, g, beta):
    """``k``, ``g`` and ``beta`` as the XLA form wants its dead rows."""
    live = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    return (jnp.where(live[..., None, None], k, 0),
            jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0))


@jax.jit
def stepwise(q, k, v, g, beta, S):
    def one(S, x):
        y, S = kd.kda_step(*x, S)
        return S, y
    S, ys = jax.lax.scan(one, S, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(ys, 0, 1), S


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("decay", ["mixed", "bound", "near0", "free"])
@pytest.mark.parametrize("Q", [64, 128, 256])
def test_the_kernel_is_the_chunked_form_and_the_recurrence(Q, decay):
    """Three lanes on slots 3, 0 and 4 with ``Q``, 80 (or ``Q``) and 1
    live rows, from a nonzero carried state: one chunk, two (the second
    reads the state the first left) and four, a lane that ends inside a
    chunk, a lane of one row.  A decay free of any bound goes through
    the pairing level by level (``exact``), in the kernel and in the XLA
    form alike."""
    exact = {"exact": True} if decay == "free" else {}
    q, k, v, g, beta, mats = draw(Q, seed=Q, decay=decay)
    slot = jnp.array([3, 0, 4])
    q_len = np.array([Q, min(80, Q), 1])
    kc, gc, bc = dead(q_len, Q, k, g, beta)
    S0 = mats[0][slot]
    want_y, want_S = jax.jit(lambda *a: kd.kda_chunked(*a, **exact))(
        q, kc, v, gc, bc, S0)
    step_y, step_S = stepwise(q, kc, v, gc, bc, S0)
    y, mats2 = kda_chunk_scan(slot, jnp.asarray(q_len), q, k, v, g, beta,
                              mats, chunk=kd.CHUNK, sub=kd.SUB, **exact)
    y = np.asarray(y).reshape(3, Q, H, D)
    assert np.isfinite(y).all()
    for b, n in enumerate(q_len):
        for ref_y, ref_S in ((want_y, want_S), (step_y, step_S)):
            np.testing.assert_allclose(y[b, :n], ref_y[b, :n], atol=TOL,
                                       rtol=TOL)
            np.testing.assert_allclose(mats2[0, slot[b]], ref_S[b],
                                       atol=TOL, rtol=TOL)
    # the slots no lane holds: bit for bit
    for s in (1, 2):
        np.testing.assert_array_equal(bits(mats2[0, s]), bits(mats[0, s]))


@pytest.mark.parametrize("q_len", [(64, 0, 0), (0, 0, 0), (0, 5, 128)],
                         ids=["tail_idle", "all_idle", "head_idle"])
def test_an_idle_lane_and_an_untouched_slot_keep_their_bits(q_len):
    q, k, v, g, beta, mats = draw(128, seed=sum(q_len))
    # (negative zeros too: a decay of 1 and an increment of +0 would
    # turn them over)
    mats = mats.at[0, 4, 0, :64].set(-0.0)
    slot = jnp.array([1, 4, 2])
    y, mats2 = kda_chunk_scan(slot, jnp.asarray(q_len), q, k, v, g, beta,
                              mats, chunk=kd.CHUNK, sub=kd.SUB)
    y = np.asarray(y)
    assert np.isfinite(y).all()
    moved = {int(slot[b]) for b in range(3) if q_len[b]}
    for s in range(SLOTS):
        same = np.array_equal(bits(mats2[0, s]), bits(mats[0, s]))
        assert same == (s not in moved), s
    for b in range(3):
        if not q_len[b]:
            assert not y[b].any()


def test_dead_rows_inside_a_live_lane_leave_the_state_bit_for_bit():
    """80 live rows of a q-block of 256 are 80 live rows of one of 128:
    the chunks past the last live row are not run, and what the dead
    rows hold moves nothing."""
    q, k, v, g, beta, mats = draw(256, seed=5)
    mats = mats.at[0, 3, 1, 64:].set(-0.0)
    slot, q_len = jnp.array([3, 0, 4]), jnp.array([80, 256, 64])
    run = lambda n, *a: kda_chunk_scan(          # noqa: E731
        slot, jnp.minimum(q_len, n), *(x[:, :n] for x in a), mats,
        chunk=kd.CHUNK, sub=kd.SUB)
    y, out = run(256, q, k, v, g, beta)
    y128, out128 = run(128, q, k, v, g, beta)
    for s in (3, 4):
        np.testing.assert_array_equal(bits(out[0, s]), bits(out128[0, s]))
    np.testing.assert_array_equal(np.asarray(y)[0, :80],
                                  np.asarray(y128)[0, :80])
    # a lane of whole dead chunks alone: exactly the state it had after
    # its one live chunk, negative zeros and all
    one = kda_chunk_scan(slot[2:], jnp.array([64]), *(
        x[2:, :64] for x in (q, k, v, g, beta)), mats, chunk=kd.CHUNK,
        sub=kd.SUB)[1]
    np.testing.assert_array_equal(bits(out[0, 4]), bits(one[0, 4]))
    noisy = [a.at[0, 80:].set(7.0) for a in (q, k, v, g, beta)]
    y2, out2 = run(256, *noisy)
    np.testing.assert_array_equal(bits(out2), bits(out))
    np.testing.assert_array_equal(np.asarray(y2)[0, :80],
                                  np.asarray(y)[0, :80])


def test_a_bfloat16_state_is_rounded_once_a_q_block():
    """The control's state: carried in float32 across the q-block's
    chunks, as ``kda_chunked`` carries it, and stored in its own dtype."""
    q, k, v, g, beta, mats = draw(128, seed=2, decay="near0")
    slot, q_len = jnp.array([3, 0, 4]), jnp.array([128, 100, 0])
    low = mats.astype(jnp.bfloat16)
    kc, gc, bc = dead(q_len, 128, k, g, beta)
    _, want = jax.jit(kd.kda_chunked)(q, kc, v, gc, bc,
                                      low[0][slot].astype(jnp.float32))
    _, got = kda_chunk_scan(slot, q_len, q, k, v, g, beta, low,
                            chunk=kd.CHUNK, sub=kd.SUB)
    assert got.dtype == jnp.bfloat16
    for b in range(2):
        w = np.asarray(want[b].astype(jnp.bfloat16), np.float32)
        # a value on a rounding boundary may fall either way
        np.testing.assert_allclose(np.asarray(got[0, slot[b]], np.float32),
                                   w, atol=2.0 ** -7 * np.abs(w).max())


@pytest.mark.parametrize("head_dim,q_block,kernel", [
    (16, 64, False), (64, 256, False), (128, 1, False), (128, 8, False),
    (128, 96, False), (128, 64, True), (128, 256, True), (256, 128, True)])
def test_the_shape_rule(head_dim, q_block, kernel):
    assert kd.takes_kernel(head_dim, q_block) is kernel


def mixer_case(d, Q, packed):
    """A wave of six slots x ``Q`` rows on ``H`` heads of ``d``: slots of
    ``Q``, ``Q`` - 3, 2 and 5 rows (two passes of three lanes, the second
    with two idle lanes), one decoding slot, one dead; as a block or
    packed into its live rows."""
    rng = np.random.default_rng(d + Q)
    B = 6
    sp = kd.KDASpec(H, d)
    q, k, v = (rng.normal(size=(B, Q, H, d)).astype(np.float32)
               for _ in range(3))
    q, k = (np.asarray(kd.l2norm(jnp.asarray(a))) for a in (q, k))
    g = (-5.0 * rng.uniform(size=(B, Q, H, d)) ** 4).astype(np.float32)
    beta = rng.uniform(0.1, 1.0, size=(B, Q, H)).astype(np.float32)
    state = (jnp.zeros((1, B, 3, 3 * H * d), jnp.float32),
             jnp.asarray(rng.normal(size=(1, B, H, d, d)), jnp.float32))
    q_len = jnp.asarray([Q, 1, Q - 3, 0, 2, 5])
    args = [jnp.asarray(a) for a in (q, k, v, g, beta)]
    rows = None
    if packed:
        rows = _Rows.of(q_len, Q, 2 * Q + 64)
        args = [rows.pack(a) for a in args]
    return sp, args, state, q_len, rows


@pytest.mark.parametrize("d,Q,kernel", [(128, 64, True), (128, 1, False),
                                        (128, 8, False), (16, 64, False)])
def test_the_mixer_takes_the_kernel_by_the_rule(d, Q, kernel):
    sp, args, state, q_len, _ = mixer_case(d, max(Q, 6), False)
    if Q == 1:
        args = [a[:, :1] for a in args]
        q_len = jnp.minimum(q_len, 1)
    jaxpr = jax.make_jaxpr(
        lambda *a: kd.kda_mixer(sp, *a, 0, q_len))(*args, state)
    assert ("pallas_call" in str(jaxpr)) is kernel


@pytest.mark.parametrize("packed", [False, True], ids=["block", "packed"])
def test_the_mixers_wave_is_the_same_wave_through_the_kernel(monkeypatch,
                                                             packed):
    Q = 64
    sp, args, state, q_len, rows = mixer_case(128, Q, packed)
    mix = lambda: jax.jit(lambda *a: kd.kda_mixer(        # noqa: E731
        sp, *a, 0, q_len, rows))(*args, state)
    y, out = mix()
    monkeypatch.setattr(kd, "takes_kernel", lambda d, Q: False)
    want_y, want = mix()
    if packed:
        y, want_y = rows.unpack(y), rows.unpack(want_y)
    for b, n in enumerate(np.asarray(q_len)):
        np.testing.assert_allclose(y[b, :n], want_y[b, :n], atol=TOL,
                                   rtol=TOL)
        np.testing.assert_allclose(out[1][0, b], want[1][0, b], atol=TOL,
                                   rtol=TOL)
    # the dead slot's state as it was, through either
    np.testing.assert_array_equal(bits(out[1][0, 3]), bits(state[1][0, 3]))
    assert out[0] is state[0] or np.array_equal(out[0], state[0])


# ------------------------------------------------------------------ #
# the engine: a model of 128-column heads serves through the kernel
# ------------------------------------------------------------------ #

def small(head_dim):
    from test_kda_latent import HELD, ROWS, SMALL
    from hetu_tpu.models.kda_latent import (KDALatentConfig,
                                            init_kda_latent_params)
    cfg = KDALatentConfig.from_hf(
        dict(SMALL, head_dim=head_dim, num_hidden_layers=3,
             partial_rotary_factor=8 / head_dim),
        held_experts=HELD[:3], vocab_rows=ROWS)
    return cfg, init_kda_latent_params(cfg, name="lng", seed=3,
                                       dt_range=(0.05, 2.0))


@pytest.mark.parametrize("head_dim,kernel", [(128, True), (16, False)])
def test_an_engine_counts_the_rows_the_kernel_took(head_dim, kernel):
    """Chunks of 64 (whole chunks of the scan): every wide q-block of a
    model of 128-column heads goes through the kernel, the counter
    equals ``kda_chunk_rows`` in the windowed snapshot and the tokens
    are the reference's; a narrow head counts 0."""
    from hetu_tpu.models import reference_kda_latent as ref
    from hetu_tpu.serving import Request, ServingEngine
    cfg, params = small(head_dim)
    eng = ServingEngine(params, cfg, slots=2, max_seq_len=256, kv_block=4,
                        prefill_chunk=64, fast_path=False)
    rng = np.random.default_rng(4)
    mark = eng.metrics.mark()
    sizes = [(70, 3), (130, 2)]
    out = eng.run([Request(rng.integers(0, 96, n).astype(np.int32), m,
                           request_id=f"r{i}")
                   for i, (n, m) in enumerate(sizes)])
    snap = eng.metrics.snapshot(since=mark)
    # 70 = 64 + 6 and 130 = 64 + 64 + 2: every prompt row rides a
    # q-block wider than one row, x 2 KDA layers; the last wave's widest
    # q-block is the tail of 2, and a program of q-blocks 2 wide runs
    # ``kda_chunked``
    assert snap["kda_chunk_rows"] == 2 * 200
    assert snap["kda_kernel_chunk_rows"] == (2 * 198 if kernel else 0)
    for r in out.values():
        seq = np.asarray(r.tokens, np.int32)
        lg = np.asarray(ref.forward(params, cfg, jnp.asarray(seq[:-1]),
                                    name="lng"))[r.prompt_len - 1:]
        chosen = lg[np.arange(len(lg)), seq[r.prompt_len:]]
        assert float((lg.max(-1) - chosen).max()) < TOL
