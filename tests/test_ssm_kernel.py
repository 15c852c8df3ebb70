"""``kernels/ssm_step`` (ISSUE 50): the Mamba-2 mixer's one-step form as
one Pallas kernel, interpreted on the CPU, against ``ssm_decode.ssd_step``
(the form in XLA's own operations, which a state narrower than a lane
tile keeps running).

Both cells' head shapes cut in slots and heads (16 heads a group, as
both published models have), everything float32: ``y`` and the state
are held to float32's rounding of sums taken in another order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import ssm_step as ks
from hetu_tpu.models import ssm_decode as sd
from test_retention_kernel import close

SLOTS = 4


def case(H, P, N, G, seed, extra=0):
    """(x, dt, A, B, C, mats) of ``SLOTS`` slots, slot 1 dead and slot 2
    wide (``dt`` 0 on both, as the mixer hands them), the manager's
    array ``extra`` slots longer than the wave."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(SLOTS, H, P))
    dt = rng.uniform(0.001, 0.2, size=(SLOTS, H))
    dt[1:3] = 0.0
    A = -rng.uniform(1.0, 16.0, size=(H,))
    Bm, Cm = rng.normal(size=(2, SLOTS, G, N))
    mats = rng.normal(size=(1, SLOTS + extra, H, P, N))
    return tuple(jnp.asarray(v, jnp.float32)
                 for v in (x, dt, A, Bm, Cm, mats))


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("block_bytes", [ks.BLOCK_BYTES, 8 * 64 * 128 * 4],
                         ids=["a-group", "part-of-a-group"])
@pytest.mark.parametrize("H,P,N,G", [(32, 64, 128, 2), (16, 128, 256, 1)],
                         ids=["nemotron-3-super", "falcon-h1"])
def test_the_kernel_is_the_xla_step(monkeypatch, H, P, N, G, block_bytes):
    """A grid step of a group's 16 heads (both cells') and of part of a
    group's (what a wider state would take); the manager's array two
    slots longer than the wave."""
    monkeypatch.setattr(ks, "BLOCK_BYTES", block_bytes)
    hb = ks.head_block(H, G, P, N)
    assert (hb == 16) == (block_bytes == 4 << 20) and 16 % hb == 0
    x, dt, A, Bm, Cm, mats = case(H, P, N, G, seed=P + G, extra=2)
    want_y, want_S = sd.ssd_step(x, dt, A, Bm, Cm, mats[0, :SLOTS])
    y, got = ks.ssm_step(x, dt, A, Bm, Cm, mats)
    assert y.dtype == got.dtype == jnp.float32
    for b in range(SLOTS):
        close(y[b], want_y[b], 1e-5)
        close(got[0, b], want_S[b], 1e-6)
    # the dead slot, the wide slot and the slots past the wave: their bits
    for b in (1, 2, 4, 5):
        np.testing.assert_array_equal(bits(got[0, b]), bits(mats[0, b]))


@pytest.mark.parametrize("heads,groups,P,N,want", [
    (128, 8, 64, 128, 16), (32, 2, 128, 256, 16),    # the cells: a group
    (256, 8, 64, 128, 32), (48, 3, 128, 512, 16), (24, 1, 128, 512, 12)])
def test_the_head_block_follows_the_shapes(heads, groups, P, N, want):
    assert ks.head_block(heads, groups, P, N) == want


@pytest.mark.parametrize("P,N,kernel", [
    (64, 128, True), (128, 256, True), (8, 128, True), (8, 16, False),
    (64, 64, False), (4, 128, False)])
def test_the_shape_rule(P, N, kernel):
    assert sd.takes_kernel(sd.SSMSpec(4, P, N, 2, 4, 8)) is kernel


# ------------------------------------------------------------------ #
# the mixer: one wave through either form
# ------------------------------------------------------------------ #

def mixer_case(N, Q):
    """A layer's mixer of 4 heads of 8 columns over ``N`` state columns
    in 2 groups, and a wave of five slots x ``Q`` rows: three slots of
    one row, one of ``min(3, Q)`` (wide where ``Q`` > 1), one dead."""
    small = dict(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        intermediate_size=48, mamba_d_ssm=32, mamba_n_heads=4,
        mamba_d_head=8, mamba_d_state=N, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.36])
    cfg = sd.SSMHybridConfig.from_hf(small)
    params = sd.init_ssm_hybrid_params(cfg, "m", seed=N + Q)
    blk, sp = cfg.block_spec(), cfg.ssm
    rng = np.random.default_rng(N + Q)
    u = jnp.asarray(rng.normal(size=(5, Q, 32)), jnp.float32)
    state = (jnp.asarray(rng.normal(size=(1, 5, 3, sp.conv_width)),
                         jnp.float32),
             jnp.asarray(rng.normal(size=(1, 5, 4, 8, N)), jnp.float32))
    q_len = jnp.asarray([1, 1, min(3, Q), 0, 1])
    return params, blk, u, state, q_len


@pytest.mark.parametrize("N,Q,kernel", [(128, 1, True), (128, 4, True),
                                        (16, 1, False), (16, 4, False)])
def test_the_mixer_takes_the_kernel_by_the_rule(N, Q, kernel):
    """Every program of a model whose state is whole lane tiles, the
    decode wave's and a chunk bucket's; none of a model whose state is
    16 columns, which lowers as the parent's mixer does
    (``tests/test_program_digests.py`` holds its text)."""
    params, blk, u, state, q_len = mixer_case(N, Q)
    assert sd.takes_kernel(blk.ssm) is kernel
    jaxpr = str(jax.make_jaxpr(lambda u, state: sd.ssm_mixer(
        params, "m_h0", blk, u, state, 0, q_len))(u, state))
    assert ("pallas_call" in jaxpr) is kernel
    assert ("ssm_step" in jaxpr) is kernel


@pytest.mark.parametrize("Q", [1, 4], ids=["decode", "chunk"])
def test_the_mixers_wave_is_the_same_wave_through_the_kernel(monkeypatch,
                                                             Q):
    params, blk, u, state, q_len = mixer_case(128, Q)
    y, (tails, S) = sd.ssm_mixer(params, "m_h0", blk, u, state, 0, q_len)
    monkeypatch.setattr(sd, "takes_kernel", lambda spec: False)
    want_y, (want_tails, want_S) = sd.ssm_mixer(params, "m_h0", blk, u,
                                                state, 0, q_len)
    for b, n in enumerate(np.asarray(q_len)):
        if n:
            close(y[b, :n], want_y[b, :n], 1e-5)
        close(S[0, b], want_S[0, b], 1e-6)
    np.testing.assert_array_equal(np.asarray(tails), np.asarray(want_tails))
    # the dead slot's state as it was, through either
    np.testing.assert_array_equal(bits(S[0, 3]), bits(state[1][0, 3]))
    np.testing.assert_array_equal(bits(want_S[0, 3]), bits(state[1][0, 3]))
