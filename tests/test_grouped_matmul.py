"""The chunk wave's grouped matmul (ISSUE 41): ``kernels/grouped_matmul``
in interpret mode against ``jax.lax.ragged_dot``, the shape rule that
chooses between them, and ``routed_ffn`` through either."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import grouped_matmul as gm
from hetu_tpu.models import moe_decode
from hetu_tpu.models.moe_decode import (
    LatentMoEConfig, grouped_matmul, init_latent_moe_params, routed_ffn,
    takes_kernel)

# one rounding of a bf16 result (8 bits of mantissa)
BF16_ULP = 2.0 ** -7


def _balanced(G, M):
    return [M // G] * G


def _ragged(G, M):
    # no multiple of any tile, some groups straddling two and three tiles
    s = [(7 * g * g + 3) % (M // G - 1) for g in range(G)]
    s[G // 3] += 131
    return s


def _empty_at(where):
    def sizes(G, M):
        s = [M // (2 * G) + (g % 5) for g in range(G)]
        if where == "start":
            s[0] = s[1] = 0
        elif where == "middle":
            s[G // 2 - 1] = s[G // 2] = s[G // 2 + 1] = 0
        else:
            s[-1] = s[-2] = 0
        return s
    return sizes


def _one_group(G, M):
    s = [0] * G
    s[G // 2] = M
    return s


def _short(G, M):
    # the groups end well before the rows do: whole tiles past the sum
    return [M // (4 * G)] * G


CASES = {
    "balanced": _balanced,
    "no-multiple-of-the-tile": _ragged,
    "empty-at-the-start": _empty_at("start"),
    "empty-in-the-middle": _empty_at("middle"),
    "empty-at-the-end": _empty_at("end"),
    "one-group-holds-every-row": _one_group,
    "rows-past-the-groups-sum": _short,
    "nobody-routed": lambda G, M: [0] * G,
}


@pytest.mark.parametrize("G", [32, 64])
@pytest.mark.parametrize("orientation", ["gate-up", "down", "gated-pair"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_equals_ragged_dot(case, orientation, G):
    """Reduced widths that keep the lane multiples: D 256, F 384 (two
    column tiles of 128 going up, one of 256 coming down)."""
    M, D, F = 512, 256, 384
    K, N = (F, D) if orientation == "down" else (D, F)
    sizes = CASES[case](G, M)
    assert sum(sizes) <= M
    rng = np.random.default_rng(len(case) * G + K)
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((G, K, N)) / 16, jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, gs,
                              preferred_element_type=jnp.float32)
    up = None
    if orientation == "gated-pair":
        up = jnp.asarray(rng.standard_normal((G, K, N)) / 16, jnp.bfloat16)
        want = jax.nn.silu(want) * jax.lax.ragged_dot(
            lhs, up, gs, preferred_element_type=jnp.float32)
    tiles = gm.group_tiles(gs, M)
    steps = int(tiles.steps[0])
    # a step a (group, tile) pair that holds a row, none for the rest
    pairs = {(g, r // gm.TILE_M) for g in range(G)
             for r in range(sum(sizes[:g]), sum(sizes[:g + 1]))}
    assert steps == len(pairs) <= gm.grid_steps(M, G)
    assert {(int(g), int(t)) for g, t in zip(tiles.group_ids[:steps],
                                             tiles.tile_ids[:steps])} == pairs
    # what the hand copies go by: a group's first step, its place among
    # the groups with rows, and the group whose matrix to fetch next
    with_rows = [g for g in range(G) if sizes[g]]
    assert int(tiles.steps[1]) == len(with_rows)
    gids = [int(g) for g in tiles.group_ids[:steps]]
    assert [int(f) for f in tiles.first[:steps]] == [
        int(i == 0 or g != gids[i - 1]) for i, g in enumerate(gids)]
    assert [int(o) for o in tiles.ordinal[:steps]] == [
        with_rows.index(g) for g in gids]
    assert [int(n) for n in tiles.next_ids[:steps]] == [
        ([h for h in with_rows if h > g] + [-1])[0] for g in gids]
    got = gm.grouped_matmul_tiled(lhs, rhs, tiles, up=up,
                                  tn=128 if orientation != "down" else None)
    assert got.shape == (M, N) and got.dtype == jnp.bfloat16
    n = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(got[:n], np.float32),
        np.asarray(want.astype(jnp.bfloat16)[:n], np.float32),
        rtol=BF16_ULP, atol=1e-6)


@pytest.mark.parametrize("form", ["plain", "gated", "relu2"])
def test_a_decode_waves_shape_in_small_equals_ragged_dot(form):
    """ISSUE 49: what a share-holding layer's decode wave hands the
    kernel: eleven row tiles of which three are live, 160 groups of 1-4
    rows, a third of them EMPTY (an expert without rows has no step; a
    tile past the groups' sum has none)."""
    rng = np.random.default_rng(49)
    M, K, N, G = 11 * gm.TILE_M, 64, 128, 160
    sizes = rng.integers(1, 5, G)
    sizes[rng.permutation(G)[:G // 3]] = 0
    n = int(sizes.sum())
    assert 2 * gm.TILE_M < n <= 3 * gm.TILE_M
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    rhs, up = (jnp.asarray(rng.standard_normal((G, K, N)) / 8, jnp.bfloat16)
               for _ in range(2))
    gs = jnp.asarray(sizes, jnp.int32)
    tiles = moe_decode.kernel_tiles(gs, M)
    assert [int(v) for v in tiles.steps][1] == int((sizes > 0).sum())
    dot = lambda w: jax.lax.ragged_dot(                     # noqa: E731
        lhs, w, gs, preferred_element_type=jnp.float32)
    if form == "gated":
        got = gm.grouped_matmul_tiled(lhs, rhs, tiles, up=up)
        want = jax.nn.silu(dot(rhs)) * dot(up)
    elif form == "relu2":
        got = gm.grouped_matmul_tiled(lhs, rhs, tiles, act="relu2")
        want = jnp.square(jax.nn.relu(dot(rhs)))
    else:
        got = gm.grouped_matmul_tiled(lhs, rhs, tiles)
        want = dot(rhs)
    np.testing.assert_allclose(
        np.asarray(got[:n], np.float32),
        np.asarray(want.astype(jnp.bfloat16)[:n], np.float32),
        rtol=BF16_ULP, atol=1e-6)


def test_the_shape_rule_reads_the_rows_alone():
    # the routed cells' decode waves: 32 slots x top-4 and top-8, 64 x 22
    # sorted rows (2 to 4 rows a group, 2.75 landing of 11): the kernel
    # since PR 49, whose sweep read it ahead at every one of them
    for M in (128, 256, 1408):
        assert takes_kernel(M)
    # the chunk buckets Q 64 / 128 / 256: M = 1,024 / 2,048 / 4,096
    for M in (1024, 2048, 4096):
        assert takes_kernel(M)
    # rows that are no whole tiles stay with the compiler
    for M in (8, 64, 4096 + 8):
        assert not takes_kernel(M)


def test_grouped_matmul_takes_the_kernel_by_the_rule(monkeypatch):
    calls = []
    real = gm.grouped_matmul_tiled
    monkeypatch.setattr(gm, "grouped_matmul_tiled",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    rng = np.random.default_rng(3)
    rhs = jnp.asarray(rng.standard_normal((8, 128, 128)) / 8, jnp.bfloat16)
    for M, kernel in ((64, False), (128, True), (256, True)):
        lhs = jnp.asarray(rng.standard_normal((M, 128)), jnp.bfloat16)
        gs = jnp.asarray([M // 8] * 8, jnp.int32)
        del calls[:]
        got = grouped_matmul(lhs, rhs, gs)
        assert bool(calls) == kernel == takes_kernel(M)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(jax.lax.ragged_dot(lhs, rhs, gs), np.float32),
            rtol=BF16_ULP, atol=1e-6)


SMALL = dict(
    vocab_size=257, hidden_size=128, num_hidden_layers=2,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=256, moe_intermediate_size=128, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, first_k_dense_replace=1,
    max_position_embeddings=64)


@pytest.fixture(scope="module")
def routed():
    cfg = LatentMoEConfig(**SMALL)
    params = {k: jnp.asarray(v, jnp.bfloat16) for k, v in
              init_latent_moe_params(cfg, name="glm", seed=2).items()}
    return cfg, params


def _with_compilers_kernel(monkeypatch):
    """What the rule gives rows that are no whole tiles, asked for here
    at whole tiles (the test's steering: the program has no such
    switch)."""
    monkeypatch.setattr(moe_decode, "takes_kernel", lambda rows: False)


@pytest.mark.parametrize("T", [64, 192])
def test_routed_ffn_through_the_kernel_equals_ragged_dots(routed,
                                                          monkeypatch, T):
    """The same rows with ``valid`` holes through both: 64 and 192 rows
    x top-2 over 8 experts are 16 and 48 expected rows a group, one and
    three row tiles."""
    cfg, params = routed
    spec = cfg.routed_spec()
    assert takes_kernel(T * spec.top_k)
    rng = np.random.default_rng(T)
    x = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.bfloat16)
    valid = jnp.asarray(rng.random(T) < 0.7)
    ks, cs = {}, {}
    kernel = routed_ffn(params, "glm_h1", x, spec, valid=valid, stats=ks)
    _with_compilers_kernel(monkeypatch)
    compiler = routed_ffn(params, "glm_h1", x, spec, valid=valid, stats=cs)
    np.testing.assert_array_equal(ks["load"], cs["load"])
    assert int(ks["load"].sum()) == int(valid.sum()) * spec.top_k
    # two bf16 roundings apart at most: the activation's and the output's
    scale = float(jnp.abs(compiler.astype(jnp.float32)).max())
    np.testing.assert_allclose(np.asarray(kernel, np.float32),
                               np.asarray(compiler, np.float32),
                               rtol=2 * BF16_ULP, atol=2 * BF16_ULP * scale)
