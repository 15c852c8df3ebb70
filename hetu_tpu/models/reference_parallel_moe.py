"""The plain reference of the ``cohere2_moe`` decoder
(``parallel_moe.ParallelMoEConfig``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one full forward over one
whole sequence with an explicit mask, every held expert computed densely
and weighted: no sort, no grouped product, no cache, no ring, no kernel,
no batching, and nothing imported from the served path.  The leaves are
the PUBLISHED layout and the rotation is the published one (interleaved
pairs); the serving path (chunked prefill and decode through two paged
pools, a ring a slot, the banded kernel, rotate-half over permuted
columns, grouped matmuls over a held share) is tested against it, logits
not tokens.

``h`` the residual [S, d], ``H`` query heads over ``Hkv`` K/V heads of
``dh``, ``W`` the window.  Every layer ``l`` of ``layer_types``:

    x   = LN(h; g_l)         LN(u; g) = g (u - mean u) / sqrt(var u + eps)
    q   = x W_q [H, dh]      k = x W_k [Hkv, dh]      v = x W_v [Hkv, dh]
    sliding_attention:  q, k <- RoPE(q), RoPE(k): INTERLEAVED pairs
                        (2j, 2j + 1) over the whole head, angle
                        ``t theta ** (-2j / dh)``; query t admits
                        t - W < j <= t
    full_attention:     q and k as projected, NO rotation; j <= t
    o_n = sum_j softmax_j(q_n . k_{n // (H / Hkv), j} / sqrt(dh)) v_..j
    a   = concat_n(o_n) W_o
    s   = sigmoid(float32(x) W_r) over ALL ``num_experts``; S = the
          ``num_experts_per_tok`` largest; w_e = s_e / sum_{S} s  (no
          selection bias)
    E(u; G, U, D) = (silu(u G) * (u U)) D
    f   = sum_{e in S, e held} w_e E(x; e)
          + (1 / n_shared) sum_j E(x; shared j)
    h  <- h + a + f          (ONE norm, ONE addition)

    logits = logit_scale * LN(h; g_final) T^T    (T the held rows)

The shared experts' leaves are one widened expert (``[d, n_shared f]``:
their sum); the average divides it.  ``held`` (first, count): the experts
whose parts are added here (the expert leaves are ``[count, ...]``); the
router scores all and normalises over all the chosen.

Departures from the published description (each in the benchmark
configuration's ``assumed`` too): "average" read as the MEAN of the
shared experts' outputs added to the routed sum; the window holds the
query's own position; the top-k normalisation adds 1e-20 to the sum of
the chosen scores (the served router's epsilon).

``wrong`` computes one thing wrongly at a time, for the tests that show
the comparison notices each (``tests/test_parallel_moe.py``):
"sequential" (``h + a`` normed again, by the same scale, before the
FFN), "rotate_full" (the full layers rotated too), "unrotated_sliding",
"window_half" / "window_double", "shared_sum", "norm_over_held" (the
weights normalised over the held chosen experts alone), "rmsnorm" (no
mean taken), "rotate_half" (halves rotated on the published layout).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WRONG = ("sequential", "rotate_full", "unrotated_sliding", "window_half",
         "window_double", "shared_sum", "norm_over_held", "rmsnorm",
         "rotate_half")


def _ln(u, g, eps, centre=True):
    if centre:
        u = u - u.mean(-1, keepdims=True)
    return g * u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + eps)


def _rotate(x, theta, halves=False):
    """x [S, heads, dh] at positions 0..S-1: pairs (2j, 2j + 1) turned
    by ``t theta ** (-2j / dh)`` (``halves``: pairs (j, j + dh / 2))."""
    S, dh = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if halves:
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _expert(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def ffn_parts(params, config, us, x, held=None, wrong=()):
    """(the held experts' routed part [S, d], the shared part [S, d],
    each row's selection margin [S]) of layer ``us`` over the normed rows
    ``x``."""
    c = config
    f32 = lambda k: jnp.asarray(params[k], jnp.float32)    # noqa: E731
    E, k = c["num_experts"], c["num_experts_per_tok"]
    first, count = held or (0, E)
    s = jax.nn.sigmoid(x @ f32(f"{us}_moe_router_weight"))
    ranked = jnp.sort(s, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k] if k < E \
        else jnp.full(x.shape[:1], jnp.inf)
    w = jnp.where(s >= ranked[:, k - 1:k], s, 0.0)         # [S, E]
    if "norm_over_held" in wrong:
        w = jnp.where((jnp.arange(E) >= first)
                      & (jnp.arange(E) < first + count), w, 0.0)
    if c.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    routed = jnp.zeros_like(x)
    for e in range(count):
        routed = routed + w[:, first + e, None] * _expert(
            x, f32(f"{us}_moe_experts_gate")[e],
            f32(f"{us}_moe_experts_up")[e],
            f32(f"{us}_moe_experts_down")[e])
    shared = _expert(x, f32(f"{us}_moe_shared_gate_weight"),
                     f32(f"{us}_moe_shared_up_weight"),
                     f32(f"{us}_moe_shared_down_weight"))
    if "shared_sum" not in wrong:
        shared = shared / c["num_shared_experts"]
    return routed, shared, margin


def forward(params, config, tokens, name="cmd", held=None, wrong=()):
    """(logits [S, V], margin [S]) for ``tokens`` [S]: every position's
    next-token logits over the held rows, and every position's smallest
    selection margin over the layers (the last chosen score against the
    first not chosen).  ``config`` holds the source's own keys, with
    ``num_experts`` the ROUTER's width; ``held`` (first, count) the
    experts the leaves hold (all, by default)."""
    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    c = config
    f32 = lambda k: jnp.asarray(params[k], jnp.float32)    # noqa: E731
    H, Hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, theta = c["layer_norm_eps"], float(c["rope_theta"])
    W = c["sliding_window"]
    if "window_half" in wrong:
        W = W // 2
    if "window_double" in wrong:
        W = W * 2
    centre = "rmsnorm" not in wrong
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = j <= i
    band = causal & (j > i - W)
    margin = jnp.full((S,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = f32(f"{name}_wte_table")[tokens]
        for l, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
            us = f"{name}_h{l}"
            g = f32(f"{us}_ln1_scale")
            x = _ln(h, g, eps, centre)
            q = (x @ f32(f"{us}_attn_q_weight")).reshape(S, H, dh)
            k = (x @ f32(f"{us}_attn_k_weight")).reshape(S, Hkv, dh)
            v = (x @ f32(f"{us}_attn_v_weight")).reshape(S, Hkv, dh)
            sliding = kind == "sliding_attention"
            if (sliding and "unrotated_sliding" not in wrong) \
                    or (not sliding and "rotate_full" in wrong):
                halves = "rotate_half" in wrong
                q, k = _rotate(q, theta, halves), _rotate(k, theta, halves)
            # query head n reads K/V head n // (H / Hkv)
            k = jnp.repeat(k, H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
            sc = jnp.einsum("qhd,shd->hqs", q, k) * dh ** -0.5
            seen = band if sliding else causal
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
            o = jnp.einsum("hqs,shd->qhd", p, v).reshape(S, H * dh)
            a = o @ f32(f"{us}_attn_proj_weight")
            xf = _ln(h + a, g, eps, centre) if "sequential" in wrong else x
            routed, shared, m = ffn_parts(params, c, us, xf, held, wrong)
            margin = jnp.minimum(margin, m)
            h = h + a + (routed + shared)
        x = _ln(h, f32(f"{name}_ln_f_scale"), eps, centre)
        logits = x @ f32(f"{name}_wte_table").T
        return logits * float(c.get("logit_scale", 1.0)), margin
