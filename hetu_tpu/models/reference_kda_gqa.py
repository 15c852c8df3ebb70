"""The plain reference of the delta-rule / grouped-query decoder
(``kda_gqa.KDAGQAConfig``, the ``solar_open2`` family): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, a full
forward over one whole sequence: the KDA recurrence STEP BY STEP (a scan
over positions: no chunks, no levels, no slots), the grouped-query layer a
full causal softmax over every position (no pages, no kernel), the routed
FFN an expert at a time.  The serving path (a chunked delta rule over slot
state, a paged K/V pool, a dropless grouped matmul) shares no line with it
and is tested against it, logits not tokens.

Per layer with input ``h``, ``u = rms(h; ln1)`` (eps 1e-5), ``H`` heads of
``D``:

  KDA layer (i not in gqa_layers)
    [q~ | k~ | v~] = u W_qkv; each column through the causal conv of K
    taps (zeros before the sequence, no bias), then SiLU; q, k
    L2-normalised a head (x rsqrt(sum x^2 + 1e-6)), q times D^-1/2;
    nothing rotated
    g_t = -exp(A_log_h) softplus(u W_f_a W_f_b + dt_bias), a channel
    beta_t = 2 sigmoid(u W_beta), a head
    S' = Diag(exp g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t
    y = rms_D(o_t) scale sigmoid(u W_g_a W_g_b + b_g), a CHANNEL;
    h += concat(y) W_o
  GQA layer (i in gqa_layers)
    q = u W_q (H x D), k = u W_k, v = u W_v (Hkv x D); no rotation, no
    q/k norm, no biases; causal softmax(q k^T D^-1/2), query head n
    reading K/V head n // (H / Hkv); o = attn sigmoid(u W_gate), a
    channel; h += o W_o
  FFN, every layer (x = rms(h; ln2)): s = sigmoid(x W_r) over all the
    experts, the top_k largest of s + b chosen, weights s at the chosen,
    normalised, scaled; the HELD experts' part (``held``), each
    down(silu(gate x) up x), plus the shared expert unscaled
  model: embedding unscaled; final rms; untied head over the rows held

Departures from the published description (arXiv:2510.26692 for the KDA
layer, arXiv:2411.12537 for beta's range, arXiv:2505.06708 for the
attention's gate; the row gives no file of equations): the low rank is
``head_dim`` and the gate's second projection alone carries a bias, as the
published implementation has them; the router is the family's earlier one
(sigmoid, a selection bias, no groups): the row names no scoring function.

``wrong`` names parts computed wrong ON PURPOSE, for the tests that show
the comparison notices each: "beta_one" (beta not doubled), "safe_gate"
(``g = -5 sigmoid(exp(A_log) (f + dt_bias))``), "gate_head" (the KDA gate
one a head: the mean of the head's pre-activations), "no_gate" (the GQA
gate off), "rope" (the GQA layer rotated, theta 10,000), "no_decay",
"no_delta".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WRONG = ("beta_one", "safe_gate", "gate_head", "no_gate", "rope",
         "no_decay", "no_delta")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _rope(x, theta=10000.0):
    """x [S, heads, d] at positions 0..S-1, rotate-half over d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _two_step(params, prefix, u, rank):
    """``u W``, in one step or through the low rank."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    if not rank:
        return u @ f32(params[f"{prefix}_weight"])
    return (u @ f32(params[f"{prefix}_a_weight"])) \
        @ f32(params[f"{prefix}_b_weight"])


def kda(params, us, cfg, u, wrong=()):
    """(the layer's part [S, d], the state after the sequence [H, D, D])
    for the normed rows ``u`` [S, d]."""
    sp = cfg.kda
    H, D, K = sp.heads, sp.head_dim, sp.conv_kernel
    S = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    x = u @ f32(params[f"{us}_kda_qkv_weight"])             # [S, 3 H D]
    w = f32(params[f"{us}_kda_conv_weight"])
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w[j] * xp[j:j + S] for j in range(K)))
    q, k, v = (x[:, j * H * D:(j + 1) * H * D].reshape(S, H, D)
               for j in range(3))
    q, k = _l2(q) * D ** -0.5, _l2(k)
    a = jnp.repeat(jnp.exp(f32(params[f"{us}_kda_A_log"])), D)
    f = _two_step(params, f"{us}_kda_f", u, sp.rank) \
        + f32(params[f"{us}_kda_dt_bias"])
    g = -5.0 * jax.nn.sigmoid(a * f) if "safe_gate" in wrong \
        else -a * jax.nn.softplus(f)
    if "no_decay" in wrong:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(u @ f32(params[f"{us}_kda_beta_weight"]))
    if "beta_one" not in wrong:
        beta = sp.beta_scale * beta

    def step(St, x):
        qt, kt, vt, gt, bt = x
        St = St * jnp.exp(gt)[..., None]
        r = vt if "no_delta" in wrong \
            else vt - jnp.einsum("hk,hkv->hv", kt, St)
        St = St + (bt[:, None] * kt)[..., None] * r[:, None, :]
        return St, jnp.einsum("hk,hkv->hv", qt, St)

    St, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32),
                         (q, k, v, g.reshape(S, H, D), beta))
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg.rms_norm_eps) \
        * f32(params[f"{us}_kda_norm_scale"]).reshape(H, D)
    z = _two_step(params, f"{us}_kda_gate", u, sp.rank)
    if sp.rank:
        z = z + f32(params[f"{us}_kda_gate_bias"])
    z = z.reshape(S, H, D)
    if "gate_head" in wrong:
        z = jnp.broadcast_to(z.mean(-1, keepdims=True), z.shape)
    o = o * jax.nn.sigmoid(z)
    return o.reshape(S, H * D) @ f32(params[f"{us}_kda_out_weight"]), St


def gqa(params, us, cfg, u, wrong=()):
    """The grouped-query layer's part [S, d]: a full causal softmax."""
    H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    S = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    q = (u @ f32(params[f"{us}_attn_q_weight"])).reshape(S, H, D)
    k = (u @ f32(params[f"{us}_attn_k_weight"])).reshape(S, Hkv, D)
    v = (u @ f32(params[f"{us}_attn_v_weight"])).reshape(S, Hkv, D)
    if "rope" in wrong:
        q, k = _rope(q), _rope(k)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,shd->hqs", q, k) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shd->qhd", p, v).reshape(S, H * D)
    if cfg.attn_gate and "no_gate" not in wrong:
        o = o * jax.nn.sigmoid(u @ f32(params[f"{us}_attn_gate_weight"]))
    return o @ f32(params[f"{us}_attn_proj_weight"])


def _expert(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def route(params, us, cfg, x):
    """(chosen [S, E] boolean, weights [S, E]) of the rows ``x``."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    s = jax.nn.sigmoid(x @ f32(params[f"{us}_moe_router_weight"]))
    pick = s + f32(params[f"{us}_moe_router_bias"])
    order = jnp.argsort(-pick, axis=-1, stable=True)[
        :, :cfg.num_experts_per_tok]
    chosen = jnp.zeros(pick.shape, bool).at[
        jnp.arange(pick.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling_factor


def ffn_parts(params, us, cfg, x, held=None):
    """(the routed part the experts ``held`` (first, count) give, the
    shared expert's part) of a layer for the normed rows ``x``: the
    expert leaves hold experts ``[first, first + count)``."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    first, count = held or (0, cfg.n_routed_experts)
    _, w = route(params, us, cfg, x)
    routed = sum(
        w[:, first + e, None] * _expert(
            x, f32(params[f"{us}_moe_experts_gate"][e]),
            f32(params[f"{us}_moe_experts_up"][e]),
            f32(params[f"{us}_moe_experts_down"][e]))
        for e in range(count))
    shared = 0.0
    if cfg.n_shared_experts:
        shared = _expert(x, f32(params[f"{us}_moe_shared_gate_weight"]),
                         f32(params[f"{us}_moe_shared_up_weight"]),
                         f32(params[f"{us}_moe_shared_down_weight"]))
    return routed, shared


def forward(params, cfg, tokens, name="slr", wrong=(), states=False):
    """Logits [S, V held] float32 of the sequence ``tokens`` [S]; with
    ``states`` also the KDA layers' states after it ``[layers, H, D,
    D]``.  ``cfg`` is the ``KDAGQAConfig`` (its ``held_experts`` the
    experts the leaves hold)."""
    bad = [w for w in wrong if w not in WRONG]
    if bad:
        raise ValueError(f"wrong={bad} not in {WRONG}")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    eps = cfg.rms_norm_eps
    kept = []
    with jax.default_matmul_precision("highest"):
        h = f32(params[f"{name}_wte_table"])[tokens]
        for i in range(cfg.num_hidden_layers):
            us = f"{name}_h{i}"
            u = _rms(h, f32(params[f"{us}_ln1_scale"]), eps)
            if cfg.op_of(i) == "kda":
                part, St = kda(params, us, cfg, u, wrong)
                kept.append(St)
            else:
                part = gqa(params, us, cfg, u, wrong)
            h = h + part
            x = _rms(h, f32(params[f"{us}_ln2_scale"]), eps)
            routed, shared = ffn_parts(params, us, cfg, x, cfg.held_experts)
            h = h + routed + shared
        logits = _rms(h, f32(params[f"{name}_ln_f_scale"]), eps) \
            @ f32(params[f"{name}_lm_head_weight"])
    return (logits, jnp.stack(kept)) if states else logits
