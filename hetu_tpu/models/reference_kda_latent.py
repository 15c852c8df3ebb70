"""The plain reference of the delta-rule / latent-attention decoder
(``kda_latent.KDALatentConfig``, the ``Ling-3.0-flash`` family): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, a full
forward over one whole sequence: the KDA recurrence STEP BY STEP (a scan
over positions: no chunks, no sub-blocks, no slots), the latent attention
in the EXPANDED form (a key and a value a head a position, no absorbed
products, no cache), the group-limited router written out.  The serving
path (a chunked delta rule over slot state, a paged latent pool, a
dropless grouped matmul) shares no line with it and is tested against
it, logits not tokens.

Per layer with input ``h``, ``u = rms(h; ln1)``, ``H`` heads of ``D``:

  KDA layer ((i + 1) % layer_group_size != 0)
    [q~ | k~ | v~] = u W_qkv; each column through the causal conv of K
    taps (zeros before the sequence), then SiLU; q, k L2-normalised a
    head (x rsqrt(sum x^2 + 1e-6)), q times D^-1/2; nothing rotated
    g_t = lower_bound sigmoid(exp(A_log_h) (u W_f + dt_bias)), a channel
    beta_t = sigmoid(u W_beta), a head
    S' = Diag(exp g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t;  y = rms_D(o_t) scale_h sigmoid(u W_g)_h;  h += y W_o
  MLA layer
    q = u W_q -> [q_n | RoPE(q_r)] a head (no low-rank step, no norm)
    [c | k_r] = u W_kva;  c <- rms(c);  k_r <- RoPE(k_r) (all heads')
    [k_n | v] = c W_kvb a head; causal softmax((q_n . k_n + q_r . k_r)
    / sqrt(dn + dr)) v; times sigmoid(u W_gate)_h; W_o
  FFN (x = rms(h; ln2)): the first ``first_k_dense_replace`` layers
    (silu(x W_g) * x W_u) W_d; the others s = sigmoid(x W_r), choice
    scores s + b, the experts in ``n_group`` groups, a group's score the
    sum of its two largest choice scores, the ``topk_group`` best groups
    kept, the ``top_k`` largest s + b among their experts chosen, weights
    s at the chosen, normalised, scaled; the HELD experts' part
    (``held``) plus the shared expert
  model: embedding; final rms; untied head over the rows held

``wrong`` names parts computed wrong ON PURPOSE, for the tests that show
the comparison notices each: "no_decay" (alpha 1), "no_delta" (the
correction dropped: ``S' + beta k v^T``, gated linear attention),
"plain_topk" (the choice among all experts), "group_max" (a group's score
its largest one), "no_gate" (the MLA gate off), "no_rope".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

WRONG = ("no_decay", "no_delta", "plain_topk", "group_max", "no_gate",
         "no_rope")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, ..., d] at positions 0..S-1, rotate-half over d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv).reshape(
        (S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


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
    g = sp.lower_bound * jax.nn.sigmoid(
        a * (u @ f32(params[f"{us}_kda_f_weight"])
             + f32(params[f"{us}_kda_dt_bias"])))
    if "no_decay" in wrong:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(u @ f32(params[f"{us}_kda_beta_weight"]))

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
    o = o * jax.nn.sigmoid(u @ f32(params[f"{us}_kda_gate_weight"])
                           )[..., None]
    return o.reshape(S, H * D) @ f32(params[f"{us}_kda_out_weight"]), St


def mla(params, us, cfg, u, wrong=()):
    """The latent attention's part [S, d], expanded form."""
    la, H = cfg.latent, cfg.num_attention_heads
    dn, dr, dv, dc = (la.qk_nope_head_dim, la.qk_rope_head_dim,
                      la.v_head_dim, la.kv_lora_rank)
    S = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    rot = (lambda a: a) if "no_rope" in wrong \
        else (lambda a: _rope(a, cfg.rope_theta))
    if la.q_lora_rank:
        q = _rms(u @ f32(params[f"{us}_attn_q_a_weight"]),
                 f32(params[f"{us}_attn_q_a_norm_scale"]), cfg.rms_norm_eps) \
            @ f32(params[f"{us}_attn_q_b_weight"])
    else:
        q = u @ f32(params[f"{us}_attn_q_weight"])
    q = q.reshape(S, H, dn + dr)
    kva = u @ f32(params[f"{us}_attn_kv_a_weight"])
    c = _rms(kva[:, :dc], f32(params[f"{us}_attn_kv_a_norm_scale"]),
             cfg.rms_norm_eps)
    k_r = rot(kva[:, dc:])                                  # [S, dr]
    kv = (c @ f32(params[f"{us}_attn_kv_b_weight"])).reshape(S, H, dn + dv)
    s = jnp.einsum("qhd,shd->hqs", q[..., :dn], kv[..., :dn]) \
        + jnp.einsum("qhd,sd->hqs", rot(q[..., dn:]), k_r)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s * (dn + dr) ** -0.5,
                                 -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shd->qhd", p, kv[..., dn:])
    if "no_gate" not in wrong:
        o = o * jax.nn.sigmoid(u @ f32(params[f"{us}_attn_gate_weight"])
                               )[..., None]
    return o.reshape(S, H * dv) @ f32(params[f"{us}_attn_proj_weight"])


def _expert(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def route(params, us, cfg, x, wrong=()):
    """(chosen [S, E] boolean, weights [S, E]) of the rows ``x``."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    s = jax.nn.sigmoid(x @ f32(params[f"{us}_moe_router_weight"]))
    pick = s + f32(params[f"{us}_moe_router_bias"]) if cfg.router_bias \
        else s
    if cfg.n_group > 1 and "plain_topk" not in wrong:
        grouped = pick.reshape(-1, cfg.n_group, E // cfg.n_group)
        ranked = jnp.sort(grouped, axis=-1)[..., ::-1]
        score = ranked[..., 0] if "group_max" in wrong \
            else ranked[..., :2].sum(-1)
        order = jnp.argsort(-score, axis=-1, stable=True)
        kept = jnp.zeros_like(score, bool).at[
            jnp.arange(score.shape[0])[:, None],
            order[:, :cfg.topk_group]].set(True)
        pick = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(-1, E)
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(pick.shape, bool).at[
        jnp.arange(pick.shape[0])[:, None], order].set(True)
    w = jnp.where(chosen, s, 0.0)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling_factor


def ffn_parts(params, us, cfg, x, held=None, wrong=()):
    """(the routed part the experts ``held`` (first, count) give, the
    shared expert's part) of a routed layer for the normed rows ``x``:
    the expert leaves hold experts ``[first, first + count)``."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    first, count = held or (0, cfg.n_routed_experts)
    _, w = route(params, us, cfg, x, wrong)
    routed = sum(
        w[:, first + e, None] * _expert(
            x, f32(params[f"{us}_moe_experts_gate"][e]),
            f32(params[f"{us}_moe_experts_up"][e]),
            f32(params[f"{us}_moe_experts_down"][e]))
        for e in range(count))
    shared = 0.0
    if cfg.shared_intermediate_size:
        shared = _expert(x, f32(params[f"{us}_moe_shared_gate_weight"]),
                         f32(params[f"{us}_moe_shared_up_weight"]),
                         f32(params[f"{us}_moe_shared_down_weight"]))
    return routed, shared


def forward(params, cfg, tokens, name="lng", wrong=(), states=False):
    """Logits [S, V held] float32 of the sequence ``tokens`` [S]; with
    ``states`` also the KDA layers' states after it ``[layers, H, D,
    D]``.  ``cfg`` is the ``KDALatentConfig`` (its ``held_experts`` the
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
                part = mla(params, us, cfg, u, wrong)
            h = h + part
            x = _rms(h, f32(params[f"{us}_ln2_scale"]), eps)
            if i < cfg.first_k_dense_replace:
                h = h + _expert(x, f32(params[f"{us}_ffn_gate_weight"]),
                                f32(params[f"{us}_ffn_up_weight"]),
                                f32(params[f"{us}_ffn_down_weight"]))
            else:
                routed, shared = ffn_parts(params, us, cfg, x,
                                           cfg.held_experts, wrong)
                h = h + routed + shared
        logits = _rms(h, f32(params[f"{name}_ln_f_scale"]), eps) \
            @ f32(params[f"{name}_lm_head_weight"])
    return (logits, jnp.stack(kept)) if states else logits
