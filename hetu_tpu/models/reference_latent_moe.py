"""The plain reference of the latent-attention, routed-FFN decoder
(``moe_decode.LatentMoEConfig``): the published equations, NOT absorbed,
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
— a full forward over one whole sequence, a Python loop over the experts
with a dense mask, no kernel, no cache, no batching.  The serving path
(absorbed projections, a latent paged cache, grouped matmuls) is tested
against it, logits not tokens.

Per layer, ``x`` the RMSNorm of the residual ``h``, no biases:

  attention   c_q = RMSNorm(x W_qa); q = c_q W_qb as H heads of
              [q_nope | q_rope]; [c_kv | k_r] = x W_kva;
              c_kv = RMSNorm(c_kv); k_r = RoPE(k_r), ONE head shared by
              all; q_rope = RoPE(q_rope); [k_nope | v]_h = c_kv W_kvb,h;
              score = (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope),
              causal softmax, o_h = P v_h, h += concat(o_h) W_o
  routed FFN  s = sigmoid(float32(x) W_g); the top_k largest of s + b
              are chosen; w = s[sel] / (sum s[sel] + 1e-20) * scale;
              y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)
  dense FFN   the leading ``first_k_dense_replace`` layers: one SwiGLU
  top         RMSNorm, then the head (untied unless the config ties it)

Departures from the published description:

* RoPE pairs column j with column j + d/2 of the rope part ("rotate
  half") over all ``qk_rope_head_dim`` columns, frequency
  ``theta ** (-2j/d)``.  The checkpoints' own code pairs neighbours
  (2j, 2j+1) after a fixed permutation; the two differ by that fixed
  permutation of ``W_qb``'s and ``W_kva``'s rope columns, which a
  converter applies once (``hf.convert_glm4_moe_lite``) and random
  weights make moot.
* ``n_group = topk_group = 1`` makes the group-limited selection the
  identity, so it is not written; other values are refused by the
  config class.
* The multi-token-prediction layer takes no part in the next-token
  logits and is not here.

``omit`` leaves out one part of the mathematics at a time; it exists for
the test that shows the comparison notices each
(``tests/test_latent_moe.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

OMISSIONS = ("shared", "scale", "norm", "bias", "k_r", "router_bf16")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, ..., d] at positions 0..S-1, rotate-half."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def forward(params, cfg, tokens, name="glm", omit=()):
    """(logits [S, V], margin [S]) for ``tokens`` [S]: every position's
    next-token logits, and every position's smallest selection margin
    over the routed layers (the gap between the last chosen and the
    first not chosen of ``s + b``; +inf for a model with no routed
    layer).  A row whose margin is tiny is one that lower precision may
    route elsewhere."""
    unknown = set(omit) - set(OMISSIONS)
    if unknown:
        raise ValueError(f"unknown omissions {sorted(unknown)}")
    f32 = lambda k: jnp.asarray(params[k], jnp.float32)    # noqa: E731
    H, eps = cfg.num_attention_heads, cfg.rms_norm_eps
    dn, dr, dv, dc = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    margin = jnp.full((S,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = f32(f"{name}_wte_table")[tokens]
        for i in range(cfg.num_hidden_layers):
            us = f"{name}_h{i}"
            x = _rms(h, f32(f"{us}_ln1_scale"), eps)
            cq = _rms(x @ f32(f"{us}_attn_q_a_weight"),
                      f32(f"{us}_attn_q_a_norm_scale"), eps)
            q = (cq @ f32(f"{us}_attn_q_b_weight")).reshape(S, H, dn + dr)
            kva = x @ f32(f"{us}_attn_kv_a_weight")
            ckv = _rms(kva[:, :dc], f32(f"{us}_attn_kv_a_norm_scale"), eps)
            k_r = _rope(kva[:, dc:], cfg.rope_theta)           # [S, dr]
            q_rope = _rope(q[..., dn:], cfg.rope_theta)        # [S, H, dr]
            kv = (ckv @ f32(f"{us}_attn_kv_b_weight")).reshape(
                S, H, dn + dv)
            s = jnp.einsum("qhd,shd->hqs", q[..., :dn], kv[..., :dn])
            if "k_r" not in omit:
                s = s + jnp.einsum("qhd,sd->hqs", q_rope, k_r)
            p = jax.nn.softmax(
                jnp.where(causal[None], s * (dn + dr) ** -0.5, -jnp.inf),
                axis=-1)
            o = jnp.einsum("hqs,shd->qhd", p, kv[..., dn:])
            h = h + o.reshape(S, H * dv) @ f32(f"{us}_attn_proj_weight")
            x = _rms(h, f32(f"{us}_ln2_scale"), eps)
            if i < cfg.first_k_dense_replace:
                h = h + _swiglu(x, f32(f"{us}_ffn_gate_weight"),
                                f32(f"{us}_ffn_up_weight"),
                                f32(f"{us}_ffn_down_weight"))
                continue
            wg = f32(f"{us}_moe_router_weight")
            if "router_bf16" in omit:
                sc = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.bfloat16), wg.astype(jnp.bfloat16)
                ).astype(jnp.float32))
            else:
                sc = jax.nn.sigmoid(x @ wg)                    # [S, E]
            pick = sc if "bias" in omit \
                else sc + f32(f"{us}_moe_router_bias")
            ranked = jnp.sort(pick, axis=-1)[:, ::-1]
            if k < E:
                margin = jnp.minimum(margin, ranked[:, k - 1] - ranked[:, k])
            chosen = pick >= ranked[:, k - 1:k]                # [S, E]
            w = jnp.where(chosen, sc, 0.0)
            if cfg.norm_topk_prob and "norm" not in omit:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            if "scale" not in omit:
                w = w * cfg.routed_scaling_factor
            y = jnp.zeros_like(x)
            for e in range(E):
                y = y + w[:, e:e + 1] * _swiglu(
                    x, f32(f"{us}_moe_experts_gate")[e],
                    f32(f"{us}_moe_experts_up")[e],
                    f32(f"{us}_moe_experts_down")[e])
            if cfg.n_shared_experts and "shared" not in omit:
                y = y + _swiglu(x, f32(f"{us}_moe_shared_gate_weight"),
                                f32(f"{us}_moe_shared_up_weight"),
                                f32(f"{us}_moe_shared_down_weight"))
            h = h + y
        h = _rms(h, f32(f"{name}_ln_f_scale"), eps)
        head = (f32(f"{name}_wte_table").T if cfg.tie_word_embeddings
                else f32(f"{name}_lm_head_weight"))
        return h @ head, margin
