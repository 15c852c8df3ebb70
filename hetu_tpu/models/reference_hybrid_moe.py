"""The plain reference of the hybrid decoder (``moe_decode.HybridMoEConfig``,
the ``lfm2_moe`` family): gated short convolutions and grouped-query
attention layer by layer, a dense SwiGLU or a routed FFN under each, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
— a full forward over one whole sequence, a Python loop over the experts
with a dense mask, no kernel, no cache, no state, no batching.  The
serving path (chunked prefill through a paged grouped K/V pool and a
slot-indexed conv state, grouped matmuls) is tested against it, logits
not tokens.

Per layer ``i`` with input ``h`` (``rms`` the RMSNorm, ``norm_eps``; no
biases anywhere):

  a   = h + Op_i(rms(h; operator_norm)),  out = a + FFN_i(rms(a; ffn_norm))
  conv        [B | C | x] = u W_in; z = B * x;
              y_t = sum_{j<K} w[j] * z_{t-(K-1)+j}   (depthwise, causal,
              K = conv_L_cache taps, z before the sequence's start is 0);
              Op = (C * y) W_out
  attention   q = u W_q (H heads), k = u W_k, v = u W_v (H_kv heads);
              q, k each RMS-normalised per head with a learned scale,
              then rotate-half RoPE over the whole head, theta
              rope_theta; causal softmax(q k^T / sqrt(head)) v, query
              head n reading K/V head n // (H / H_kv); W_o
  dense FFN   the leading ``num_dense_layers``: (silu(u W_1) * u W_3) W_2
  routed FFN  s = sigmoid(float32(u) W_g); the top_k largest of s + b
              are chosen; w = s[sel] / (sum s[sel] + 1e-20) * scale;
              y = sum_e w_e SwiGLU_e(u)
  top         rms (embedding_norm), then the embedding table as the head

Departures from the family's public implementation:

* the top-k normalisation adds 1e-20 to the sum of the chosen scores
  (``moe_decode.route``'s, shared with the latent decoder) where the
  family's code adds 1e-6: a relative 5e-7 of weights that sum to about
  2, far under what float32 against bfloat16 shows;
* ``use_expert_bias`` false is a zero bias, not a missing one.

``omit`` leaves out one part of the mathematics at a time; it exists for
the test that shows the comparison notices each
(``tests/test_hybrid_moe.py``): the conv taps' history (every tap but
the last), the per-head q/k norm, the selection bias, the LAST layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

OMISSIONS = ("conv_history", "qk_norm", "bias", "layer")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, H, d] at positions 0..S-1, rotate-half over all of d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def forward(params, cfg, tokens, name="lfm", omit=()):
    """(logits [S, V], margin [S]) for ``tokens`` [S]: every position's
    next-token logits, and every position's smallest selection margin
    over the routed layers (the gap between the last chosen and the
    first not chosen of ``s + b``; +inf for a model with no routed
    layer)."""
    unknown = set(omit) - set(OMISSIONS)
    if unknown:
        raise ValueError(f"unknown omissions {sorted(unknown)}")
    f32 = lambda k: jnp.asarray(params[k], jnp.float32)    # noqa: E731
    H, Hkv, eps = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.norm_eps)
    dh, K = cfg.head_dim, cfg.conv_L_cache
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    margin = jnp.full((S,), jnp.inf, jnp.float32)
    layers = cfg.num_hidden_layers - ("layer" in omit)
    with jax.default_matmul_precision("highest"):
        h = f32(f"{name}_wte_table")[tokens]
        for i, op in enumerate(cfg.operators()[:layers]):
            us = f"{name}_h{i}"
            u = _rms(h, f32(f"{us}_ln1_scale"), eps)
            if op == "conv":
                b, c, x = jnp.split(u @ f32(f"{us}_conv_in_weight"), 3, -1)
                z = jnp.pad(b * x, ((K - 1, 0), (0, 0)))    # zeros before 0
                w = f32(f"{us}_conv_weight")                # [K, d]
                taps = [K - 1] if "conv_history" in omit else range(K)
                y = sum(w[j] * z[j:j + S] for j in taps)
                h = h + (c * y) @ f32(f"{us}_conv_out_weight")
            else:
                q = (u @ f32(f"{us}_attn_q_weight")).reshape(S, H, dh)
                kk = (u @ f32(f"{us}_attn_k_weight")).reshape(S, Hkv, dh)
                v = (u @ f32(f"{us}_attn_v_weight")).reshape(S, Hkv, dh)
                if "qk_norm" not in omit:
                    q = _rms(q, f32(f"{us}_attn_q_norm_scale"), eps)
                    kk = _rms(kk, f32(f"{us}_attn_k_norm_scale"), eps)
                q = _rope(q, cfg.rope_theta)
                kk = _rope(kk, cfg.rope_theta)
                # query head n reads K/V head n // (H / Hkv)
                kk = jnp.repeat(kk, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
                s = jnp.einsum("qhd,shd->hqs", q, kk) * dh ** -0.5
                p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
                o = jnp.einsum("hqs,shd->qhd", p, v).reshape(S, H * dh)
                h = h + o @ f32(f"{us}_attn_proj_weight")
            u = _rms(h, f32(f"{us}_ln2_scale"), eps)
            if i < cfg.num_dense_layers:
                h = h + _swiglu(u, f32(f"{us}_ffn_gate_weight"),
                                f32(f"{us}_ffn_up_weight"),
                                f32(f"{us}_ffn_down_weight"))
                continue
            sc = jax.nn.sigmoid(u @ f32(f"{us}_moe_router_weight"))
            pick = sc if "bias" in omit \
                else sc + f32(f"{us}_moe_router_bias")
            ranked = jnp.sort(pick, axis=-1)[:, ::-1]
            if k < E:
                margin = jnp.minimum(margin, ranked[:, k - 1] - ranked[:, k])
            chosen = pick >= ranked[:, k - 1:k]                # [S, E]
            w = jnp.where(chosen, sc, 0.0)
            if cfg.norm_topk_prob:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            w = w * cfg.routed_scaling_factor
            y = jnp.zeros_like(u)
            for e in range(E):
                y = y + w[:, e:e + 1] * _swiglu(
                    u, f32(f"{us}_moe_experts_gate")[e],
                    f32(f"{us}_moe_experts_up")[e],
                    f32(f"{us}_moe_experts_down")[e])
            h = h + y
        h = _rms(h, f32(f"{name}_ln_f_scale"), eps)
        return h @ f32(f"{name}_wte_table").T, margin
