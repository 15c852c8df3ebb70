"""The plain reference of the power-retention decoder
(``retention_decode.RetentionConfig``, the ``brumby`` family): the
grouped-query block with the softmax replaced by power retention of
degree 2, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, a full forward over one
whole sequence in the ATTENTION form: no state, no ``phi``, no chunking,
no cache, no batching.  The serving path (a recurrence over a gated
state ``phi(k) v^T``, chunked prefill, decode by one step, no K/V page)
shares no line with it and is tested against it, logits not tokens.

Per layer with input ``h``, ``u = rms(h; input_layernorm)``:

  q, k, v  u W_q (n heads of d), u W_k, u W_v (g heads), no biases; q
           and k through the per-head RMSNorm over d (learned scales),
           then rotate-half RoPE over the whole head, theta rope_theta,
           angles in float32
  gate     lg_t = log sigmoid(u_t W_g + b_g), one a K/V head a token
  weights  a_tj = (q_t . k_j)^2 exp(sum_{l = j+1 .. t} lg_l), j <= t,
           query head n reading K/V head n // (n_heads / g)
  y_t      sum_j a_tj v_j / sum_j a_tj; concat over heads; W_o
  h <- h + y;  h <- h + (silu(x W_gate) * x W_up) W_down, x = rms(h)
  model    embedding; final rms; untied head

Departures from the published description (arXiv:2507.04239 and the
family's ``retention`` package): any scale on ``q . k`` is left out (it
cancels between numerator and denominator); no epsilon in the
denominator (the ``j = t`` term is a square); the package's inference
switch from K/V to the state form at a sequence length is a choice of
that package, not part of the function, and is not here.

``omit`` leaves out one part of the mathematics at a time; it exists for
the tests that show the comparison notices each: "gate" (every ``lg``
0), "normaliser" (the numerator alone), "position" (the keys' RoPE
positions shifted by one against the queries').
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

OMISSIONS = ("gate", "normaliser", "position")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta, shift=0):
    """x [S, H, d] at positions shift..shift+S-1, rotate-half over d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ((jnp.arange(S, dtype=jnp.float32) + shift)[:, None]
           * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def retention(params, us, cfg, u, omit=None):
    """The layer's mixer over the normed rows ``u`` [S, hidden]:
    ``concat(y) W_o`` [S, hidden]."""
    S = u.shape[0]
    n, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    q = _rms((u @ params[f"{us}_attn_q_weight"]).reshape(S, n, d),
             params[f"{us}_attn_q_norm_scale"], eps)
    k = _rms((u @ params[f"{us}_attn_k_weight"]).reshape(S, g, d),
             params[f"{us}_attn_k_norm_scale"], eps)
    v = (u @ params[f"{us}_attn_v_weight"]).reshape(S, g, d)
    q = _rope(q, cfg.rope_theta).reshape(S, g, n // g, d)
    k = _rope(k, cfg.rope_theta, 1 if omit == "position" else 0)
    lg = jax.nn.log_sigmoid(u @ params[f"{us}_ret_gate_weight"]
                            + params[f"{us}_ret_gate_bias"])    # [S, g]
    if omit == "gate":
        lg = jnp.zeros_like(lg)
    cum = jnp.cumsum(lg, axis=0).T                         # [g, S]
    live = jnp.tril(jnp.ones((S, S), bool))
    # masked BEFORE the exponential: above the diagonal the sum of the
    # gates is positive
    decay = jnp.exp(jnp.where(live, cum[:, :, None] - cum[:, None, :],
                              -jnp.inf))                   # [g, t, j]
    s = jnp.einsum("tgmd,jgd->gmtj", q, k)
    a = s * s * decay[:, None]
    y = jnp.einsum("gmtj,jgd->tgmd", a, v)
    if omit != "normaliser":
        y = y / a.sum(-1).transpose(2, 0, 1)[..., None]
    return y.reshape(S, n * d) @ params[f"{us}_attn_proj_weight"]


def forward(params, cfg, tokens, name="bru", omit=None, stats=None):
    """Logits [S, V] float32 for one sequence ``tokens`` [S].  ``stats``
    (a dict) receives the RMS of the residual and of each branch's
    contribution to it, a layer, and the logits' standard deviation."""
    if omit is not None and omit not in OMISSIONS:
        raise ValueError(f"omit={omit!r} not in {OMISSIONS}")
    eps = cfg.rms_norm_eps
    rms_of = lambda a: float(jnp.sqrt(jnp.mean(a * a)))    # noqa: E731
    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
                  if k.startswith(name + "_")}
        tokens = jnp.asarray(tokens, jnp.int32)
        h = params[f"{name}_wte_table"][tokens]
        for i in range(cfg.num_hidden_layers):
            us = f"{name}_h{i}"
            y = retention(params, us, cfg,
                          _rms(h, params[f"{us}_ln1_scale"], eps), omit)
            if stats is not None:
                stats.setdefault("layers", []).append(
                    {"residual": rms_of(h), "retention": rms_of(y)})
            h = h + y
            x = _rms(h, params[f"{us}_ln2_scale"], eps)
            f = (jax.nn.silu(x @ params[f"{us}_ffn_gate_weight"])
                 * (x @ params[f"{us}_ffn_up_weight"])) \
                @ params[f"{us}_ffn_down_weight"]
            if stats is not None:
                stats["layers"][-1]["mlp"] = rms_of(f)
            h = h + f
        h = _rms(h, params[f"{name}_ln_f_scale"], eps)
        logits = h @ params[f"{name}_lm_head_weight"]
        if stats is not None:
            stats["logits"] = float(logits.std())
        return logits
