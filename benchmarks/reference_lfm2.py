"""LFM2-8B-A1B's block stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (the equations of
``hetu_tpu/models/reference_hybrid_moe.py``, written again here and not
imported: the yardstick must not move with the program), laid out so
that a 12,800-token sequence fits on the chip beside 9.3 GB of served
weights.  It decides ``correct``.

The equations are the family's public ones: no cache, no state, no
batching, one sequence at a time (``u`` the RMSNorm of the residual
``h``; no biases):

  conv        [B | C | x] = u W_in; z = B * x;
              y_t = sum_{j<K} w[j] * z_{t-(K-1)+j}  (depthwise, causal,
              K = conv_L_cache = 3, z before the sequence's start is 0);
              h += (C * y) W_out
  attention   q = u W_q (32 heads of 64), k = u W_k, v = u W_v (8 heads);
              q, k each RMS-normalised per head with a learned scale,
              then rotate-half RoPE over all 64 columns, theta 1e6;
              causal softmax(q k^T / 8) v, query head n reading K/V head
              n // 4; h += concat(o) W_o
  dense FFN   the leading ``num_dense_layers``: one SwiGLU
  routed FFN  s = sigmoid(float32(u) W_g); the top_k largest of s + b
              chosen; w = s[sel] / (sum s[sel] + 1e-20) * scale;
              y = sum_e w_e SwiGLU_e(u)
  top         RMSNorm (embedding_norm), the embedding table as the head

What differs from the program's copy is only how the work is cut: every
layer is one jitted call whose weights are upcast inside it (a layer at
a time), the query rows of attention are taken ``ROW_BLOCK`` at a time,
the experts one at a time in a Python loop with a dense mask (each
upcast alone), and the head over ``VOCAB_BLOCK`` columns at a time for
the answer's rows only.  The dense FFN, the router, an expert and the
head are ``reference_glm47flash``'s own functions (the same equations;
the benchmark's code, not the program's).  Departure from the family's
public code: the top-k normalisation adds 1e-20 where it adds 1e-6 (the
configuration's ``assumed``).

``lower`` rounds the operands of every weight product to float8
(e4m3), the nearest precision below the bfloat16 the configuration
states: what a system serving in that precision would give.  The
comparison's limits lie between what the bfloat16 engine shows against
this reference and what ``lower`` shows (``PERF.md`` section 6, PR 34),
and the benchmark's test shows that ``lower`` fails them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import (
    _add_expert, _dense_ffn, _head, _mm, _rms, _rope, _route)

ROW_BLOCK = 256
VOCAB_BLOCK = 8192           # 65,536 / 8


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _conv(h, w, eps, lower):
    """h + the gated short convolution, the whole sequence at once."""
    mm = _mm(lower)
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S = h.shape[0]
    K = w["taps"].shape[0]
    u = _rms(h, f32(w["ln1"]), eps)
    b, c, x = jnp.split(mm(u, w["in"]), 3, axis=-1)
    z = jnp.pad(b * x, ((K - 1, 0), (0, 0)))               # zeros before 0
    y = sum(f32(w["taps"])[j] * z[j:j + S] for j in range(K))
    return h + mm(c * y, w["out"])


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _attention(h, w, sizes, lower):
    """h + grouped-query attention, rows in blocks of ``ROW_BLOCK``."""
    H, Hkv, dh, eps, theta = sizes
    mm = _mm(lower)
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S = h.shape[0]
    u = _rms(h, f32(w["ln1"]), eps)
    q = _rms(mm(u, w["q"]).reshape(S, H, dh), f32(w["q_norm"]), eps)
    k = _rms(mm(u, w["k"]).reshape(S, Hkv, dh), f32(w["k_norm"]), eps)
    v = mm(u, w["v"]).reshape(S, Hkv, dh)
    q = _rope(q, theta).reshape(S, Hkv, H // Hkv, dh)      # head n = (n // g, n % g)
    k = _rope(k, theta)
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block, 0)
        s = jnp.einsum("qhgd,shd->hgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        live = jnp.arange(S)[None, :] <= (r0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(
            jnp.where(live[None, None], s * dh ** -0.5, -jnp.inf), -1)
        return jnp.einsum("hgqs,shd->qhgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, S, block)).reshape(S, H * dh)
    return h + mm(o, w["proj"])


def forward(params, config, tokens, rows, name="lfm", lower=False):
    """(logits [len(rows), V] as numpy float32, margin [S]) for the
    sequence ``tokens`` [S] (``S`` a multiple of ``ROW_BLOCK`` or below
    it): the next-token logits after each position in ``rows``, and
    every position's smallest selection margin over the routed layers
    (the last chosen against the first not chosen of ``s + b``).
    ``config`` holds the source's keys (``layer_types``, ``norm_eps``,
    ``num_experts``, ...)."""
    c = config
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    eps, E = float(c["norm_eps"]), c["num_experts"]
    sizes = (H, Hkv, c["hidden_size"] // H, eps, float(c["rope_theta"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(tokens.shape[0], np.inf, np.float32)
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        us = f"{name}_h{i}"
        if kind == "conv":
            h = _conv(h, {"ln1": params[f"{us}_ln1_scale"],
                          "in": params[f"{us}_conv_in_weight"],
                          "taps": params[f"{us}_conv_weight"],
                          "out": params[f"{us}_conv_out_weight"]},
                      eps, lower)
        else:
            h = _attention(h, {
                "ln1": params[f"{us}_ln1_scale"],
                "q": params[f"{us}_attn_q_weight"],
                "k": params[f"{us}_attn_k_weight"],
                "v": params[f"{us}_attn_v_weight"],
                "q_norm": params[f"{us}_attn_q_norm_scale"],
                "k_norm": params[f"{us}_attn_k_norm_scale"],
                "proj": params[f"{us}_attn_proj_weight"]}, sizes, lower)
        if i < c["num_dense_layers"]:
            h = _dense_ffn(h, params[f"{us}_ln2_scale"],
                           params[f"{us}_ffn_gate_weight"],
                           params[f"{us}_ffn_up_weight"],
                           params[f"{us}_ffn_down_weight"], eps, lower)
            continue
        x, w, m = _route(h, params[f"{us}_ln2_scale"],
                         params[f"{us}_moe_router_weight"],
                         params[f"{us}_moe_router_bias"], eps,
                         c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
                         float(c["routed_scaling_factor"]), lower)
        margin = np.minimum(margin, np.asarray(m))
        y = jnp.zeros_like(x)
        gate, up, down = (params[f"{us}_moe_experts_{n}"]
                          for n in ("gate", "up", "down"))
        for e in range(E):
            y = _add_expert(y, x, w[:, e], gate[e], up[e], down[e], lower)
        h = h + y
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    head = params[f"{name}_wte_table"].T
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    out = [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                            head[:, v0:v0 + step], eps, lower))
           for v0 in range(0, V, step)]
    return np.concatenate(out, axis=1), margin
