"""Mellum2-12B-A2.5B's block stack in plain float32 ``jax.numpy``,
precision ``highest``: the benchmark's own copy of the reference (the
equations of ``hetu_tpu/models/reference_window_moe.py``, written again
here and not imported: the yardstick must not move with the program),
laid out so that a 12,800-token sequence fits on the chip beside 10.9 GB
of served weights.  It decides ``correct``.

The equations (``u`` the RMSNorm of the residual ``h``, eps 1e-6, a
learned scale; no biases), layer ``l`` of ``layer_types``:

  attention   q = u W_q (32 heads of 128), k = u W_k, v = u W_v (4
              heads); rotate-half RoPE over all 128 columns with the
              frequencies of the layer's KIND (``rope_parameters``):
              sliding: theta ** (-2i / 128); full (YaRN): the same below
              index ``low``, over ``factor`` above ``high``, a linear
              ramp between, cos and sin both times ``attention_factor``
              (``rope_frequencies`` below: the closed form);
              softmax(q k^T / sqrt(128)) v in float32, query head n
              reading K/V head n // 8; full: j <= i; sliding:
              i - window < j <= i; h += concat(o) W_o
  routed FFN  p = softmax(float32(u) W_g) over all 64 experts; the top_k
              largest chosen; w = p[sel] / (sum p[sel] + 1e-20);
              y = sum_e w_e SwiGLU_e(u); every layer routed, no shared
              expert, no selection bias
  top         RMSNorm, the untied head

What differs from the program's copy is only how the work is cut: every
layer is one jitted call whose weights are upcast inside it, the query
rows of attention are taken ``ROW_BLOCK`` at a time against ALL keys
with the explicit band, the experts one at a time in a Python loop with
a dense mask, and the head over ``VOCAB_BLOCK`` columns at a time for the
answer's rows only.  An expert, the norm and the head are
``reference_glm47flash``'s own functions (the same equations; the
benchmark's code, not the program's).  Departures from the published
description: the configuration's ``assumed`` (softmax scoring, no q/k
norm, the window includes the query, no next-token module) and the
1e-20 in the top-k normalisation.

``lower`` rounds the operands of every weight product to float8 (e4m3),
the nearest precision below the bfloat16 the configuration states, and
computes the router's product and softmax and the attention's softmax
in bfloat16 where the configuration says float32: what a system serving
in that precision would give.  The comparison's limits lie between what
the bfloat16 engine shows against this reference and what ``lower``
shows (``PERF.md`` section 6, PR 42).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import _add_expert, _head, _mm, _rms

ROW_BLOCK = 256
VOCAB_BLOCK = 8192           # 98,304 / 12


def rope_frequencies(head_dim, rope_type="default", rope_theta=10000.0,
                     factor=1.0, original_max_position_embeddings=0,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                     **ignored):
    """(inv_freq: head_dim / 2 floats, the factor on cos and sin) of one
    ``rope_parameters`` section, as a hashable pair."""
    d = head_dim
    base = rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope_type == "default":
        return tuple(float(v) for v in base), 1.0
    if rope_type != "yarn":
        raise ValueError(f"rope_type={rope_type!r}")

    def index(rotations):
        return d * math.log(original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(index(beta_fast)), 0)
    high = min(math.ceil(index(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 - ramp) * base + ramp * base / factor
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return tuple(float(v) for v in inv), float(attention_factor)


def _rotate(x, inv, factor):
    S, d = x.shape[0], x.shape[-1]
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "rope", "window",
                                             "lower"))
def _attention(h, w, sizes, rope, window, lower):
    """h + grouped-query attention, rows in blocks of ``ROW_BLOCK``;
    ``window`` 0: causal, else the band."""
    H, Hkv, dh, eps = sizes
    inv, factor = rope
    mm = _mm(lower)
    low = jnp.bfloat16 if lower else jnp.float32
    S = h.shape[0]
    u = _rms(h, w["ln1"].astype(jnp.float32), eps)
    q = _rotate(mm(u, w["q"]).reshape(S, H, dh), inv, factor)
    k = _rotate(mm(u, w["k"]).reshape(S, Hkv, dh), inv, factor)
    v = mm(u, w["v"]).reshape(S, Hkv, dh)
    q = q.reshape(S, Hkv, H // Hkv, dh)        # head n = (n // g, n % g)
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block, 0)
        s = jnp.einsum("qhgd,shd->hgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        i = (r0 + jnp.arange(block))[:, None]
        j = jnp.arange(S)[None, :]
        seen = j <= i
        if window:
            seen &= j > i - window
        p = jax.nn.softmax(
            jnp.where(seen[None, None], s * dh ** -0.5, -jnp.inf
                      ).astype(low), -1).astype(jnp.float32)
        return jnp.einsum("hgqs,shd->qhgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, S, block)).reshape(S, H * dh)
    return h + mm(o, w["proj"])


@functools.partial(jax.jit, static_argnames=("eps", "k", "norm", "scale",
                                             "lower"))
def _route(h, ln2, w_router, eps, k, norm, scale, lower):
    """(x, dense weights [S, E], each row's selection margin): softmax
    over all the experts, the top ``k`` chosen."""
    x = _rms(h, ln2.astype(jnp.float32), eps)
    if lower:
        sc = jax.nn.softmax(jnp.dot(x.astype(jnp.bfloat16),
                                    w_router.astype(jnp.bfloat16)),
                            -1).astype(jnp.float32)
    else:
        sc = jax.nn.softmax(jnp.dot(x, w_router.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST), -1)
    ranked = jnp.sort(sc, axis=-1)[:, ::-1]
    w = jnp.where(sc >= ranked[:, k - 1:k], sc, 0.0)
    if norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return x, w * scale, ranked[:, k - 1] - ranked[:, k]


def forward(params, config, tokens, rows, name="mel", lower=False):
    """(logits [len(rows), V] as numpy float32, margin [S]) for the
    sequence ``tokens`` [S] (``S`` a multiple of ``ROW_BLOCK`` or below
    it): the next-token logits after each position in ``rows``, and
    every position's smallest selection margin over the layers (the
    last chosen against the first not chosen of the softmax).
    ``config`` holds the source's keys."""
    c = config
    H, Hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, E = float(c["rms_norm_eps"]), c["num_experts"]
    sizes = (H, Hkv, dh, eps)
    ropes = {kind: rope_frequencies(dh, **p)
             for kind, p in c["rope_parameters"].items()}
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(tokens.shape[0], np.inf, np.float32)
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        us = f"{name}_h{i}"
        window = int(c["sliding_window"]) if kind == "sliding_attention" \
            else 0
        h = _attention(h, {"ln1": params[f"{us}_ln1_scale"],
                           "q": params[f"{us}_attn_q_weight"],
                           "k": params[f"{us}_attn_k_weight"],
                           "v": params[f"{us}_attn_v_weight"],
                           "proj": params[f"{us}_attn_proj_weight"]},
                       sizes, ropes[kind], window, lower)
        x, w, m = _route(h, params[f"{us}_ln2_scale"],
                         params[f"{us}_moe_router_weight"], eps,
                         c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
                         float(c.get("routed_scaling_factor", 1.0)), lower)
        margin = np.minimum(margin, np.asarray(m))
        y = jnp.zeros_like(x)
        gate, up, down = (params[f"{us}_moe_experts_{n}"]
                          for n in ("gate", "up", "down"))
        for e in range(E):
            y = _add_expert(y, x, w[:, e], gate[e], up[e], down[e], lower)
        h = h + y
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    head = (params[f"{name}_wte_table"].T if c.get("tie_word_embeddings")
            else params[f"{name}_lm_head_weight"])
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    out = [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                            head[:, v0:v0 + step], eps, lower))
           for v0 in range(0, V, step)]
    return np.concatenate(out, axis=1), margin
