"""The plain reference of the decoder whose layers attend EITHER over a
sliding window OR over everything, each over a softmax-routed FFN
(``moe_decode.HybridMoEConfig`` with ``model_type`` "mellum"): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, one full
forward over one whole sequence with an explicit banded mask, every
expert computed densely and weighted: no sort, no grouped product, no
cache, no ring, no kernel, no batching, and nothing imported from the
served path (``gpt_decode``, ``moe_decode``, ``ragged_attention``): the
rotary frequencies are worked out here again from the file's own
``rope_parameters``.  The serving path (chunked prefill and decode
through two paged pools, a ring a slot, the banded kernel, grouped
matmuls) is tested against it, logits not tokens.

Per layer ``l`` of ``layer_types`` with input ``x`` [S, hidden] (``rms``
the RMSNorm with a learned scale, ``rms_norm_eps``; no bias anywhere):

  u = rms(x);  q = u W_q (H heads of d), k = u W_k, v = u W_v (H_kv
  heads of d); query head n reads K/V head n // (H / H_kv)
  rotation    rotate-half over the whole head, ``inv_freq`` BY LAYER KIND
              (``rope_parameters[kind]``):
              "default"  inv_freq_i = theta ** (-2i / d), i < d / 2
              "yarn"     low  = floor(d ln(orig / (beta_fast 2 pi))
                                      / (2 ln theta)),
                         high = ceil(d ln(orig / (beta_slow 2 pi))
                                     / (2 ln theta)), clamped to
                         [0, d - 1]; ramp_i = clip((i - low) / (high -
                         low), 0, 1); inv_freq_i = (1 - ramp_i) theta **
                         (-2i / d) + ramp_i theta ** (-2i / d) / factor;
                         cos AND sin both times ``attention_factor`` (a
                         score carries its square)
  scores      q k^T / sqrt(d), softmax in float32.  full_attention:
              j <= i.  sliding_attention: i - window < j <= i (a query
              sees itself and the ``window - 1`` positions before it)
  x = x + softmax(.) v W_o
  u = rms(x);  p = softmax(float32(u) W_g) over ALL the experts; the
  ``top_k`` largest chosen; w = p[sel] / sum p[sel] (``norm_topk_prob``)
  times ``routed_scaling_factor``;
  x = x + sum_e w_e (silu(u W1_e) * (u W3_e)) W2_e
  top         rms, then the untied head (or the embedding table)

Departures from the published description (each listed in the
benchmark configuration's ``assumed`` too):

* the description's "MTP head" has no key in the published config and
  takes no part in the next-token logits: left out;
* the router's scoring function has no key either: softmax over all the
  experts, the convention of the ``norm_topk_prob`` /
  ``moe_intermediate_size`` family of routers;
* no q/k norm (the config names none);
* the top-k normalisation adds 1e-20 to the sum of the chosen scores
  (the served router's epsilon): a relative 1e-19 of a sum near 0.2.

``wrong`` computes one thing wrongly at a time; it exists for the tests
that show the comparison notices each (``tests/test_window_moe.py``):
"window_as_full" (a sliding layer scored over everything),
"default_rope" (the full layers rotated with the default frequencies and
no factor), "no_attention_factor" (YaRN's frequencies, factor 1),
"sigmoid" (sigmoid scores in place of the softmax), "bf16" (the router's
product and softmax and the attention's softmax in bfloat16, where the
configuration says float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

WRONG = ("window_as_full", "default_rope", "no_attention_factor",
         "sigmoid", "bf16")


def inv_freq(head_dim, rope_type="default", rope_theta=10000.0, factor=1.0,
             original_max_position_embeddings=0, beta_fast=32.0,
             beta_slow=1.0, attention_factor=None, **ignored):
    """(inv_freq [head_dim / 2] float64, the factor on cos and sin) of
    one ``rope_parameters`` section: the closed form in the module's
    docstring."""
    d = head_dim
    base = rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope_type == "default":
        return base, 1.0
    if rope_type != "yarn":
        raise ValueError(f"rope_type={rope_type!r}")

    def index(rotations):
        return d * math.log(original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(index(beta_fast)), 0)
    high = min(math.ceil(index(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return (1 - ramp) * base + ramp * base / factor, float(attention_factor)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, inv, factor):
    """x [S, H, d] at positions 0..S-1, rotate-half over all of d."""
    S, d = x.shape[0], x.shape[-1]
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def forward(params, config, tokens, name="mel", wrong=()):
    """(logits [S, V], margin [S]) for ``tokens`` [S]: every position's
    next-token logits, and every position's smallest selection margin
    over the layers (the gap between the last chosen and the first not
    chosen of the router's scores).  ``config`` holds the source's own
    keys (``layer_types``, ``rope_parameters``, ``sliding_window``,
    ``num_experts``, ...)."""
    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    c = config
    f32 = lambda k: jnp.asarray(params[k], jnp.float32)    # noqa: E731
    H, Hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, E, k = c["rms_norm_eps"], c["num_experts"], c["num_experts_per_tok"]
    window = c["sliding_window"]
    low = jnp.bfloat16 if "bf16" in wrong else jnp.float32
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = j <= i
    band = causal & (j > i - window)
    margin = jnp.full((S,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(f"{name}_wte_table")[tokens]
        for l, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
            us = f"{name}_h{l}"
            rope = dict(c["rope_parameters"][kind])
            if kind == "full_attention" and "default_rope" in wrong:
                rope = {"rope_type": "default",
                        "rope_theta": rope["rope_theta"]}
            inv, factor = inv_freq(dh, **rope)
            if "no_attention_factor" in wrong:
                factor = 1.0
            u = _rms(x, f32(f"{us}_ln1_scale"), eps)
            q = (u @ f32(f"{us}_attn_q_weight")).reshape(S, H, dh)
            kk = (u @ f32(f"{us}_attn_k_weight")).reshape(S, Hkv, dh)
            v = (u @ f32(f"{us}_attn_v_weight")).reshape(S, Hkv, dh)
            q, kk = _rotate(q, inv, factor), _rotate(kk, inv, factor)
            # query head n reads K/V head n // (H / Hkv)
            kk = jnp.repeat(kk, H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
            s = jnp.einsum("qhd,shd->hqs", q, kk) * dh ** -0.5
            seen = band if kind == "sliding_attention" \
                and "window_as_full" not in wrong else causal
            p = jax.nn.softmax(
                jnp.where(seen[None], s, -jnp.inf).astype(low), -1)
            o = jnp.einsum("hqs,shd->qhd", p.astype(jnp.float32),
                           v).reshape(S, H * dh)
            x = x + o @ f32(f"{us}_attn_proj_weight")
            u = _rms(x, f32(f"{us}_ln2_scale"), eps)
            scores = (u.astype(low) @ f32(f"{us}_moe_router_weight"
                                          ).astype(low))
            sc = (jax.nn.sigmoid(scores) if "sigmoid" in wrong
                  else jax.nn.softmax(scores, -1)).astype(jnp.float32)
            ranked = jnp.sort(sc, axis=-1)[:, ::-1]
            if k < E:
                margin = jnp.minimum(margin, ranked[:, k - 1] - ranked[:, k])
            w = jnp.where(sc >= ranked[:, k - 1:k], sc, 0.0)   # [S, E]
            if c["norm_topk_prob"]:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            w = w * c.get("routed_scaling_factor", 1.0)
            y = jnp.zeros_like(u)
            for e in range(E):
                y = y + w[:, e:e + 1] * _swiglu(
                    u, f32(f"{us}_moe_experts_gate")[e],
                    f32(f"{us}_moe_experts_up")[e],
                    f32(f"{us}_moe_experts_down")[e])
            x = x + y
        x = _rms(x, f32(f"{name}_ln_f_scale"), eps)
        head = f32(f"{name}_wte_table").T \
            if c.get("tie_word_embeddings") \
            else f32(f"{name}_lm_head_weight")
        return x @ head, margin
