"""GLM-4.7-Flash's block stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (copied from
``hetu_tpu/models/reference_latent_moe.py``, not imported: the yardstick
must not move with the program), laid out so that a 5,120-token sequence
fits on the chip beside 9 GB of served weights.  It decides ``correct``.

The equations are the published ones, NOT absorbed, no cache, no
batching, one sequence at a time (``x`` the RMSNorm of the residual
``h``; no biases):

  attention   c_q = RMSNorm(x W_qa); q = c_q W_qb as H heads of
              [q_nope | q_rope]; [c_kv | k_r] = x W_kva;
              c_kv = RMSNorm(c_kv); k_r = RoPE(k_r), one head shared by
              all; q_rope = RoPE(q_rope); [k_nope | v]_h = c_kv W_kvb,h;
              score = (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope),
              causal softmax, o_h = P v_h, h += concat(o_h) W_o
  routed FFN  s = sigmoid(float32(x) W_g); the top_k largest of s + b
              chosen; w = s[sel] / (sum s[sel] + 1e-20) * scale;
              y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)
  dense FFN   the leading layers: one SwiGLU.  Top: RMSNorm, the head.

What differs from the program's copy is only how the work is cut: every
layer is one jitted call whose weights are upcast inside it (a layer at
a time), the query rows of attention are taken ``ROW_BLOCK`` at a time,
the experts one at a time in a Python loop with a dense mask (each
upcast alone), and the head over ``VOCAB_BLOCK`` columns at a time for
the answer's rows only.  Departures from the published description: RoPE
pairs column j with j + d/2 (rotate-half; the checkpoints' neighbour
pairing is a fixed permutation of W_qb's and W_kva's rope columns);
``n_group = topk_group = 1`` is the identity and not written; the MTP
layer takes no part in these logits.

``lower`` rounds the operands of every weight product to float8
(e4m3), the nearest precision below the bfloat16 the configuration
states: what a system serving in that precision would give.  The
comparison's limits lie between what the bfloat16 engine shows against
this reference and what ``lower`` shows (``PERF.md`` section 6, PR 28),
and the benchmark's test shows that ``lower`` fails them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512
VOCAB_BLOCK = 19360          # 154,880 / 8


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mm(lower):
    """The matrix product in float32 ``highest``; with ``lower`` both
    operands are first rounded to float8 (e4m3)."""
    def f32(a):
        if lower:
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(jnp.float32)
    return lambda a, b: jnp.dot(f32(a), f32(b),
                                precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("sizes", "lower"))
def _attention(h, w, sizes, lower):
    """h + attention, rows in blocks of ``ROW_BLOCK``."""
    H, dn, dr, dv, dc, eps, theta = sizes
    mm = _mm(lower)
    S = h.shape[0]
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    x = _rms(h, f32(w["ln1"]), eps)
    cq = _rms(mm(x, w["q_a"]), f32(w["q_a_norm"]), eps)
    q = mm(cq, w["q_b"]).reshape(S, H, dn + dr)
    kva = mm(x, w["kv_a"])
    ckv = _rms(kva[:, :dc], f32(w["kv_a_norm"]), eps)
    k_r = _rope(kva[:, dc:], theta)
    q_rope = _rope(q[..., dn:], theta)
    kv = mm(ckv, w["kv_b"]).reshape(S, H, dn + dv)
    qk = jnp.concatenate([q[..., :dn], q_rope], -1)        # [S, H, dn+dr]
    kk = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (S, H, dr))], -1)
    v = kv[..., dn:]
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(qk, r0, block, 0)
        s = jnp.einsum("qhd,shd->hqs", qb, kk,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        live = jnp.arange(S)[None, :] <= (r0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(
            jnp.where(live[None], s * (dn + dr) ** -0.5, -jnp.inf), -1)
        return jnp.einsum("hqs,shd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, S, block)).reshape(S, H * dv)
    return h + mm(o, w["proj"])


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _dense_ffn(h, ln2, wg, wu, wd, eps, lower):
    mm = _mm(lower)
    x = _rms(h, ln2.astype(jnp.float32), eps)
    return h + mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


@functools.partial(jax.jit, static_argnames=("eps", "k", "norm", "scale",
                                             "lower"))
def _route(h, ln2, w_router, bias, eps, k, norm, scale, lower):
    """(x, dense weights [S, E], each row's selection margin)."""
    x = _rms(h, ln2.astype(jnp.float32), eps)
    sc = jax.nn.sigmoid(_mm(lower)(x, w_router))
    pick = sc + bias.astype(jnp.float32)
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    chosen = pick >= ranked[:, k - 1:k]
    w = jnp.where(chosen, sc, 0.0)
    if norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return x, w * scale, ranked[:, k - 1] - ranked[:, k]


@functools.partial(jax.jit, static_argnames=("lower",), donate_argnums=(0,))
def _add_expert(y, x, we, wg, wu, wd, lower):
    """y + we * SwiGLU(x): one expert over every row, a dense mask."""
    mm = _mm(lower)
    return y + we[:, None] * mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(h_rows, ln_f, head_cols, eps, lower):
    return _mm(lower)(_rms(h_rows, ln_f.astype(jnp.float32), eps), head_cols)


def forward(params, config, tokens, rows, name="glm", lower=False):
    """(logits [len(rows), V] as numpy float32, margin [S]) for the
    sequence ``tokens`` [S]: the next-token logits after each position
    in ``rows``, and every position's smallest selection margin over
    the routed layers (the last chosen against the first not chosen of
    ``s + b``)."""
    c = config
    H = c["num_attention_heads"]
    sizes = (H, c["qk_nope_head_dim"], c["qk_rope_head_dim"],
             c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"],
             float(c["rope_theta"]))
    eps, E = c["rms_norm_eps"], c["n_routed_experts"]
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(tokens.shape[0], np.inf, np.float32)
    for i in range(c["num_hidden_layers"]):
        us = f"{name}_h{i}"
        h = _attention(h, {
            "ln1": params[f"{us}_ln1_scale"],
            "q_a": params[f"{us}_attn_q_a_weight"],
            "q_a_norm": params[f"{us}_attn_q_a_norm_scale"],
            "q_b": params[f"{us}_attn_q_b_weight"],
            "kv_a": params[f"{us}_attn_kv_a_weight"],
            "kv_a_norm": params[f"{us}_attn_kv_a_norm_scale"],
            "kv_b": params[f"{us}_attn_kv_b_weight"],
            "proj": params[f"{us}_attn_proj_weight"]}, sizes, lower)
        if i < c["first_k_dense_replace"]:
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
        if c["n_shared_experts"]:
            y = _add_expert(y, x, jnp.ones((x.shape[0],), jnp.float32),
                            params[f"{us}_moe_shared_gate_weight"],
                            params[f"{us}_moe_shared_up_weight"],
                            params[f"{us}_moe_shared_down_weight"], lower)
        h = h + y
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    head = (params[f"{name}_wte_table"].T if c["tie_word_embeddings"]
            else params[f"{name}_lm_head_weight"])
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    out = [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                            head[:, v0:v0 + step], eps, lower))
           for v0 in range(0, V, step)]
    return np.concatenate(out, axis=1), margin
