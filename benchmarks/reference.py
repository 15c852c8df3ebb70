"""GPT-2 in plain float32 ``jax.numpy``: the benchmark's own reference.

The published forward pass (pre-LN blocks, learned positions, GELU-tanh,
tied head), no kernels, no cache, no batching tricks.  Weights are the
system's own dict (``<name>_h<i>_attn_q_weight`` ...), upcast to float32
one layer at a time so that a 1.5 B-parameter model never needs a second
full copy on the device.  On a TPU a float32 product runs in bf16 passes
unless the precision is raised, so every call here raises it.

Departures from the published model: none in the mathematics; q, k and v
are three matrices (as the system stores them), which is the fused
``c_attn`` split by columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("ln1_scale", "ln1_bias", "attn_q_weight", "attn_q_bias",
               "attn_k_weight", "attn_k_bias", "attn_v_weight", "attn_v_bias",
               "attn_proj_weight", "attn_proj_bias", "ln2_scale", "ln2_bias",
               "ffn_wi_weight", "ffn_wi_bias", "ffn_wo_weight", "ffn_wo_bias")


def _ln(x, scale, bias, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _block(h, w, heads, eps):
    """One pre-LN block over h [B, S, d]; ``w`` is the layer's weights in
    whatever type they are stored, upcast here."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, S, d = h.shape
    dh = d // heads
    x = _ln(h, w["ln1_scale"], w["ln1_bias"], eps)
    q, k, v = ((x @ w[f"attn_{n}_weight"] + w[f"attn_{n}_bias"])
               .reshape(B, S, heads, dh) for n in "qkv")
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(B, S, d) @ w["attn_proj_weight"] + w["attn_proj_bias"]
    x = _ln(h, w["ln2_scale"], w["ln2_bias"], eps)
    f = _gelu_tanh(x @ w["ffn_wi_weight"] + w["ffn_wi_bias"])
    return h + f @ w["ffn_wo_weight"] + w["ffn_wo_bias"]


@jax.jit
def _embed(tokens, wte, wpe):
    S = tokens.shape[1]
    return wte.astype(jnp.float32)[tokens] + wpe.astype(jnp.float32)[:S]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, scale, bias, wte, head_bias, eps):
    x = _ln(h, scale.astype(jnp.float32), bias.astype(jnp.float32), eps)
    return x @ wte.astype(jnp.float32).T + head_bias.astype(jnp.float32)


def hidden(params, cfg, tokens, name="gpt"):
    """Final hidden state [B, S, d] (before ln_f) of ``tokens`` [B, S]."""
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    with jax.default_matmul_precision("highest"):
        h = _embed(jnp.asarray(tokens, jnp.int32),
                   params[f"{name}_wte_table"], params[f"{name}_wpe"])
        for i in range(cfg["n_layer"]):
            w = {k: params[f"{name}_h{i}_{k}"] for k in _LAYER_KEYS}
            h = _block(h, w, heads=cfg["n_head"], eps=eps)
    return h


def _row_logits(params, cfg, h, name):
    """Next-token logits [S, V] of one sequence's final hidden state."""
    zero = jnp.zeros((cfg["vocab_size"],), jnp.float32)
    with jax.default_matmul_precision("highest"):
        return _head(h, params[f"{name}_ln_f_scale"],
                     params[f"{name}_ln_f_bias"], params[f"{name}_wte_table"],
                     params.get(f"{name}_head_bias", zero),
                     eps=float(cfg.get("layer_norm_epsilon", 1e-5)))


def logits(params, cfg, tokens, name="gpt"):
    """Next-token logits [S, V] of ONE sequence ``tokens`` [S]."""
    h = hidden(params, cfg, jnp.asarray(tokens, jnp.int32)[None], name)
    return _row_logits(params, cfg, h[0], name)


def mean_loss(params, cfg, tokens, labels, name="gpt"):
    """Mean next-token cross-entropy of ``tokens`` [B, S] against
    ``labels`` [B, S] (already aligned: row j's target is labels[:, j]),
    the head taken one sequence at a time to bound the logits."""
    tokens = jnp.asarray(tokens, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    h = hidden(params, cfg, tokens, name)
    total = 0.0
    for b in range(tokens.shape[0]):
        lg = _row_logits(params, cfg, h[b], name)
        lse = jax.nn.logsumexp(lg, -1)
        total += float((lse - lg[jnp.arange(lg.shape[0]), labels[b]]).mean())
    return total / tokens.shape[0]
