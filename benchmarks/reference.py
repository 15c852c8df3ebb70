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

``operands`` computes the same model in a lower precision, for the
controls and for the measure of what a stated precision costs: every
parameter is read through that type and both operands of every product
(the six matmuls, the scores, the weighted values, the head) are rounded
to it; accumulation, the residual stream, LayerNorm, softmax and the loss
stay float32.  ``"bfloat16"`` is what "bf16 compute over f32 masters"
states, ``"float8_e4m3fn"`` (with a scale a tensor) the step below it.
``mask_shift`` moves the causal mask by that many positions (a control:
1 shows a row the token after it); the other controls need no code here,
they are made on the weights or the labels that are handed in.

``gradient_norms`` is the training cell's reference for the first step:
the loss and, leaf by leaf, the norm of its gradient, rows in blocks and
blocks recomputed in the backward pass so that 355 M parameters in
float32 fit beside their gradient.
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


def _rounded(x, operands):
    """``x`` (float32) with the values of a type of ``operands``'s
    exponent and mantissa bits (``reduce_precision``: a convert there and
    back is what the TPU's compiler is free to drop).  An 8-bit type gets
    what a careful float8 path gives it, one scale a tensor that puts its
    largest magnitude at the type's largest value; without it a weight
    under 2**-6 would flush to zero.  A gradient passes as if nothing had
    been rounded (a float8 cotangent would flush to zero), so the
    backward products see the rounded operands the forward saved and
    float32 cotangents."""
    if operands is None:
        return x
    info = jnp.finfo(operands)
    top = min(float(info.max), (2.0 - 2.0 ** -info.nmant)
              * 2.0 ** (2 ** (info.nexp - 1) - 1))
    scale = 1.0 if info.bits > 8 else \
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    low = scale * jax.lax.reduce_precision(
        jnp.clip(x / scale, -top, top), info.nexp, info.nmant)
    return x + jax.lax.stop_gradient(low - x)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit,
                   static_argnames=("heads", "eps", "operands", "mask_shift"))
def _block(h, w, heads, eps, operands=None, mask_shift=0):
    """One pre-LN block over h [B, S, d]; ``w`` is the layer's weights in
    whatever type they are stored, upcast here."""
    def r(x):
        return _rounded(x, operands)
    w = {k: r(v.astype(jnp.float32)) for k, v in w.items()}
    B, S, d = h.shape
    dh = d // heads
    x = r(_ln(h, w["ln1_scale"], w["ln1_bias"], eps))
    q, k, v = (r(x @ w[f"attn_{n}_weight"] + w[f"attn_{n}_bias"])
               .reshape(B, S, heads, dh) for n in "qkv")
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool), mask_shift), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", r(jax.nn.softmax(s, -1)), v)
    h = h + r(o.reshape(B, S, d)) @ w["attn_proj_weight"] \
        + w["attn_proj_bias"]
    x = r(_ln(h, w["ln2_scale"], w["ln2_bias"], eps))
    f = r(_gelu_tanh(x @ w["ffn_wi_weight"] + w["ffn_wi_bias"]))
    return h + f @ w["ffn_wo_weight"] + w["ffn_wo_bias"]


@functools.partial(jax.jit, static_argnames=("operands",))
def _embed(tokens, wte, wpe, operands=None):
    S = tokens.shape[1]
    return (_rounded(wte.astype(jnp.float32), operands)[tokens]
            + _rounded(wpe.astype(jnp.float32), operands)[:S])


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def _head(h, scale, bias, wte, head_bias, eps, operands=None):
    def r(x):
        return _rounded(x.astype(jnp.float32), operands)
    x = r(_ln(h, r(scale), r(bias), eps))
    return x @ r(wte).T + r(head_bias)


def hidden(params, cfg, tokens, name="gpt", operands=None, mask_shift=0):
    """Final hidden state [B, S, d] (before ln_f) of ``tokens`` [B, S]."""
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    with jax.default_matmul_precision("highest"):
        h = _embed(jnp.asarray(tokens, jnp.int32),
                   params[f"{name}_wte_table"], params[f"{name}_wpe"],
                   operands=operands)
        for i in range(cfg["n_layer"]):
            w = {k: params[f"{name}_h{i}_{k}"] for k in _LAYER_KEYS}
            h = _block(h, w, heads=cfg["n_head"], eps=eps, operands=operands,
                       mask_shift=mask_shift)
    return h


def _row_logits(params, cfg, h, name, operands=None):
    """Next-token logits [S, V] of one sequence's final hidden state."""
    zero = jnp.zeros((cfg["vocab_size"],), jnp.float32)
    with jax.default_matmul_precision("highest"):
        return _head(h, params[f"{name}_ln_f_scale"],
                     params[f"{name}_ln_f_bias"], params[f"{name}_wte_table"],
                     params.get(f"{name}_head_bias", zero),
                     eps=float(cfg.get("layer_norm_epsilon", 1e-5)),
                     operands=operands)


def logits(params, cfg, tokens, name="gpt"):
    """Next-token logits [S, V] of ONE sequence ``tokens`` [S]."""
    h = hidden(params, cfg, jnp.asarray(tokens, jnp.int32)[None], name)
    return _row_logits(params, cfg, h[0], name)


@jax.jit
def _xent(lg, labels):
    return (jax.nn.logsumexp(lg, -1)
            - lg[jnp.arange(lg.shape[0]), labels]).mean()


def row_losses(params, cfg, tokens, labels, name="gpt", operands=None,
               mask_shift=0):
    """Mean next-token cross-entropy of each sequence of ``tokens``
    [B, S] against ``labels`` [B, S] (already aligned: row j's target is
    labels[:, j]), the head taken one sequence at a time to bound the
    logits."""
    tokens = jnp.asarray(tokens, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    h = hidden(params, cfg, tokens, name, operands, mask_shift)
    rows = [_xent(_row_logits(params, cfg, h[b], name, operands), labels[b])
            for b in range(tokens.shape[0])]
    return [float(r) for r in rows]


def mean_loss(params, cfg, tokens, labels, name="gpt", operands=None,
              mask_shift=0):
    """The mean of ``row_losses``: every sequence is as long."""
    rows = row_losses(params, cfg, tokens, labels, name, operands,
                      mask_shift)
    return sum(rows) / len(rows)


@functools.partial(jax.jit, static_argnames=(
    "name", "layers", "heads", "eps", "operands", "mask_shift"))
def _loss_and_gradient(params, tokens, labels, name, layers, heads, eps,
                       operands, mask_shift):
    """Sum of the rows' mean cross-entropies, and its gradient."""
    def loss(p):
        h = _embed(tokens, p[f"{name}_wte_table"], p[f"{name}_wpe"],
                   operands=operands)
        stacked = {k: jnp.stack([p[f"{name}_h{i}_{k}"]
                                 for i in range(layers)])
                   for k in _LAYER_KEYS}

        @jax.checkpoint
        def block(h, w):
            return _block(h, w, heads=heads, eps=eps, operands=operands,
                          mask_shift=mask_shift)

        h, _ = jax.lax.scan(lambda h, w: (block(h, w), None), h, stacked)

        @jax.checkpoint
        def row(hb, yb):
            lg = _head(hb, p[f"{name}_ln_f_scale"], p[f"{name}_ln_f_bias"],
                       p[f"{name}_wte_table"], p[f"{name}_head_bias"],
                       eps=eps, operands=operands)
            return _xent(lg, yb)

        return jax.lax.map(lambda a: row(*a), (h, labels)).sum()
    return jax.value_and_grad(loss)(params)


@jax.jit
def _leaf_norms(grads, scale):
    return {k: jnp.sqrt(jnp.sum(jnp.square(g * scale)))
            for k, g in grads.items()}


def gradient_norms(params, cfg, tokens, labels, name="gpt", operands=None,
                   mask_shift=0, rows_per_block=2):
    """(mean loss, {leaf: norm of d loss / d leaf}) of ``tokens`` [B, S]
    against ``labels`` [B, S], every parameter in float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    params.setdefault(f"{name}_head_bias",
                      jnp.zeros((cfg["vocab_size"],), jnp.float32))
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for at in range(0, tokens.shape[0], rows_per_block):
            part, g = _loss_and_gradient(
                params, tokens[at:at + rows_per_block],
                labels[at:at + rows_per_block], name=name,
                layers=cfg["n_layer"], heads=cfg["n_head"],
                eps=float(cfg.get("layer_norm_epsilon", 1e-5)),
                operands=operands, mask_shift=mask_shift)
            total += float(part)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    rows = tokens.shape[0]
    norms = _leaf_norms(grads, 1.0 / rows)
    return total / rows, {k: float(v) for k, v in norms.items()}
