"""Loss op factories.

Reference: gpu_ops/SoftmaxCrossEntropy.py, SoftmaxCrossEntropySparse.py,
CrossEntropy.py, CrossEntropySparse.py, BinaryCrossEntropy.py, NllLoss.py
(kernels src/ops/SoftmaxCrossEntropy.cu etc.).  Reference ops return the
per-example loss vector (reduction happens via reduce_mean in user code),
and we preserve that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ops_math import _simple
from .ops_misc import SharedBackwardOp


def softmaxcrossentropy_op(a, labels, ctx=None):
    """One-hot labels; returns per-example loss (N,)."""
    def f(x, y):
        lse = jax.nn.log_softmax(x, axis=-1)
        return -jnp.sum(y * lse, axis=-1)
    return _simple("SoftmaxCrossEntropy", f, a, labels,
                   grad_rule=lambda n, g: _sce_grad(n, g), ctx=ctx)


def _sce_grad(node, g):
    x, y = node.inputs

    def f(gr, xx, yy):
        p = jax.nn.softmax(xx, axis=-1)
        return gr[..., None] * (p - yy)
    return [_simple("SoftmaxCrossEntropyGrad", f, g, x, y), None]


def softmaxcrossentropy_sparse_op(a, labels, ignored_index=-1, ctx=None):
    """Integer labels; entries equal to ignored_index contribute 0."""
    def f(x, y):
        y = y.astype(jnp.int32)
        lse = jax.nn.log_softmax(x, axis=-1)
        safe = jnp.where(y == ignored_index, 0, y)
        ll = jnp.take_along_axis(lse, safe[..., None], axis=-1)[..., 0]
        return jnp.where(y == ignored_index, 0.0, -ll)
    return _simple("SoftmaxCrossEntropySparse", f, a, labels,
                   grad_rule=lambda n, g: _sce_sparse_grad(n, g, ignored_index),
                   ctx=ctx)


def _sce_sparse_grad(node, g, ignored_index):
    x, y = node.inputs

    def f(gr, xx, yy):
        yy = yy.astype(jnp.int32)
        p = jax.nn.softmax(xx, axis=-1)
        onehot = jax.nn.one_hot(jnp.where(yy == ignored_index, 0, yy),
                                xx.shape[-1], dtype=xx.dtype)
        grad = gr[..., None] * (p - onehot)
        return jnp.where((yy == ignored_index)[..., None], 0.0, grad)
    return [_simple("SoftmaxCrossEntropySparseGrad", f, g, x, y), None]


def crossentropy_op(probs, labels, ctx=None):
    """-sum(y * log p) given probabilities (reference CrossEntropy.py)."""
    def f(p, y):
        return -jnp.sum(y * jnp.log(jnp.maximum(p, 1e-12)), axis=-1)
    return _simple("CrossEntropy", f, probs, labels, ctx=ctx)


def crossentropy_sparse_op(probs, labels, ignored_index=-1, ctx=None):
    def f(p, y):
        y = y.astype(jnp.int32)
        safe = jnp.where(y == ignored_index, 0, y)
        pl = jnp.take_along_axis(p, safe[..., None], axis=-1)[..., 0]
        loss = -jnp.log(jnp.maximum(pl, 1e-12))
        return jnp.where(y == ignored_index, 0.0, loss)
    return _simple("CrossEntropySparse", f, probs, labels, ctx=ctx)


def binarycrossentropy_op(preds, labels, ctx=None):
    def f(p, y):
        p = jnp.clip(p, 1e-12, 1 - 1e-12)
        return -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    return _simple("BinaryCrossEntropy", f, preds, labels, ctx=ctx)


def binarycrossentropywithlogits_op(logits, labels, ctx=None):
    def f(z, y):
        return jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return _simple("BCEWithLogits", f, logits, labels, ctx=ctx)


def nll_loss_op(log_probs, labels, ctx=None):
    def f(lp, y):
        y = y.astype(jnp.int32)
        return -jnp.take_along_axis(lp, y[..., None], axis=-1)[..., 0]
    return _simple("NllLoss", f, log_probs, labels, ctx=ctx)


def mseloss_op(preds, labels, ctx=None):
    return _simple("MSELoss", lambda p, y: jnp.mean((p - y) ** 2), preds, labels,
                   ctx=ctx)


# --------------------------------------------------------------------- #
# fused LM-head + softmax-xent (chunked over rows)
# --------------------------------------------------------------------- #

def _xent_chunk_shapes(N, n_chunks):
    C = -(-N // n_chunks)
    return C, C * n_chunks - N


def _chunked_xent_fwd(h, W, b, y, ignored_index, n_chunks):
    """Per-row loss of ``softmax_xent(h @ W.T + b, y)`` without ever
    materializing the full [N, V] logits: a scan over row chunks keeps
    only one [C, V] block live.  The block stays in the compute dtype
    (bf16 under mixed precision, matching the unfused path's numerics);
    the logsumexp/softmax reductions run in fp32 via casts that fuse
    into the reductions."""
    N, H = h.shape
    C, pad = _xent_chunk_shapes(N, n_chunks)
    y = y.astype(jnp.int32)
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad), constant_values=ignored_index)
    hs = h.reshape(n_chunks, C, H)
    ys = y.reshape(n_chunks, C)

    def body(_, hy):
        hc, yc = hy
        # logits stay in the compute dtype (matching the unfused path's
        # numerics under bf16 mixed precision); the f32 upcast fuses
        # into the reductions so no f32 [C, V] buffer materializes
        logits = jnp.matmul(hc, W.T,
                            preferred_element_type=jnp.float32) \
            .astype(hc.dtype) + b
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1)
        safe = jnp.where(yc == ignored_index, 0, yc)
        ll = jnp.take_along_axis(logits, safe[:, None],
                                 axis=-1)[:, 0].astype(jnp.float32)
        return None, jnp.where(yc == ignored_index, 0.0, lse - ll)

    _, losses = jax.lax.scan(body, None, (hs, ys))
    return losses.reshape(n_chunks * C)[:N]


def _chunked_xent_bwd(gr, h, W, b, y, ignored_index, n_chunks):
    """(dh, dW, db) for _chunked_xent_fwd, recomputing each logits chunk
    instead of reading a stored [N, V] gradient tensor.  dW/db
    accumulate in fp32 scan carries."""
    N, H = h.shape
    V = W.shape[0]
    C, pad = _xent_chunk_shapes(N, n_chunks)
    y = y.astype(jnp.int32)
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad), constant_values=ignored_index)
        gr = jnp.pad(gr, (0, pad))
    hs = h.reshape(n_chunks, C, H)
    ys = y.reshape(n_chunks, C)
    grs = gr.reshape(n_chunks, C)

    def body(carry, hyg):
        dW, db = carry
        hc, yc, gc = hyg
        logits = jnp.matmul(hc, W.T,
                            preferred_element_type=jnp.float32) \
            .astype(hc.dtype) + b
        # softmax with f32 reductions but a compute-dtype [C, V] buffer
        # (the f32 casts fuse into the reductions/matmul epilogues)
        m = jnp.max(logits.astype(jnp.float32), axis=-1, keepdims=True)
        e = jnp.exp(logits.astype(jnp.float32) - m)
        p = e / e.sum(axis=-1, keepdims=True)
        safe = jnp.where(yc == ignored_index, 0, yc)
        onehot = jax.nn.one_hot(safe, V, dtype=p.dtype)
        live = (yc != ignored_index).astype(p.dtype) * gc.astype(p.dtype)
        dlog_mm = ((p - onehot) * live[:, None]).astype(W.dtype)
        dh_c = jnp.matmul(dlog_mm, W,
                          preferred_element_type=jnp.float32)
        dW = dW + jnp.matmul(dlog_mm.T, hc,
                             preferred_element_type=jnp.float32)
        db = db + dlog_mm.astype(jnp.float32).sum(axis=0)
        return (dW, db), dh_c.astype(h.dtype)

    (dW, db), dhs = jax.lax.scan(
        body, (jnp.zeros((V, H), jnp.float32),
               jnp.zeros((V,), jnp.float32)), (hs, ys, grs))
    dh = dhs.reshape(n_chunks * C, H)[:N]
    return dh, dW.astype(W.dtype), db.astype(b.dtype)


def tied_lm_head_xent_op(h, table, bias, labels, ignored_index=-1,
                         n_chunks=8, ctx=None):
    """Fused LM head + sparse softmax cross-entropy, chunked over rows.

    Equivalent to ``softmaxcrossentropy_sparse_op(linear_op(h, table,
    bias, trans_B=True), labels)`` but the [N, V] logits (and their
    gradient) never hit HBM in full — at BERT scale that tensor chain is
    gigabytes per step, pure memory-bandwidth cost the reference pays
    with a dedicated CUDA kernel pair instead
    (src/ops/SoftmaxCrossEntropySparse.cu).  The three gradient nodes
    share ONE ``_chunked_xent_bwd`` call a trace (one scan, three results:
    ``ops_misc.SharedBackwardOp``), traced under whichever of them the
    executor reaches first.  Three calls each keeping one result were NOT
    merged by XLA's CSE: the compiler prunes each scan to the carry its
    node keeps and is left with three different loops, each rebuilding the
    same ``[chunk, V]`` logits and softmax (ledger, PR 32: 11.1 % of the
    GPT-2 medium step's busy time under ``TiedXentGradH/W/B``).
    """
    def f(hh, W, b, yy):
        return _chunked_xent_fwd(hh, W, b, yy, ignored_index, n_chunks)

    def bwd(gv, hv, Wv, bv, yv):
        return _chunked_xent_bwd(gv, hv, Wv, bv, yv, ignored_index, n_chunks)

    def grad_rule(n, g):
        hh, W, b, yy = n.inputs
        return [SharedBackwardOp(name, bwd, idx, g, hh, W, b, yy)
                for idx, name in enumerate(
                    ("TiedXentGradH", "TiedXentGradW", "TiedXentGradB"))] \
            + [None]

    return _simple("TiedXentChunked", f, h, table, bias, labels,
                   grad_rule=grad_rule, ctx=ctx)
