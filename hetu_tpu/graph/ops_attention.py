"""Attention graph ops backed by the Pallas flash kernel.

No reference counterpart (the reference builds attention from
batch_matmul/softmax inline, examples/nlp/bert/hetu_bert.py); this is the
fused fast path.  Gradient flows through the kernel's custom_vjp via the
generic VJPOp fallback: the executor computes the node under ``jax.vjp``
and the q, k and v gradient nodes share one call of the saved pullback, so
a layer is one ``flash_fwd``, one ``flash_bwd_dkv`` and one ``flash_bwd_dq``
by construction (``ops_misc.Backward``).  XLA's CSE merged the three
gradient nodes' forwards with one another but not with the node's own: the
custom_vjp's forward also returns ``lse``, and a Mosaic call with another
output set is another call (ledger, PR 32: ``flash_fwd`` 0.2500 s beside
``jvp_flash_fwd`` 0.2501 s of a 3 s trace).
"""

from __future__ import annotations

from .node import Op, SimpleOp, vjp_gradient


class CausalMaskOp(Op):
    """Additive causal mask ``(1, 1, S, S)`` built in-trace from iota
    comparisons (as the flash kernel does) — never materialized as a stored
    Variable, so it costs no checkpoint bytes and is fused by XLA into the
    consuming add.  Emits the trace's mixed-precision policy dtype, exactly
    as a stored-Variable mask would have entered via the executor's input
    cast — otherwise a f32 mask would silently promote the whole unfused
    attention tail under a bf16 policy."""

    def __init__(self, seq_len, neg, ctx=None):
        super().__init__(name="CausalMask", ctx=ctx)
        self.seq_len = seq_len
        self.neg = neg

    def compute(self, input_vals, tc):
        import jax
        import jax.numpy as jnp
        S = self.seq_len
        dtype = (getattr(tc.config, "mixed_precision", None)
                 if tc.config is not None else None) or jnp.float32
        i = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        return jnp.where(j <= i, 0.0, self.neg).astype(dtype)[None, None]

    def gradient(self, output_grad):
        return []


def causal_mask_op(seq_len, neg=None, ctx=None):
    if neg is None:
        from ..kernels.flash_attention import NEG_INF
        neg = NEG_INF
    return CausalMaskOp(seq_len, neg, ctx=ctx)


class FlashAttentionOp(Op):
    """The Pallas flash kernel as a graph node.  Under a mesh the kernel
    runs per shard inside ``shard_map`` — batch over the data axis, heads
    over 'tp': GSPMD cannot partition a Mosaic kernel, and on the chip a
    sharded step with a bare ``pallas_call`` in it does not even lower
    ("Mosaic kernels cannot be automatically partitioned")."""

    def __init__(self, q, k, v, kv_lens, causal, blocks, ctx=None):
        inputs = (q, k, v) + ((kv_lens,) if kv_lens is not None else ())
        super().__init__(*inputs, name="FlashAttention", ctx=ctx)
        self.causal = causal
        self.blocks = blocks

    def _specs(self, mesh, shape):
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import batch_axis
        B, _, H, _ = shape
        batch = batch_axis(mesh, B)
        heads = "tp" if "tp" in mesh.axis_names \
            and H % mesh.shape["tp"] == 0 else None
        return P(batch, None, heads, None), P(batch)

    def compute(self, input_vals, tc):
        import jax
        from ..kernels.flash_attention import flash_attention

        def fn(q, k, v, lens=None):
            return flash_attention(q, k, v, causal=self.causal,
                                   kv_lens=lens, **self.blocks)

        mesh = tc.mesh
        # inside a manual trace (pipeline body) the values already are
        # per-shard; a one-device mesh has nothing to partition
        if mesh is None or mesh.size == 1 or tc.axis_env:
            return fn(*input_vals)
        qkv, lens = self._specs(mesh, input_vals[0].shape)
        in_specs = (qkv, qkv, qkv) + ((lens,) * (len(input_vals) - 3))
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=qkv, check_vma=False)(*input_vals)

    def gradient(self, output_grad):
        return vjp_gradient(self, output_grad)


def flash_attention_op(q, k, v, causal=False, kv_lens=None, block_q=None,
                       block_k=None, ctx=None):
    """Fused attention on [B, S, H, D] q/k/v nodes -> [B, S, H, D].

    ``kv_lens``: optional [B] int node — keys/values at positions >=
    kv_lens[b] are masked (padding mask).  block_q/block_k default to
    the kernel's tuned values (single source of truth in
    kernels/flash_attention.py)."""
    blocks = {}
    if block_q is not None:
        blocks["block_q"] = block_q
    if block_k is not None:
        blocks["block_k"] = block_k
    return FlashAttentionOp(q, k, v, kv_lens, causal, blocks, ctx=ctx)


def ring_attention_op(q, k, v, mesh, axis="cp", causal=False, impl=None,
                      ctx=None):
    """Ring attention over a sequence-sharded 'cp' mesh axis (long-context
    path, SURVEY.md §5.7 — new capability vs the reference).  ``impl``:
    'flash' (fused Pallas block kernel — the TPU default), 'exact', or
    None = auto by backend."""
    from ..parallel.context_parallel import ring_attention

    def fn(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, axis=axis, causal=causal,
                              impl=impl)

    return SimpleOp(fn, q, k, v, name="RingAttention", ctx=ctx)
