"""Communication ops: the reference comm-op surface, TPU-native semantics.

Reference: gpu_ops/AllReduceCommunicate.py, AllGatherCommunicate.py,
ReduceScatterCommunicate.py, BroadcastCommunicate.py, ReduceCommunicate.py,
AllToAll.py, HAllToAll.py, PipelineSend.py/PipelineReceive.py,
ParameterServerCommunicate.py, DataTransfer.py.

TPU-native semantics (SURVEY.md §2.2 "TPU equivalent"): under pjit with
sharding annotations, XLA inserts the collectives — so inside a plain jit
trace these ops are *annotation markers* (identity + sharding constraint).
Inside a shard_map trace (tc.axis_env non-empty) they execute the real
``jax.lax`` collective over the named mesh axis.  This dual behavior means
the same user graph runs under either execution style.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .node import Op, TraceContext


class CollectiveOp(Op):
    """Base: collective over a mesh axis; identity annotation under pjit."""

    axis_default = "dp"

    def __init__(self, node, axis=None, name=None, ctx=None):
        super().__init__(node, name=name, ctx=ctx)
        self.axis = axis or self.axis_default

    def collective(self, x, axis):
        raise NotImplementedError

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        if tc.has_axis(self.axis):
            return self.collective(x, self.axis)
        return x  # pjit mode: XLA inserts the collective from shardings

    def gradient(self, output_grad):
        # gradient of psum is psum (identity in pjit mode) — reference
        # AllReduceCommunicate has no gradient (applied to grads already)
        return [output_grad]


class AllReduceCommunicateOp(CollectiveOp):
    def collective(self, x, axis):
        return jax.lax.psum(x, axis)


class GroupAllReduceCommunicateOp(AllReduceCommunicateOp):
    pass


class AllGatherCommunicateOp(CollectiveOp):
    axis_default = "tp"

    def collective(self, x, axis):
        return jax.lax.all_gather(x, axis, tiled=True)


class ReduceScatterCommunicateOp(CollectiveOp):
    axis_default = "tp"

    def collective(self, x, axis):
        return jax.lax.psum_scatter(x, axis, tiled=True)


class BroadcastCommunicateOp(CollectiveOp):
    def __init__(self, node, root=0, axis=None, ctx=None):
        super().__init__(node, axis=axis, ctx=ctx)
        self.root = root

    def collective(self, x, axis):
        idx = jax.lax.axis_index(axis)
        n = jax.lax.axis_size(axis)
        src = jnp.where(idx == self.root, x, jnp.zeros_like(x))
        return jax.lax.psum(src, axis)


class ReduceCommunicateOp(CollectiveOp):
    def __init__(self, node, root=0, axis=None, ctx=None):
        super().__init__(node, axis=axis, ctx=ctx)
        self.root = root

    def collective(self, x, axis):
        return jax.lax.psum(x, axis)  # all ranks get it; root semantics free


def allreduceCommunicate_op(node, comm=None, axis="dp", ctx=None):
    return AllReduceCommunicateOp(node, axis=axis, ctx=ctx)


def allreduceCommunicatep2p_op(node, comm=None, axis="dp", ctx=None):
    return AllReduceCommunicateOp(node, axis=axis, ctx=ctx)


def groupallreduceCommunicate_op(node, comm=None, axis="dp", ctx=None):
    return GroupAllReduceCommunicateOp(node, axis=axis, ctx=ctx)


def allgatherCommunicate_op(node, comm=None, axis="tp", ctx=None):
    return AllGatherCommunicateOp(node, axis=axis, ctx=ctx)


def reducescatterCommunicate_op(node, comm=None, axis="tp", ctx=None):
    return ReduceScatterCommunicateOp(node, axis=axis, ctx=ctx)


def broadcastCommunicate_op(node, comm=None, root=0, axis="dp", ctx=None):
    return BroadcastCommunicateOp(node, root=root, axis=axis, ctx=ctx)


def reduceCommunicate_op(node, comm=None, root=0, axis="dp", ctx=None):
    return ReduceCommunicateOp(node, root=root, axis=axis, ctx=ctx)


# --------------------------------------------------------------------- #
# quantized collective pair (HETU_COMM_QUANT=int8; EQuARX lineage)
# --------------------------------------------------------------------- #
#
# A quantized gradient aggregation is THREE nodes, so the static
# checkers can see (and reject a broken) pairing before compile:
#
#     QuantizeCommOp  ->  QuantAllReduceCommunicateOp  ->  DequantizeCommOp
#     f32 -> (int8,scales)    all_gather the pair          decode + sum
#
# int8 cannot be psum'd directly (overflow, and the scales would sum
# wrong), so the collective is an all_gather of the (payload, scales)
# pytree — the interconnect carries int8 bytes — and the dequantize side
# decodes each participant's contribution and reduces in f32.  Under
# shard_map execution (tc.has_axis) this is the real quantized
# collective; under pjit, where XLA owns collective insertion and the
# plain CollectiveOp degrades to an annotation, the pair degrades to a
# shape-preserving fake-quant of the gradient (EQuARX does the int8
# rewrite inside XLA itself, which is exactly the part we cannot reach
# from op level).  ``analysis/shard_check.check_quantized_collectives``
# rejects any quantize without its paired dequantize across the
# collective; emit the trio via :func:`quantized_allreduce_op`.

class QuantizeCommOp(Op):
    """Encode a float tensor to (int8 payload, f32 scales) for a
    quantized collective.  Output is a 2-tuple pytree; its ONLY legal
    consumer is a quantized collective (shard_check enforces this)."""

    def __init__(self, node, axis=None, chunk=None, ctx=None):
        super().__init__(node, name="QuantizeComm", ctx=ctx)
        self.axis = axis or "dp"
        from .. import quant as _quant
        self.chunk = int(chunk or _quant.DEFAULT_CHUNK)

    def compute(self, input_vals, tc: TraceContext):
        from .. import quant as _quant
        (x,) = input_vals
        flat = x.astype(jnp.float32).reshape(-1)
        pad = (-flat.shape[0]) % self.chunk
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return _quant.quantize_jax(flat, self.chunk)

    def gradient(self, output_grad):
        return [output_grad]


class QuantAllReduceCommunicateOp(CollectiveOp):
    """The collective leg of the pair: all_gather the (int8, scales)
    pytree over ``axis`` so the wire moves quantized bytes.  Always
    emits a leading participant dim (size 1 under pjit, where the
    collective is an annotation) so the dequantize side can reduce
    uniformly."""

    axis_default = "dp"

    def compute(self, input_vals, tc: TraceContext):
        (pair,) = input_vals
        if tc.has_axis(self.axis):
            return jax.lax.all_gather(pair, self.axis)
        return jax.tree_util.tree_map(lambda a: a[None], pair)

    def gradient(self, output_grad):
        return [output_grad]


class DequantizeCommOp(Op):
    """Decode the gathered (int8, scales) pair and reduce: each
    participant's contribution dequantizes to f32 and the sum is the
    quantized AllReduce's result, reshaped back to the original
    gradient shape."""

    def __init__(self, node, shape, axis=None, chunk=None, ctx=None):
        super().__init__(node, name="DequantizeComm", ctx=ctx)
        self.axis = axis or "dp"
        self.shape = tuple(int(d) for d in shape)
        from .. import quant as _quant
        self.chunk = int(chunk or _quant.DEFAULT_CHUNK)

    def compute(self, input_vals, tc: TraceContext):
        from .. import quant as _quant
        (pair,) = input_vals
        q, scales = pair                       # [n, padded], [n, chunks]
        out = _quant.dequantize_jax(
            q.reshape(-1, q.shape[-1]), scales.reshape(-1, scales.shape[-1]),
            self.chunk).sum(axis=0)
        n = 1
        for d in self.shape:
            n *= d
        return out[:n].reshape(self.shape)

    def infer_shape(self, input_shapes, input_dtypes=None):
        return self.shape

    def gradient(self, output_grad):
        return [output_grad]


def quantized_allreduce_op(node, axis="dp", chunk=None, shape=None,
                           ctx=None):
    """Emit the quantize→all_gather→dequantize trio for one gradient
    (``shape`` = the gradient's shape; taken from ``node.shape`` when
    the node carries one).  Returns the DequantizeCommOp head."""
    if shape is None:
        shape = getattr(node, "shape", None)
    if shape is None:
        raise ValueError(
            f"quantized_allreduce_op needs the gradient shape for "
            f"{node!r}: pass shape= (the node carries none)")
    q = QuantizeCommOp(node, axis=axis, chunk=chunk, ctx=ctx)
    g = QuantAllReduceCommunicateOp(q, axis=axis, ctx=ctx)
    return DequantizeCommOp(g, shape, axis=axis, chunk=q.chunk, ctx=ctx)


class PipelineSendOp(Op):
    """P2P send to the next pipeline stage.  Under the scan-based pipeline
    executor these become ppermute rotations (parallel/pipeline.py); as a
    standalone node it is a ppermute by +1 on the 'pp' axis.
    Reference: gpu_ops/PipelineSend.py (NCCL send on p2p stream)."""

    def __init__(self, node, dst=None, axis="pp", ctx=None):
        super().__init__(node, name="PipelineSend", ctx=ctx)
        self.dst = dst
        self.axis = axis

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        if tc.has_axis(self.axis):
            n = jax.lax.axis_size(self.axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, self.axis, perm)
        return x

    def gradient(self, output_grad):
        return [PipelineReceiveOp(output_grad, axis=self.axis)]


class PipelineReceiveOp(Op):
    """P2P receive from the previous stage (ppermute by -1)."""

    def __init__(self, node, src=None, axis="pp", ctx=None):
        super().__init__(node, name="PipelineReceive", ctx=ctx)
        self.src = src
        self.axis = axis

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        if tc.has_axis(self.axis):
            n = jax.lax.axis_size(self.axis)
            perm = [(i, (i - 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, self.axis, perm)
        return x

    def gradient(self, output_grad):
        return [PipelineSendOp(output_grad, axis=self.axis)]


def pipeline_send_op(node, dst=None, comm=None, stream=None, ctx=None):
    return PipelineSendOp(node, dst=dst, ctx=ctx)


def pipeline_receive_op(node, src=None, comm=None, stream=None, ctx=None):
    return PipelineReceiveOp(node, src=src, ctx=ctx)


class ParameterServerCommunicateOp(Op):
    """PS push-pull of a gradient (reference ParameterServerCommunicate.py).
    The TPU build routes PS traffic through the host-side KV server
    (hetu_tpu.ps); in-graph this is an annotation consumed by the executor's
    hybrid path, identity otherwise."""

    def __init__(self, node, ps_table=None, ctx=None):
        super().__init__(node, name="PSCommunicate", ctx=ctx)
        self.ps_table = ps_table

    def jax_fn(self, x):
        return x

    def gradient(self, output_grad):
        return [output_grad]


def parameterServerCommunicate_op(node, comm=None, optimizer=None, ctx=None):
    return ParameterServerCommunicateOp(node, ctx=ctx)


class ParameterServerSparsePullOp(Op):
    def __init__(self, node, ids, ctx=None):
        super().__init__(node, ids, name="PSSparsePull", ctx=ctx)

    def jax_fn(self, table, ids):
        return jnp.take(table, ids.astype(jnp.int32), axis=0)

    def gradient(self, output_grad):
        from .ops_embed import IndexedSlicesOp
        return [IndexedSlicesOp(self.inputs[0], self.inputs[1], output_grad),
                None]


def parameterServerSparsePull_op(node, ids, ctx=None):
    return ParameterServerSparsePullOp(node, ids, ctx=ctx)


# Host<->device transfers are owned by XLA/PJRT; kept as identity for parity
# (reference gpu_ops/DataTransfer.py).

class DataTransferOp(Op):
    def __init__(self, node, ctx=None, name="DataTransfer"):
        super().__init__(node, name=name, ctx=ctx)

    def jax_fn(self, x):
        return x

    def gradient(self, output_grad):
        return [output_grad]


def datah2d_op(node, ctx=None):
    return DataTransferOp(node, ctx=ctx, name="DataH2D")


def datad2h_op(node, ctx=None):
    return DataTransferOp(node, ctx=ctx, name="DataD2H")
