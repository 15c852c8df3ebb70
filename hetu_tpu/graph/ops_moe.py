"""MoE ops: capacity dispatch/combine, all-to-all, gating helpers.

Reference: gpu_ops/LayoutTransform.py (Tutel-style fast dispatch; kernels
src/ops/LayoutTransform.cu), ReverseLayoutTransform.py, AllToAll.py,
HAllToAll.py (hierarchical A2A via node-leader staging,
src/communication/mpi_nccl_communication.cu:152-243), BalanceAssignment.py
(auction assignment), SamGroupSum.cu / SamMax.cu / GroupTopKIdx.cu (SAM
gate), Dispatch.py (model-parallel annotation).

TPU-native: dispatch/combine take the GShard-style one-hot-matmul
formulation (_scatter_rows) — MXU work with no data-dependent writes —
and the row-scatter form where the one-hot mask would outgrow
_ONEHOT_DISPATCH_MAX_ELEMS.  Combine stays a gather (fast on TPU).
All-to-all is ``jax.lax.all_to_all`` over the 'ep' mesh axis inside
shard_map; hierarchical A2A decomposes over ('dcn', 'ici') axes — the
natural mapping of the reference's gather→exchange→scatter staging.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .node import Op, TraceContext
from .ops_math import _simple


def _flat_int(x):
    return x.reshape(-1).astype(jnp.int32)


def _slot_weights(pos_valid_weight, n_slots, dtype):
    """[N, n_slots] slot-assignment weight matrix from (pos, valid,
    weight) triples — the GShard-style dense dispatch mask (the dispatch
    einsum of GShard, arXiv:2006.16668, and Tutel).  Invalid
    (capacity-dropped) rows map to class -1 == an all-zero one-hot
    row."""
    W = None
    for pos, valid, w in pos_valid_weight:
        safe = jnp.where(valid, pos, -1)
        oh = jax.nn.one_hot(safe, n_slots, dtype=dtype)
        if w is not None:
            oh = oh * w.reshape(-1, 1).astype(dtype)
        W = oh if W is None else W + oh
    return W


# above this many mask elements (N * E * cap) the one-hot formulation's
# [N, n_slots] operand becomes the dominant memory/FLOP cost and the
# scatter form wins regardless of its lowering: 2^27 elems = 256 MB bf16
_ONEHOT_DISPATCH_MAX_ELEMS = 1 << 27


def _scatter_rows(terms, n_slots, src, dtype):
    """Rows of ``src`` summed into ``n_slots`` buckets.

    One-hot MXU matmul (sum_i onehot(pos_i, weighted)^T @ src) — row
    scatter-adds can lower to a serialized scatter on TPU, while this
    formulation is pure matmul work.  The .at[].add scatter form is used
    instead when the [N, n_slots] mask would exceed
    _ONEHOT_DISPATCH_MAX_ELEMS, past which the mask's memory/FLOPs
    dominate the experts themselves (at top-k capacity, mask elements
    grow as k*N^2)."""
    N = src.shape[0]
    if N * n_slots > _ONEHOT_DISPATCH_MAX_ELEMS:
        out = jnp.zeros((n_slots, src.shape[-1]), dtype)
        for pos, valid, w in terms:
            rows = src if w is None else w.reshape(-1, 1).astype(dtype) * src
            safe = jnp.where(valid, pos, n_slots)
            out = out.at[safe].add(rows, mode="drop")
        return out
    W = _slot_weights(terms, n_slots, dtype)
    return jnp.matmul(W.T, src,
                      preferred_element_type=jnp.float32).astype(dtype)


class LayoutTransformOp(Op):
    """Capacity dispatch: tokens (N,D) -> expert buffers (E*capacity, D).

    Signature parity: layout_transform_op(input, indices_s, location_s,
    capacity, total_experts) (LayoutTransform.py:13-24); top-1 and top-2.
    Tokens whose location >= capacity are dropped (scatter mode='drop').
    """

    def __init__(self, inp, indices_s, location_s, capacity, total_experts,
                 ctx=None):
        super().__init__(inp, *indices_s, *location_s, name="LayoutTransform",
                         ctx=ctx)
        self.capacity = int(capacity)
        self.topK = len(indices_s)
        self.total_experts = int(total_experts)

    def jax_fn(self, x, *idx_loc):
        k, cap = self.topK, self.capacity
        terms = []
        for i in range(k):
            idx = _flat_int(idx_loc[i])
            loc = _flat_int(idx_loc[k + i])
            terms.append((idx * cap + loc, loc < cap, None))
        return _scatter_rows(terms, self.total_experts * cap, x, x.dtype)

    def gradient(self, output_grad):
        k = self.topK
        grads = [
            layout_transform_gradient_op(
                output_grad, self.inputs[1 + i], self.inputs[1 + k + i],
                self.capacity, ctx=self.raw_ctx)
            for i in range(k)
        ]
        total = grads[0]
        for g in grads[1:]:
            total = total + g
        return [total] + [None] * (2 * k)


class LayoutTransformGradientOp(Op):
    """grad_in[token] = grad_out[idx*cap + loc] (0 when dropped)."""

    def __init__(self, grad, indice, location, capacity, ctx=None):
        super().__init__(grad, indice, location,
                         name="LayoutTransformGrad", ctx=ctx)
        self.capacity = int(capacity)

    def jax_fn(self, g, indice, location):
        idx = _flat_int(indice)
        loc = _flat_int(location)
        pos = idx * self.capacity + loc
        rows = jnp.take(g, jnp.clip(pos, 0, g.shape[0] - 1), axis=0)
        return jnp.where((loc < self.capacity)[:, None], rows, 0.0)

    def gradient(self, output_grad):
        raise NotImplementedError


def layout_transform_op(inp, indices_s, location_s, capacity, total_experts,
                        ctx=None):
    return LayoutTransformOp(inp, indices_s, location_s, capacity,
                             total_experts, ctx=ctx)


def layout_transform_gradient_op(grad, indice, location, capacity, ctx=None):
    return LayoutTransformGradientOp(grad, indice, location, capacity, ctx=ctx)


class ReverseLayoutTransformOp(Op):
    """Weighted combine: expert buffers (E*cap, D) -> tokens (N, D).

    out[t] = sum_k gate_k[t] * data[idx_k[t]*cap + loc_k[t]]
    (ReverseLayoutTransform.py:12-40).
    """

    def __init__(self, inp, indices_s, location_s, gates, capacity,
                 num_experts, ctx=None):
        super().__init__(inp, *indices_s, *location_s, *gates,
                         name="ReverseLayoutTransform", ctx=ctx)
        self.capacity = int(capacity)
        self.topK = len(indices_s)
        self.num_experts = int(num_experts)

    def jax_fn(self, data, *rest):
        k, cap = self.topK, self.capacity
        indices = rest[:k]
        locations = rest[k:2 * k]
        gates = rest[2 * k:]
        out = None
        for i in range(k):
            idx = _flat_int(indices[i])
            loc = _flat_int(locations[i])
            pos = idx * cap + loc
            rows = jnp.take(data, jnp.clip(pos, 0, data.shape[0] - 1), axis=0)
            rows = jnp.where((loc < cap)[:, None], rows, 0.0)
            term = gates[i].reshape(-1, 1) * rows
            out = term if out is None else out + term
        return out

    def gradient(self, output_grad):
        k = self.topK
        grad_data = reverse_layout_transform_gradient_data_op(
            output_grad, list(self.inputs[1:1 + k]),
            list(self.inputs[1 + k:1 + 2 * k]),
            list(self.inputs[1 + 2 * k:]), self.capacity, self.num_experts,
            ctx=self.raw_ctx)
        grad_gates = [
            reverse_layout_transform_gradient_gate_op(
                output_grad, self.inputs[0], self.inputs[1 + i],
                self.inputs[1 + k + i], self.capacity, ctx=self.raw_ctx)
            for i in range(k)
        ]
        return [grad_data] + [None] * (2 * k) + grad_gates


class ReverseLayoutTransformGradientDataOp(Op):
    """grad wrt expert buffers: scatter gate-weighted token grads back."""

    def __init__(self, grad, indices_s, location_s, gates, capacity,
                 num_experts, ctx=None):
        super().__init__(grad, *indices_s, *location_s, *gates,
                         name="ReverseLayoutTransformGradData", ctx=ctx)
        self.capacity = int(capacity)
        self.topK = len(indices_s)
        self.num_experts = int(num_experts)

    def jax_fn(self, g, *rest):
        k, cap = self.topK, self.capacity
        indices = rest[:k]
        locations = rest[k:2 * k]
        gates = rest[2 * k:]
        terms = []
        for i in range(k):
            idx = _flat_int(indices[i])
            loc = _flat_int(locations[i])
            terms.append((idx * cap + loc, loc < cap,
                          gates[i].reshape(-1)))
        return _scatter_rows(terms, self.num_experts * cap, g, g.dtype)

    def gradient(self, output_grad):
        raise NotImplementedError


class ReverseLayoutTransformGradientGateOp(Op):
    """grad wrt gate_k: dot(token grad, dispatched row)."""

    def __init__(self, grad, data, indice, location, capacity, ctx=None):
        super().__init__(grad, data, indice, location,
                         name="ReverseLayoutTransformGradGate", ctx=ctx)
        self.capacity = int(capacity)

    def jax_fn(self, g, data, indice, location):
        idx = _flat_int(indice)
        loc = _flat_int(location)
        pos = idx * self.capacity + loc
        rows = jnp.take(data, jnp.clip(pos, 0, data.shape[0] - 1), axis=0)
        rows = jnp.where((loc < self.capacity)[:, None], rows, 0.0)
        return jnp.sum(g * rows, axis=-1)

    def gradient(self, output_grad):
        raise NotImplementedError


def reverse_layout_transform_op(inp, indices_s, location_s, gates, capacity,
                                num_experts, ctx=None):
    return ReverseLayoutTransformOp(inp, indices_s, location_s, gates,
                                    capacity, num_experts, ctx=ctx)


def reverse_layout_transform_gradient_data_op(grad, indices_s, location_s,
                                              gates, capacity, num_experts,
                                              ctx=None):
    return ReverseLayoutTransformGradientDataOp(
        grad, indices_s, location_s, gates, capacity, num_experts, ctx=ctx)


def reverse_layout_transform_gradient_gate_op(grad, data, indice, location,
                                              capacity, ctx=None):
    return ReverseLayoutTransformGradientGateOp(
        grad, data, indice, location, capacity, ctx=ctx)


def reverse_layout_transform_no_gate_op(inp, indices_s, location_s, capacity,
                                        num_experts, ctx=None):
    """Combine without gate weighting (ReverseLayoutTransformNoGate,
    ReverseLayoutTransform.py:140)."""
    k = len(indices_s)

    class _NoGate(Op):
        def __init__(self):
            super().__init__(inp, *indices_s, *location_s,
                             name="ReverseLayoutTransformNoGate", ctx=ctx)
            self.capacity = int(capacity)
            self.num_experts = int(num_experts)

        def jax_fn(self, data, *rest):
            out = None
            for i in range(k):
                idx = _flat_int(rest[i])
                loc = _flat_int(rest[k + i])
                pos = idx * self.capacity + loc
                rows = jnp.take(data, jnp.clip(pos, 0, data.shape[0] - 1),
                                axis=0)
                rows = jnp.where((loc < self.capacity)[:, None], rows, 0.0)
                out = rows if out is None else out + rows
            return out

        def gradient(self, output_grad):
            # adjoint of the gather-combine is the scatter-dispatch
            total = LayoutTransformOp(
                output_grad, list(self.inputs[1:1 + k]),
                list(self.inputs[1 + k:1 + 2 * k]), self.capacity,
                self.num_experts, ctx=self.raw_ctx)
            return [total] + [None] * (2 * k)

    return _NoGate()


def _pin_dim0(x, mesh, axes):
    """pjit-mode a2a marker: constrain dim 0 over the given mesh axes
    (those present), ordered as the MESH orders them (outer-major — the
    device-order truth), so the constraint matches the expert-weight
    sharding convention and GSPMD materializes the token exchange at this
    site.  Returns x unchanged when no named axis is usable."""
    present = tuple(ax for ax in mesh.axis_names if ax in axes)
    total = 1
    for ax in present:
        total *= mesh.shape[ax]
    if not present or x.shape[0] % total:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [None] * x.ndim
    spec[0] = present if len(present) > 1 else present[0]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


class AllToAllOp(Op):
    """Expert-parallel all-to-all (gpu_ops/AllToAll.py:8-50; NCCL send/recv
    loop mpi_nccl_communication.cu:245-275).

    Input (E_total*cap, D): rows grouped by destination expert.  Inside
    shard_map over the 'ep' axis this runs jax.lax.all_to_all so each device
    ends with the rows destined for its local experts; under pjit it is an
    identity marker (XLA inserts the reshuffle from shardings).
    """

    def __init__(self, node, axis="ep", ctx=None):
        super().__init__(node, name="AllToAll", ctx=ctx)
        self.axis = axis

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        if tc.has_axis(self.axis):
            n = jax.lax.axis_size(self.axis)
            parts = x.reshape(n, x.shape[0] // n, *x.shape[1:])
            out = jax.lax.all_to_all(parts, self.axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            return out.reshape(x.shape)
        if tc.mesh is not None:
            # pjit mode: pin the expert-major dim to the 'ep' axis so GSPMD
            # must materialize the redistribution (the actual all-to-all)
            # between the token-sharded dispatch and the expert compute
            return _pin_dim0(x, tc.mesh, (self.axis,))
        return x

    def gradient(self, output_grad):
        return [AllToAllOp(output_grad, axis=self.axis, ctx=self.raw_ctx)]


def alltoall_op(node, comm=None, axis="ep", ctx=None):
    return AllToAllOp(node, axis=axis, ctx=ctx)


class HAllToAllOp(Op):
    """Hierarchical all-to-all (gpu_ops/HAllToAll.py:24-50): the reference
    stages intra-node gather -> leader exchange -> scatter.  On TPU the same
    economy comes from running all_to_all per mesh axis: first over the
    intra-slice 'ici' axis, then over the cross-slice 'dcn' axis."""

    def __init__(self, node, axes=("ici", "dcn"), ctx=None):
        super().__init__(node, name="HAllToAll", ctx=ctx)
        self.axes = tuple(axes)

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        present = [ax for ax in self.axes if tc.has_axis(ax)]
        if len(present) == 2:
            # Two-stage exchange equal to one flat all-to-all over the
            # (outer, inner) superaxis: view local rows as
            # [outer_dest, inner_dest, r, ...] and exchange each stage over
            # its OWN destination dim — splitting dim 0 twice (naive
            # composition) interleaves blocks wrongly.
            a_inner, a_outer = self.axes
            n_in = jax.lax.axis_size(a_inner)
            n_out = jax.lax.axis_size(a_outer)
            r = x.shape[0] // (n_in * n_out)
            parts = x.reshape(n_out, n_in, r, *x.shape[1:])
            parts = jax.lax.all_to_all(parts, a_inner, split_axis=1,
                                       concat_axis=1)
            parts = jax.lax.all_to_all(parts, a_outer, split_axis=0,
                                       concat_axis=0)
            return parts.reshape(x.shape)
        for ax in present:
            n = jax.lax.axis_size(ax)
            parts = x.reshape(n, x.shape[0] // n, *x.shape[1:])
            x = jax.lax.all_to_all(parts, ax, split_axis=0,
                                   concat_axis=0).reshape(x.shape)
        if not present and tc.mesh is not None:
            x = _pin_dim0(x, tc.mesh, self.axes)
        return x

    def gradient(self, output_grad):
        return [HAllToAllOp(output_grad, axes=self.axes, ctx=self.raw_ctx)]


def halltoall_op(node, comm=None, axes=("ici", "dcn"), ctx=None):
    return HAllToAllOp(node, axes=axes, ctx=ctx)


def balance_assignment_op(scores, max_iterations=100, ctx=None):
    """Balanced assignment (BalanceAssignment.py:87; used by BalanceGate /
    BalanceAssignmentLayer, layers/moe_layer.py:95-133): assign each of N
    tokens to E experts with exactly-equal load N/E, maximizing score.

    Output parity with the reference kernel: a *permutation of token
    indices* of shape (N,) — the concatenation over experts of the token
    ids assigned to each expert — consumed downstream by
    ``indexing_op(tokens, indice)``.

    Implemented as auction price refinement (bounded fori_loop) followed by
    a capacity-enforcing greedy pass (lax.scan over tokens in priority
    order), which guarantees the equal-load contract the auction alone does
    not.
    """

    def f(s):
        n, e = s.shape
        cap = n // e
        eps = 1e-4

        def price_round(_, prices):
            net = s - prices[None, :]
            choice = jnp.argmax(net, axis=1)
            load = jnp.zeros((e,), jnp.float32).at[choice].add(1.0)
            return prices + jnp.where(load > cap, eps * (load - cap), 0.0)

        prices = jax.lax.fori_loop(0, max_iterations, price_round,
                                   jnp.zeros((e,), jnp.float32))
        net = s - prices[None, :]
        # greedy capacity-respecting pass: tokens in descending order of
        # their best net score each take their best expert with a free slot
        best = jnp.max(net, axis=1)
        token_order = jnp.argsort(-best)

        def take(counts, tok):
            avail = counts < cap
            sc = jnp.where(avail, net[tok], -jnp.inf)
            c = jnp.argmax(sc)
            return counts.at[c].add(1), c

        _, choice_sorted = jax.lax.scan(
            take, jnp.zeros((e,), jnp.int32), token_order)
        choice = jnp.zeros((n,), jnp.int32).at[token_order].set(choice_sorted)
        # flatten per-expert token lists: stable sort of token ids by expert
        perm = jnp.argsort(choice, stable=True)
        return perm.astype(jnp.float32)

    return _simple("BalanceAssignment", f, scores, nondiff=True, ctx=ctx)


def group_topk_idx_op(a, top1_group, topk=1, num_local_gpus=8, ctx=None):
    """Top-k expert indices restricted to the token's chosen group
    (GroupTopKIdx.cu: searches [group*num_local_gpus,(group+1)*num_local_gpus))."""
    def f(x, grp):
        g = _flat_int(grp)
        n, e = x.shape
        cols = jnp.arange(e)[None, :]
        lo = (g * num_local_gpus)[:, None]
        hi = ((g + 1) * num_local_gpus)[:, None]
        masked = jnp.where((cols >= lo) & (cols < hi), x,
                           jnp.full_like(x, -1e4))
        _, idx = jax.lax.top_k(masked, topk)
        return idx.astype(jnp.float32)
    return _simple("GroupTopKIdx", f, a, top1_group, nondiff=True, ctx=ctx)


def sam_group_sum_op(gate, num_local_gpus, ctx=None):
    """Per-node gate mass: (N, E) -> (N, G) summing contiguous expert groups
    (SamGroupSum.cu)."""
    def f(x):
        n, e = x.shape
        return x.reshape(n, num_local_gpus, e // num_local_gpus).sum(-1)
    return _simple("SamGroupSum", f, gate, ctx=ctx)


class SamMaxOp(Op):
    """SamMax.cu: outside the chosen group, keep (x - x[topk_idx]) where
    positive; zero inside the group."""

    def __init__(self, a, top1_group, topk_indice, num_local_gpus, ctx=None):
        super().__init__(a, top1_group, topk_indice, name="SamMax", ctx=ctx)
        self.num_local_gpus = num_local_gpus

    def jax_fn(self, x, grp, tki):
        g = _flat_int(grp)
        t = _flat_int(tki)
        n, e = x.shape
        ref = jnp.take_along_axis(x, t[:, None], axis=1)
        cols = jnp.arange(e)[None, :]
        in_group = (cols >= (g * self.num_local_gpus)[:, None]) & \
                   (cols < ((g + 1) * self.num_local_gpus)[:, None])
        out = jnp.where((x > ref) & ~in_group, x - ref, 0.0)
        return out

    def gradient(self, output_grad):
        from .node import vjp_gradient
        g = vjp_gradient(self, output_grad)
        return [g[0], None, None]


def sam_max_op(a, top1_group, topk_indice, num_local_gpus, ctx=None):
    return SamMaxOp(a, top1_group, topk_indice, num_local_gpus, ctx=ctx)


class DispatchOp(Op):
    """Model-parallel annotation (gpu_ops/Dispatch.py:5-34).  In the
    reference this fed a graph-splitting pass absent from the fork
    (SURVEY.md §2.5 TP caveat); here it attaches a PartitionSpec hint and is
    identity at trace time — pjit consumes the sharding."""

    def __init__(self, node, parts, ctx=None):
        super().__init__(node, name="Dispatch", ctx=ctx)
        self.parts = parts

    def compute(self, input_vals, tc: TraceContext):
        (x,) = input_vals
        if tc.mesh is not None:
            from jax.sharding import PartitionSpec as P
            from jax.lax import with_sharding_constraint
            try:
                spec = _parts_to_spec(self.parts, x.ndim, tc.mesh)
                return with_sharding_constraint(x, spec)
            except Exception:
                return x
        return x

    def gradient(self, output_grad):
        return [output_grad]


def _parts_to_spec(parts, ndim, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * ndim
    if isinstance(parts, dict):
        for dim, axis in parts.items():
            spec[dim] = axis if isinstance(axis, str) else "tp"
    return NamedSharding(mesh, P(*spec))


def dispatch(node, parts, ctx=None):
    return DispatchOp(node, parts, ctx=ctx)
