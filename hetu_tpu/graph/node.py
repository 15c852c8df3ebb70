"""Graph IR core: the Op node base class and trace machinery.

TPU-native counterpart of the reference's ``python/hetu/gpu_ops/Node.py``
(Op base at Node.py:18).  The reference executes each node eagerly by
launching a CUDA kernel per op per step; here every node instead carries a
pure ``jax_fn`` and the executor *traces* a whole named subgraph once into a
single jitted XLA program (SURVEY.md §1 "Key structural facts").  Placement
hooks (forward_hook's H2D/D2H insertion, Node.py:192-213) are unnecessary:
XLA owns transfers; ``raw_ctx`` survives as a sharding/stage hint.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..context import get_current_context

# rng stream index space: [0, n_topo) for topo-tracked nodes; untracked
# nodes are shifted far above any realistic topo position (see rng_for)
_UNTRACKED_RNG_OFFSET = 1 << 24


class ShapeInferenceError(ValueError):
    """``Op.infer_shape`` failed: the message names the node, its op
    type, and its input shapes/dtypes (the graph-wide verifier in
    ``hetu_tpu.analysis.verify`` wraps whole-graph walks the same way —
    this covers standalone per-node use)."""


class TraceContext:
    """Per-trace state threaded through ``Op.compute`` calls.

    Replaces the reference's per-op stream/event plumbing
    (executor.py:1039-1058): under jit there are no streams to order, but
    ops still need RNG keys, the training/inference flag, mesh info, and
    access to variable values.
    """

    def __init__(self, params=None, rng=None, training=True, mesh=None,
                 axis_env=(), config=None, step=None):
        self.params = params or {}
        self._rng = rng
        self.training = training
        self.mesh = mesh
        # tuple of mesh axis names currently visible as collective axes
        # (non-empty only inside shard_map traces)
        self.axis_env = tuple(axis_env)
        self.config = config
        self.step = step
        self.extra_outputs = {}
        # node.id -> stable stream index (topo position).  The raw global
        # id counter differs between two builds of the same graph (e.g.
        # checkpoint resume in a process that built a graph before), so
        # executors install topo positions here to keep dropout/rand
        # streams — and therefore resumed trajectories — build-invariant.
        self.rng_ids = {}
        # What this trace's gradient nodes share (``ops_misc.Backward``).
        # An executor that evaluates a whole subgraph inside ONE jax trace
        # installs one; a node evaluated alone (``infer_shape``, the
        # graph verifier's per-node ``eval_shape``) finds None and traces
        # what it needs itself: a value cannot cross jax traces.
        self.backward = None

    def rng_for(self, node) -> jax.Array:
        assert self._rng is not None, (
            "op %s needs an RNG key but the trace has none" % node)
        stream = self.rng_ids.get(node.id)
        if stream is None:
            # Untracked node: raw global ids share the small-int range with
            # topo positions, so fold in a disjoint offset — otherwise an
            # untracked rng consumer could silently share a dropout stream
            # with a topo-indexed one.
            stream = node.id + _UNTRACKED_RNG_OFFSET
        return jax.random.fold_in(self._rng, stream)

    def has_axis(self, name) -> bool:
        return name in self.axis_env


class Op:
    """A node in the dataflow graph.

    Mirrors the reference Op (gpu_ops/Node.py:18-76): ``inputs``,
    ``raw_ctx`` placement hint, operator overloading; but ``compute`` is a
    pure function over jax values evaluated at trace time instead of a CUDA
    kernel launch.
    """

    _next_id = 0

    def __init__(self, *inputs, name=None, ctx=None, dtype=None):
        for i, x in enumerate(inputs):
            assert isinstance(x, Op), (
                f"input {i} of {type(self).__name__} is {type(x)}; "
                "wrap constants with ht.Variable or *_byconst ops")
        self.inputs = list(inputs)
        self.id = Op._next_id
        Op._next_id += 1
        base = name if name is not None else type(self).__name__.replace("Op", "")
        self.name = f"{base}_{self.id}"
        self.raw_ctx = ctx if ctx is not None else get_current_context()
        self.dtype = dtype

    # ------------------------------------------------------------------ #
    # evaluation
    # ------------------------------------------------------------------ #

    def jax_fn(self, *input_vals):
        raise NotImplementedError(f"{type(self).__name__} has no jax_fn")

    def compute(self, input_vals, tc: TraceContext):
        """Evaluate this node given already-evaluated input values.

        Default delegates to the stateless ``jax_fn``; ops that need RNG,
        the training flag, collective axes, or variable state override this.
        """
        return self.jax_fn(*input_vals)

    def gradient(self, output_grad):
        """Build backward-graph nodes for each input (reference: each op
        file's ``gradient``).  Return a list aligned with ``self.inputs``;
        ``None`` entries mean no gradient flows to that input."""
        raise NotImplementedError(
            f"{type(self).__name__} has no gradient rule")

    # ------------------------------------------------------------------ #
    # shape/dtype inference — free via jax.eval_shape (the reference hand
    # writes infer_shape per op, e.g. Node.py + every gpu_ops file)
    # ------------------------------------------------------------------ #

    def infer_shape(self, input_shapes, input_dtypes=None):
        if input_dtypes is None:
            input_dtypes = [jnp.float32] * len(input_shapes)
        args = [
            jax.ShapeDtypeStruct(tuple(s), d)
            for s, d in zip(input_shapes, input_dtypes)
        ]
        tc = TraceContext(rng=None, training=False)
        try:
            out = jax.eval_shape(lambda *a: self.compute(list(a), tc),
                                 *args)
        except Exception as e:
            ins = ", ".join(
                f"{jnp.dtype(d).name}{tuple(s)}"
                for s, d in zip(input_shapes, input_dtypes))
            raise ShapeInferenceError(
                f"shape inference failed at node {self.name!r} (op "
                f"{type(self).__name__}) with inputs [{ins}]"
                + (f" produced by {[i.name for i in self.inputs]}"
                   if self.inputs else "")
                + f": {type(e).__name__}: {e}") from e
        return out.shape

    # ------------------------------------------------------------------ #
    # sugar — reference Node.py:48-76
    # ------------------------------------------------------------------ #

    def __add__(self, other):
        from . import ops_math as m
        if isinstance(other, Op):
            return m.add_op(self, other)
        return m.addbyconst_op(self, other)

    __radd__ = __add__

    def __sub__(self, other):
        from . import ops_math as m
        if isinstance(other, Op):
            return m.minus_op(self, other)
        return m.addbyconst_op(self, -other)

    def __rsub__(self, other):
        from . import ops_math as m
        return m.addbyconst_op(m.opposite_op(self), other)

    def __neg__(self):
        from . import ops_math as m
        return m.opposite_op(self)

    def __mul__(self, other):
        from . import ops_math as m
        if isinstance(other, Op):
            return m.mul_op(self, other)
        return m.mul_byconst_op(self, other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from . import ops_math as m
        if isinstance(other, Op):
            return m.div_op(self, other)
        return m.mul_byconst_op(self, 1.0 / other)

    def __rtruediv__(self, other):
        from . import ops_math as m
        return m.div_const_op(self, other)

    def __repr__(self):
        return self.name

    __str__ = __repr__


class SimpleOp(Op):
    """An Op wrapping a closed-over pure function — the workhorse for the
    ~100-op factory surface (reference gpu_ops/__init__.py exports)."""

    def __init__(self, fn, *inputs, name=None, grad_rule=None, ctx=None):
        super().__init__(*inputs, name=name, ctx=ctx)
        self._fn = fn
        self._grad_rule = grad_rule

    def jax_fn(self, *input_vals):
        return self._fn(*input_vals)

    def gradient(self, output_grad):
        if self._grad_rule is None:
            return vjp_gradient(self, output_grad)
        return self._grad_rule(self, output_grad)


def vjp_gradient(node: Op, output_grad: Op):
    """Fallback gradient: one VJPOp per input, each picking its cotangent
    out of ``jax.vjp`` of the node's own compute — this replaces dozens of
    hand-written backward kernels in the reference (src/ops/*.cu).

    The forward runs once and the backward once a cotangent BY
    CONSTRUCTION: the executor computes a node that has VJPOp consumers
    under ``jax.vjp`` and keeps the pullback for the trace, and the VJPOps
    of one ``(node, output_grad)`` share one call of it
    (``ops_misc.Backward``).  Leaving the repeats to XLA's CSE held for a
    matmul and failed where the forward is opaque to the compiler: a
    Mosaic call that also returns its ``lse`` is another call than the
    node's own, so the flash forward ran twice a layer (ledger, PR 32:
    ``flash_fwd`` 0.2500 s beside ``jvp_flash_fwd`` 0.2501 s)."""
    from .ops_misc import VJPOp
    return [VJPOp(node, output_grad, i) for i in range(len(node.inputs))]
