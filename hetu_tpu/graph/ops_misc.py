"""Variables, placeholders, constants, and the generic VJP gradient op.

Reference counterparts: gpu_ops/Variable.py (PlaceholderOp at Variable.py:19),
gpu_ops/OnesLike.py / ZerosLike.py, gpu_ops/Arange.py, gpu_ops/Full.py.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .node import Op, SimpleOp, TraceContext


class PlaceholderOp(Op):
    """A leaf node: either a fed input (shape unknown until feed) or a
    variable (trainable parameter / non-trainable state) with a value or an
    initializer.  Reference: Variable.py:19-63.

    ``is_embed`` marks embedding tables routed to the parameter-server path
    in Hybrid mode (Variable.py:57-63).  ``reshape_in_mp`` model-parallel
    repartition (Variable.py:83-120) is unnecessary here — sharding specs
    partition parameters without touching their logical shape.
    """

    def __init__(self, name, value=None, initializer=None, trainable=True,
                 dtype=jnp.float32, ctx=None, is_embed=False):
        super().__init__(name=name, ctx=ctx)
        self.name = name  # placeholders keep their exact user name
        if dtype is np.float32:
            dtype = jnp.float32
        self.dtype = dtype
        self.is_embed = is_embed
        # sharding hint: optional PartitionSpec-like tuple set by strategies
        self.sharding_spec = None
        if value is None and initializer is None:
            trainable = False
            self.shape = None
        elif value is not None:
            assert initializer is None, "value given; initializer must be None"
            value = np.asarray(value, dtype=np.dtype(dtype) if dtype != jnp.bfloat16 else np.float32)
            self.shape = tuple(value.shape)
        else:
            self.shape = tuple(initializer.shape)
        self.tensor_value = value
        self.initializer = initializer
        self.trainable = trainable

    @property
    def is_variable(self):
        return self.tensor_value is not None or self.initializer is not None

    def init_value(self, seed: int) -> jnp.ndarray:
        """Materialize the initial value (host side, before jit).

        The stream is keyed by the variable NAME, not the global node-id
        counter: ids shift with every graph built earlier in the process,
        which would make init values depend on build order (and diverge
        across jax processes building the same model after different
        warm-up work).  Names are unique per executor."""
        if self.tensor_value is not None:
            return jnp.asarray(self.tensor_value, dtype=self.dtype)
        import zlib
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(self.name.encode()) & 0x7FFFFFFF)
        return self.initializer.generate(key, self.dtype)

    def compute(self, input_vals, tc: TraceContext):
        raise AssertionError(
            f"placeholder {self.name} must be fed or bound by the executor")

    def gradient(self, output_grad):
        return None

    def infer_shape(self, input_shapes, input_dtypes=None):
        assert self.shape is not None, f"feed shape needed for {self.name}"
        return self.shape


def Variable(name, value=None, initializer=None, trainable=True,
             dtype=jnp.float32, ctx=None):
    """Reference Variable.py:8-16."""
    return PlaceholderOp(name, value, initializer, trainable, dtype, ctx)


def placeholder_op(name, value=None, initializer=None, trainable=True,
                   dtype=jnp.float32, ctx=None):
    return PlaceholderOp(name, value, initializer, trainable, dtype, ctx)


def _forward_vjp(node: Op, xs, tc: TraceContext):
    """``node``'s compute under ``jax.vjp`` -> (output, pullback, side
    writes).  Stateful ops (BatchNorm's running statistics) write to
    ``tc.extra_outputs``; here they write to a context of the inner trace
    and leave it as the vjp's ``has_aux`` outputs, so no inner tracer
    leaks into the outer jit trace and no write is lost."""
    inner = TraceContext(
        params=tc.params, rng=tc._rng, training=tc.training,
        mesh=tc.mesh, axis_env=tc.axis_env, config=tc.config,
        step=tc.step)
    # the outer trace's RNG stream ids: a forward traced here sees the
    # dropout mask every other trace of the node sees
    inner.rng_ids = tc.rng_ids
    written = []        # the keys are nodes, not values: safe to close over

    def primal(*a):
        out = node.compute(list(a), inner)
        written[:] = inner.extra_outputs
        return out, [inner.extra_outputs[k] for k in written]

    out, pullback, side = jax.vjp(primal, *xs, has_aux=True)
    return out, pullback, dict(zip(written, side))


class Backward:
    """What the gradient nodes of ONE trace share, kept in
    ``TraceContext.backward`` by an executor that evaluates the whole
    subgraph inside one jax trace.

    - The pullback of every forward node that a ``VJPOp`` of the subgraph
      differentiates: ``compute`` evaluates such a node under ``jax.vjp``
      where the trace reaches it, so the node's output and its VJP's
      forward are one computation.
    - The result of every backward called through ``once``: the gradient
      nodes of one ``(node, output_grad)`` call it one time and each keeps
      its own input's part.
    """

    def __init__(self, topo):
        self._wanted = {id(n._orig) for n in topo if isinstance(n, VJPOp)}
        self._pullbacks = {}    # id(forward node) -> (pullback, out dtype)
        self._results = {}      # backward's key -> what it returned

    def compute(self, node, input_vals, tc: TraceContext):
        """``node``'s output, leaving its pullback where a VJPOp of the
        subgraph will ask for it."""
        if id(node) not in self._wanted:
            return node.compute(input_vals, tc)
        out, pullback, side = _forward_vjp(node, input_vals, tc)
        for var, value in side.items():
            tc.extra_outputs[var] = value
        self._pullbacks[id(node)] = (pullback, out.dtype)
        return out

    def cotangents(self, node, grad_node, g):
        """Every input's cotangent of ``node`` for the output gradient
        ``grad_node`` (its value ``g``), or None where this trace holds
        no pullback of ``node``."""
        saved = self._pullbacks.get(id(node))
        if saved is None:
            return None
        pullback, dtype = saved
        return self.once((id(node), id(grad_node)),
                         lambda: pullback(jnp.asarray(g, dtype=dtype)))

    def once(self, key, backward):
        if key not in self._results:
            self._results[key] = backward()
        return self._results[key]


def _count(shared: bool):
    """Trace-time counters beside ``exec.compile_cache_miss``: gradient
    nodes served from what the trace saved, and those that traced their
    forward (or ran a backward of their own) again."""
    from .. import telemetry
    telemetry.inc("exec.grad.shared" if shared else "exec.grad.retraced")


class VJPOp(Op):
    """Generic cotangent node: grad of ``orig``'s ``input_index``-th input.
    This one node replaces the majority of hand-written backward kernels in
    the reference (src/ops/*.cu).

    Where the trace holds ``orig``'s pullback (``Backward``: the executor
    computed ``orig`` under ``jax.vjp``), the VJPOps of one ``(orig,
    output_grad)`` share one call of it and each picks its cotangent: one
    forward and one backward in the compiled program by construction.
    Where it does not (the node evaluated alone by ``infer_shape`` or the
    graph verifier; a subgraph that does not hold ``orig``), the forward is
    traced again here.  XLA's CSE merged that copy with the node's own for
    a matmul, but not a Pallas call whose VJP forward returns one more
    output, nor loops pruned to different carries (ledger, PR 32)."""

    def __init__(self, orig: Op, output_grad: Op, input_index: int):
        super().__init__(*orig.inputs, output_grad,
                         name=f"grad_{orig.name}_in{input_index}")
        self._orig = orig
        self._idx = input_index

    def compute(self, input_vals, tc: TraceContext):
        *xs, g = input_vals
        cot = None if tc.backward is None else tc.backward.cotangents(
            self._orig, self.inputs[-1], g)
        _count(shared=cot is not None)
        if cot is None:
            # the side writes are the forward node's to make, not this
            # copy's: dropped
            primal_out, pullback, _ = _forward_vjp(self._orig, xs, tc)
            cot = pullback(jnp.asarray(g, dtype=primal_out.dtype))
        return cot[self._idx]

    def gradient(self, output_grad):
        raise NotImplementedError("second-order autodiff not supported")


class SharedBackwardOp(Op):
    """One part of a hand-written backward that returns several inputs'
    gradients at once: ``fn(*input_vals)`` gives a tuple and this node
    keeps ``[index]``.  The nodes built over one ``fn`` and the same
    inputs call it once a trace and share what it returned; a node
    evaluated alone calls it for itself."""

    def __init__(self, name, fn, index, *inputs, ctx=None):
        super().__init__(*inputs, name=name, ctx=ctx)
        self._fn = fn
        self._key = (fn,) + tuple(id(i) for i in inputs)
        self._idx = index

    def compute(self, input_vals, tc: TraceContext):
        def call():
            return self._fn(*input_vals)

        _count(shared=tc.backward is not None)
        parts = call() if tc.backward is None \
            else tc.backward.once(self._key, call)
        return parts[self._idx]

    def gradient(self, output_grad):
        raise NotImplementedError("second-order autodiff not supported")


class SumOp(Op):
    """Merge partial adjoints (reference executor.py:1393 sum_node_list via
    gpu_ops/Sum.py). Dense inputs sum elementwise; IndexedSlices-style
    sparse adjoints are densified first (sparse path: ops_embed)."""

    def __init__(self, nodes, ctx=None):
        super().__init__(*nodes, name="Sum", ctx=ctx)

    def jax_fn(self, *vals):
        out = vals[0]
        for v in vals[1:]:
            out = out + v
        return out

    def gradient(self, output_grad):
        return [output_grad for _ in self.inputs]


def sum_op(nodes, ctx=None):
    return SumOp(nodes, ctx=ctx)


class OnesLikeOp(Op):
    def __init__(self, node, ctx=None):
        super().__init__(node, name="OnesLike", ctx=ctx)

    def jax_fn(self, x):
        return jnp.ones_like(x)

    def gradient(self, output_grad):
        return [None]


class ZerosLikeOp(Op):
    def __init__(self, node, ctx=None):
        super().__init__(node, name="ZerosLike", ctx=ctx)

    def jax_fn(self, x):
        return jnp.zeros_like(x)

    def gradient(self, output_grad):
        return [None]


def oneslike_op(node, ctx=None):
    return OnesLikeOp(node, ctx=ctx)


def zeroslike_op(node, ctx=None):
    return ZerosLikeOp(node, ctx=ctx)


def full_op(shape, fill_value, ctx=None):
    op = SimpleOp(lambda: jnp.full(shape, fill_value), name="Full", ctx=ctx)
    op.gradient = lambda output_grad: []
    return op


def full_like_op(node, fill_value, ctx=None):
    op = SimpleOp(lambda x: jnp.full_like(x, fill_value), node,
                  name="FullLike", ctx=ctx)
    op.gradient = lambda output_grad: [None]
    return op


def arange_op(start, end, step=1, ctx=None):
    op = SimpleOp(lambda: jnp.arange(start, end, step, dtype=jnp.float32),
                  name="Arange", ctx=ctx)
    op.gradient = lambda output_grad: []
    return op


class RandOp(Op):
    """Uniform [0,1) random tensor, fresh each step (reference gpu_ops/Rand.py)."""

    def __init__(self, shape, ctx=None):
        super().__init__(name="Rand", ctx=ctx)
        self.shape = tuple(shape)

    def compute(self, input_vals, tc: TraceContext):
        return jax.random.uniform(tc.rng_for(self), self.shape)

    def gradient(self, output_grad):
        return []


def rand_op(shape, ctx=None):
    return RandOp(shape, ctx=ctx)
