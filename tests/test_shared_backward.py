"""One backward a node (ISSUE 33): the gradient nodes of a trace use what
the forward's own trace saved.

The executor computes a node that has ``VJPOp`` consumers under
``jax.vjp`` and keeps the pullback in the trace (``ops_misc.Backward``);
the VJPOps of one ``(node, output_grad)`` share one call of it, and the
tied-head loss's three gradient nodes share one backward scan.  Checked
here on the CPU (Pallas kernels interpreted): the STRUCTURE of a GPT train
step, bit-for-bit PARITY with the path that traces every forward again,
the FALLBACK where a trace holds no pullback, and the COUNTERS.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import executor as executor_mod
from hetu_tpu import telemetry
from hetu_tpu.executor import gather_feeds
from hetu_tpu.graph.node import TraceContext
from hetu_tpu.graph.ops_misc import Backward, SharedBackwardOp, VJPOp


class _NothingShared(Backward):
    """A trace that saves nothing: every gradient node traces its forward
    again and every hand-written backward runs once a node, which is what
    the graph did before the trace kept anything."""

    def __init__(self, topo):
        super().__init__(())

    def once(self, key, backward):
        return backward()


def _grad_counters():
    counters = telemetry.snapshot()["counters"]
    return (counters.get("exec.grad.shared", 0),
            counters.get("exec.grad.retraced", 0))


# ------------------------------------------------------------------- #
# (a) structure, (d) counters: a two-layer GPT train step
# ------------------------------------------------------------------- #

LAYERS = 2


def _primitives(jaxpr, counts):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    by primitive; a ``pallas_call`` by its kernel's ``name=``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        else:
            counts[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, counts)
    return counts


@pytest.fixture(scope="module")
def gpt_step():
    """(primitive counts of the step's jaxpr, the counters its one trace
    moved, its gradient nodes).  The run-time verifier walks the graph a
    node at a time and would count every gradient node as retraced before
    the step is traced: off here, as in a deployment."""
    from hetu_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=LAYERS, num_attention_heads=2,
                    max_position_embeddings=256, dropout_rate=0.0,
                    batch_size=2, seq_len=256, use_flash=True)
    model = GPTForCausalLM(cfg, name="sb_gpt")
    ids = ht.placeholder_op("sb_gpt_ids")
    labels = ht.placeholder_op("sb_gpt_labels")
    loss, _ = model(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     mixed_precision="bf16", seed=0)
    x = np.random.RandomState(0).randint(0, 512, (2, 256)).astype(np.int32)
    sub = ex.subexecutor["train"]
    feeds = gather_feeds(sub, {ids: x, labels: x}, peek=True)
    sig = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                       for k, v in feeds.items()))
    before = _grad_counters()
    jaxpr = jax.make_jaxpr(sub._compile(sig))(
        ex.var_values, ex.opt_states, ex.step, ex.rng, feeds)
    after = _grad_counters()
    grad_nodes = [n for n in sub.topo
                  if isinstance(n, (VJPOp, SharedBackwardOp))]
    return (_primitives(jaxpr.jaxpr, collections.Counter()),
            (after[0] - before[0], after[1] - before[1]), grad_nodes)


@pytest.mark.parametrize("primitive,calls", [
    ("flash_fwd", LAYERS),          # was 4 a layer: the node + q, k, v
    ("flash_bwd_dkv", LAYERS),      # was 3 a layer
    ("flash_bwd_dq", LAYERS),
    ("scan", 2),                    # the tied head: forward + ONE backward
    ("custom_vjp_call", 0),         # no forward left outside a jax.vjp
])
def test_gpt_step_traces_each_forward_and_backward_once(gpt_step, primitive,
                                                        calls):
    counts, _, _ = gpt_step
    assert counts[primitive] == calls, dict(counts)


def test_gpt_step_retraces_nothing(gpt_step):
    _, (shared, retraced), grad_nodes = gpt_step
    assert retraced == 0
    assert shared == len(grad_nodes) > 0
    assert sum(isinstance(n, SharedBackwardOp) for n in grad_nodes) == 3


# ------------------------------------------------------------------- #
# (b) parity with the re-traced path, bit for bit
# ------------------------------------------------------------------- #

def _var(name, value, trainable=True, dtype=jnp.float32):
    return ht.Variable(name, value=value, trainable=trainable, dtype=dtype)


def _sum_sq(node, axes):
    return ht.reduce_sum_op(ht.mul_op(node, node), axes)


def _linear(p, rng):
    x = _var(p + "x", rng.randn(6, 8).astype(np.float32))
    w = _var(p + "w", rng.randn(8, 5).astype(np.float32))
    b = _var(p + "b", rng.randn(5).astype(np.float32))
    return _sum_sq(ht.linear_op(x, w, b), [0, 1]), [x, w, b]


def _flash(p, rng, masked):
    q, k, v = (_var(p + n, rng.randn(2, 128, 2, 64).astype(np.float32))
               for n in "qkv")
    lens = _var(p + "lens", np.array([128, 70], np.int32), trainable=False,
                dtype=jnp.int32) if masked else None
    o = ht.flash_attention_op(q, k, v, causal=not masked, kv_lens=lens)
    return _sum_sq(o, [0, 1, 2, 3]), [q, k, v]


def _tied_head(p, rng):
    h = _var(p + "h", rng.randn(12, 16).astype(np.float32))
    table = _var(p + "table", rng.randn(40, 16).astype(np.float32))
    bias = _var(p + "bias", rng.randn(40).astype(np.float32))
    y = rng.randint(0, 40, 12).astype(np.int32)
    y[3] = -1                                           # an ignored row
    labels = _var(p + "y", y, trainable=False, dtype=jnp.int32)
    loss = ht.tied_lm_head_xent_op(h, table, bias, labels, ignored_index=-1,
                                   n_chunks=5)          # 12 rows pad to 15
    return ht.reduce_sum_op(loss, [0]), [h, table, bias]


def _dropout(p, rng):
    x = _var(p + "x", rng.randn(16, 32).astype(np.float32))
    return _sum_sq(ht.dropout_op(x, 0.5), [0, 1]), [x]


def _two_consumers(p, rng):
    """``h`` feeds two consumers, and so does ``r``: their adjoints are
    sums, each forward still has ONE output gradient."""
    x = _var(p + "x", rng.randn(6, 8).astype(np.float32))
    w = _var(p + "w", rng.randn(8, 8).astype(np.float32))
    b = _var(p + "b", rng.randn(8).astype(np.float32))
    h = ht.linear_op(x, w, b)
    r = ht.relu_op(h)
    return ht.reduce_sum_op(ht.mul_op(ht.gelu_op(h), ht.matmul_op(r, w))
                            + r, [0, 1]), [x, w, b]


def _two_output_grads(p, rng):
    """ONE forward differentiated for two losses: two output gradients,
    two calls of the one saved pullback."""
    x = _var(p + "x", rng.randn(6, 8).astype(np.float32))
    w = _var(p + "w", rng.randn(8, 5).astype(np.float32))
    b = _var(p + "b", rng.randn(5).astype(np.float32))
    h = ht.linear_op(x, w, b)
    second = ht.reduce_sum_op(ht.tanh_op(h), [0, 1])
    extra = ht.gradients(second, [x, w])
    return _sum_sq(h, [0, 1]), [x, w, b], extra


def _integer_input(p, rng):
    """Integer labels through the generic fallback: their cotangent is
    ``float0`` on both paths and nobody reads it."""
    logits = _var(p + "z", rng.randn(6, 10).astype(np.float32))
    labels = _var(p + "y", rng.randint(0, 10, 6).astype(np.int32),
                  trainable=False, dtype=jnp.int32)
    probs = ht.softmax_op(logits)
    return ht.reduce_sum_op(ht.crossentropy_sparse_op(probs, labels),
                            [0]), [logits]


GRAPHS = {
    "linear": _linear,
    "flash-causal": lambda p, rng: _flash(p, rng, masked=False),
    "flash-kv_lens": lambda p, rng: _flash(p, rng, masked=True),
    "tied-head-ignored-row": _tied_head,
    "dropout-same-mask": _dropout,
    "two-consumers": _two_consumers,
    "two-output-grads": _two_output_grads,
    "integer-labels": _integer_input,
}


def _run(build, prefix, seed=7):
    """[loss, gradients...] of one TRAINING step (dropout on): the
    subgraph holds an optimizer, whose learning rate is 0."""
    loss, wrt, *extra = build(prefix, np.random.RandomState(seed))
    nodes = [loss] + ht.gradients(loss, wrt) + (extra[0] if extra else [])
    step = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(loss)
    ex = ht.Executor({"g": nodes + [step]}, seed=3)
    return ex.run("g", convert_to_numpy_ret_vals=True)[:-1]


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_shared_path_equals_the_retraced_path(graph, monkeypatch):
    before = _grad_counters()
    shared = _run(GRAPHS[graph], f"sp_{graph}_")
    served, retraced = (a - b for a, b in zip(_grad_counters(), before))
    assert served > 0
    monkeypatch.setattr(executor_mod, "Backward", _NothingShared)
    again = _run(GRAPHS[graph], f"rt_{graph}_")
    assert len(shared) == len(again) > 1
    for a, b in zip(shared, again):
        assert np.all(np.isfinite(a))
        np.testing.assert_array_equal(a, b)
    if graph == "dropout-same-mask":
        dropped = shared[1] == 0
        assert dropped.any() and not dropped.all()


def test_batchnorm_writes_its_statistics_once(monkeypatch):
    """The running statistics leave the forward's ``jax.vjp`` as
    ``has_aux`` outputs: written (a leaked inner tracer would raise when
    the step returns them), and written by the forward alone."""
    def run(prefix):
        rng = np.random.RandomState(5)
        x = _var(prefix + "x", rng.randn(8, 4, 3, 3).astype(np.float32))
        scale = _var(prefix + "scale", rng.rand(4).astype(np.float32) + .5)
        bias = _var(prefix + "bias", rng.randn(4).astype(np.float32))
        bn = ht.batch_normalization_op(x, scale, bias, momentum=0.9)
        loss = _sum_sq(ht.relu_op(bn), [0, 1, 2, 3])
        opt = ht.optim.SGDOptimizer(learning_rate=0.0)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)] +
                          ht.gradients(loss, [x, scale, bias])}, seed=1)
        out = ex.run("train", convert_to_numpy_ret_vals=True)
        stats = [np.asarray(ex.var_values[prefix + "scale_running_" + s])
                 for s in ("mean", "var")]
        return [out[0]] + list(out[2:]) + stats

    shared = run("bn_s_")
    monkeypatch.setattr(executor_mod, "Backward", _NothingShared)
    again = run("bn_r_")
    assert not np.allclose(shared[-2], 0.0)        # the mean moved off 0
    for a, b in zip(shared, again):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------------- #
# (c) fallback: a trace that holds no pullback
# ------------------------------------------------------------------- #

def _lone_vjp(prefix):
    x = _var(prefix + "x", np.ones((6, 8), np.float32))
    w = _var(prefix + "w", np.ones((8, 5), np.float32))
    b = _var(prefix + "b", np.ones((5,), np.float32))
    g = _var(prefix + "g", np.full((6, 5), 2.0, np.float32),
             trainable=False)
    return VJPOp(ht.linear_op(x, w, b), g, 1), (x, w, b, g)


def _infer_shape_alone():
    node, _ = _lone_vjp("fb_shape_")
    assert node.infer_shape([(6, 8), (8, 5), (5,), (6, 5)]) == (8, 5)
    return 1


def _verify_walks_node_by_node():
    from hetu_tpu.analysis.verify import verify_graph
    loss, wrt = _linear("fb_verify_", np.random.RandomState(0))
    grads = ht.gradients(loss, wrt)
    verify_graph([loss] + grads)
    return sum(isinstance(n, VJPOp)
               for n in executor_mod.find_topo_sort(grads))


def _subgraph_without_its_forward():
    node, _ = _lone_vjp("fb_sub_")
    ex = ht.Executor({"g": [node]})
    out, = ex.run("g", convert_to_numpy_ret_vals=True)
    np.testing.assert_array_equal(out, np.full((8, 5), 12.0, np.float32))
    return 1


def _tied_head_node_alone():
    loss, wrt = _tied_head("fb_tied_", np.random.RandomState(0))
    grad_w = ht.gradients(loss, wrt)[1]
    assert isinstance(grad_w, SharedBackwardOp)
    tc = TraceContext(training=False)
    h, table, bias, y = (np.asarray(v.tensor_value)
                         for v in grad_w.inputs[1:])
    out = grad_w.compute([np.ones((12,), np.float32), h, table, bias, y],
                         tc)
    assert out.shape == table.shape
    return 1


@pytest.mark.parametrize("alone", [
    _infer_shape_alone, _verify_walks_node_by_node,
    _subgraph_without_its_forward, _tied_head_node_alone,
], ids=lambda f: f.__name__.strip("_"))
def test_a_trace_without_the_pullback_traces_it_again(alone, monkeypatch):
    monkeypatch.setenv("HETU_VALIDATE", "0")
    before = _grad_counters()
    expected = alone()
    shared, retraced = (a - b for a, b in zip(_grad_counters(), before))
    assert (shared, retraced) == (0, expected)
