"""Planner profiler: measure what the cost models need.

Galvatron profiles per-layer forward time and inter-GPU bandwidth with
standalone scripts (tools/Galvatron/test_env, bert/profile_forward.py)
whose outputs feed the cost models.  Here both probes are jax functions:

- :func:`profile_matmul_throughput` — achieved bf16 matmul FLOP/s (the
  ``flops_per_sec * mfu`` product).
- :func:`profile_collective_bandwidth` — ring-allreduce bytes/s over a
  mesh axis (ICI when the mesh spans real chips).
- :func:`profile_layer` — measured per-sample forward seconds for a layer
  callable, written into :class:`LayerSpec.fwd_time_per_sample`.
- :func:`measure_cluster` — bundle everything into a ClusterSpec.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from .cost_model import ClusterSpec


def _timeit(fn, *args, warmup=2, iters=5):
    """Wall time per call; ``block_until_ready`` on the last output is the
    barrier (calls on one device run in dispatch order)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def profile_matmul_throughput(dim=4096, dtype=jnp.bfloat16):
    a = jnp.ones((dim, dim), dtype)
    b = jnp.ones((dim, dim), dtype)
    f = jax.jit(lambda x, y: x @ y)
    t = _timeit(f, a, b)
    return 2.0 * dim ** 3 / t


def graph_layer_fn(output_node, feed_node):
    """Jitted ``x -> output`` from a built graph block — lets the profiler
    time REAL model layers (built from the hetu_tpu graph API) instead of
    analytic stand-ins.  Reference counterpart: Galvatron's per-model
    profile scripts time the actual torch modules
    (bert/profile_forward.py)."""
    from ..executor import Executor
    ex = Executor({"fwd": [output_node]})
    sub = ex.subexecutor["fwd"]
    params = dict(ex.var_values)

    def fn(x):
        _, _, outputs, _ = sub._trace(
            params, {}, jnp.zeros((), jnp.int32), jax.random.PRNGKey(0),
            {feed_node.name: x})
        return outputs[0]

    return jax.jit(fn)


def calibrate_layers(layers, layer_fns, batch=8, dtype=jnp.float32):
    """Measure each layer callable and write the result into its
    LayerSpec.fwd_time_per_sample (the TimeCostModel then uses measured
    time instead of the flops estimate).  ``layer_fns`` may be shorter
    than ``layers``: the last fn calibrates the remaining (identical)
    layers — the common N-identical-encoder case profiles once."""
    times = []
    for i, spec in enumerate(layers):
        if i < len(layer_fns):
            fn = layer_fns[i]
            t = profile_layer(fn, (spec.seq_len, spec.hidden),
                              batch=batch, dtype=dtype)
            times.append(t)
        else:
            t = times[-1]
        spec.fwd_time_per_sample = t
    return layers


def profile_collective_bandwidth(mesh, axis, size_mb=16):
    """Achieved allreduce bandwidth (algorithm bytes/s) over one mesh
    axis, via shard_map psum."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    k = mesh.shape[axis]
    if k <= 1:
        return float("inf")
    n = int(size_mb * 1024 * 1024 / 4)
    n -= n % k
    x = jnp.ones((n,), jnp.float32)

    # check_vma off: the input may be replicated over the mesh's other
    # axes, which static varying-axes inference can't always prove for
    # out_specs P()
    f = jax.jit(shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                          in_specs=P(axis), out_specs=P(),
                          check_vma=False))
    t = _timeit(f, x)
    nbytes = n * 4 / k  # per-device message size (input sharded over axis)
    return 2.0 * (k - 1) / k * nbytes / t


def profile_layer(layer_fn, sample_shape, batch=8, dtype=jnp.float32,
                  seed=0):
    """Measured per-sample forward time of ``layer_fn(batch_input)``."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(batch, *sample_shape).astype(
        np.dtype(dtype.dtype.name if hasattr(dtype, "dtype") else "float32")))
    f = jax.jit(layer_fn)
    t = _timeit(f, x)
    return t / batch


def measure_cluster(mesh=None, n_devices=None, hbm_bytes=None,
                    probe_dim=4096):
    """Build a ClusterSpec from live measurements (analytic defaults fill
    anything unmeasurable on the current backend).  ``probe_dim`` sizes
    the matmul probe — shrink it on slow backends (CPU tests)."""
    spec = ClusterSpec()
    spec.n_devices = n_devices or (
        int(np.prod(list(mesh.shape.values()))) if mesh is not None
        else jax.device_count())
    achieved = profile_matmul_throughput(dim=probe_dim)
    spec.flops_per_sec = achieved
    spec.provenance["flops_per_sec"] = "measured"
    spec.mfu = 1.0  # 'achieved' already folds utilization in
    spec.provenance["mfu"] = "measured"
    if hbm_bytes:
        spec.hbm_bytes = hbm_bytes
        spec.provenance["hbm_bytes"] = "measured"   # caller-supplied cap
    else:
        try:
            stats = jax.devices()[0].memory_stats()
            if stats and "bytes_limit" in stats:
                spec.hbm_bytes = float(stats["bytes_limit"])
                spec.provenance["hbm_bytes"] = "measured"
        except Exception:
            pass
    if mesh is not None:
        for axis in mesh.shape:
            if mesh.shape[axis] > 1:
                bw = profile_collective_bandwidth(mesh, axis, size_mb=4)
                if np.isfinite(bw):
                    spec.ici_bandwidth = min(spec.ici_bandwidth, bw)
                    spec.provenance["ici_bandwidth"] = "measured"
                break
    return spec
