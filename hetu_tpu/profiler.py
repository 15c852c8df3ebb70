"""Profilers: per-step timing, per-op HLO cost attribution, comm probe.

Reference: python/hetu/profiler.py (HetuProfiler:55 times each node over
synthetic inputs with CUDA events; NCCLProfiler:389 measures allreduce
bandwidth per group topology; TimerSubExecutor wraps each compute).

TPU-native: the per-op wall-clock loop is meaningless under XLA fusion, so
HetuProfiler reports (a) whole-step wall time with device sync and (b) XLA
cost-analysis FLOPs/bytes per compiled step (a device trace is the
benchmark's to take and reduce: benchmarks/xplane.py).  NCCLProfiler becomes a collective probe over
mesh axes (ICI/DCN bandwidth), feeding the planner's cost model exactly as
the reference's fed Galvatron.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp


class HetuProfiler:
    def __init__(self, executor=None, feed_shapes=None, log_file=None):
        self.executor = executor
        self.feed_shapes = feed_shapes or {}
        self.log_file = log_file
        self.records = []

    def profile_step(self, name="train", feed_dict=None, warmup=2, iters=10):
        """Whole-step timing with blocking on outputs."""
        feed_dict = feed_dict or self._synth_feeds()
        sub = self.executor.subexecutor[name]
        for _ in range(warmup):
            res = sub.run(feed_dict)
        jax.block_until_ready(res)
        t0 = time.perf_counter()
        for _ in range(iters):
            res = sub.run(feed_dict)
        jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / iters
        self.records.append({"name": name, "step_time_s": dt})
        if self.log_file:
            with open(self.log_file, "a") as f:
                f.write(f"{name} step_time_s={dt:.6f}\n")
        return dt

    def _compiled_step(self, name):
        """AOT-lower + compile the step once for analysis (a full extra
        XLA compile — shared by cost_analysis/memory_analysis so asking
        for both pays it once)."""
        cached = getattr(self, "_analysis_cache", {}).get(name)
        if cached is not None:
            return cached
        sub = self.executor.subexecutor[name]
        if not sub._compiled:
            return None
        fn = next(iter(sub._compiled.values()))
        try:
            from .executor import gather_feeds
            # the compiled step takes NAME-keyed feeds (node-keyed dicts
            # don't even sort as a jax pytree); route synthetic feeds
            # through the same conversion SubExecutor.run uses — with
            # peek=True so the analysis never consumes a training batch
            compiled = fn.lower(
                self.executor.var_values, self.executor.opt_states,
                self.executor.step, self.executor.rng,
                gather_feeds(sub, self._synth_feeds(),
                             peek=True)).compile()
        except Exception:
            return None
        if not hasattr(self, "_analysis_cache"):
            self._analysis_cache = {}
        self._analysis_cache[name] = compiled
        return compiled

    def cost_analysis(self, name="train"):
        """FLOPs / bytes-accessed of the compiled step (XLA cost model)."""
        compiled = self._compiled_step(name)
        if compiled is None:
            return None
        try:
            cost = compiled.cost_analysis()
        except Exception:
            return None
        # pre-0.5 jax returns a one-element list of per-device dicts;
        # newer jax returns the dict directly
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return cost

    def memory_analysis(self, name="train"):
        """HBM footprint of the compiled step — the role of the
        reference's memory-plan dry-run (memory_pool.py:142 test_memory):
        bytes for arguments (params+opt state+feeds), outputs, temps, and
        the generated program, per the XLA allocator.  Returns a dict or
        None before first compile."""
        compiled = self._compiled_step(name)
        if compiled is None:
            return None
        try:
            m = compiled.memory_analysis()
        except Exception:
            return None
        if m is None:
            return None
        out = {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
            if hasattr(m, k)}
        if out:
            # donation aliases params/opt state into outputs; only the
            # NON-aliased output bytes (losses, metrics, PS side grads)
            # are additional live memory at step end
            out["peak_estimate_bytes"] = (
                out.get("argument_size_in_bytes", 0)
                + out.get("temp_size_in_bytes", 0)
                + out.get("generated_code_size_in_bytes", 0)
                + max(0, out.get("output_size_in_bytes", 0)
                      - out.get("alias_size_in_bytes", 0)))
        return out or None

    def _synth_feeds(self):
        return {k: np.zeros(s, np.float32) for k, s in self.feed_shapes.items()}


class TPUProfiler(HetuProfiler):
    pass


class NCCLProfiler:
    """Collective bandwidth probe over mesh axes (reference profiler.py:389
    NCCLProfiler measured allreduce over enumerated NCCL groups; here we
    measure psum/all_gather/all_to_all over each axis of a mesh — the
    numbers feed the auto-parallel cost model)."""

    def __init__(self, mesh=None):
        from .parallel.mesh import default_mesh
        self.mesh = mesh if mesh is not None else default_mesh()

    def profile_allreduce(self, size_mb=16, axis=None, iters=5):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        axis = axis or self.mesh.axis_names[0]
        n = self.mesh.shape[axis]
        nelem = int(size_mb * 1024 * 1024 / 4)
        x = jnp.ones((n * ((nelem + n - 1) // n),), jnp.float32)

        @jax.jit
        def f(x):
            return shard_map(lambda v: jax.lax.psum(v, axis), mesh=self.mesh,
                             in_specs=P(axis), out_specs=P(axis))(x)

        jax.block_until_ready(f(x))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(x)
        jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / iters
        bytes_moved = 2 * (n - 1) / n * x.nbytes
        return {"axis": axis, "time_s": dt,
                "algo_bw_gbps": bytes_moved / dt / 1e9}
