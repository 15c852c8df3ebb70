"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json`` (named by the entry's
``file``), ``traffic/<traffic>.json``, ``metrics/<metric>.json``, and
the code they name, ``runners/<runner>.py`` and ``readers/<reader>.py``.
Adding a cell, a configuration, a metric, a runner or a reader edits no
file that is there (see README.md).

Without a TPU, with fewer chips than the cell asks for, or on a
``device_kind`` that ``peaks.json`` does not hold, the command exits
non-zero before it builds anything and prints no result.  The last line
of standard output is the result object; everything else a reader may
want (MFU, sample counts, cache hits, the engine's settings) is on
earlier lines, one JSON object each.  The last lines of standard error
are the numbers ``correct`` compared, each beside its limit (a runner's
``compared``).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, here=HERE):
    """``<here>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind[:-1]} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(bench, workload, root=ROOT, here=HERE):
    """The cell's entry, its configuration and traffic files, and the
    names of the metrics it reports, all from ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no cell {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def reported(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(os.path.join(
            here, "traffic", f"{cell['traffic']}.json")),
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
    }


def require_device(chips, peaks):
    """The device as JAX reports it, or exit before any work."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX found platform="
                         f"{d.platform!r} ({d.device_kind}). Nothing was run.")
    if d.device_kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device_kind "
                         f"{d.device_kind!r} in peaks.json. Nothing was run.")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s); JAX "
                         f"found {len(devices)}. Nothing was run.")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def enable_compile_cache(root=ROOT):
    """JAX's persistent cache where the environment says, else at the
    fixed ``<checkout>/.jax_cache`` (the path is part of the cache key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, is kept: a warm run must
    # find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Harness:
    """What a runner is handed: the cell's data, the clock of the
    window, spans on the trace's clock and the trace itself."""

    def __init__(self, resolved, seed, seconds, trace, peak, root=ROOT,
                 out=None):
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.peak = peak
        self.root = root
        self.out = out
        self.setup_s = None
        self.tracing = False
        self.spans_muted = False
        # the profiler's files of the cell's last traced run stay here (git
        # ignores the directory) for whoever wants to look at one by hand
        self.trace_dir = os.path.join(root, ".bench_trace", self.cell["name"])
        self.compiles = {"programs": 0, "cache_hits": 0}
        self._at_open = None
        self._listening = False

    # ---- earlier lines ------------------------------------------------ #

    def log(self, **record):
        print(json.dumps(record, default=float), file=self.out or sys.stdout,
              flush=True)

    # ---- compiles ----------------------------------------------------- #

    def count_compiles(self):
        """Count every program this process builds or loads from the
        persistent cache from now on."""
        if self._listening:
            return
        import jax
        self._listening = True

        def on_duration(event, _secs, **_):
            if event == COMPILE_EVENT:
                self.compiles["programs"] += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.compiles["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    # ---- the window --------------------------------------------------- #

    def open_window(self):
        """Set-up ends here.  Returns the window's zero on the
        ``time.perf_counter`` clock."""
        now = time.perf_counter()
        self.setup_s = now - T_PROCESS_START
        self._at_open = self.compiles["programs"]
        return now

    def mute_spans(self):
        """The measured window is over: what the trace still records (a
        drain, a profiler that is stopped a little later) stays outside
        the span-to-span window the reduction reads."""
        self.spans_muted = True

    def close_window(self):
        """Nothing may have compiled, or loaded, since ``open_window``."""
        built = self.compiles["programs"] - self._at_open
        if built:
            raise SystemExit(
                f"benchmark: {built} program(s) were compiled or loaded "
                f"inside the measured window; warm them up in set-up")

    # ---- spans and the trace ------------------------------------------ #

    def span(self, name):
        """A host span on the device trace's clock while a trace is
        being taken; nothing otherwise (end-to-end runs pay nothing)."""
        if not self.tracing or self.spans_muted:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def trace_start(self):
        if not self.trace or self.tracing:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.tracing = True

    def trace_stop(self):
        if not self.tracing:
            return
        import jax
        jax.profiler.stop_trace()
        self.tracing = False


def per_layer_metrics(entries, data, here=HERE):
    """Each per-layer metric through the reader its file names; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        spec = load_json(os.path.join(here, "metrics", f"{m['name']}.json"))
        reader = load_module("readers", spec["reader"], here)
        value = reader.read(data, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def report_compared(result, err=None):
    """Each number ``correct`` compared, beside its limit, as the last
    lines of standard error: what is left of a run that was not correct
    is the end of that stream and the result line's keys."""
    for c in result.get("compared", []):
        print(f"compared: {c['name']} = {c['value']!r} limit {c['limit']!r} "
              f"({'within' if c['within'] else 'OUTSIDE'})",
              file=err or sys.stderr, flush=True)
    print(f"correct: {bool(result['correct'])}", file=err or sys.stderr,
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = resolve_cell(bench, args.workload)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    device = require_device(resolved["cell"]["chips"], peaks)
    cache_dir = enable_compile_cache()

    sys.path.insert(0, ROOT)
    h = Harness(resolved, args.seed, args.seconds, args.trace,
                peaks[device["kind"]])
    h.count_compiles()
    runner = load_module("runners", resolved["config"]["runner"])
    try:
        result = runner.run(h)
    finally:
        h.trace_stop()

    e2e = dict(result["end_to_end"], setup_s=h.setup_s)
    h.log(line="process", cell=args.workload, seed=args.seed,
          seconds=args.seconds, compile_cache_dir=cache_dir,
          programs_built_or_loaded=h.compiles["programs"],
          compile_cache_hits=h.compiles["cache_hits"],
          end_to_end=e2e, notes=result.get("notes", {}))

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        from benchmarks import xplane
        path = xplane.find_xplane(h.trace_dir)
        if path is None:
            raise SystemExit("benchmark: the profiler wrote no trace")
        trace = xplane.load(path)
        busy_s, window_s = xplane.busy_seconds(trace)
        if not busy_s:
            raise SystemExit("benchmark: no operation ran on the device "
                             "inside the traced window")
        data = dict(result.get("data", {}), trace=trace, harness=h)
        line["metrics"] = per_layer_metrics(resolved["per_layer"], data)
        device["busy_s"], device["window_s"] = busy_s, window_s
        line["breakdown"] = {"device_ops": xplane.top_ops(trace),
                             "idle_gaps": xplane.idle_gaps(trace)}
    else:
        missing = [m["name"] for m in resolved["end_to_end"]
                   if e2e.get(m["name"]) is None]
        if missing:
            raise SystemExit(f"benchmark: the runner reported no {missing}")
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
                           for m in resolved["end_to_end"]}
    line["device"] = device
    print(json.dumps(line), file=h.out or sys.stdout, flush=True)
    report_compared(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
