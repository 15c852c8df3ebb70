"""Where compiled programs are kept between processes, and what
building them cost this one.

Two things, one module.  ``enable_compile_cache()`` is the one helper
for every entry point that compiles on the chip (``chip_smoke.py``,
``benchmarks/run.py``, the examples): JAX's persistent compilation
cache, placed from outside when the environment says where and
otherwise at one fixed place inside the checkout.  The directory is part of the cache key, so
it must not move between runs: never ``/tmp``, a pid or a timestamp.

``watch()`` is the one place ``hetu_tpu`` listens to ``jax.monitoring``
(installed when ``hetu_tpu.telemetry`` is imported with
``HETU_TELEMETRY`` on).  Every program JAX traces, lowers, compiles or
loads from the persistent cache becomes ``compile`` records:

    {"t": <start>, "event": "compile",
     "phase": "trace" | "lower" | "backend" | "cache_load",
     "fun": <JAX's fun_name>, "ms", "t0", "t1",      # time.time()
     "end_perf": <time.perf_counter() when the record arrived>,
     "parent": {"name": <innermost open telemetry span of the thread>,
                **<its fields>} | None,
     "under": [<every open span's name, outermost first>],
     "cache": "hit" | "miss" | None,                 # backend only
     "nested": <short traces inside it that were not kept>,  # outermost only
     "pid", "tid", "us"}

``lower`` holds Pallas' lowering to Mosaic; ``backend`` is XLA, or the
cache's load on a hit, of which ``cache_load`` (a duration alone: JAX
gives it no start) is the part spent reading the cache; ``cache`` is
None where the cache was not asked or kept nothing (off, or a program
under its thresholds).  The records live in a bounded store of the
watch's own (``records()``; the sink's ring is turned over by a serving
window's ``serve_step`` events), go to ``$HETU_TELEMETRY_LOG`` through
``telemetry.emit`` when it is set (``bin/hetu_trace.py --export`` draws
them as a track of their own), and feed the registry: counters
``compile.programs``, ``compile.cache_hits``, ``compile.cache_misses``
and the running sums ``compile.trace_ms``, ``compile.lower_ms``,
``compile.backend_ms``, ``compile.cache_load_ms``.

A phase's seconds are the UNION of its records' intervals: a ``jit``
traced inside another's trace reports a duration of its own, and adding
durations counts the inner one twice.  A training step's trace holds
sixteen thousand such inner traces (every ``jax.numpy`` function is a
``jit``, and a lowering traces more), nearly all of them microseconds
long: a trace that is shorter than a millisecond and lies inside another
phase of its thread, a trace or a lowering, is counted into the
outermost one's ``nested`` and otherwise left alone (its time is that
phase's already; keeping each cost the training cell 4 s of its
set-up).  ``summary(before=)`` gives the seconds per phase over
the records that ended before a ``time.perf_counter()`` mark: a
process's set-up, taken apart.
"""

from __future__ import annotations

import collections
import os
import threading
import time

from . import envvars, locks
from .telemetry import events
from .telemetry.metrics import REGISTRY

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_SUMS = {"trace": "compile.trace_ms", "lower": "compile.lower_ms",
         "backend": "compile.backend_ms",
         "cache_load": "compile.cache_load_ms"}
_COUNTS = {"hit": "compile.cache_hits", "miss": "compile.cache_misses"}
# a cell's set-up is a thousand records or two (every eager operation's
# first shape is a program too)
_KEEP = 4096
# a trace inside another phase that is shorter than this is counted, not kept
_NESTED_MIN_S = 1e-3


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already uses that directory and
    nothing is set in code.  Unset: ``<checkout>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def merge_interval(merged, t0, t1):
    """Add ``[t0, t1]`` to ``merged``, a sorted list of disjoint
    ``[start, end]`` pairs, in place; returns the seconds of it that no
    earlier interval covered.  Intervals come in any order (JAX reports
    an inner trace before the one that holds it)."""
    lo = len(merged)
    while lo and merged[lo - 1][1] >= t0:
        lo -= 1
    hi, covered, start, end = lo, 0.0, t0, t1
    while hi < len(merged) and merged[hi][0] <= t1:
        a, b = merged[hi]
        covered += max(0.0, min(b, t1) - max(a, t0))
        start, end = min(start, a), max(end, b)
        hi += 1
    merged[lo:hi] = [[start, end]]
    return (t1 - t0) - covered


def union_seconds(intervals):
    """Seconds covered by the union of ``(t0, t1)`` pairs."""
    merged = []
    return sum(merge_interval(merged, t0, t1) for t0, t1 in intervals)


class CompileWatch:
    """The store and the listeners; the process has one, ``WATCH``."""

    def __init__(self, keep=_KEEP):
        self._lock = locks.TracedLock("compile.watch")
        self._records = collections.deque(maxlen=keep)
        self._covered = {phase: [] for phase in _SPANS.values()}
        # what a thread's open phases have seen: its cache events since
        # its last ``backend`` span (which closes after them and names
        # the program they belong to), how deep its open trace is nested
        # and the short traces inside it
        self._pending = threading.local()
        self.seen = 0        # records ever made; the store keeps the newest
        self.installed = False

    def install(self):
        """Register the listeners; once, however often it is called."""
        from jax import monitoring
        with self._lock:
            if self.installed:
                return True
            self.installed = True
        monitoring.register_event_time_span_listener(self.on_span)
        monitoring.register_scalar_listener(self.on_start)
        monitoring.register_event_listener(self.on_event)
        monitoring.register_event_duration_secs_listener(self.on_duration)
        return True

    # ---- JAX's side ---------------------------------------------------- #

    def on_start(self, event, _start, **_):
        # JAX reports a phase's start as a scalar; of the sixteen thousand
        # traces inside a training step's this is all the first half pays
        if event in _SPANS:
            seen = vars(self._pending)
            seen["depth"] = seen.get("depth", 0) + 1

    def on_event(self, event, **_):
        outcome = _CACHE_EVENTS.get(event)
        if outcome is not None and events.enabled():
            vars(self._pending)["cache"] = outcome

    def on_duration(self, event, secs, **_):
        if event == _LOAD and events.enabled():
            vars(self._pending)["load_s"] = float(secs)

    def on_span(self, event, t0, t1, fun_name=None, **_):
        phase = _SPANS.get(event)
        if phase is None:
            return
        seen = vars(self._pending)
        depth = seen["depth"] = max(seen.get("depth", 1) - 1, 0)
        if depth and phase == "trace" and t1 - t0 < _NESTED_MIN_S:
            seen["nested"] = seen.get("nested", 0) + 1
            return
        extra = {} if depth else {"nested": seen.pop("nested", 0)}
        if not events.enabled():
            return
        end_perf = time.perf_counter()
        spans = events.open_spans()
        common = {
            "fun": str(fun_name), "end_perf": end_perf,
            "parent": spans[-1] if spans else None,
            "under": [s["name"] for s in spans], "pid": os.getpid(),
            "tid": threading.current_thread().name}
        if phase != "backend":
            self._record(phase, t0, t1, **extra, **common)
            return
        load_s, outcome = seen.pop("load_s", None), seen.pop("cache", None)
        if load_s is not None:
            # somewhere inside the backend span; drawn at its start
            self._record("cache_load", t0, t0 + load_s, **common)
        self._record("backend", t0, t1, cache=outcome, **extra, **common)
        REGISTRY.counter("compile.programs").inc()
        if outcome is not None:
            REGISTRY.counter(_COUNTS[outcome]).inc()

    def _record(self, phase, t0, t1, **fields):
        fields.update(phase=phase, ms=round((t1 - t0) * 1e3, 3),
                      t0=t0, t1=t1, us=int(t0 * 1e6))
        if envvars.is_set("HETU_TELEMETRY_LOG"):
            rec = events.emit("compile", _t=t0, **fields)
        else:
            rec = events.make_record("compile", t=t0, **fields)
        with self._lock:
            self.seen += 1
            self._records.append(rec)
            if phase == "cache_load":
                new_s = t1 - t0
            else:
                covered = self._covered[phase]
                new_s = merge_interval(covered, t0, t1)
                # nothing new reaches back past the store's own length
                del covered[:-self._records.maxlen]
        REGISTRY.counter(_SUMS[phase]).inc(round(new_s * 1e3, 3))

    # ---- the reader's side --------------------------------------------- #

    def records(self):
        with self._lock:
            return list(self._records)

    def summary(self, before=None, under=None):
        """Seconds by phase (the union of the phase's intervals;
        ``cache_load`` a plain sum, a part of ``backend``), ``union_s``
        over the three phases together (less than their sum where a
        lowering traces), the counts and the ten longest records, over
        the records that ended before ``before`` (``time.perf_counter``)
        and, with ``under``, inside an open span of that name."""
        with self._lock:
            kept, seen = list(self._records), self.seen
        recs = [r for r in kept
                if (before is None or r["end_perf"] <= before)
                and (under is None or under in r["under"])]
        by_phase = {phase: [(r["t0"], r["t1"]) for r in recs
                            if r["phase"] == phase] for phase in _SUMS}
        seconds = {phase: union_seconds(by_phase[phase])
                   for phase in _SPANS.values()}
        seconds["cache_load"] = sum(
            t1 - t0 for t0, t1 in by_phase["cache_load"])
        outcomes = collections.Counter(
            r["cache"] for r in recs if r["phase"] == "backend")
        return {
            "seconds": seconds,
            "union_s": union_seconds(
                span for phase in _SPANS.values()
                for span in by_phase[phase]),
            "programs": sum(outcomes.values()),
            "cache_hits": outcomes["hit"],
            "cache_misses": outcomes["miss"],
            "records": len(recs), "dropped": seen - len(kept),
            "longest": sorted(recs, key=lambda r: -r["ms"])[:10]}

    def reset(self):
        """Forget the records (test isolation); the listeners stay."""
        with self._lock:
            self._records.clear()
            for merged in self._covered.values():
                merged.clear()
            self.seen = 0


WATCH = CompileWatch()


def watch():
    """Install the process's watch unless ``HETU_TELEMETRY`` is off;
    True when it is listening."""
    return events.enabled() and WATCH.install()


def records():
    """The process's ``compile`` records, oldest first."""
    return WATCH.records()


def summary(before=None, under=None):
    """``CompileWatch.summary`` of the process's watch."""
    return WATCH.summary(before=before, under=under)
