"""The compile watch (ISSUE 53, ``hetu_tpu/compile_cache.py``): what JAX
traced, lowered, compiled or loaded, as ``compile`` records that name
the telemetry span they were built under, and a phase's seconds as the
UNION of its intervals.

Everything here runs on the CPU: it shows what is recorded and when,
never how long anything takes on a device.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

import hetu_tpu as ht
from hetu_tpu import compile_cache, telemetry
from hetu_tpu.compile_cache import (
    CompileWatch, merge_interval, union_seconds)
from hetu_tpu.serving import Request, ServingEngine

from test_serving import _rand_gpt

pytestmark = pytest.mark.smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture(autouse=True)
def telemetry_on(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    compile_cache.WATCH.reset()
    yield
    telemetry.reset()


def new_records(seen):
    """The process's records since ``WATCH.seen`` read ``seen``."""
    n = compile_cache.WATCH.seen - seen
    return compile_cache.records()[-n:] if n else []


# --------------------------------------------------------------------- #
# the union of a phase's intervals
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("intervals, want", [
    ([(1, 4)], 3),
    ([(2, 3), (1, 4)], 3),                     # inner first, as JAX reports
    ([(1, 4), (2, 3)], 3),
    ([(1, 3), (2, 5)], 4),                     # overlapping
    ([(2, 5), (1, 3)], 4),
    ([(1, 2), (3, 4)], 2),                     # disjoint
    ([(3, 4), (1, 2)], 2),
    ([(1, 2), (2, 3)], 2),                     # touching
    ([(1, 2), (5, 6), (3, 4), (0, 10)], 10),   # one over three
    ([(1, 2), (5, 6), (1.5, 5.5)], 5),         # a bridge between two
    ([(1, 2), (1, 2), (1, 2)], 1),
    ([], 0),
], ids=["one", "nested-inner-first", "nested-outer-first", "overlap",
        "overlap-reversed", "disjoint", "disjoint-reversed", "touching",
        "one-over-three", "bridge", "same-thrice", "none"])
def test_union_of_intervals(intervals, want):
    assert union_seconds(intervals) == pytest.approx(want)
    merged = []
    parts = [merge_interval(merged, a, b) for a, b in intervals]
    assert all(p >= -1e-12 for p in parts) and sum(parts) \
        == pytest.approx(want)
    # the list stays sorted and disjoint
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))


def test_a_trace_inside_a_trace_is_counted_once():
    """JAX reports the inner ``jit``'s trace, then the outer one that
    held it: the phase's seconds are the outer's, not their sum; the
    registry's running sum says the same."""
    w = CompileWatch()
    w.on_span(TRACE, 102.0, 103.0, fun_name="inner")
    w.on_span(TRACE, 100.0, 104.0, fun_name="outer")
    w.on_span(LOWER, 104.0, 104.5, fun_name="jit(outer)")
    w.on_span(TRACE, 104.25, 104.75, fun_name="traced_by_a_lowering")
    w.on_span(BACKEND, 104.5, 106.0, fun_name="jit(outer)")
    s = w.summary()
    assert s["seconds"] == pytest.approx(
        {"trace": 4.5, "lower": 0.5, "backend": 1.5, "cache_load": 0})
    # the three phases together: 100 .. 106 less nothing, and less than
    # their sum by the quarter second the lowering spent tracing
    assert s["union_s"] == pytest.approx(6.0)
    assert s["programs"] == 1 and s["records"] == 5 and s["dropped"] == 0
    assert [r["fun"] for r in s["longest"][:2]] == ["outer", "jit(outer)"]
    counters = telemetry.snapshot()["counters"]
    assert counters["compile.trace_ms"] == pytest.approx(4500)
    assert counters["compile.lower_ms"] == pytest.approx(500)
    assert counters["compile.backend_ms"] == pytest.approx(1500)
    assert counters["compile.programs"] == 1


def test_a_short_trace_inside_another_is_counted_not_kept():
    """A training step's trace holds sixteen thousand inner traces of
    microseconds each (every ``jax.numpy`` function is a ``jit``): one
    that is nested and under a millisecond is counted into its outermost
    phase's ``nested``; a long one, and a short one that is nobody's
    inner trace, are records like any other.  The union is the same
    either way: an inner trace lies inside the outer's interval."""
    w = CompileWatch()
    w.on_start(TRACE, 100.0, fun_name="step_fn")
    for i in range(5):
        w.on_start(TRACE, 100.1 + i, fun_name="add")
        w.on_span(TRACE, 100.1 + i, 100.1004 + i, fun_name="add")
    w.on_start(TRACE, 100.2, fun_name="kernel_body")
    w.on_start(TRACE, 100.3, fun_name="where")         # two deep
    w.on_span(TRACE, 100.3, 100.3001, fun_name="where")
    w.on_span(TRACE, 100.2, 100.7, fun_name="kernel_body")
    w.on_span(TRACE, 100.0, 106.0, fun_name="step_fn")
    w.on_start(TRACE, 107.0, fun_name="zeros")
    w.on_span(TRACE, 107.0, 107.0002, fun_name="zeros")
    # what a lowering rule traces is the lowering's time
    w.on_start(LOWER, 108.0, fun_name="jit(step_fn)")
    for i in range(3):
        w.on_start(TRACE, 108.5 + i, fun_name="square")
        w.on_span(TRACE, 108.5 + i, 108.5003 + i, fun_name="square")
    w.on_span(LOWER, 108.0, 112.0, fun_name="jit(step_fn)")
    recs = w.records()
    assert [(r["phase"], r["fun"], r.get("nested")) for r in recs] == [
        ("trace", "kernel_body", None), ("trace", "step_fn", 6),
        ("trace", "zeros", 0), ("lower", "jit(step_fn)", 3)]
    assert w.seen == 4
    assert w.summary()["seconds"] == pytest.approx(
        {"trace": 6.0002, "lower": 4.0, "backend": 0, "cache_load": 0})
    # a listener that joined in the middle of a trace (the watch
    # installed by an import inside one) never goes below the ground
    late = CompileWatch()
    late.on_span(TRACE, 1.0, 1.0001, fun_name="inner")
    late.on_span(TRACE, 0.0, 2.0, fun_name="outer")
    assert [r["fun"] for r in late.records()] == ["inner", "outer"]


def test_cache_events_join_the_backend_record_that_closes_after_them():
    w = CompileWatch()
    w.on_event(HIT)
    w.on_duration(LOAD, 0.25)
    w.on_duration(BACKEND, 0.5, fun_name="jit(step)")   # not the span
    w.on_span(BACKEND, 10.0, 10.5, fun_name="jit(step)")
    w.on_event(MISS)
    w.on_span(BACKEND, 11.0, 14.0, fun_name="jit(wave)")
    w.on_span(BACKEND, 15.0, 15.5, fun_name="jit(small)")
    load, hit, miss, neither = w.records()
    assert (load["phase"], load["fun"], load["ms"]) \
        == ("cache_load", "jit(step)", 250.0)
    assert [r["cache"] for r in (hit, miss, neither)] \
        == ["hit", "miss", None]
    s = w.summary()
    assert (s["programs"], s["cache_hits"], s["cache_misses"]) == (3, 1, 1)
    # the load lies inside the backend span: a part of it, never added
    assert s["seconds"]["backend"] == pytest.approx(4.0)
    assert s["seconds"]["cache_load"] == pytest.approx(0.25)
    assert s["union_s"] == pytest.approx(4.0)
    counters = telemetry.snapshot()["counters"]
    assert counters["compile.cache_hits"] == 1
    assert counters["compile.cache_misses"] == 1
    assert counters["compile.cache_load_ms"] == pytest.approx(250)


def test_the_store_is_bounded_and_the_running_sums_are_not():
    w = CompileWatch(keep=4)
    for i in range(6):
        w.on_span(TRACE, float(i), i + 0.5, fun_name=f"f{i}")
    assert [r["fun"] for r in w.records()] == ["f2", "f3", "f4", "f5"]
    s = w.summary()
    assert s["records"] == 4 and s["dropped"] == 2 and w.seen == 6
    assert s["seconds"]["trace"] == pytest.approx(2.0)
    assert telemetry.snapshot()["counters"]["compile.trace_ms"] \
        == pytest.approx(3000)
    w.reset()
    assert w.records() == [] and w.seen == 0


def test_records_go_to_the_merged_log_in_the_contracts_shape(
        tmp_path, monkeypatch):
    log = tmp_path / "telemetry.jsonl"
    monkeypatch.setenv("HETU_TELEMETRY_LOG", str(log))
    w = CompileWatch()
    with telemetry.span("serve.engine.build"):
        w.on_span(LOWER, 5.0, 6.0, fun_name="jit(zeros)")
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    rec = next(r for r in recs if r["event"] == "compile")
    assert telemetry.REQUIRED_FIELDS["compile"] == ("phase", "fun", "ms")
    assert telemetry.validate_record(rec) == []
    assert (rec["phase"], rec["fun"], rec["ms"]) \
        == ("lower", "jit(zeros)", 1000.0)
    assert rec["parent"] == {"name": "serve.engine.build"}
    assert rec["us"] == 5_000_000 and rec["t0"] == 5.0 and rec["t1"] == 6.0
    # without the variable the record is kept and written nowhere
    monkeypatch.delenv("HETU_TELEMETRY_LOG")
    w.on_span(LOWER, 7.0, 8.0, fun_name="jit(ones)")
    assert len(w.records()) == 2
    assert len(log.read_text().splitlines()) == len(recs)


# --------------------------------------------------------------------- #
# installed once, with telemetry, and silent without it
# --------------------------------------------------------------------- #

def _listening():
    ours = compile_cache.WATCH
    return (monitoring.get_event_time_span_listeners().count(ours.on_span),
            monitoring.get_scalar_listeners().count(ours.on_start),
            monitoring.get_event_listeners().count(ours.on_event),
            monitoring.get_event_duration_listeners().count(
                ours.on_duration))


def test_watch_twice_registers_once():
    assert compile_cache.WATCH.installed     # importing telemetry did
    assert _listening() == (1, 1, 1, 1)
    assert compile_cache.watch() is True and compile_cache.watch() is True
    assert _listening() == (1, 1, 1, 1)


def test_telemetry_off_installs_nothing_and_records_nothing(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "0")
    fresh = CompileWatch()
    monkeypatch.setattr(compile_cache, "WATCH", fresh)
    n = len(monitoring.get_event_time_span_listeners())
    assert compile_cache.watch() is False and not fresh.installed
    assert len(monitoring.get_event_time_span_listeners()) == n
    # the process's own watch, installed while telemetry was on, is
    # silent while it is off
    monkeypatch.undo()
    monkeypatch.setenv("HETU_TELEMETRY", "0")
    seen = compile_cache.WATCH.seen
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    assert compile_cache.WATCH.seen == seen
    assert "compile.programs" not in telemetry.snapshot()["counters"]


@pytest.mark.parametrize("switch", ["1", "0"])
def test_importing_the_package_installs_the_watch_and_times_itself(switch):
    """A process of its own: the watch is there as soon as ``hetu_tpu``
    is imported, the package has timed its own import, and
    ``HETU_TELEMETRY=0`` leaves JAX without a listener of ours."""
    code = (
        "import json, jax\n"
        "from jax._src import monitoring\n"
        "import hetu_tpu\n"
        "from hetu_tpu import compile_cache, telemetry\n"
        "jax.jit(lambda x: x + 1)(1.0)\n"
        "print(json.dumps({'installed': compile_cache.WATCH.installed,\n"
        "  'listeners': len(monitoring.get_event_time_span_listeners()),\n"
        "  'records': len(compile_cache.records()),\n"
        "  'import_ms': telemetry.snapshot()['gauges'].get(\n"
        "      'process.import_ms')}))\n")
    env = dict(os.environ, HETU_TELEMETRY=switch, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT)
    env.pop("HETU_TELEMETRY_LOG", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    said = json.loads(out.stdout.strip().splitlines()[-1])
    if switch == "1":
        assert said["installed"] and said["listeners"] == 1
        assert said["records"] >= 3 and said["import_ms"] > 0
    else:
        assert said == {"installed": False, "listeners": 0, "records": 0,
                        "import_ms": None}


# --------------------------------------------------------------------- #
# a record names the span it was built under
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def model():
    return _rand_gpt(name="cw", V=61, S=64)


def small_engine(model):
    # a pool of a size nobody else builds: its zeros are programs of THIS
    # constructor's, whatever the worker ran before
    params, cfg = model
    return ServingEngine(params, cfg, slots=4, fast_path=False, kv_block=4,
                         prefill_chunk=8, max_seq_len=64, pool_blocks=37,
                         prefix_share=False)


def test_a_wave_program_names_its_dispatch_and_the_constructor_its_build(
        model):
    seen = compile_cache.WATCH.seen
    eng = small_engine(model)
    built = new_records(seen)
    # the pools' zeros are programs of the constructor's, under both
    # build spans; nothing of a wave's is built yet
    assert built and all(r["under"][0] == "serve.engine.build"
                         for r in built)
    assert any(r["parent"]["name"] == "serve.kv.build" for r in built)
    hists = telemetry.snapshot()["histograms"]
    assert hists["span.serve.engine.build"]["count"] == 1
    assert hists["span.serve.kv.build"]["count"] == 1
    # the constructors' calls keep a clock of their own: inner first
    kv, engine = telemetry.spanned_calls()
    assert (kv["name"], engine["name"]) \
        == ("serve.kv.build", "serve.engine.build")
    assert engine["ms"] == pytest.approx(
        hists["span.serve.engine.build"]["sum"])
    assert engine["ms"] >= kv["ms"] > 0 \
        and engine["end_perf"] >= kv["end_perf"]

    seen = compile_cache.WATCH.seen
    eng.run([Request(list(range(1, 9)), 2, request_id="first")])
    new = new_records(seen)
    waves = [r for r in new if r["phase"] == "backend"
             and r["fun"] == "jit(_serve_mixed_paged)"]
    assert all(r["parent"]["name"] == "serve.wave.dispatch" for r in waves)
    # the first chunk wave's program, then the first decode wave's
    assert [(r["parent"]["kind"], r["parent"]["q"]) for r in waves] \
        == [("chunk", 8), ("decode", 1)]
    first = waves[0]
    assert first["parent"]["wave"] == 1 and first["parent"]["ahead"] is False
    assert first["under"] == ["serve.wave", "serve.wave.dispatch"]
    # its trace and its lowering say the same
    for phase in ("trace", "lower"):
        assert any(r["phase"] == phase and r["parent"] == first["parent"]
                   for r in new)


def test_a_trainers_program_names_its_compiled_dispatch():
    x = ht.placeholder_op("x")
    w = ht.init.xavier_uniform((16, 16), name="cw_w")
    loss = ht.reduce_mean_op(ht.reduce_mean_op(
        ht.relu_op(ht.matmul_op(x, w)), axes=1), axes=0)
    train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
    ex = ht.Executor({"train": [loss, train]})
    assert telemetry.snapshot()["histograms"]["span.exec.build"]["count"] \
        == 1
    seen = compile_cache.WATCH.seen
    feed = {x: np.ones((4, 16), np.float32)}
    ex.run("train", feed_dict=feed)
    new = new_records(seen)
    steps = [r for r in new if r["phase"] == "backend" and r["parent"]
             and r["parent"]["name"] == "exec.dispatch"]
    assert len(steps) == 1
    assert steps[0]["parent"] == {"name": "exec.dispatch",
                                  "subgraph": "train", "step": 1,
                                  "compiled": True}
    assert steps[0]["under"] == ["exec.step", "exec.dispatch"]
    # the second step of the same feed signature builds nothing
    seen = compile_cache.WATCH.seen
    ex.run("train", feed_dict=feed)
    assert compile_cache.WATCH.seen == seen


# --------------------------------------------------------------------- #
# the window pays nothing; what compiles after it is no part of set-up
# --------------------------------------------------------------------- #

def test_a_warmed_engine_adds_no_record_and_a_later_program_is_left_out():
    # a name of its own: the name is a static argument of the wave, so
    # this engine's programs are built here whatever ran before
    eng = small_engine(_rand_gpt(name="cx", V=61, S=64))
    for n in (8, 16):
        eng.run([Request(((np.arange(n) + n) % 61).tolist(), 3)])
    opened = time.perf_counter()
    seen = compile_cache.WATCH.seen
    programs = telemetry.snapshot()["counters"]["compile.programs"]
    rng = np.random.default_rng(5)
    reqs = [Request(rng.integers(1, 61, 8 * int(rng.integers(1, 3))
                                 ).tolist(), int(rng.integers(2, 6)),
                    request_id=f"w{i}") for i in range(12)]
    out = eng.run(reqs)
    assert len(out) == 12 and eng.steps > 20
    # not a trace, not a lowering, not a compile: no listener ran
    assert compile_cache.WATCH.seen == seen
    assert telemetry.snapshot()["counters"]["compile.programs"] == programs

    # the runners' float32 reference compiles after the window
    jax.jit(lambda x: jnp.tanh(x) * 7)(jnp.ones(5))
    assert compile_cache.WATCH.seen > seen
    whole = compile_cache.summary()
    setup = compile_cache.summary(before=opened)
    assert setup["records"] == whole["records"] \
        - (compile_cache.WATCH.seen - seen)
    assert setup["programs"] < whole["programs"]
    assert all(r["end_perf"] <= opened
               for r in compile_cache.records()[:setup["records"]])
    under = compile_cache.summary(before=opened, under="serve.wave.dispatch")
    assert 0 < under["union_s"] <= setup["union_s"]
    assert under["records"] <= setup["records"]
