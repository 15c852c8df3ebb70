"""The seven per-layer metrics that move ``setup_s`` (ISSUE 53): one
reader, ``benchmarks/readers/setup_phase.py``, over the program's compile
watch and registry, cut at the window's opening.

Canned records and a stub harness; then the tiny trainer through the real
``Harness``.  CPU runs: what is read and from where, never a device time.
"""

import io
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from hetu_tpu import compile_cache, telemetry  # noqa: E402
from hetu_tpu.compile_cache import CompileWatch  # noqa: E402
from hetu_tpu.telemetry import events  # noqa: E402

pytestmark = pytest.mark.smoke

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
ENTRIES = [m for m in BENCH["per_layer"] if m["moves"] == "setup_s"]
CELLS = [w["name"] for w in BENCH["workloads"]]
READER = bench_run.load_module("readers", "setup_phase")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"

# a stub harness is "defined in" this module: its clock's zero is here
T_PROCESS_START = time.perf_counter() - 100.0


class StubHarness:
    def __init__(self, setup_s):
        self.setup_s = setup_s


@pytest.fixture()
def canned(monkeypatch):
    """A watch of its own with a set-up's records, a mark, and one
    program compiled after the mark; the registry's gauge and span."""
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    w = CompileWatch()
    w.installed = True
    monkeypatch.setattr(compile_cache, "WATCH", w)
    telemetry.set_gauge("process.import_ms", 1500.0)
    with telemetry.span("serve.engine.build"):
        # the constructor's zeros: half a second of its four
        w.on_span(TRACE, 10.0, 10.1, fun_name="zeros")
        w.on_span(LOWER, 10.1, 10.2, fun_name="jit(zeros)")
        w.on_span(BACKEND, 10.2, 10.5, fun_name="jit(zeros)")
    events._SPANNED.append({"name": "serve.engine.build", "ms": 4000.0,
                            "end_perf": time.perf_counter()})
    with telemetry.span("serve.wave.dispatch", wave=1, kind="chunk", q=256):
        w.on_span(TRACE, 21.0, 23.0, fun_name="inner")
        w.on_span(TRACE, 20.0, 28.0, fun_name="_serve_mixed_paged")
        w.on_span(LOWER, 28.0, 31.0, fun_name="jit(_serve_mixed_paged)")
        w.on_event("/jax/compilation_cache/cache_hits")
        w.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                      0.75)
        w.on_span(BACKEND, 31.0, 32.0, fun_name="jit(_serve_mixed_paged)")
        w.on_event("/jax/compilation_cache/cache_misses")
        w.on_span(BACKEND, 33.0, 39.0, fun_name="jit(_hand_over)")
        w.on_event("/jax/compilation_cache/cache_hits")
        w.on_span(BACKEND, 40.0, 40.5, fun_name="jit(_sample)")
    opened = time.perf_counter()
    # the float32 reference, after the window
    w.on_span(TRACE, 90.0, 95.0, fun_name="reference")
    w.on_event("/jax/compilation_cache/cache_misses")
    w.on_span(BACKEND, 95.0, 99.0, fun_name="jit(reference)")
    # ... and the training runner's second executor, for its check
    events._SPANNED.append({"name": "exec.build", "ms": 1500.0,
                            "end_perf": time.perf_counter()})
    yield {"harness": StubHarness(opened - T_PROCESS_START)}
    telemetry.reset()


WANT = {
    "setup_import_s": 1.5,
    # 4 s of constructor less the 0.5 s of compile phases under it
    "setup_build_s": 3.5,
    "setup_trace_s": 0.1 + 8.0,
    "setup_lower_s": 0.1 + 3.0,
    "setup_backend_s": 0.3 + 1.0 + 6.0 + 0.5,
    "setup_cache_load_s": 0.75,
    "setup_cache_miss_share": 100.0 / 3,
}


def test_benchmark_json_lists_the_seven_for_every_cell():
    assert [m["name"] for m in ENTRIES] == list(WANT)
    # seven entries in a row (later PRs append their metrics behind them)
    first = BENCH["per_layer"].index(ENTRIES[0])
    assert BENCH["per_layer"][first:first + 7] == ENTRIES
    for m in ENTRIES:
        assert m["workloads"] == CELLS and m["layer"] == "set-up"
        assert m["better"] == "lower"
        assert m["unit"] == ("%" if m["name"].endswith("share") else "s")


@pytest.mark.parametrize("name", list(WANT))
def test_each_metric_file_reads_its_part_before_the_window(canned, name):
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert spec["reader"] == "setup_phase"
    got = READER.read(canned, **spec["args"])
    assert got == pytest.approx(WANT[name], abs=2e-3)
    line = bench_run.per_layer_metrics(
        [m for m in ENTRIES if m["name"] == name], canned)
    assert line[name]["value"] == pytest.approx(WANT[name], abs=2e-3)


@pytest.mark.parametrize("why", ["parent", "not_listening", "no_window"])
def test_nothing_to_read_leaves_every_metric_out(canned, monkeypatch, why):
    if why == "parent":             # a program without the watch
        monkeypatch.delattr(compile_cache, "WATCH")
    elif why == "not_listening":    # HETU_TELEMETRY=0 when it was imported
        compile_cache.WATCH.installed = False
    else:
        canned["harness"].setup_s = None
    assert bench_run.per_layer_metrics(ENTRIES, canned) == {}


def test_a_program_that_built_nothing_reports_no_build_and_no_share(
        monkeypatch):
    telemetry.reset()
    w = CompileWatch()
    w.installed = True
    monkeypatch.setattr(compile_cache, "WATCH", w)
    data = {"harness": StubHarness(50.0)}
    got = bench_run.per_layer_metrics(ENTRIES, data)
    # no gauge, no build span, the cache never asked: left out; the
    # phases read 0, which is what they were
    assert got == {name: {"value": 0.0, "unit": "s"} for name in (
        "setup_trace_s", "setup_lower_s", "setup_backend_s",
        "setup_cache_load_s")}


@pytest.mark.parametrize("module", ["__main__", "benchmarks.run"])
def test_the_windows_opening_is_found_where_the_harness_was_defined(
        monkeypatch, module):
    """``run.py`` as the command defines ``Harness`` in ``__main__``;
    imported (the tests, a probe) in ``benchmarks.run``."""
    if module == "__main__":
        monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START",
                            7.25, raising=False)
        harness = type("Harness", (), {"__module__": "__main__"})()
    else:
        assert bench_run.__name__ == "benchmarks.run"
        monkeypatch.setattr(bench_run, "T_PROCESS_START", 7.25)
        resolved = bench_run.resolve_cell(BENCH, CELLS[0])
        harness = bench_run.Harness(resolved, seed=1, seconds=1, trace=False,
                                    peak={}, out=io.StringIO())
    harness.setup_s = None
    assert READER.window_opening(harness) is None
    harness.setup_s = 40.0
    assert READER.window_opening(harness) == 47.25


def test_the_tiny_trainer_reports_its_set_up_through_the_real_harness(
        monkeypatch):
    """End to end on the CPU: the runner opens the window, the reader
    cuts there.  The persistent cache is off under pytest, so the two
    cache metrics have nothing to read."""
    from benchmarks.runners import train
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    telemetry.set_gauge("process.import_ms", 1234.0)
    compile_cache.WATCH.reset()
    resolved = bench_run.resolve_cell(BENCH, "train-gpt2-medium-s1024")
    resolved["config"] = dict(
        resolved["config"], vocab_size=96, n_positions=64, n_embd=32,
        n_layer=2, n_head=4, runner_args={
            "first_gradient_gap_max": 0.015, "first_loss_gap_max": 0.004,
            "trained_loss_gap_max": 0.02, "loss_fall_min": 0.3})
    resolved["traffic"] = dict(resolved["traffic"], batch=2, seq=32,
                               pool=4, data_ids=16)
    h = bench_run.Harness(resolved, seed=3_000_000_019, seconds=1.0,
                          trace=False, peak=bench_run.load_json(os.path.join(
                              ROOT, "benchmarks", "peaks.json"))["TPU v5 lite"],
                          out=io.StringIO())
    h.count_compiles()
    out = train.run(h, train.gpt_config(h.config, 2, 32))
    assert out["attempted"] >= 2, h.out.getvalue()
    got = bench_run.per_layer_metrics(ENTRIES, {"harness": h})
    assert set(got) == {"setup_import_s", "setup_build_s", "setup_trace_s",
                        "setup_lower_s", "setup_backend_s",
                        "setup_cache_load_s"}
    assert got["setup_import_s"]["value"] == pytest.approx(1.234)
    assert got["setup_cache_load_s"]["value"] == 0
    for name in ("setup_build_s", "setup_trace_s", "setup_lower_s",
                 "setup_backend_s"):
        assert 0 < got[name]["value"] < h.setup_s, (name, got)
    # no record of the window's: every one ended before it opened or
    # after the runner closed it (the float32 check)
    opened = READER.window_opening(h)
    inside = [r for r in compile_cache.records()
              if opened < r["end_perf"] <= opened + h.seconds]
    assert not inside, json.dumps(inside[:3], default=str)
