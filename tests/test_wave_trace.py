"""The benchmark's readers of a serving wave's identity (ISSUE 40:
``benchmarks/wave_trace.py`` and the readers and metrics that PR adds).

They live here and not in ``tests/benchmark/``: that directory's own test
asserts that it holds one file.  A hand-made trace with hand-worked
numbers, the faults that must give a missing metric and never a number,
the PR 25 fixture (a program that says nothing of a wave), and the
fixture recorded on the chip from PR 40's tree.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import program_trace, wave_trace, xplane  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
MS = 1e6            # the hand-made trace is written in milliseconds

WAVE_METRICS = [
    "decode_wave_device_ms", "chunk_wave_device_ms",
    "chunk_wave_time_share.serve", "moe_experts_chunk_wave_ms",
    "attention_chunk_wave_ms", "kv_write_chunk_wave_ms",
    "inorder_step_share.serve", "idle_in_inorder_share.serve"]
SPAN_METRICS = [
    "idle_in_admit_share.serve", "idle_in_assemble_share.serve",
    "idle_in_dispatch_share.serve", "idle_in_unpack_share.serve",
    "admit_p95_ms"]


class _Log:
    """A harness as far as the readers need one."""

    def __init__(self):
        self.lines = []
        self.trace_dir = "/nonexistent"

    def log(self, **record):
        self.lines.append(record)

    def of(self, line):
        return [r for r in self.lines if r["line"] == line]


def metric(name, data):
    """A metric of BENCHMARK.json through its own file and reader."""
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == name]
    got = bench_run.per_layer_metrics([entry], data)
    return got[name]["value"] if name in got else None


def fresh(trace):
    log = _Log()
    return {"trace": trace, "harness": log}, log


def ev(name, start, end):
    return [name, start * MS, (end - start) * MS]


J = "jit(_serve_mixed_paged)"


def wave_trace_by_hand():
    """Five scheduler iterations, four waves (ms; see the numbers worked
    out in ``test_every_wave_metric_on_the_hand_made_trace``):

    ============  =========  ==========================================
    iteration     order      what it ran
    ============  =========  ==========================================
    [0, 10)       first      launches wave 1 (chunk, q 32)
    [10, 50)      inorder    lands wave 1, retires somebody, returns
    [50, 60)      first      launches wave 2 (decode)
    [60, 75)      ahead      launches wave 3 (decode), lands wave 2
    [75, 90)      ahead      launches wave 4 (chunk), lands wave 3
    ============  =========  ==========================================

    Device: wave 1 [5, 45), wave 2 [55, 65), wave 3 [65.5, 75.5), wave 4
    [79, 119): it outlasts the window, which ends at 90."""
    ops = [
        # wave 1, a chunk wave
        (ev("%fusion.1 = bf16[1024,1600] fusion(%p)", 5, 15),
         f"{J}/wave_chunk/attn_qkv/dot_general:"),
        (ev('%ragged_paged_mixed.3 = bf16[4,32,25,64] custom-call(%q), '
            'custom_call_target="tpu_custom_call"', 15, 30),
         f"{J}/wave_chunk/attention/ragged_paged_mixed/pallas_call:"),
        # the compiler's grouped matmul: its own name, no name stack
        (ev("%ragged-dot-none.1 = bf16[1024,1536] custom-call(%x)", 30, 40),
         ""),
        (ev("%fusion.3 = bf16[48,449,16,1664] fusion(%pool)", 40, 44),
         f"{J}/wave_chunk/kv_write/scatter:"),
        (ev("%while.2 = (s32[], f32[4,50257]) while(%t)", 44, 45), ""),
        (ev("%sort.4 = f32[50257] sort(%x)", 44.2, 44.8),
         f"{J}/sample/while/body/sort:"),
        # wave 2, a decode wave
        (ev("%fusion.11 = bf16[4,1600] fusion(%p)", 55, 60),
         f"{J}/wave_decode/attention/dot_general:"),
        (ev("%ragged-dot-none.2 = bf16[16,1536] custom-call(%x)", 60, 64),
         ""),
        (ev("%fusion.12 = f32[4,50257] fusion(%h)", 64, 65),
         f"{J}/wave_decode/lm_head/dot_general:"),
        # wave 3, a decode wave
        (ev("%fusion.11 = bf16[4,1600] fusion(%p)", 65.5, 70.5),
         f"{J}/wave_decode/attention/dot_general:"),
        (ev("%fusion.13 = bf16[4,6400] fusion(%p)", 70.5, 75.5),
         f"{J}/wave_decode/mlp/dot_general:"),
        # wave 4, a chunk wave that outlasts the window
        (ev("%fusion.21 = bf16[4,32,25,64] fusion(%q)", 79, 89),
         f"{J}/wave_chunk/attention/dot_general:"),
        (ev("%fusion.22 = bf16[1024,1600] fusion(%o)", 89, 91),
         f"{J}/wave_chunk/attn_out/dot_general:"),
        (ev("%fusion.23 = bf16[1024,1536] fusion(%x)", 91, 119),
         f"{J}/wave_chunk/moe_experts/dot_general:"),
    ]
    modules = [ev("jit__serve_mixed_paged(7)", 5, 45),
               ev("jit__hand_over(3)", 54.9, 55),
               ev("jit__serve_mixed_paged(9)", 55, 65),
               ev("jit__serve_mixed_paged(9)", 65.5, 75.5),
               ev("jit__serve_mixed_paged(7)", 79, 119)]
    f = dict
    spans = [
        (ev("hetu.serve.wave", 0.5, 9.5), f(order="first")),
        (ev("hetu.serve.admit", 1, 2), f(wave=1, queue=4)),
        (ev("hetu.serve.kv_alloc", 1.2, 1.8), f(wave=1, queue=4)),
        (ev("hetu.serve.wave.assemble", 2, 3), f(wave=1, kind="chunk")),
        (ev("hetu.serve.wave.dispatch", 3, 5),
         f(wave=1, kind="chunk", q=32, ahead=0)),
        (ev("hetu.serve.wave", 10.5, 49.5), f(order="inorder")),
        (ev("hetu.serve.wave.sync", 11, 46),
         f(wave=1, kind="chunk", ahead=0)),
        (ev("hetu.serve.wave.unpack", 46, 48), f(wave=1, kind="chunk")),
        (ev("hetu.serve.wave", 50.5, 59.5), f(order="first")),
        (ev("hetu.serve.admit", 51, 52), f(wave=2, queue=3)),
        (ev("hetu.serve.kv_alloc", 51.2, 51.8), f(wave=2, queue=3)),
        (ev("hetu.serve.wave.assemble", 52, 53), f(wave=2, kind="decode")),
        (ev("hetu.serve.wave.dispatch", 53, 55),
         f(wave=2, kind="decode", q=1, ahead=0)),
        (ev("hetu.serve.wave", 60.5, 74.5), f(order="ahead")),
        (ev("hetu.serve.admit", 60.8, 62), f(wave=3, queue=3)),
        (ev("hetu.serve.kv_alloc", 61.2, 61.8), f(wave=3, queue=3)),
        (ev("hetu.serve.wave.assemble", 62, 63), f(wave=3, kind="decode")),
        (ev("hetu.serve.wave.dispatch", 63, 64),
         f(wave=3, kind="decode", q=1, ahead=1)),
        (ev("hetu.serve.wave.sync", 64, 66),
         f(wave=2, kind="decode", ahead=0)),
        (ev("hetu.serve.wave.unpack", 66, 68), f(wave=2, kind="decode")),
        (ev("hetu.serve.wave", 75.5, 89.5), f(order="ahead")),
        (ev("hetu.serve.admit", 76, 77), f(wave=4, queue=3)),
        (ev("hetu.serve.kv_alloc", 76.2, 76.8), f(wave=4, queue=3)),
        (ev("hetu.serve.wave.assemble", 77, 78), f(wave=4, kind="chunk")),
        (ev("hetu.serve.wave.dispatch", 78, 79),
         f(wave=4, kind="chunk", q=32, ahead=1)),
        (ev("hetu.serve.wave.sync", 79, 80),
         f(wave=3, kind="decode", ahead=1)),
        (ev("hetu.serve.wave.unpack", 80, 82), f(wave=3, kind="decode")),
    ]
    bench = [ev("bench.engine_step", a, b)
             for a, b in ((0, 10), (10, 50), (50, 60), (60, 75), (75, 90))]
    table = sorted({s for _, s in ops})
    return {
        "planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": modules},
                {"name": "XLA Ops", "events": [e for e, _ in ops]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "python3",
                 "events": bench + [e for e, _ in spans]}]}],
        "op_scopes": {"table": table,
                      "index": [table.index(s) for _, s in ops]},
        "span_fields": [e + [fields] for e, fields in spans],
    }


# ------------------------------------------------------------------ #
# the join, and the numbers by hand
# ------------------------------------------------------------------ #

def test_waves_are_labelled_by_scope_and_joined_to_their_dispatch():
    data, log = fresh(wave_trace_by_hand())
    w = wave_trace.waves(data)
    assert [(m["wave"], m["kind"], m["q"], m["ahead"], m["start"] / MS,
             m["end"] / MS) for m in w["modules"]] == [
        (1, "chunk", 32, False, 5, 45), (2, "decode", 1, False, 55, 65),
        (3, "decode", 1, True, 65.5, 75.5), (4, "chunk", 32, True, 79, 119)]
    assert [(r["order"], r["launched"], r["landed"])
            for r in w["roots"]] == [
        ("first", 1, None), ("inorder", None, 1), ("first", 2, None),
        ("ahead", 3, 2), ("ahead", 4, 3)]
    [line] = log.of("wave_kinds")
    # decode + chunk (+ verify, none here) = every module module_time reads
    assert line["by_kind"] == {"chunk": 2, "decode": 2}
    assert line["modules_in_window"] == len(xplane.matching_events(
        data["trace"], xplane.MODULES_LINE, ["_serve_mixed_paged"])) == 4
    assert line["n_disagreements"] == 0
    assert wave_trace.waves(data) is w and len(log.of("wave_kinds")) == 1


def test_the_decode_waves_experts_on_the_hand_made_trace():
    """PR 49's ``moe_experts_decode_wave_ms``, the decode twin of
    ``moe_experts_chunk_wave_ms`` (same reader, same scopes and names,
    ``kind`` "decode"): wave 2's ``ragged-dot-none`` [60, 64) over the
    two decode waves; wave 3 has no expert operation."""
    data, _ = fresh(wave_trace_by_hand())
    assert metric("moe_experts_decode_wave_ms", data) == pytest.approx(4 / 2)
    twin, mine = (bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", f"moe_experts_{kind}_wave_ms.json"))
        for kind in ("chunk", "decode"))
    assert mine == dict(twin, args=dict(twin["args"], kind="decode"))
    [entry] = [m for m in BENCH["per_layer"]
               if m["name"] == "moe_experts_decode_wave_ms"]
    assert entry["moves"] == "serve_tokens_per_s"
    # the cells whose traced windows always hold decode waves
    assert entry["workloads"] == ["serve-glm47flash-reason-closed",
                                  "serve-nemotron3-super-agent-closed"]


def test_every_wave_metric_on_the_hand_made_trace():
    """Busy inside [0, 90): 40 + 10 + 10 + 11 = 71; idle 19 in the gaps
    [0, 5), [45, 55), [65, 65.5), [75.5, 79)."""
    data, log = fresh(wave_trace_by_hand())
    got = {name: metric(name, data) for name in WAVE_METRICS + SPAN_METRICS}
    assert got["decode_wave_device_ms"] == 10.0
    assert got["chunk_wave_device_ms"] == 40.0
    # chunk modules hold 40 + [79, 90) = 51 of the 71 busy
    assert got["chunk_wave_time_share.serve"] == pytest.approx(100 * 51 / 71)
    # wave 1's grouped matmul, 10; wave 4's experts start past the window
    assert got["moe_experts_chunk_wave_ms"] == pytest.approx(10 / 2)
    assert got["attention_chunk_wave_ms"] == pytest.approx((15 + 10) / 2)
    assert got["kv_write_chunk_wave_ms"] == pytest.approx(4 / 2)
    # three roots landed a wave, one of them in order
    assert got["inorder_step_share.serve"] == pytest.approx(100 * 1 / 3)
    # under the three roots that did not run ahead: [0.5, 5), [45, 49.5),
    # [50.5, 55)
    assert got["idle_in_inorder_share.serve"] == pytest.approx(
        100 * 13.5 / 19)
    assert got["idle_in_admit_share.serve"] == pytest.approx(100 * 3 / 19)
    assert got["idle_in_assemble_share.serve"] == pytest.approx(100 * 3 / 19)
    assert got["idle_in_dispatch_share.serve"] == pytest.approx(100 * 5 / 19)
    assert got["idle_in_unpack_share.serve"] == pytest.approx(100 * 2 / 19)
    # the four are the accepted metric's parts
    assert sum(got[n] for n in SPAN_METRICS[:4]) == pytest.approx(
        metric("idle_in_host_work_share.serve", data)) \
        == pytest.approx(100 * 13 / 19)
    # admissions of 1, 1, 1.2 and 1 ms
    assert got["admit_p95_ms"] == pytest.approx(1.17)
    [p] = log.of("span_percentile")
    assert (p["samples"], p["samples_beyond"], p["max_ms"]) == (4, 0, 1.2)
    chunk = [r for r in log.of("wave_module_time") if r["kind"] == "chunk"]
    assert chunk[0]["waves"] == 2 and chunk[0]["median_fell_on_q"] == 32
    assert chunk[0]["by_q"] == {"32": {"waves": 2, "median_ms": 40.0}}
    [orders] = log.of("root_orders")
    assert orders["by_order"] == {"first": 2, "inorder": 1, "ahead": 2}
    assert (orders["roots_that_landed"], orders["waves_dispatched_ahead"],
            orders["modules_in_window"]) == (3, 2, 4)
    assert not log.of("metric_missing")


def test_the_new_metrics_move_a_serving_rate_in_the_cells_they_list():
    serving = {w["name"] for w in BENCH["workloads"]
               if w["name"].startswith("serve-")}
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in WAVE_METRICS + SPAN_METRICS:
        m = entries[name]
        assert m["moves"] == "serve_tokens_per_s" and m["better"] == "lower"
        assert set(m["workloads"]) <= serving
    assert set(entries["chunk_wave_device_ms"]["workloads"]) == serving
    # every wave of the RAG cell carries a chunk
    assert "serve-lfm2-8b-a1b-rag-closed" not in \
        entries["decode_wave_device_ms"]["workloads"]


# ------------------------------------------------------------------ #
# a wrong label is a missing metric, never a number
# ------------------------------------------------------------------ #

def _edited(edit):
    trace = wave_trace_by_hand()
    edit(trace)
    return fresh(trace)


def _set_field(trace, name, wave, **fields):
    for e in trace["span_fields"]:
        if e[0] == name and e[3].get("wave") == wave:
            e[3].update(fields)


def test_a_scope_that_disagrees_with_its_span_is_a_missing_metric():
    data, log = _edited(lambda t: _set_field(
        t, "hetu.serve.wave.dispatch", 2, kind="chunk"))
    assert all(metric(n, data) is None for n in WAVE_METRICS)
    [line] = log.of("wave_kinds")
    assert line["n_disagreements"] == 1
    assert line["disagreements"][0]["wave"] == 2
    assert (line["disagreements"][0]["scope"],
            line["disagreements"][0]["span"]) == ("decode", "chunk")
    missing = log.of("metric_missing")
    assert missing and "agrees with its span" in missing[0]["missing"]


def test_a_module_launched_before_the_trace_began_is_left_out():
    def edit(t):
        t["planes"][0]["lines"][0]["events"].insert(
            0, ev("jit__serve_mixed_paged(9)", -12, -4))
        t["planes"][0]["lines"][1]["events"].insert(
            0, ev("%fusion.11 = bf16[4,1600] fusion(%p)", -12, -4))
        scopes = t["op_scopes"]
        scopes["index"].insert(0, scopes["table"].index(
            f"{J}/wave_decode/attention/dot_general:"))
    data, log = _edited(edit)
    w = wave_trace.waves(data)
    assert [m["wave"] for m in w["modules"]] == [1, 2, 3, 4]
    assert log.of("wave_kinds")[0]["launched_before_the_trace"] == 1
    assert metric("decode_wave_device_ms", data) == 10.0


def test_a_module_cannot_precede_its_dispatch_by_more_than_the_lead():
    """With the runtime's launch and done events the lead is bounded
    (here to [0, 0.5] ms); a join that puts a module 2 ms before its own
    dispatch span is a wrong join."""
    def edit(t):
        host = t["planes"][1]["lines"][0]["events"]
        for m in t["planes"][0]["lines"][0]["events"]:
            host.append([program_trace.LAUNCH, m[1], 0.01 * MS])
            host.append([program_trace.DONE, m[1] + m[2] + 0.5 * MS,
                         0.01 * MS])
    data, log = _edited(edit)
    assert program_trace.device_clock_lead(data["trace"]) == (0.0, 0.5 * MS)
    assert wave_trace.waves(data) is not None

    def late(t):
        edit(t)
        for e in t["span_fields"]:
            if e[0] == "hetu.serve.wave.dispatch" and e[3]["wave"] == 3:
                e[1] = 67.5 * MS          # wave 3's module began at 65.5
    data, log = _edited(late)
    assert wave_trace.waves(data) is None
    [wrong] = log.of("wave_kinds")[0]["disagreements"]
    assert wrong["wave"] == 3
    assert wrong["module_before_dispatch_ns"] == pytest.approx(2 * MS)


@pytest.mark.parametrize("without", ["fields", "scopes"])
def test_a_program_without_fields_or_scopes_gives_no_wave_metric(without):
    def edit(t):
        if without == "fields":
            for e in t["span_fields"]:
                e[3].clear()
        else:
            t["op_scopes"]["table"] = [
                s.replace("wave_chunk/", "").replace("wave_decode/", "")
                for s in t["op_scopes"]["table"]]
    data, log = _edited(edit)
    assert all(metric(n, data) is None for n in WAVE_METRICS)
    assert log.of("metric_missing")[0]["missing"] == (
        "span fields (wave=, kind=, order=)" if without == "fields"
        else ["wave_chunk", "wave_decode", "wave_verify"])
    # the spans' own metrics need their names alone
    assert metric("admit_p95_ms", data) == pytest.approx(1.17)
    assert metric("idle_in_unpack_share.serve", data) == pytest.approx(
        100 * 2 / 19)


def test_every_new_reader_is_silent_on_the_parents_trace():
    """The PR 25 fixture: spans without stats, no ``wave_*`` scope.  The
    five readers of a wave's identity return None and say what they
    missed; the readers of a span's NAME alone read what is there."""
    trace = xplane.load(os.path.join(
        FIXTURES, "serve-gpt2-xl-batch-closed.trace.json.gz"))
    assert "span_fields" not in trace
    data, log = fresh(trace)
    for name in WAVE_METRICS:
        before = len(log.of("metric_missing"))
        assert metric(name, data) is None, name
        assert len(log.of("metric_missing")) > before or \
            data.get("waves", 0) is None
    assert log.of("metric_missing")[0] == {
        "line": "metric_missing", "reader": "wave_trace",
        "missing": "span fields (wave=, kind=, order=)"}
    parts = [metric(n, data) for n in SPAN_METRICS[:4]]
    assert sum(parts) == pytest.approx(
        metric("idle_in_host_work_share.serve", data))
    assert metric("admit_p95_ms", data) > 0
    train, tlog = fresh(xplane.load(os.path.join(
        FIXTURES, "train-gpt2-medium-s1024.trace.json.gz")))
    assert bench_run.load_module("readers", "span_percentile").read(
        train, "serve.admit", 95) is None
    assert tlog.of("metric_missing")


def test_a_profilers_file_keeps_the_fields_a_span_was_opened_with(tmp_path):
    """``read_span_fields`` on a file this process's profiler writes:
    the event's NAME stays ``hetu.<name>`` and the entry fields are its
    stats (what the first traced chip run checks before anything)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu import telemetry
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.span("serve.wave", order="ahead") as root:
            with telemetry.span("serve.wave.dispatch", wave=7, kind="chunk",
                                q=128, ahead=True):
                jnp.ones(8).block_until_ready()
            root.set(launched=7)
    finally:
        jax.profiler.stop_trace()
    found = {e[0]: e[3] for e in wave_trace.read_span_fields(
        xplane.find_xplane(str(tmp_path)))}
    assert found == {
        "hetu.serve.wave": {"order": "ahead"},
        "hetu.serve.wave.dispatch": {"wave": 7, "kind": "chunk", "q": 128,
                                     "ahead": 1}}
    names = [e[0] for p in xplane.load(xplane.find_xplane(str(tmp_path)))[
        "planes"] for line in p["lines"] for e in line["events"]
        if e[0].startswith("hetu.")]
    assert sorted(names) == ["hetu.serve.wave", "hetu.serve.wave.dispatch"]


def test_clipped_keeps_what_lies_inside_the_holders():
    assert wave_trace.clipped([(0, 5), (6, 10), (20, 30)],
                              [(1, 2), (4, 7), (9, 25)]) == [
        (1, 2), (4, 5), (6, 7), (9, 10), (20, 25)]
    assert wave_trace.clipped([(0, 5)], []) == []


# ------------------------------------------------------------------ #
# the fixture recorded on the chip from PR 40's tree
# ------------------------------------------------------------------ #

def test_readers_on_the_waves_recorded_on_the_chip():
    """Eight scheduler iterations of a traced run of
    ``serve-glm47flash-reason-closed`` (``benchmarks/
    PROGRAM_SPANS.waves.md`` says how it was recorded).  The numbers
    were worked out from the JSON by a plain sweep, apart from
    ``wave_trace.py``, ``program_trace.py`` and ``xplane.py``."""
    trace = xplane.load(os.path.join(
        FIXTURES, "serve-glm47flash-reason-closed.waves.trace.json.gz"))
    data, log = fresh(trace)
    w = wave_trace.waves(data)
    # the wave in flight when the slice begins (2664) has no module in
    # it; six begin inside it, one of them a decode wave
    assert [(m["wave"], m["kind"], m["q"], m["ahead"])
            for m in w["modules"]] == [
        (2665, "chunk", 128, True), (2666, "decode", 1, True),
        (2667, "chunk", 256, False), (2668, "chunk", 256, True),
        (2669, "chunk", 256, False), (2670, "chunk", 256, True)]
    assert [(r["order"], r["launched"], r["landed"])
            for r in w["roots"]] == [
        ("ahead", 2665, 2664), ("ahead", 2666, 2665),
        ("inorder", None, 2666), ("first", 2667, None),
        ("ahead", 2668, 2667), ("inorder", None, 2668),
        ("first", 2669, None), ("ahead", 2670, 2669)]
    [kinds] = log.of("wave_kinds")
    assert kinds["by_kind"] == {"chunk": 5, "decode": 1}
    assert kinds["n_disagreements"] == 0
    # a slice cut out of a trace pairs the runtime's launch and done
    # events with the wrong modules: no lead, gaps stay where they are
    assert kinds["device_clock_lead_ns"] is None
    assert xplane.busy_seconds(trace) == pytest.approx(
        (0.242442441, 0.260633578))
    got = {n: metric(n, data) for n in WAVE_METRICS + SPAN_METRICS}
    assert got["decode_wave_device_ms"] == pytest.approx(9.997978)
    assert got["chunk_wave_device_ms"] == pytest.approx(49.21607)
    assert got["chunk_wave_time_share.serve"] == pytest.approx(
        100 * 186945624 / 242442441)
    assert got["moe_experts_chunk_wave_ms"] == pytest.approx(
        73021723 / 5 / 1e6)
    assert got["attention_chunk_wave_ms"] == pytest.approx(
        89964252 / 5 / 1e6)
    assert got["kv_write_chunk_wave_ms"] == pytest.approx(461792 / 5 / 1e6)
    # six roots landed a wave, two of them in order
    assert got["inorder_step_share.serve"] == pytest.approx(100 * 2 / 6)
    idle = 18191137
    assert got["idle_in_inorder_share.serve"] == pytest.approx(
        100 * 17271426 / idle)
    assert got["idle_in_admit_share.serve"] == pytest.approx(
        100 * (82072 + 5120563) / idle)
    assert got["idle_in_assemble_share.serve"] == pytest.approx(
        100 * 398958 / idle)
    assert got["idle_in_dispatch_share.serve"] == pytest.approx(
        100 * 3533304 / idle)
    assert got["idle_in_unpack_share.serve"] == pytest.approx(
        100 * 1421827 / idle)
    assert sum(got[n] for n in SPAN_METRICS[:4]) == pytest.approx(
        metric("idle_in_host_work_share.serve", data))
    # six admissions: 0.015-0.025 ms with nothing to admit, 1.83 and
    # 3.37 ms where a request was claimed
    assert got["admit_p95_ms"] == pytest.approx(2.987445)
    [chunk] = [r for r in log.of("wave_module_time") if r["kind"] == "chunk"]
    assert chunk["median_fell_on_q"] == 256
    assert chunk["by_q"]["128"] == {"waves": 1, "median_ms": 33.471012}
    [orders] = log.of("root_orders")
    assert orders["by_order"] == {"ahead": 4, "inorder": 2, "first": 2}
    assert (orders["roots_that_landed"],
            orders["waves_dispatched_ahead"]) == (6, 4)
    assert not log.of("metric_missing")
    # the accepted readers read the same trace as before
    assert metric("mixed_step_device_ms", data) == pytest.approx(
        (49.21607 + 48.834443) / 2)
    assert metric("wave_host_ms", data) is not None
