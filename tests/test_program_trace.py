"""The benchmark's readers of the program's spans and names
(``benchmarks/program_trace.py`` and the readers PR 25 adds).

They live here and not in ``tests/benchmark/``: that directory's own test
asserts that it holds one file.  Hand-made traces with hand-worked
numbers, the wire-format reader against a profiler file recorded on the
chip, and the readers on the recorded serving fixture.
"""

import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import program_trace, xplane  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
U = 10_000.0        # one unit of the hand-made trace, in ns


def reader(name):
    return bench_run.load_module("readers", name)


def metric(name, data):
    """A metric of BENCHMARK.json through its own file and reader."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    got = bench_run.per_layer_metrics([entry], data)
    return got[name]["value"] if name in got else None


class _Log:
    """A harness as far as ``program_trace.missing`` needs one."""

    def __init__(self):
        self.lines = []
        self.trace_dir = "/nonexistent"

    def log(self, **record):
        self.lines.append(record)


def ev(name, start, dur):
    return [name, start * U, dur * U]


def serve_trace():
    """Two engine steps: a wave with all its children, then a step with
    nothing live.  Device: 500 units busy of a 2000-unit window."""
    kernel = ('%ragged_paged_mixed.3 = bf16[16,1,25,64] custom-call(%q), '
              'custom_call_target="tpu_custom_call"')
    ops = [
        (ev("%fusion.1 = bf16[16,1600] fusion(%p)", 250, 100),
         "jit(_serve_mixed_paged)/attn_qkv/dot_general:"),
        (ev(kernel, 350, 100),
         "jit(_serve_mixed_paged)/attention/ragged_paged_mixed/pallas_call:"),
        # names the kernel as an OPERAND: not the kernel
        (ev("%fusion.9 = bf16[16,1600] fusion(%ragged_paged_mixed.3)",
            450, 50), "jit(_serve_mixed_paged)/attn_out/add:"),
        (ev("%while.2 = (s32[], f32[16,50257]) while(%t)", 500, 200), ""),
        (ev("%iota.6 = s32[50257] iota()", 505, 5), ""),
        (ev("%sort.4 = f32[50257] sort(%x)", 510, 80),
         "jit(_serve_mixed_paged)/sample/while/body/sort:"),
        (ev("%fusion.5 = f32[] fusion(%sort.4)", 600, 50),
         "jit(_serve_mixed_paged)/sample/while/body/add:"),
        (ev("%copy.7 = bf16[48,449,16,25,64] copy(%pool)", 720, 30), ""),
        (ev("%dynamic-update-slice.8 = bf16[48,449,16,25,64] "
            "dynamic-update-slice(%copy.7)", 750, 20),
         "jit(_serve_mixed_paged)/kv_write/scatter:"),
    ]
    table = sorted({s for _, s in ops})
    host = [
        ev("bench.engine_step", 0, 1000),
        ev("hetu.serve.wave", 10, 900),
        ev("hetu.serve.admit", 20, 50),
        ev("hetu.serve.kv_alloc", 30, 10),
        ev("hetu.serve.wave.assemble", 100, 100),
        ev("hetu.serve.wave.dispatch", 200, 50),
        ev("hetu.serve.wave.sync", 300, 500),
        ev("hetu.serve.wave.unpack", 800, 100),
        ev("bench.engine_step", 1000, 1000),
        ev("hetu.serve.wave", 1010, 100),
        ev("hetu.serve.admit", 1020, 30),
        ev("np.asarray(jax.Array)", 300, 500),
    ]
    return {
        "planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": [e for e, _ in ops]}]},
            {"name": "/host:CPU", "lines": [
                {"name": "python3", "events": host},
                # another thread's span holds nothing of this line
                {"name": "psb-train_0", "events": [
                    ev("hetu.exec.phase_b", 5, 1900)]}]},
        ],
        "op_scopes": {"table": table,
                      "index": [table.index(s) for _, s in ops]},
    }


# ------------------------------------------------------------------ #
# spans
# ------------------------------------------------------------------ #

def test_spans_nest_by_containment_on_their_own_line():
    nodes = program_trace.span_forest(serve_trace())
    by = {}
    for n in nodes:
        by.setdefault(n["name"], []).append(n)
    wave, idle_wave = by["serve.wave"]
    assert wave["parent"] is None and idle_wave["parent"] is None
    assert [c["name"] for c in wave["children"]] == [
        "serve.admit", "serve.wave.assemble", "serve.wave.dispatch",
        "serve.wave.sync", "serve.wave.unpack"]
    assert [c["name"] for c in idle_wave["children"]] == ["serve.admit"]
    [alloc] = by["serve.kv_alloc"]
    assert alloc["parent"]["name"] == "serve.admit"
    [other] = by["exec.phase_b"]
    assert other["parent"] is None and other["children"] == []


def test_the_innermost_span_over_a_moment():
    nodes = [n for n in program_trace.span_forest(serve_trace())
             if n["name"].startswith("serve.")]
    times = [5, 35, 60, 80, 400, 950]
    over = program_trace.innermost_each(nodes, [t * U for t in times])
    assert [n and n["name"] for n in over] == [
        None, "serve.kv_alloc", "serve.admit",
        "serve.wave",            # between two children
        "serve.wave.sync", None]


def test_wave_host_ms_is_the_wave_less_its_wait_for_the_device():
    data = {"trace": serve_trace()}
    # (900 - 500) units = 4 ms; the step with nothing live has no sync
    # child and is skipped, not counted as a 1 ms wave
    assert metric("wave_host_ms", data) == pytest.approx(4.0)
    assert reader("span_median").read(data, "serve.wave") == \
        pytest.approx((9.0 + 1.0) / 2)


def test_idle_time_is_shared_out_by_overlap_with_the_innermost_spans():
    data = {"trace": serve_trace(), "harness": _Log()}
    # window 0..2000, busy 250..700 and 720..770: three gaps, 1500 idle.
    # 0..250: admit 20..70 (kv_alloc inside it), assemble 100..200 and
    # dispatch 200..250 = 200; 700..720: the sync; 770..2000: unpack
    # 800..900 and the next step's admit 1020..1050 = 130
    assert metric("idle_in_host_work_share.serve", data) == \
        pytest.approx(100 * 330 / 1500)
    assert reader("idle_under_spans").read(
        data, ["serve.wave.sync"]) == pytest.approx(100 * (20 + 30) / 1500)
    # no runtime events in this trace: the gaps were not moved, and the
    # run says so
    assert data["harness"].lines[0] == {
        "line": "device_clock_lead", "reader": "idle_under_spans",
        "bounds_ns": None}


def clocked_trace(launch=300, done=850):
    """``serve_trace`` with its one program as the runtime shows it: on
    the device 250..770, handed over by the host at ``launch`` and known
    done at ``done``."""
    trace = serve_trace()
    trace["planes"][0]["lines"].append(
        {"name": "XLA Modules",
         "events": [ev("jit__serve_mixed_paged(1)", 250, 520)]})
    trace["planes"][1]["lines"].append(
        {"name": "main/298", "events": [
            ev(program_trace.LAUNCH, launch, 5),
            ev(program_trace.LAUNCH + "=>IssueSequencedEvent", 301, 2)]})
    trace["planes"][1]["lines"].append(
        {"name": "futex/434", "events": [ev(program_trace.DONE, done, 5)]})
    return trace


def test_causality_bounds_the_device_clocks_lead_and_the_gaps_move_by_it():
    trace = clocked_trace()
    # the device "started" 50 units before its launch: it leads by 50 at
    # least; it ended 80 before the host knew: by 80 at most
    assert program_trace.device_clock_lead(trace) == \
        pytest.approx((50 * U, 80 * U))
    data = {"trace": trace, "harness": _Log()}
    # gaps 65 later: 65..315 (admit 65..70, assemble, dispatch = 155),
    # 765..785 (sync), 835..2065 (unpack 835..900, admit 30 = 95)
    assert metric("idle_in_host_work_share.serve", data) == \
        pytest.approx(100 * 250 / 1500)
    assert data["harness"].lines[0]["bounds_ns"] == \
        pytest.approx((50 * U, 80 * U))


@pytest.mark.parametrize("change", ["no_done", "crossed", "no_modules"])
def test_no_lead_where_the_trace_cannot_bound_it(change):
    trace = clocked_trace(launch=900) if change == "crossed" \
        else clocked_trace()
    if change == "no_done":
        trace["planes"][1]["lines"].pop()
    if change == "no_modules":
        trace["planes"][0]["lines"].pop()
    assert program_trace.device_clock_lead(trace) is None


def test_step_host_ms_leaves_out_the_fetch_where_a_step_has_one():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ev("hetu.exec.step", 0, 100), ev("hetu.exec.dispatch", 10, 70),
            ev("hetu.exec.step", 1000, 900),
            ev("hetu.exec.dispatch", 1010, 70),
            ev("hetu.exec.fetch", 1100, 790)]}]}]}
    # 1.0 ms whole, and (9.0 - 7.9) ms: the wait for the device is not
    # the host's time to feed and enqueue a step
    assert metric("step_host_ms", {"trace": trace}) == \
        pytest.approx((1.0 + 1.1) / 2)


# ------------------------------------------------------------------ #
# names on the device
# ------------------------------------------------------------------ #

def test_a_kernel_is_found_by_its_own_name_not_as_an_operand():
    data = {"trace": serve_trace()}
    for text, name in [
            ("%ragged_paged_mixed.3 = bf16[2] custom-call()",
             "ragged_paged_mixed"),
            # a VJP node's recompute: jvp(flash_fwd), as XLA spells it
            ("%jvp_flash_fwd_.7 = bf16[2] custom-call()", "flash_fwd"),
            ("%transpose_jvp_flash_bwd_dq__.1 = bf16[2] custom-call()",
             "flash_bwd_dq"),
            ("%jvp__.4 = bf16[2] custom-call()", "jvp__"),
            ("%convert_reduce_fusion = f32[2] fusion()",
             "convert_reduce_fusion")]:
        assert program_trace.op_name(text) == name
    assert metric("ragged_kernel_share.serve", data) == \
        pytest.approx(100 * 100 / 500)
    # the existing substring reader counts the same single call
    assert reader("kernel_share").read(
        data, ['custom_call_target="tpu_custom_call"']) == \
        pytest.approx(100 * 100 / 500)


def test_a_while_counts_once_and_takes_its_bodys_scope():
    trace = serve_trace()
    top = program_trace.top_level(trace)
    assert [program_trace.op_name(e[0]) for e, _ in top] == [
        "fusion", "ragged_paged_mixed", "fusion", "while", "copy",
        "dynamic-update-slice"]
    assert dict((program_trace.op_name(e[0]), s) for e, s in top)[
        "while"].endswith("/sample/while/body/sort:")
    data = {"trace": trace}
    # the while's 200 units once; sort and add inside it not again
    assert metric("sample_share.serve", data) == pytest.approx(40.0)
    # the scatter alone: the compiler's copy carries no scope here
    assert metric("kv_write_share.serve", data) == pytest.approx(4.0)
    shares = [metric(m, data) for m in (
        "sample_share.serve", "kv_write_share.serve",
        "ragged_kernel_share.serve")]
    assert all(0 <= s <= 100 for s in shares)


@pytest.mark.parametrize("stack,scope,inside", [
    ("jit(f)/jit(main)/sample/while/body/sort:", "sample", True),
    ("jit(step_fn)/transpose(jvp(FlashAttention))/flash_bwd_dq/"
     "pallas_call:", "FlashAttention", True),
    ("jit(f)/resample/while:", "sample", False),
    ("jit(f)/sample/mul:", "sample", True),
    ("", "sample", False),
])
def test_a_scope_is_a_whole_component_of_the_name_stack(stack, scope,
                                                        inside):
    assert program_trace.under_scope(stack, [scope]) is inside


# ------------------------------------------------------------------ #
# nothing to read: the parent's program, a rename
# ------------------------------------------------------------------ #

def parents_trace():
    """What the program before PR 25 leaves: transform-named kernels,
    no ``hetu.`` span, and a profiler file without name stacks."""
    trace = serve_trace()
    del trace["op_scopes"]
    dev = trace["planes"][0]["lines"][0]
    dev["events"][1][0] = dev["events"][1][0].replace(
        "%ragged_paged_mixed.3", "%_serve_mixed_paged.24")
    host = trace["planes"][1]["lines"][0]
    host["events"] = [e for e in host["events"]
                      if not e[0].startswith("hetu.")]
    trace["planes"][1]["lines"].pop()
    return trace


def test_every_new_metric_is_left_out_on_the_parents_trace():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    log = _Log()
    data = {"trace": parents_trace(), "harness": log,
            "snapshot": {}, "samples": {}}
    new = bench["per_layer"][10:20]                    # PR 25's ten
    assert len(new) == 10
    assert bench_run.per_layer_metrics(new, data) == {}
    # each says what it missed, on a line of its own
    assert len(log.lines) == 10
    assert all(r["line"] == "metric_missing" for r in log.lines)
    assert {"op_share", "scope_share", "span_median",
            "idle_under_spans"} == {r["reader"] for r in log.lines}
    # the ten the benchmark had read this trace as before
    old = bench_run.per_layer_metrics(
        [m for m in bench["per_layer"][:10]
         if m["source"] == "device_trace"
         and m["name"].endswith(".serve")], data)
    assert set(old) == {"pallas_kernel_share.serve",
                        "device_idle_share.serve"}
    # PR 28's seven find nothing either: no such kernel, scope or counter
    log28 = _Log()
    seven = bench["per_layer"][20:27]
    assert [m["name"] for m in seven][::3] == [
        "mla_kernel_share.serve", "moe_experts_roofline.serve",
        "expert_load_imbalance.serve"]
    assert bench_run.per_layer_metrics(
        seven, dict(data, harness=log28, counters={})) == {}
    assert all(r["line"] == "metric_missing" for r in log28.lines)
    assert {"op_share", "scope_share", "scope_or_op_share",
            "kernel_roofline"} == {r["reader"] for r in log28.lines}


def test_a_renamed_scope_or_span_is_a_missing_metric_not_a_zero():
    log = _Log()
    data = {"trace": serve_trace(), "harness": log}
    assert reader("scope_share").read(data, ["sampling"]) is None
    assert reader("op_share").read(data, ["ragged_mixed"]) is None
    assert reader("span_median").read(data, "serve.step") is None
    assert reader("span_median").read(data, "serve.wave",
                                      minus="serve.wave.wait") is None
    assert [r["missing"] for r in log.lines] == [
        ["sampling"], ["ragged_mixed"], ["serve.step"],
        ["serve.wave", "serve.wave.wait"]]


# ------------------------------------------------------------------ #
# the profiler's own file, recorded on the chip
# ------------------------------------------------------------------ #

TINY = os.path.join(FIXTURES, "tiny-program.xplane.pb.gz")


def test_name_stacks_are_read_from_the_profilers_file():
    scopes = program_trace.read_scopes(TINY)
    table, index = scopes["table"], scopes["index"]
    # three runs of one program: 38 operations each
    assert len(index) == 114 and len(table) == 8
    assert "jit(f)/FlashAttention/flash_fwd/pallas_call:" in table
    assert "jit(f)/sample/while/body/closed_call/jit(sort)/sort:" in table
    assert "" in table              # the while itself has none
    first = [table[i] for i in index[:6]]
    assert first == [
        "jit(f)/FlashAttention/transpose:",
        "jit(f)/FlashAttention/flash_fwd/pallas_call:",
        "jit(f)/sample/reshape:", "jit(f)/sample/reshape:",
        "jit(f)/sample/while:", ""]
    assert index[:38] == index[38:76] == index[76:]


def test_the_wire_reader_agrees_with_the_profilers_own_reader(tmp_path):
    import gzip
    pb = str(tmp_path / "tiny.xplane.pb")
    with gzip.open(TINY, "rb") as a, open(pb, "wb") as b:
        b.write(a.read())
    trace = xplane.load(pb)
    trace["op_scopes"] = program_trace.read_scopes(pb)
    ops = xplane.line_events(xplane.device_planes(trace)[0],
                             xplane.OPS_LINE)
    assert len(ops) == len(trace["op_scopes"]["index"]) == 114
    names = [program_trace.op_name(e[0]) for e in ops[:6]]
    assert names == ["copy", "flash_fwd", "convert", "copy", "copy",
                     "while"]
    top = program_trace.top_level(trace)
    whiles = [(e, s) for e, s in top
              if program_trace.op_name(e[0]) == "while"]
    # the benchmark's span opens after the first of the three runs
    assert len(whiles) == 2
    assert all("/sample/while/body/" in s for _, s in whiles)
    data = {"trace": trace}
    flash = reader("op_share").read(data, ["flash_fwd"])
    scoped = reader("scope_share").read(data, ["FlashAttention"])
    sample = reader("scope_share").read(data, ["sample"])
    # worked by hand from the file: two calls, 54.124 + 54.123 us, of
    # 266.954 us busy; the FlashAttention scope adds its transpose copies
    assert flash == pytest.approx(100 * 108.247 / 266.954, rel=1e-4)
    assert flash < scoped < flash + 5
    assert scoped + sample == pytest.approx(100.0, abs=0.5)
    # the device ran each of the three programs 1.12-1.17 ms "before"
    # the host handed it over, and ended 1.64-1.72 ms before the host
    # heard of it (by hand from the file): the device's clock leads
    assert program_trace.device_clock_lead(trace) == (1166019.0, 1642235.0)
    # a slice of it, as a fixture is recorded
    start = ops[0][1]
    fx = program_trace.record(pb, start, ops[38][1])
    kept = xplane.line_events(xplane.device_planes(fx)[0], xplane.OPS_LINE)
    assert len(kept) == 38 == len(fx["op_scopes"]["index"])
    assert [fx["op_scopes"]["table"][i] for i in fx["op_scopes"]["index"]] \
        == [trace["op_scopes"]["table"][i]
            for i in trace["op_scopes"]["index"][:38]]


# ------------------------------------------------------------------ #
# the serving cell's trace, recorded on the chip
# ------------------------------------------------------------------ #

SERVE = os.path.join(FIXTURES, "serve-gpt2-xl-batch-closed.trace.json.gz")


def test_readers_on_the_serving_trace_recorded_on_the_chip():
    """Two decode waves and a 128-row chunk wave of PR 25's tree on
    one v5e chip (PROGRAM_SPANS.md says how it was recorded).  The numbers
    were worked out apart from ``program_trace.py`` and ``xplane.py`` (a
    plain sweep over the JSON), in nanoseconds."""
    trace = xplane.load(SERVE)
    busy_s, window_s = xplane.busy_seconds(trace)
    assert window_s == pytest.approx(0.437420274)
    assert busy_s == pytest.approx(0.420965606)
    ops = xplane.line_events(xplane.device_planes(trace)[0],
                             xplane.OPS_LINE)
    assert len(ops) == 18816 == len(trace["op_scopes"]["index"])
    # every wave: one serve.wave with its five children and kv_alloc,
    # inside a bench.engine_step
    nodes = program_trace.span_forest(trace)
    waves = [n for n in nodes if n["name"] == "serve.wave"]
    assert len(waves) == 3 and len(nodes) == 21
    steps = [e for e in xplane.host_spans(trace)
             if e[0] == "bench.engine_step"]
    for wave, step in zip(waves, steps):
        assert [c["name"] for c in wave["children"]] == [
            "serve.admit", "serve.wave.assemble", "serve.wave.dispatch",
            "serve.wave.sync", "serve.wave.unpack"]
        assert step[1] <= wave["start"] and wave["end"] <= step[1] + step[2]
    data = {"trace": trace}
    # 144 kernel calls (3 waves x 48 layers), 101,315,306 ns; equal to
    # what the substring reader counts: no other Pallas kernel runs here
    assert metric("ragged_kernel_share.serve", data) == \
        pytest.approx(100 * 101315306 / 420965606)
    assert metric("pallas_kernel_share.serve", data) == \
        pytest.approx(metric("ragged_kernel_share.serve", data))
    # the chunk wave's one while (130,805,720 ns, 130 sorts inside it,
    # counted once) and the operations of the scope outside it
    assert metric("sample_share.serve", data) == \
        pytest.approx(100 * 132776065 / 420965606)
    assert metric("kv_write_share.serve", data) == \
        pytest.approx(100 * 504237 / 420965606)
    # waves less their syncs: 2.860749, 3.50706 and 4.994049 ms
    assert metric("wave_host_ms", data) == pytest.approx(3.50706)
    # the five programs started 0.92-1.17 ms "before" their launches
    # and ended 1.62-1.83 ms before the host heard of it
    assert program_trace.device_clock_lead(trace) == (1171087.0, 1615647.0)
    # 16,454,668 ns idle.  Moved by the middle of those bounds
    # (1,393,367 ns) and cut at every span's edge: 6,076,126 under
    # dispatch, 1,916,565 kv_alloc, 1,224,100 unpack, 197,680 assemble,
    # 51,990 admit = 9,466,461 (unmoved it would be 6,798,743, 41.3 %;
    # by the gaps' middles 4,495,258, 27.3 %)
    assert metric("idle_in_host_work_share.serve", data) == \
        pytest.approx(100 * 9466461 / 16454668)
    assert metric("device_idle_share.serve", data) == \
        pytest.approx(100 * 16454668 / 437420274)
