"""CPU tests of the benchmark's own code (tiny widths, handed in by the
test; ``run.py`` has no option that narrows anything).

This directory is one of ``BENCHMARK.json``'s ``paths`` and holds nothing
else: tier-1 collects ``tests/``, so the yardstick's own tests run with
the repo's.  A CPU run shows control flow and counts; no time, rate or
share read here is a device number.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import copy
import gc
import io
import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import opcount, reference, run as bench_run  # noqa: E402
from benchmarks import loadgen, xplane  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
TINY = {"vocab_size": 96, "n_positions": 64, "n_embd": 32, "n_layer": 2,
        "n_head": 4, "layer_norm_epsilon": 1e-5}


def harness(cell, seconds, config_over, traffic_over, trace=False, root=ROOT):
    resolved = bench_run.resolve_cell(BENCH, cell)
    resolved["config"] = dict(resolved["config"], **config_over)
    resolved["traffic"] = dict(resolved["traffic"], **traffic_over)
    h = bench_run.Harness(resolved, seed=3_000_000_019, seconds=seconds,
                          trace=trace, peak=PEAK, root=root,
                          out=io.StringIO())
    h.count_compiles()
    return h


# ------------------------------------------------------------------ #
# runners, end to end at a tiny width
# ------------------------------------------------------------------ #

# 32 channels round far more coarsely in bf16 than 1024 do, and the CPU's
# bf16 is not the chip's: the tiny model gets limits of its own, the
# cell's stay in its file
TRAIN_ARGS = {"first_gradient_gap_max": 0.015, "first_loss_gap_max": 0.004,
              "trained_loss_gap_max": 0.02, "loss_fall_min": 0.3}
TRAIN_MIX = {"batch": 2, "seq": 32, "pool": 4, "data_ids": 16}
CHECK_KEYS = {
    "first_loss_system", "first_loss_reference", "first_loss_gap",
    "first_loss_gap_max", "first_gradient_gap", "first_gradient_gap_max",
    "first_gradient_worst_leaf", "first_step_agrees",
    "trained_state_loss_system", "trained_state_loss_reference",
    "trained_state_reading", "trained_state_limit",
    "trained_state_agrees_share", "trained_state_agrees", "steps",
    "loss_fall", "check_s"}
COMPARED = ["first_gradient_gap", "first_loss_gap", "trained_state_loss_gap",
            "loss_fall", "losses_finite"]


def train_harness(seconds):
    return harness("train-gpt2-medium-s1024", seconds,
                   dict(TINY, runner_args=TRAIN_ARGS), TRAIN_MIX)


def test_train_runner_end_to_end():
    from benchmarks.runners import train
    h = train_harness(1.5)
    cfg = train.gpt_config(h.config, 2, 32)
    out = train.run(h, cfg)
    assert out["correct"], h.out.getvalue()
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert h.setup_s > 0
    assert len(out["data"]["samples"]["train_step_ms"]) == out["attempted"]
    assert "flash_call_cost" not in out["data"]


def test_train_check_says_what_it_read():
    """Every number compared, its limit and the trained-state reading as
    a share of its limit are on the ``reference`` line, in the result's
    notes and in what ``run.py`` prints last on standard error."""
    from benchmarks.runners import train
    h = train_harness(1.0)
    out = train.run(h, train.gpt_config(h.config, 2, 32))
    lines = [json.loads(l) for l in h.out.getvalue().splitlines()]
    ref = next(l for l in lines if l["line"] == "reference")
    assert CHECK_KEYS <= set(ref) and CHECK_KEYS <= set(out["notes"])
    assert ref["steps"] == out["attempted"]
    assert ref["first_loss_gap"] == pytest.approx(
        abs(ref["first_loss_system"] - ref["first_loss_reference"]))
    assert ref["trained_state_reading"] == pytest.approx(abs(
        ref["trained_state_loss_system"]
        - ref["trained_state_loss_reference"]))
    assert ref["trained_state_limit"] == TRAIN_ARGS["trained_loss_gap_max"]
    assert ref["trained_state_agrees_share"] == pytest.approx(
        ref["trained_state_reading"] / ref["trained_state_limit"])
    assert 0 < ref["first_gradient_gap"] <= ref["first_gradient_gap_max"] \
        == TRAIN_ARGS["first_gradient_gap_max"]
    assert out["notes"]["trained_state_agrees_share"] \
        == ref["trained_state_agrees_share"]
    assert [c["name"] for c in out["compared"]] == COMPARED
    assert all(c["within"] for c in out["compared"]) == out["correct"]
    by_name = {c["name"]: c for c in out["compared"]}
    assert by_name["trained_state_loss_gap"]["value"] \
        == ref["trained_state_reading"]
    assert by_name["first_gradient_gap"]["value"] == ref["first_gradient_gap"]
    err = io.StringIO()
    bench_run.report_compared(out, err)
    said = err.getvalue().splitlines()
    assert len(said) == len(COMPARED) + 1
    assert said[-1] == f"correct: {out['correct']}"
    assert said[0].startswith("compared: first_gradient_gap = ") \
        and f"limit {TRAIN_ARGS['first_gradient_gap_max']!r}" in said[0]


@pytest.fixture(scope="module")
def checked_tiny():
    """The tiny trainer's two checks, sound and with each fault on the
    reference's side: (first step, its faults, trained state after 60
    steps, the reference's loss there with each fault)."""
    from benchmarks import probe_train_check as probe
    from benchmarks.runners import train
    h = harness("train-gpt2-medium-s1024", 1, dict(TINY), TRAIN_MIX)
    cfg = train.gpt_config(h.config, 2, 32)
    ex, ids, labels = train.build_trainer(cfg, 11)
    batches = loadgen.train_batches(h.traffic, 11, cfg.vocab_size)

    def step(batch):
        return train.one_step(h, ex, ids, labels, batch)
    # the seeded weights stand until the first step donates them
    want = train.reference_first_step(ex.var_values, h.config, batches[0])
    first_faults = probe.first_step_controls(
        ex.var_values, h.config, batches[0], want, TRAIN_ARGS)
    first = train.judge_first_step(
        (step(batches[0]), train.first_gradient_norms(
            train.first_gradient_squares(ex))), want, TRAIN_ARGS)
    for i in range(1, 60):
        step(batches[i % len(batches)])
    # before the check's own step, which moves the weights on
    faults = probe.controls(ex.var_values, h.config, batches[-1])
    trained = train.read_trained_state(ex, step, h.config, batches[-1])
    return first, first_faults, trained, faults


@pytest.mark.parametrize("fault", [
    "mask_off_by_one", "block_skipped", "positions_shifted", "head_rolled",
    "float8_products"])
def test_train_check_catches_a_fault_on_the_references_side(checked_tiny,
                                                            fault):
    """The reference with the fault, put in the system's place, is
    outside a limit by a factor of two or more; the system is inside
    every limit, and a fault moves no limit."""
    from benchmarks.runners import train
    first, first_faults, trained, faults = checked_tiny
    sound = train.judge_trained_state(trained, TRAIN_ARGS)
    assert first["first_step_agrees"] and sound["agrees"], (first, sound)
    assert first["first_gradient_gap"] \
        < 0.75 * TRAIN_ARGS["first_gradient_gap_max"]
    assert sound["agrees_share"] < 0.5
    broken = train.judge_trained_state(
        dict(trained, loss_system=faults[fault]), TRAIN_ARGS)
    assert broken["limit"] == sound["limit"]
    shares = {
        "first_gradient_gap": first_faults[fault]["first_gradient_gap"]
        / TRAIN_ARGS["first_gradient_gap_max"],
        "first_loss_gap": first_faults[fault]["first_loss_gap"]
        / TRAIN_ARGS["first_loss_gap_max"],
        "trained_state_loss_gap": broken["agrees_share"]}
    assert max(shares.values()) > 2, shares
    if fault in ("mask_off_by_one", "block_skipped", "float8_products"):
        # what a faster kernel or a cheaper product would break shows in
        # the first gradient, wherever the window ends
        assert shares["first_gradient_gap"] > 2, shares


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    optimizer hands every parameter and its own state back as it got
    them.  The losses stay finite and agree with the reference; they do
    not fall, and no gradient ever reached the optimizer's state."""
    from benchmarks.runners import train
    from hetu_tpu import optimizer
    monkeypatch.setattr(optimizer.AdamWOptimizer, "update_one",
                        lambda self, p, g, s, lr, step: (p, s))
    h = train_harness(1.0)
    out = train.run(h, train.gpt_config(h.config, 2, 32))
    assert not out["correct"]
    within = {c["name"]: c["within"] for c in out["compared"]}
    assert within == {"first_gradient_gap": False, "first_loss_gap": True,
                      "trained_state_loss_gap": True, "loss_fall": False,
                      "losses_finite": True}


SERVE_ARGS = {"slots": 4, "pool_blocks": 17, "prefill_chunk": 16,
              "queue_limit": 64, "logit_margin": 0.05, "check_requests": 3}
SERVE_MIX = {"prompt_len": {"median": 12, "sigma": 0.6, "lo": 4, "hi": 40,
                            "round_to": 4},
             "output_len": {"median": 4, "sigma": 0.5, "lo": 2, "hi": 8},
             "ramp_seconds": 0.3, "drain_limit_seconds": 60.0}


@pytest.fixture
def tpu_default_paths(monkeypatch):
    """The engine's TPU defaults, on the CPU: mixed wave over a paged
    pool of block 16 (the masked attention, not the interpreted kernel)."""
    monkeypatch.setenv("HETU_SERVE_RAGGED", "1")
    monkeypatch.setenv("HETU_KV_BLOCK", "16")
    # the engine asserts that a request waits under 50 ms between its
    # claim and its wave; a collection of the whole test process's garbage
    # takes longer than that
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def h_tokens(out, log):
    """Tokens the window saw, from the serve line of the log."""
    return next(json.loads(l) for l in log.splitlines()
                if json.loads(l)["line"] == "serve")["tokens_in_window"]


def serve_once(h):
    """The engine ASSERTS that a claimed request waits under 50 ms
    outside a wave (PERF.md section 6); a test machine shared by six
    workers can pause a process that long.  That hazard is the program's
    and not what these tests are about, so a run it stops is made again."""
    from benchmarks.runners import serve
    for attempt in range(3):
        try:
            return serve.run(h, serve.gpt_config(h.config))
        except AssertionError as e:
            if "chunk_stall" not in str(e) or attempt == 2:
                raise
            h.out.seek(0)
            h.out.truncate()


@pytest.mark.parametrize("mix", [
    {"loop": "closed", "clients": 4, "request_pool": 64},
    # the open loop is the generator's other half: no cell of the accepted
    # benchmark uses it yet (PERF.md section 7, row 0), later ones will
    {"loop": "open", "rate_per_s": 6.0},
], ids=["closed", "open"])
def test_serve_runner_end_to_end(tpu_default_paths, mix):
    from benchmarks.runners import serve
    h = harness("serve-gpt2-xl-batch-closed", 2.0,
                dict(TINY, dtype="float32", runner_args=SERVE_ARGS),
                dict(SERVE_MIX, **mix))
    out = serve_once(h)
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = out["end_to_end"]
    assert e2e["ttft_p95_ms"] > 0 and e2e["tpot_p95_ms"] > 0
    # every token that landed inside the window, whoever's request it was
    assert e2e["serve_tokens_per_s"] * 2.0 == pytest.approx(
        h_tokens(out, log))
    line = next(json.loads(l) for l in log.splitlines()
                if json.loads(l)["line"] == "serve")
    eng = line["engine"]
    assert eng["ragged"] and eng["paged"]
    assert eng["warmed_buckets"] == [4, 8, 16]
    # untraced run: the readers see every finished request of the window
    assert line["untraced_until_s"] is None
    assert len(out["data"]["samples"]["tpot_ms"]) == line["tpot_samples"]
    snap = out["data"]["snapshot"]
    assert snap["decode_ms_p50"] > 0 and snap["prefill_ms_p50"] > 0
    assert snap["steps"] > 0 and 0 < snap["mean_batch_occupancy"] <= 1
    if mix["loop"] == "open":
        # every arrival due in the window is attempted: round(6.0 * 2.0)
        # of the round(6.0 * 2.3) arrivals, give or take the ramp's share
        assert 8 <= out["attempted"] <= 14
        assert len(out["data"]["samples"]["gen_lag_ms"]) == out["attempted"]
        # the fixed set is exactly the arrivals of ramp + window
        assert serve.request_count(h.traffic, 2.0) == round(6.0 * 2.3)


def test_traced_run_reads_host_samples_from_before_the_profiler(
        tpu_default_paths, tmp_path):
    """``--trace 1``: the profiler is started inside the window, and what
    the readers get from the host's clock and the engine's counters ends
    where it started."""
    h = harness("serve-gpt2-xl-batch-closed", 2.0,
                dict(TINY, dtype="float32", runner_args=SERVE_ARGS),
                dict(SERVE_MIX, loop="closed", clients=4, request_pool=64,
                     trace_seconds=0.7), trace=True, root=str(tmp_path))
    out = serve_once(h)
    log = h.out.getvalue()
    line = next(json.loads(l) for l in log.splitlines()
                if json.loads(l)["line"] == "serve")
    started = line["untraced_until_s"]
    assert started is not None and 1.3 <= started < 2.0, log
    samples = out["data"]["samples"]["tpot_ms"]
    assert 0 < len(samples) == line["untraced_tpot_samples"]
    assert len(samples) < line["tpot_samples"]
    assert out["data"]["snapshot"]["steps"] > 0
    # the end-to-end numbers still cover the whole window
    assert out["end_to_end"]["serve_tokens_per_s"] * 2.0 == pytest.approx(
        h_tokens(out, log))
    trace = xplane.load(xplane.find_xplane(h.trace_dir))
    spans = {e[0] for e in xplane.host_spans(trace)}
    assert "bench.engine_step" in spans
    first, last = xplane.window_of(trace)
    assert 0 < (last - first) / 1e9 < 2.0        # span to span, in the window


def test_window_view_subtracts_the_warm_up():
    """Ten warm-up waves of one live slot in four, then thirty full
    waves: the window's occupancy is 1.0, not the life's 0.8125."""
    from benchmarks.runners import serve
    at_open = {"steps": 10, "mean_batch_occupancy": 0.25}
    now = {"steps": 40, "mean_batch_occupancy": (10 * 0.25 + 30 * 1.0) / 40,
           "decode_ms_p50": 65.0, "prefill_ms_p50": 300.0}
    view = serve.window_view(at_open, now)
    assert view["steps"] == 30
    assert view["mean_batch_occupancy"] == pytest.approx(1.0)
    assert view["decode_ms_p50"] == 65.0 and view["prefill_ms_p50"] == 300.0
    # an engine that has made no step yet reports None and 0
    first = serve.window_view({"steps": 0, "mean_batch_occupancy": None},
                              {"steps": 4, "mean_batch_occupancy": 0.5})
    assert first["mean_batch_occupancy"] == pytest.approx(0.5)
    assert "mean_batch_occupancy" not in serve.window_view(at_open, at_open)


def test_load_knows_who_waits_for_a_first_token(tpu_default_paths):
    """From the stream callback alone, not from the engine's insides."""
    from benchmarks.runners import serve
    mix = dict(SERVE_MIX, loop="closed", clients=2, request_pool=8)
    load = serve.Load(mix, 5, 96, 8)
    a, b = load.next_request(0.0, 0.0), load.next_request(0.0, 0.1)
    assert load.no_token_yet == {"q0", "q1"}
    load.on_token(a, 17)
    assert load.no_token_yet == {"q1"} and load.emitted == 1
    load.on_token(a, 18)
    load.rejected(b)
    assert not load.no_token_yet and load.emitted == 2
    assert load.rows["q1"]["result"] is None          # counted as failed


def test_chunk_buckets_follow_the_traffic():
    from benchmarks.runners import serve
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "batch-closed.json"))
    assert serve.chunk_buckets(mix, 256) == [16, 32, 64, 128, 256]
    assert serve.chunk_buckets(mix, 128) == [16, 32, 64, 128]


# ------------------------------------------------------------------ #
# the generator
# ------------------------------------------------------------------ #

def test_generator_is_a_pure_function_of_the_seed():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "batch-closed.json"))
    a = loadgen.request_sizes(mix, 2_500_000_001, 512)
    assert a == loadgen.request_sizes(mix, 2_500_000_001, 512)
    b = loadgen.request_sizes(mix, 7, 512)
    assert a != b and sorted(a) == sorted(b)    # same work, another order
    p, n = np.array(a).T
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert p.min() >= lo and p.max() <= hi and not (p % 16).any()
    assert n.min() >= mix["output_len"]["lo"]
    assert n.max() <= mix["output_len"]["hi"]
    assert p.max() + n.max() <= 1024
    assert 100 <= np.median(p) <= 160 and 40 <= np.median(n) <= 56
    t = loadgen.prompt_tokens(7, 3, 32, 50257)
    assert (t == loadgen.prompt_tokens(7, 3, 32, 50257)).all()
    assert t.min() >= 0 and t.max() < 50257


def test_arrivals_fill_the_span_whatever_the_seed():
    mix = {"rate_per_s": 2.5, "base_seed": 1}
    a = loadgen.poisson_arrivals(mix, 11, -4.0, 30.0)
    b = loadgen.poisson_arrivals(mix, 2 ** 31 + 5, -4.0, 30.0)
    assert len(a) == len(b) == 85
    assert a == sorted(a) and a[0] >= -4.0 and a[-1] < 30.0
    assert a != b
    assert np.allclose(sorted(np.diff(a + [30.0])), sorted(np.diff(b + [30.0])))


def test_train_batches_follow_the_smoke_task():
    mix = {"batch": 2, "seq": 8, "pool": 3, "data_ids": 16}
    a = loadgen.train_batches(mix, 5, 96)
    b = loadgen.train_batches(mix, 5, 96)
    assert len(a) == 3 and all((x == u).all() for (x, _), (u, _) in zip(a, b))
    x, y = a[0]
    assert x.shape == (2, 8) and x.max() < 16
    assert (y == (3 * x + 7) % 16).all()


# ------------------------------------------------------------------ #
# TTFT from the due time; the failure count
# ------------------------------------------------------------------ #

class _Result:
    def __init__(self, ttft_s, latency_s, n):
        self.ttft_s, self.latency_s, self.n_generated = ttft_s, latency_s, n


class _Request:
    max_new_tokens = 5


def _row(due, submitted, result, done):
    return {"due": due, "submitted": submitted, "request": _Request(),
            "result": result, "done": done}


def test_ttft_counts_from_the_due_time_and_failures_are_counted():
    from benchmarks.runners import serve

    class L:
        tokens_in_window = 10
        rows = {
            # due at 1.0, submitted 0.25 s late, first token 0.5 s later
            "a": _row(1.0, 1.25, _Result(0.5, 0.9, 5), 2.15),
            # rejected at submit: attempted, failed
            "b": _row(2.0, 2.0, None, None),
            # due in the ramp: not attempted
            "c": _row(-1.0, -1.0, _Result(0.1, 2.0, 5), 1.0),
            # due in the window, finished in the drain: attempted, timed
            "d": _row(9.0, 9.0, _Result(0.2, 3.0, 5), 12.0),
        }
    out = serve.reduce_rows(L, 10.0)
    assert out["attempted"] == 3 and out["failed"] == 1
    assert sorted(out["ttft_ms"]) == pytest.approx([200.0, 750.0])
    assert sorted(out["tpot_ms"]) == pytest.approx([100.0, 700.0])
    assert out["gen_lag_ms"] == pytest.approx([250.0, 0.0, 0.0])
    assert out["tokens_per_s"] == pytest.approx(1.0)     # 10 tokens / 10 s
    assert out["exact_lengths"]
    # untraced: the readers' samples are the end-to-end ones
    assert sorted(out["untraced"]["tpot_ms"]) == pytest.approx([100.0, 700.0])
    # the profiler was started at 4.0: only "a" had finished by then, and
    # the end-to-end samples do not change
    traced = serve.reduce_rows(L, 10.0, untraced_until=4.0)
    assert traced["untraced"]["tpot_ms"] == pytest.approx([100.0])
    assert traced["untraced"]["gen_lag_ms"] == pytest.approx([250.0])
    assert sorted(traced["tpot_ms"]) == pytest.approx([100.0, 700.0])
    assert traced["attempted"] == 3 and traced["failed"] == 1


# ------------------------------------------------------------------ #
# the command: no TPU, the result line, files found by name
# ------------------------------------------------------------------ #

def _run_cli(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=root, timeout=120)


def test_cli_exits_nonzero_without_a_tpu_before_building_anything():
    r = _run_cli(ROOT, "--workload", BENCH["workloads"][0]["name"],
                 "--seed", "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout == ""                      # no result, no earlier line
    assert "needs a TPU" in r.stderr and "Nothing was run" in r.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    fake = type("D", (), {"platform": "tpu", "device_kind": "TPU v9 mega"})()
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(SystemExit, match="no peaks for device_kind"):
        bench_run.require_device(1, {"TPU v5 lite": PEAK})
    ok = type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(jax, "devices", lambda: [ok])
    with pytest.raises(SystemExit, match="needs 4 chip"):
        bench_run.require_device(4, {"TPU v5 lite": PEAK})
    assert bench_run.require_device(1, {"TPU v5 lite": PEAK}) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_last_line_holds_the_contracts_keys(monkeypatch, capsys):
    """``main`` with the device check and the runner stubbed: the last
    printed line parses and holds what the driver reads."""
    monkeypatch.setattr(bench_run, "require_device", lambda chips, peaks: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(bench_run, "enable_compile_cache", lambda: "unused")

    class Runner:
        @staticmethod
        def run(h):
            h.open_window()
            h.close_window()
            return {"correct": True, "attempted": 7, "failed": 0,
                    "memory_peak_bytes": 5 * 2 ** 30,
                    "end_to_end": {"train_tokens_per_s": 31234.5678}}
    monkeypatch.setattr(bench_run, "load_module",
                        lambda kind, name, here=None: Runner)
    bench_run.main(["--workload", "train-gpt2-medium-s1024", "--seed",
                    str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert last["metrics"]["train_tokens_per_s"] == {
        "value": 31234.5678, "unit": "tokens/s"}
    assert last["metrics"]["setup_s"]["value"] > 0
    assert last["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 5 * 2 ** 30}


def test_a_compile_inside_the_window_fails_the_run():
    import jax
    import jax.numpy as jnp
    h = harness("train-gpt2-medium-s1024", 1, {}, {})
    h.open_window()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    with pytest.raises(SystemExit, match="inside the measured window"):
        h.close_window()


def test_new_cell_config_and_metric_are_found_without_editing_a_file(tmp_path):
    """A later PR's cell: new files and appended entries only."""
    here = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "gpt2-medium.json").read_text())
    cfg.update(n_layer=36, n_embd=1280, n_head=20, runner="serve")
    (here / "configs" / "gpt2-large.json").write_text(json.dumps(cfg))
    (here / "traffic" / "long-prompt.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 0.5}))
    (here / "metrics" / "steps_counted.json").write_text(json.dumps(
        {"reader": "times_two", "args": {"key": "steps"}}))
    (here / "readers" / "times_two.py").write_text(
        "def read(data, key):\n    return 2 * data['counters'][key]\n")
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "gpt2-large", "source": "x",
                             "file": "benchmarks/configs/gpt2-large.json",
                             "reduced": [], "why": "y"})
    bench["workloads"].append({"name": "serve-gpt2-large-long-prompt",
                               "config": "gpt2-large", "traffic":
                               "long-prompt", "chips": 1, "why": "z"})
    bench["per_layer"].append({
        "name": "steps_counted", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "serving scheduler",
        "moves": "ttft_p95_ms",
        "workloads": ["serve-gpt2-large-long-prompt"]})
    r = bench_run.resolve_cell(bench, "serve-gpt2-large-long-prompt",
                               root=str(tmp_path), here=str(here))
    assert r["config"]["n_layer"] == 36 and r["config"]["runner"] == "serve"
    assert r["traffic"]["rate_per_s"] == 0.5
    assert [m["name"] for m in r["end_to_end"]] == ["setup_s"]
    assert [m["name"] for m in r["per_layer"]] == ["steps_counted"]
    got = bench_run.per_layer_metrics(
        r["per_layer"], {"counters": {"steps": 21}}, here=str(here))
    assert got == {"steps_counted": {"value": 42.0, "unit": "steps"}}
    assert callable(bench_run.load_module("runners", "serve", str(here)).run)
    # nothing that was there was edited
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_entry_of_benchmark_json_has_its_files():
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    assert os.listdir(os.path.dirname(os.path.abspath(__file__))) in (
        ["test_benchmark.py"], ["test_benchmark.py", "__pycache__"],
        ["__pycache__", "test_benchmark.py"])
    assert os.path.isfile(os.path.join(ROOT, *BENCH["command"][1].split("/")))
    for c in BENCH["configs"]:
        config = bench_run.load_json(os.path.join(ROOT, c["file"]))
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "runners", config["runner"] + ".py"))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        r = bench_run.resolve_cell(BENCH, w["name"])
        assert {"setup_s"} < {m["name"] for m in r["end_to_end"]}
        assert r["per_layer"]
        reported = {m["name"] for m in r["end_to_end"]}
        for m in r["per_layer"]:
            assert m["moves"] in e2e and m["moves"] in reported, m["name"]
            spec = bench_run.load_json(os.path.join(
                ROOT, "benchmarks", "metrics", m["name"] + ".json"))
            assert os.path.isfile(os.path.join(
                ROOT, "benchmarks", "readers", spec["reader"] + ".py"))


# ------------------------------------------------------------------ #
# operations and bytes, against hand-worked numbers
# ------------------------------------------------------------------ #

MEDIUM = {"n_embd": 1024, "n_layer": 24, "n_head": 16, "vocab_size": 50257}


def test_6pt_step_of_gpt2_medium_is_18_6_tflop():
    # 12 * 24 * 1024^2 = 301,989,888 in the blocks; 50257 * 1024 in the head
    assert opcount.matmul_params(MEDIUM) == 301_989_888 + 51_463_168
    # attention: 24 layers * 8 seqs * 16 heads * 2 products * 2*1024^2*64
    # = 824.6 G forward, halved for the causal triangle, times 3 for
    # forward + backward = 1.237 T
    attn = 3 * 24 * 8 * 16 * (4 * 1024 * 1024 * 64) // 2
    assert attn == 1_236_950_581_248
    want = 6 * 353_453_056 * 8192 + attn
    assert opcount.train_step_flops(MEDIUM, 8, 1024) == want
    assert round(want / 1e12, 1) == 18.6


def test_mfu_line_follows_the_peaks_table():
    # 34,400 tokens a second x 18.61 TFLOP / 8192 tokens / 197 TFLOP/s
    flops = opcount.train_step_flops(MEDIUM, 8, 1024)
    mfu = 100 * 34_400 / 8192 * flops / PEAK["bf16_flops_per_s"]
    assert PEAK["bf16_flops_per_s"] == 197e12
    assert PEAK["hbm_bytes_per_s"] == 819e9
    assert mfu == pytest.approx(39.67, abs=0.01)


# ------------------------------------------------------------------ #
# the plain reference against the system's own teacher-forced forward
# ------------------------------------------------------------------ #

def test_reference_agrees_with_teacher_forced_logits():
    import jax.numpy as jnp
    from benchmarks.runners import serve
    from hetu_tpu.models.gpt_decode import teacher_forced_logits
    cfg = serve.gpt_config(TINY)
    params = serve.init_params(cfg, 2 ** 31 + 3, jnp.float32)
    # biases and LayerNorm off their initial 0/1, so that a dropped one shows
    rng = np.random.default_rng(0)
    params = {k: v + 0.05 * rng.standard_normal(v.shape).astype(np.float32)
              for k, v in params.items()}
    seq = rng.integers(0, 96, 48)
    want = np.asarray(teacher_forced_logits(params, cfg, seq))
    got = np.asarray(reference.logits(params, TINY, seq))
    assert got.shape == want.shape == (48, 96)
    assert np.abs(got - want).max() < 2e-4
    # mean_loss is the cross-entropy of those logits
    labels = rng.integers(0, 96, 48)
    lse = np.log(np.exp(want).sum(-1))
    xent = (lse - want[np.arange(48), labels]).mean()
    assert reference.mean_loss(params, TINY, seq[None], labels[None]) == \
        pytest.approx(xent, abs=2e-4)


# ------------------------------------------------------------------ #
# the trace reduction, on a trace recorded on the chip
# ------------------------------------------------------------------ #

def _synthetic_trace():
    ops = [["fusion.1", 0.0, 100.0], ["_fwd_kernel", 100.0, 50.0],
           ["fusion.2", 300.0, 100.0], ["_fwd_kernel", 350.0, 100.0],
           ["copy.3", 900.0, 50.0]]
    spans = [["bench.train_step", 0.0, 500.0], ["bench.next_batch", 500.0, 350.0],
             ["bench.train_step", 850.0, 150.0], ["other", 0.0, 5000.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit_step(1)", 0.0, 450.0],
                                               ["jit_step(1)", 900.0, 50.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": spans}]}]}


def test_reduction_on_a_hand_made_trace():
    t = _synthetic_trace()
    assert xplane.window_of(t) == (0.0, 1000.0)
    # busy: [0,150] + [300,450] + [900,950] = 350 of 1000 ns
    busy_s, window_s = xplane.busy_seconds(t)
    assert busy_s == pytest.approx(350e-9) and window_s == pytest.approx(1e-6)
    assert xplane.op_seconds(t)["_fwd_kernel"] == pytest.approx(150e-9)
    # gaps: 150..300 under train_step; 450..900 has its middle (675) under
    # next_batch; 950..1000 under the second train_step
    assert xplane.idle_gaps(t) == [["bench.next_batch", pytest.approx(450e-9)],
                                   ["bench.train_step", pytest.approx(200e-9)]]
    # kinds, not single operations: fusion.1 + fusion.2
    assert xplane.top_ops(t, top=2) == [["fusion", pytest.approx(200e-9)],
                                        ["_fwd_kernel", pytest.approx(150e-9)]]
    call = ('%step_fn.24 = (bf16[8]) custom-call(bf16[8] %x), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert xplane.short_name(call) == "step_fn[tpu_custom_call]"
    from benchmarks.readers import idle_share, kernel_share, module_time
    data = {"trace": t}
    assert idle_share.read(data) == pytest.approx(65.0)
    assert kernel_share.read(data, ["_fwd_kernel"]) == pytest.approx(
        100 * 150 / 350)
    assert kernel_share.read(data, ["_no_such_kernel"]) is None
    assert module_time.read(data, ["jit_step"]) == pytest.approx(250e-6)
    # trimming to the first step keeps its operations and the bench spans
    small = xplane.trim(t, 0.0, 500.0)
    assert xplane.window_of(small) == (0.0, 500.0)
    assert len(xplane.line_events(small["planes"][0], xplane.OPS_LINE)) == 4
    assert [e[0] for e in xplane.host_spans(small)] == ["bench.train_step"]


def test_a_trimmed_trace_survives_the_fixture_format(tmp_path):
    """``dump`` writes what ``load`` reads: how the fixture was made."""
    small = xplane.trim(_synthetic_trace(), 0.0, 500.0)
    path = str(tmp_path / "small.trace.json.gz")
    xplane.dump(small, path)
    again = xplane.load(path)
    assert xplane.busy_seconds(again) == xplane.busy_seconds(small)
    assert xplane.top_ops(again) == xplane.top_ops(small)


FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "train-gpt2-medium-s1024.trace.json.gz")


def test_reduction_on_the_trace_recorded_on_the_chip():
    """The first two steps of this PR's first traced run on the chip (see
    fixtures/README.md).  The expected numbers were worked out apart from
    ``xplane.py``, by a plain sweep over the sorted intervals:

    window   first ``bench.`` span's start to the last one's end: 0.476474068 s
    busy     union of the 22,692 ``XLA Ops`` intervals inside it: 0.46722964 s
    idle     1 - 0.46722964 / 0.476474068 = 1.9402 %
    kernels  2 steps x 24 layers x 4 Pallas calls (forward twice, dkv, dq)
             = 192 ``tpu_custom_call`` events, 0.153351155 s together:
             0.153351155 / 0.46722964 = 32.82 % of the busy time
    """
    t = xplane.load(FIXTURE)
    busy_s, window_s = xplane.busy_seconds(t)
    assert window_s == pytest.approx(0.476474068, rel=1e-9)
    assert busy_s == pytest.approx(0.46722964, rel=1e-9)
    calls = xplane.matching_events(
        t, xplane.OPS_LINE, ['custom_call_target="tpu_custom_call"'])
    assert len(calls) == 192
    assert sum(e[2] for e in calls) / 1e9 == pytest.approx(0.153351155, rel=1e-9)
    from benchmarks.readers import idle_share, kernel_share
    assert idle_share.read({"trace": t}) == pytest.approx(1.9401744, rel=1e-6)
    assert kernel_share.read({"trace": t}, [
        'custom_call_target="tpu_custom_call"']) == pytest.approx(
            100 * 0.153351155 / 0.46722964, rel=1e-9)
    # every idle gap of a synced step lies under the step's own span
    assert xplane.idle_gaps(t)[0][0] == "bench.train_step"
    kinds = dict(xplane.top_ops(t))
    assert kinds["step_fn[tpu_custom_call]"] == pytest.approx(
        kinds["jvp__[tpu_custom_call]"], rel=0.01)     # the forward, twice
