"""CPU tests of what ISSUE 37 adds to the benchmark: the runner
``serve_ssm_hybrid`` end to end at a small size with both parts of its
comparison, each control coming out not correct, the configuration, cell,
traffic and metric entries and their files, ``opcount_ssm_hybrid`` against
numbers worked by hand, and the new reader on a hand-made trace.

They live here and not in ``tests/benchmark/``: that directory's own test
pins its listing to one file, and a PR may not edit a file the benchmark
already has.  A CPU run shows control flow and counts; no time, rate or
share read here is a device number.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import gc
import io
import json
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import opcount_ssm_hybrid, run as bench_run  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-falcon-h1-34b-chat-closed"
SOURCE = ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
          "config.json")
SMALL = dict(vocab_size=257, hidden_size=64, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=96, mamba_d_ssm=32, mamba_n_heads=4,
             mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
             mamba_chunk_size=8, max_position_embeddings=256)
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.6, "lo": 4, "hi": 48,
                             "round_to": 4},
                 output_len={"median": 6, "sigma": 0.5, "lo": 2, "hi": 12})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 64: the order of the sums is all that
    # differs, so the limits are a thousandth of the cell's; a prompt of
    # 3 chunks of 8 is "long" here
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=33,
                prefill_chunk=8, max_seq_len=64, check_requests=5,
                state_requests=2, long_prompt_chunks=3, reference_pad=16,
                reference_rows=8, logit_margin=2e-4, state_margin=2e-4)
    args.update(args_over)
    resolved["config"] = dict(resolved["config"], **SMALL, dtype="float32",
                              runner_args=args)
    resolved["traffic"] = dict(resolved["traffic"], **SMALL_MIX)
    h = bench_run.Harness(resolved, seed=3_000_000_019, seconds=seconds,
                          trace=False, peak=PEAK, root=ROOT,
                          out=io.StringIO())
    h.count_compiles()
    return h


@pytest.fixture
def tpu_default_paths():
    """The engine's defaults as the runner takes them, with the collector
    held off as ``tests/benchmark``'s own fixture does."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "serve_ssm_hybrid")


@pytest.fixture(scope="module")
def window(runner):
    """One served window at the small size, shared by the tests that
    read it again under a control."""
    from hetu_tpu.models.ssm_decode import SSMHybridConfig
    gc.collect()
    h = harness()
    w = runner.serve_window(h, cfg=SSMHybridConfig.from_hf(h.config))
    return h, w


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    from hetu_tpu.models.ssm_decode import SSMHybridConfig
    h = harness()
    out = runner.run(h, cfg=SSMHybridConfig.from_hf(h.config))
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    lines = {json.loads(l)["line"]: json.loads(l) for l in log.splitlines()}
    eng = lines["serve"]["engine"]
    assert eng["ragged"] and eng["paged"] and eng["stateful"]
    assert eng["drained"] and eng["state_resets"] >= out["attempted"]
    assert eng["warmed_buckets"] == [4, 8]
    # a layer's conv tail [1, 4, 3, 96] and matrix state [1, 4, 4, 8, 16],
    # four layers of each, float32
    assert lines["setup"]["state_bytes"] == (4 * 4 * 3 * 96
                                             + 4 * 4 * 4 * 8 * 16) * 4
    assert lines["setup"]["state_dtypes"] == ["float32"] * 8
    ref = lines["reference"]
    assert ref["control"] is None
    assert ref["requests_checked"] == 5 and ref["rows_checked"] > 0
    assert ref["state_requests_checked"] == 2
    assert ref["widest_logit_gap"] <= 2e-4
    assert ref["widest_state_error"] <= 2e-4
    assert ref["longest_checked_prompt_chunks"] >= 3
    # every branch moves the residual, and the logits are of order one
    for layer in ref["rms"]:
        for branch in ("attention", "ssm", "mlp"):
            assert layer[branch] > 0.05 * layer["residual"]
        assert layer["scores"] > 0.1
    assert 0.3 < ref["logit_std"] < 3.0
    assert {c["name"] for c in out["compared"]} == {
        "widest_logit_gap", "widest_state_error", "state_requests_checked",
        "longest_checked_prompt_chunks", "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    assert c["ssm_rows"] == c["wave_rows_live"] * 4
    assert 0 < c["ssm_slot_steps"] <= c["ssm_rows"]
    assert c["ssm_chunk_pairs"] > 0
    assert c["attn_score_pairs"] >= c["attn_ctx_tokens"] > 0
    assert h.setup_s > 0


@pytest.mark.parametrize("control", [
    "float8", "ssm", "attention", "carry", "position", "state_bf16"])
def test_each_control_comes_out_not_correct(window, runner, control):
    """The same served window read against the reference computed another
    way: every control is outside one of the two limits.  At this width
    the limits are float32's (2e-4); the cell's are set between what the
    bfloat16 engine reads and what each control reads on the chip
    (PERF.md section 6, PR 37)."""
    h, w = window
    args = h.config["runner_args"]
    ok, record = runner.agree(h, w["params"], w["ref_config"],
                              w["out"]["done"], args, h.seconds,
                              states=w["states"])
    assert ok, record
    ok, other = runner.agree(h, w["params"], w["ref_config"],
                             w["out"]["done"], args, h.seconds,
                             states=w["states"], control=control)
    assert not ok, other
    assert other["widest_logit_gap"] > args["logit_margin"] \
        or other["widest_state_error"] > args["state_margin"]
    if control in ("carry", "state_bf16", "float8"):
        assert other["widest_state_error"] > 10 * args["state_margin"]


@pytest.mark.parametrize("limit,value", [
    ("logit_margin", -1.0), ("state_margin", -1.0), ("state_requests", 99),
    ("long_prompt_chunks", 99)])
def test_each_limit_alone_refuses(window, runner, limit, value):
    h, w = window
    args = dict(h.config["runner_args"], **{limit: value})
    ok, _ = runner.agree(h, w["params"], w["ref_config"], w["out"]["done"],
                         args, h.seconds, states=w["states"])
    assert not ok


def test_without_the_states_the_run_is_not_correct(window, runner):
    h, w = window
    ok, record = runner.agree(h, w["params"], w["ref_config"],
                              w["out"]["done"], h.config["runner_args"],
                              h.seconds, states=None)
    assert not ok and record["state_requests_checked"] == 0


def test_sample_takes_drained_requests_first_and_a_long_prompt(runner):
    class R:
        def __init__(self, p):
            self.prompt_len = p

    class H:
        seed = 11

    done = [{"result": R(300), "done": 10.0 + i * 0.1} for i in range(40)]
    done += [{"result": R(900), "done": 3.0}]
    for i in (5, 17, 30):
        done[i]["done"] = 60.0                       # finished in the drain
    args = {"check_requests": 6, "state_requests": 2, "prefill_chunk": 256,
            "long_prompt_chunks": 3}
    picks, drained, longest = runner.sample(H(), done, 51.0, args)
    assert len(picks) == 6 and len(set(picks)) == 6
    assert len(drained) == 2 and drained <= {5, 17, 30}
    assert set(picks[:2]) == drained
    assert 40 in picks and longest == 4
    assert runner.sample(H(), done, 51.0, args)[0] == picks   # the seed's
    none, _, longest = runner.sample(H(), done[:40], 51.0, args)
    assert len(none) == 6 and longest == 2


def test_state_error_finds_the_slot_and_reads_the_widest_head(runner):
    rng = np.random.default_rng(0)
    served = rng.normal(size=(2, 5, 3, 4, 6)).astype(np.float32)
    want = served[:, 3].copy()
    rel, ratio, slot = runner.state_error(served, want)
    assert slot == 3 and rel == 0.0 and ratio == 0.0
    want[1, 2] *= 1.1                                # one head of layer 1
    rel, ratio, slot = runner.state_error(served, want)
    assert slot == 3
    assert rel == pytest.approx(0.1 / 1.1, rel=1e-5)
    assert ratio == pytest.approx(1 / 1.1 - 1, abs=1e-5) or \
        ratio == pytest.approx(0.1 / 1.1, rel=1e-5)


# ------------------------------------------------------------------ #
# entries and their files
# ------------------------------------------------------------------ #

def test_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "falcon-h1-34b")
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 72}
    assert "eleven further chips" in config["deployment"]
    assert set(config["assumed"]) >= {"state_dtype", "A_log_dt_bias_D",
                                      "weights", "conv", "max_seq_len"}
    assert config["runner"] == "serve_ssm_hybrid"
    assert config["dtype"] == "bfloat16"
    args = config["runner_args"]
    for key in ("logit_margin", "state_margin", "check_requests",
                "state_requests", "long_prompt_chunks"):
        assert args[key] > 0 and args[key + "_why"]
    assert args["slots"] == 64 and args["prefill_chunk"] == 256
    assert (args["pool_blocks"] - 1) * 16 == args["slots"] * args[
        "max_seq_len"]
    mem = config["memory_analysis"]
    for q in (1, 64, 128, 256):
        m = mem[f"slots_64_Q_{q}_pool_8193"]
        assert m["peak_GB"] < 15.75
        # the pool pair (1.61 GB) and the states (1.62 GB) in place
        assert m["aliased_GB"] > 3.2
    # the weights as served: 10.51 GB
    runner = bench_run.load_module("runners", "serve_ssm_hybrid")
    cfg = runner.model_config(config)
    nbytes = sum(int(np.prod(s)) * 2
                 for s in cfg.param_shapes("fh1").values())
    assert 10.45e9 < nbytes < 10.57e9
    # a slot's state a layer: 4.19 MB float32 beside a 30 KB conv tail
    shapes = cfg.block_spec().state_shapes(6, 5120)
    assert len(shapes) == 12
    tails, mats = shapes[0], shapes[6]
    assert int(np.prod(mats[0][1:])) * 4 == 4_194_304
    assert int(np.prod(tails[0][1:])) * 2 == 30_720


def test_traffic_file_holds_the_issues_table():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "chat-closed.json"))
    assert mix.pop("note")
    pool = mix.pop("request_pool")
    assert pool % 64 == 0 and pool >= 64
    assert mix == {
        "kind": "requests", "loop": "closed", "clients": 64, "base_seed": 37,
        "prompt_len": {"median": 384, "sigma": 0.6, "lo": 64, "hi": 1536,
                       "round_to": 64},
        "output_len": {"median": 192, "sigma": 0.5, "lo": 32, "hi": 512},
        "ramp_seconds": 12.0, "drain_limit_seconds": 60.0,
        "trace_seconds": 6.0}
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [64, 128, 256]
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        == config["runner_args"]["max_seq_len"]


NEW_METRICS = ["ssm_share.serve", "ssm_scan_roofline.serve",
               "lm_head_share.serve"]
SHARED_METRICS = ["decode_wave_ms", "wave_occupancy", "tpot_p95_ms",
                  "mixed_step_device_ms", "pallas_kernel_share.serve",
                  "device_idle_share.serve", "ragged_kernel_share.serve",
                  "sample_share.serve", "kv_write_share.serve",
                  "wave_host_ms", "idle_in_host_work_share.serve"]


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS)
def test_the_cell_reports_the_metric_and_its_files_are_there(name):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    entry = next(m for m in resolved["per_layer"] if m["name"] == name)
    assert entry["moves"] == "serve_tokens_per_s"
    if name in NEW_METRICS:
        # this cell's own, first of its list (PR 44 appended a cell to
        # ``lm_head_share.serve``'s, PR 48 one to all three)
        assert entry["workloads"][0] == CELL
        assert entry["layer"] == "serving cores"
    else:
        # appended after the cells accepted before it; later cells follow
        assert CELL in entry["workloads"][1:]
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))


def test_the_cell_is_one_chip_and_the_old_entries_stand():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="falcon-h1-34b", traffic="chat-closed",
                        chips=1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]][:5] == [
        "train-gpt2-medium-s1024", "serve-gpt2-xl-batch-closed",
        "serve-glm47flash-reason-closed", "serve-lfm2-8b-a1b-rag-closed",
        CELL]
    assert [c["name"] for c in BENCH["configs"]][4] == "falcon-h1-34b"
    assert BENCH["run_seconds"] == 51
    resolved = bench_run.resolve_cell(BENCH, CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert not {m["name"] for m in resolved["per_layer"]} & {
        "gqa_kernel_roofline.serve", "conv_share.serve",
        "moe_experts_share.serve", "mla_kernel_share.serve",
        "prefill_wave_ms"}
    for old in ("serve-gpt2-xl-batch-closed",
                "serve-glm47flash-reason-closed",
                "serve-lfm2-8b-a1b-rag-closed"):
        names = {m["name"] for m in bench_run.resolve_cell(
            BENCH, old)["per_layer"]}
        assert not set(NEW_METRICS) & names


def test_the_parent_exits_cleanly_on_the_cell(runner, monkeypatch):
    """A program without ``ssm_decode`` (the parent of this PR under this
    PR's benchmark files) stops before anything is built."""
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.ssm_decode", None)
    with pytest.raises(SystemExit, match="no SSMHybridConfig"):
        runner.model_config({})


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

FH1 = {"mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
       "mamba_n_groups": 2, "num_hidden_layers": 6}


def test_one_decode_wave_of_64_slots_is_bound_by_the_states_bytes():
    """64 live slots x 6 layers, a row each: every slot's 4.19 MB state
    in and out once a layer."""
    counters = {"ssm_slot_steps": 64 * 6, "ssm_rows": 64 * 6,
                "ssm_chunk_pairs": 0}
    ops, nbytes = opcount_ssm_hybrid.ssm_scan(counters, FH1)
    # a row: 32 heads x 4 x 128 x 256 operations
    assert ops == 384 * 32 * 4 * 128 * 256 == 1_610_612_736
    # a slot step: 2 x 32 x 128 x 256 x 4 B = 8,388,608 B; a row: x and y
    # 2 x 4096 x 2 B, B and C 2 x 512 x 2 B, dt 32 x 4 B = 18,560 B
    assert nbytes == 384 * (8_388_608 + 18_560) == 3_228_352_512
    assert nbytes / 819e9 > 100 * ops / 197e12      # 3.94 ms against 8 us


def test_a_chunk_of_256_rows_adds_its_pairs_and_moves_the_state_once():
    """One slot's 256-row chunk in one layer: two chunks of 128, each
    128 x 129 / 2 pairs; the state still moves once."""
    counters = {"ssm_slot_steps": 1, "ssm_rows": 256,
                "ssm_chunk_pairs": 2 * 128 * 129 // 2}
    ops, nbytes = opcount_ssm_hybrid.ssm_scan(counters, FH1)
    assert ops == 256 * 32 * 4 * 128 * 256 \
        + 16_512 * (2 * 2 * 256 + 32 * 2 * 128) == 1_225_916_416
    assert nbytes == 8_388_608 + 256 * 18_560 == 13_139_968


def _trace():
    """A hand-made trace: 30 ms window; the scan's fusion 4 ms, the
    state's write 1 ms, the in-projection 2 ms, the head 3 ms, another
    operation 6 ms: 16 ms busy."""
    ms = 1e6
    stacks = ["jit(f)/ssm_scan", "jit(f)/state_write", "jit(f)/ssm_in",
              "jit(f)/lm_head", "jit(f)/mlp"]
    ops = [["%fusion.1 = f32[] fusion()", 1 * ms, 4 * ms],
           ["%fusion.2 = f32[] fusion()", 6 * ms, 1 * ms],
           ["%fusion.3 = f32[] fusion()", 8 * ms, 2 * ms],
           ["%fusion.4 = f32[] fusion()", 11 * ms, 3 * ms],
           ["%fusion.5 = f32[] fusion()", 15 * ms, 6 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 30 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(5))}}


class _H:
    peak = PEAK
    config = FH1

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def metric_args(name):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))["args"]


def test_scan_roofline_and_shares_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_ssm")
    counters = {"ssm_slot_steps": 384, "ssm_rows": 384, "ssm_chunk_pairs": 0}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    # 3,228,352,512 B / 819e9 = 3.942 ms over 4 + 1 ms of the work
    got = reader.read(data, **metric_args("ssm_scan_roofline.serve"))
    assert got == pytest.approx(100 * (3_228_352_512 / 819e9) / 5e-3)
    assert got < 100
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(5e-3)
    # the parent (no counters), another configuration, no such scope
    args = metric_args("ssm_scan_roofline.serve")
    assert reader.read({"trace": _trace(), "harness": h}, **args) is None
    assert reader.read(dict(data, counters={"traced": {}}), **args) is None
    assert reader.read(dict(data, harness=_H({"n_layer": 48})),
                       **args) is None
    assert reader.read(data, model="ssm_scan", scopes=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    # the mixer's share by the accepted reader: 4 + 1 + 2 of 16 busy ms
    share = bench_run.load_module("readers", "scope_or_op_share")
    assert share.read({"trace": _trace(), "harness": _H()},
                      **metric_args("ssm_share.serve")) \
        == pytest.approx(100 * 7 / 16)
    head = bench_run.load_module("readers", "scope_share")
    assert head.read({"trace": _trace(), "harness": _H()},
                     **metric_args("lm_head_share.serve")) \
        == pytest.approx(100 * 3 / 16)
