"""CPU tests of what ISSUE 48 adds to the benchmark: the runner
``serve_nemotron_h`` end to end at a small size with every part of its
comparison, each control coming out not correct, the configuration, cell,
traffic and metric entries and their files, ``opcount_nemotron_h``
against numbers worked by hand, and the new readers on a hand-made trace.

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

from benchmarks import opcount_nemotron_h, run as bench_run  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-nemotron3-super-agent-closed"
CONFIG = "nemotron-3-super-120b-a12b"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B"
          "-BF16/blob/main/config.json")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
# the file's way of saying it: ``n_routed_experts`` is what is HELD, the
# router's width is ``published``'s
SMALL = dict(vocab_size=257, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
             mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=8,
             moe_intermediate_size=48, moe_latent_size=32,
             moe_shared_expert_intermediate_size=96, n_routed_experts=4,
             num_experts_per_tok=4, max_position_embeddings=256)
SMALL_ROUTER = 16
SMALL_HELD = [4, 4]
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.6, "lo": 4, "hi": 48,
                             "round_to": 4},
                 output_len={"median": 6, "sigma": 0.5, "lo": 2, "hi": 12})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 64: the order of the sums is all that
    # differs, so the limits are float32's; a prompt of 3 chunks of 8 is
    # "long" here; with 16 experts a near tie is rare
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=33,
                prefill_chunk=8, max_seq_len=64, check_requests=5,
                state_requests=2, long_prompt_chunks=3, reference_pad=16,
                reference_rows=8, logit_margin=2e-4, state_margin=2e-4,
                state_margin_all=2e-4,
                tie_margin=1e-5, held_over_share_max=0.0,
                over_margin_share_max=0.0, held_rows_min=10,
                tie_share_max=0.5)
    args.update(args_over)
    config = dict(resolved["config"], **SMALL, dtype="float32",
                  runner_args=args)
    config["published"] = dict(config["published"],
                               n_routed_experts=SMALL_ROUTER)
    config["deployment"] = dict(config["deployment"],
                                experts_held=SMALL_HELD)
    resolved["config"] = config
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
    return bench_run.load_module("runners", "serve_nemotron_h")


@pytest.fixture(scope="module")
def window(runner):
    """One served window at the small size, shared by the tests that
    read it again under a control."""
    gc.collect()
    h = harness()
    w = runner.serve_window(h, cfg=runner.model_config(h.config))
    return h, w


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    h = harness()
    out = runner.run(h, cfg=runner.model_config(h.config))
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    lines = {json.loads(l)["line"]: json.loads(l) for l in log.splitlines()}
    eng = lines["serve"]["engine"]
    assert eng["ragged"] and eng["paged"] and eng["stateful"]
    assert eng["drained"] and eng["state_resets"] >= out["attempted"]
    assert eng["warmed_buckets"] == [4, 8]
    # a mixer's conv tail [1, 4, 3, 96] and matrix state [1, 4, 4, 8, 16],
    # five mixers of each, float32
    assert lines["setup"]["state_bytes"] == 5 * (4 * 3 * 96
                                                 + 4 * 4 * 8 * 16) * 4
    assert lines["setup"]["state_dtypes"] == ["float32"] * 10
    assert lines["setup"]["experts_held"] == SMALL_HELD
    assert lines["setup"]["router_experts"] == SMALL_ROUTER
    ref = lines["reference"]
    assert ref["control"] is None
    assert ref["requests_checked"] == 5 and ref["rows_checked"] > 0
    assert ref["state_requests_checked"] == 2
    assert ref["held_over_share"] == 0 and ref["over_margin_share"] == 0
    assert ref["first_mixer_state_error"] <= ref["widest_state_error"] \
        <= 2e-4
    assert ref["longest_checked_prompt_chunks"] >= 3
    # every layer's one part moves the residual; the logits are of order 1
    assert [layer["kind"] for layer in ref["rms"]] == list("MEMEMEM*EME")
    for layer in ref["rms"]:
        assert layer["part"] > 0.02 * layer["residual"], layer
    assert 0.3 < ref["logit_std"] < 5.0
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "over_margin_share", "near_tie_share",
        "first_mixer_state_error", "widest_state_error", "held_rows",
        "state_requests_checked",
        "longest_checked_prompt_chunks", "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    assert c["ssm_rows"] == c["wave_rows_live"] * 5
    assert c["moe_assignments_routed"] == c["wave_rows_live"] * 4 * 5
    assert 0 < c["moe_assignments"] < c["moe_assignments_routed"]
    assert len(c["moe_load"]) == 4 and sum(c["moe_load"]) == \
        c["moe_assignments"]
    assert c["attn_score_pairs"] >= c["attn_ctx_tokens"] > 0
    assert "moe_load" not in lines["serve"]["counters"]["untraced"]
    assert h.setup_s > 0


CONTROLS = ["float8", "state_bf16", "carry", "position", "mixer", "latent",
            "wrong_share", "norm_held"]


def test_the_probe_and_the_reference_name_the_same_controls():
    from benchmarks import reference_nemotron_h
    assert list(reference_nemotron_h.CONTROLS) == CONTROLS


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_comes_out_not_correct(window, runner, control):
    """The same served window read against the reference computed another
    way: every control is outside one of the limits.  At this width the
    limits are float32's; the cell's are set between what the bfloat16
    engine reads and what each control reads on the chip (PERF.md
    section 6, PR 48)."""
    h, w = window
    args = h.config["runner_args"]
    ok, record = runner.agree(h, w["params"], w["ref_config"], w["held"],
                              w["out"]["done"], args, h.seconds,
                              states=w["states"])
    assert ok, record
    ok, other = runner.agree(h, w["params"], w["ref_config"], w["held"],
                             w["out"]["done"], args, h.seconds,
                             states=w["states"], control=control)
    assert not ok, other
    assert other["over_margin_share"] > args["over_margin_share_max"] \
        or other["widest_state_error"] > args["state_margin"]
    if control in ("carry", "state_bf16", "float8"):
        assert other["first_mixer_state_error"] > 10 * args["state_margin"]
    if control in ("mixer", "position", "latent", "wrong_share",
                   "norm_held"):
        # what the first mixer reads lies before all of these
        assert other["first_mixer_state_error"] <= args["state_margin"]
    if control in ("wrong_share", "norm_held", "latent"):
        # the state of the FIRST mixer sees no expert layer before it;
        # the logits do
        assert other["over_margin_share"] > 0


@pytest.mark.parametrize("limit,value", [
    ("held_over_share_max", -1.0), ("over_margin_share_max", -1.0),
    ("tie_share_max", -1.0), ("held_rows_min", 10 ** 9),
    ("state_margin", -1.0), ("state_margin_all", -1.0),
    ("state_requests", 99),
    ("long_prompt_chunks", 99)])
def test_each_limit_alone_refuses(window, runner, limit, value):
    h, w = window
    args = dict(h.config["runner_args"], **{limit: value})
    ok, _ = runner.agree(h, w["params"], w["ref_config"], w["held"],
                         w["out"]["done"], args, h.seconds,
                         states=w["states"])
    assert not ok


def test_without_the_states_the_run_is_not_correct(window, runner):
    h, w = window
    ok, record = runner.agree(h, w["params"], w["ref_config"], w["held"],
                              w["out"]["done"], h.config["runner_args"],
                              h.seconds, states=None)
    assert not ok and record["state_requests_checked"] == 0


def test_the_file_and_the_deployment_must_agree_on_the_experts_held(runner):
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    source, held = runner.published_router(config)
    assert source["n_routed_experts"] == 512 and held == (0, 128)
    assert config["n_routed_experts"] == 128
    bad = dict(config, deployment=dict(config["deployment"],
                                       experts_held=[0, 64]))
    with pytest.raises(SystemExit, match="disagree"):
        runner.published_router(bad)


# ------------------------------------------------------------------ #
# entries and their files
# ------------------------------------------------------------------ #

def test_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["why"]) <= 200
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B"
                       "-BF16")
        assert row["source_url"] == SOURCE
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k) != v)
        assert differs == sorted(REDUCED)
        assert config["published"] == {k: row["config"][k] for k in REDUCED}
    assert config["published"]["n_routed_experts"] == 512
    assert config["published"]["vocab_size"] == 131072
    assert config["published"]["num_hidden_layers"] == 88
    assert len(config["published"]["hybrid_override_pattern"]) == 88
    assert config["published"]["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"])
    assert config["hybrid_override_pattern"] == "MEMEMEM*EME"
    widths = {"hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64,
              "mamba_num_heads": 128, "ssm_state_size": 128, "n_groups": 8,
              "conv_kernel": 4, "moe_intermediate_size": 2688,
              "moe_latent_size": 1024, "intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376, "expand": 2,
              "num_experts_per_tok": 22, "num_attention_heads": 32,
              "num_key_value_heads": 2, "routed_scaling_factor": 5}
    assert {k: config[k] for k in widths} == widths
    assert set(config["reduced_why"]) >= set(REDUCED)
    assert config["deployment"]["chips_a_layer"] == 4
    assert config["deployment"]["experts_held"] == [0, 128]
    assert config["deployment"]["vocab_rows_held"] == [0, 32768]
    assert set(config["assumed"]) >= {
        "positions", "state_dtype", "A_log_dt_bias_D", "selection_bias",
        "norm_scales", "weights", "max_seq_len"}
    # what ``opcount_ssm_hybrid`` reads, beside the source's own keys
    for derived, own in (("mamba_n_heads", "mamba_num_heads"),
                         ("mamba_d_head", "mamba_head_dim"),
                         ("mamba_d_state", "ssm_state_size"),
                         ("mamba_n_groups", "n_groups")):
        assert config[derived] == config[own]
    assert config["derived"]["note"]
    assert config["runner"] == "serve_nemotron_h"
    assert config["dtype"] == "bfloat16"
    args = config["runner_args"]
    for key in ("logit_margin", "tie_margin", "held_over_share_max",
                "over_margin_share_max", "held_rows_min", "tie_share_max",
                "state_margin", "state_margin_all", "check_requests",
                "state_requests", "long_prompt_chunks"):
        assert args[key] > 0 and len(args[key + "_why"]) > 40, key
    assert args["slots"] == 64 and args["prefill_chunk"] == 256
    assert (args["pool_blocks"] - 1) * 16 == args["slots"] * args[
        "max_seq_len"]


def test_the_weights_held_are_nine_gigabytes_and_the_programs_fit():
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    runner = bench_run.load_module("runners", "serve_nemotron_h")
    cfg = runner.model_config(config)
    shapes = cfg.param_shapes("nmh")
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert 4.64e9 < count < 4.66e9              # 4.648 B parameters
    assert 9.28e9 < 2 * count < 9.34e9
    # the published count closes: 40 mixers, 8 attentions, 40 expert
    # layers of 512 experts, the embedding and the head: 120.67 B
    layer = {c: sum(int(np.prod(s)) for k, s in shapes.items()
                    if k.startswith(f"nmh_h{i}_") and "experts" not in k)
             for c, i in (("M", 0), ("E", 1), ("*", 7))}
    expert = 2 * 1024 * 2688
    total = 40 * layer["M"] + 8 * layer["*"] + 40 * (layer["E"]
                                                     + 512 * expert) \
        + 2 * 131072 * 4096 + 4096
    assert round(total / 1e9, 2) == 120.67
    assert round(layer["M"] / 1e6, 2) == 109.64
    assert round(layer["*"] / 1e6, 2) == 35.66
    assert round(layer["E"] / 1e6, 2) == 54.53
    # a slot's state a mixer: 4.19 MB float32 beside a 61 KB conv tail
    spec = cfg.block_spec().state_shapes(11, 4096)
    assert len(spec) == 10
    assert int(np.prod(spec[5][0][1:])) * 4 == 4_194_304
    assert int(np.prod(spec[0][0][1:])) * 2 == 61_440
    mem = config["memory_analysis"]
    for q in (1, 64, 128, 256):
        m = mem[f"slots_64_Q_{q}_pool_16385"]
        assert m["peak_GB"] < 15.75
        # the pool pair (0.27 GB) and the ten state arrays (1.36 GB)
        assert m["aliased_GB"] > 1.6
    assert 9.28 < mem["weights_GB"] < 9.34


def test_traffic_file_holds_the_issues_table():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "agent-closed.json"))
    assert len(mix.pop("note")) > 200
    pool = mix.pop("request_pool")
    assert pool % 64 == 0 and pool >= 64
    assert mix == {
        "kind": "requests", "loop": "closed", "clients": 64, "base_seed": 48,
        "prompt_len": {"median": 384, "sigma": 0.7, "lo": 64, "hi": 2048,
                       "round_to": 64},
        "output_len": {"median": 384, "sigma": 0.6, "lo": 64, "hi": 1536},
        "ramp_seconds": 12.0, "drain_limit_seconds": 60.0,
        "trace_seconds": 6.0}
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [64, 128, 256]
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= config["runner_args"]["max_seq_len"]


NEW_METRICS = ["latent_experts_roofline.serve", "moe_latent_share.serve",
               "moe_shared_share.serve", "held_assignment_share.serve"]
SHARED_METRICS = [
    "decode_wave_ms", "wave_occupancy", "tpot_p95_ms",
    "mixed_step_device_ms", "pallas_kernel_share.serve",
    "device_idle_share.serve", "ragged_kernel_share.serve",
    "sample_share.serve", "kv_write_share.serve", "wave_host_ms",
    "idle_in_host_work_share.serve", "moe_experts_share.serve",
    "moe_route_share.serve", "expert_load_imbalance.serve",
    "ssm_share.serve", "ssm_scan_roofline.serve", "lm_head_share.serve",
    "decode_wave_device_ms", "chunk_wave_device_ms",
    "chunk_wave_time_share.serve", "moe_experts_chunk_wave_ms",
    "attention_chunk_wave_ms", "kv_write_chunk_wave_ms",
    "inorder_step_share.serve", "idle_in_inorder_share.serve",
    "idle_in_admit_share.serve", "idle_in_assemble_share.serve",
    "idle_in_dispatch_share.serve", "idle_in_unpack_share.serve",
    "admit_p95_ms"]


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS)
def test_the_cell_reports_the_metric_and_its_files_are_there(name):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    entry = next(m for m in resolved["per_layer"] if m["name"] == name)
    assert entry["moves"] == "serve_tokens_per_s"
    if name in NEW_METRICS:
        assert entry["workloads"][0] == CELL
    else:
        # appended after the cells accepted before it
        assert CELL in entry["workloads"][1:]
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))


def test_the_cell_is_one_chip_and_the_old_entries_stand():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="agent-closed", chips=1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]][:8] == [
        "train-gpt2-medium-s1024", "serve-gpt2-xl-batch-closed",
        "serve-glm47flash-reason-closed", "serve-lfm2-8b-a1b-rag-closed",
        "serve-falcon-h1-34b-chat-closed", "serve-mellum2-12b-code-closed",
        "serve-brumby-14b-docs-closed", CELL]
    assert [c["name"] for c in BENCH["configs"]][7] == CONFIG
    assert BENCH["run_seconds"] == 51
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    resolved = bench_run.resolve_cell(BENCH, CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    # three matrices an expert at the hidden width is not this expert
    assert not {m["name"] for m in resolved["per_layer"]} & {
        "moe_experts_roofline.serve", "gqa_kernel_roofline.serve",
        "conv_share.serve", "mla_kernel_share.serve", "prefill_wave_ms",
        "retention_share.serve", "window_kernel_share.serve"}
    for old in [w["name"] for w in BENCH["workloads"]][:7]:
        names = {m["name"] for m in bench_run.resolve_cell(
            BENCH, old)["per_layer"]}
        assert not set(NEW_METRICS) & names


def test_the_parent_exits_cleanly_on_the_cell(runner, monkeypatch):
    """A program without ``nemotron_h`` (the parent of this PR under this
    PR's benchmark files) stops before anything is built."""
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.nemotron_h", None)
    with pytest.raises(SystemExit, match="no NemotronHConfig"):
        runner.model_config({})


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

NMH = {"moe_latent_size": 1024, "moe_intermediate_size": 2688,
       "num_hidden_layers": 11}


def test_one_decode_wave_of_64_slots_is_bound_by_the_touched_experts():
    """64 rows x 22 x 5 layers = 7,040 routed, about 1,760 landed; say
    every landed assignment found an expert of its own in 3 of 4 cases:
    120 of 128 touched a layer."""
    counters = {"moe_assignments": 1760, "moe_experts_touched": 5 * 120}
    ops, nbytes = opcount_nemotron_h.latent_experts(counters, NMH)
    # an assignment: two products of 1,024 x 2,688, 2 operations each
    assert ops == 1760 * 2 * 2 * 1024 * 2688 == 19_377_684_480
    # a touched expert: 2 x 1,024 x 2,688 x 2 B = 11,010,048 B; an
    # assignment: 1,024 in and 1,024 out, 2 B each
    assert nbytes == 600 * 11_010_048 + 1760 * 4096 == 6_613_237_760
    assert nbytes / 819e9 > 50 * ops / 197e12       # 8.07 ms against 0.1


def test_a_packed_chunk_wave_touches_every_expert_once_a_layer():
    """1,024 rows x 22 x 5 = 112,640 routed, 28,160 landed on 5 x 128
    experts: still the matrices' bytes (7.05 GB) over the rows'."""
    counters = {"moe_assignments": 28160, "moe_experts_touched": 640}
    ops, nbytes = opcount_nemotron_h.latent_experts(counters, NMH)
    assert ops == 28160 * 11_010_048 == 310_042_951_680
    assert nbytes == 640 * 11_010_048 + 28160 * 4096 == 7_161_774_080
    assert nbytes / 819e9 > 5 * ops / 197e12        # 8.7 ms against 1.6


def _trace():
    """A hand-made trace: 40 ms window; the experts' two kernels 6 + 4
    ms, the latent projections 1 + 1 ms, the shared expert 3 ms, the
    router 2 ms, another operation 3 ms: 20 ms busy."""
    ms = 1e6
    stacks = ["jit(f)/wave_chunk/moe_experts", "jit(f)/wave_chunk/moe_experts",
              "jit(f)/wave_chunk/moe_latent_in",
              "jit(f)/wave_chunk/moe_latent_out",
              "jit(f)/wave_chunk/moe_shared", "jit(f)/wave_chunk/moe_route",
              "jit(f)/wave_chunk/ssm_in"]
    ops = [["%moe_grouped_matmul.1 = bf16[] custom-call()", 1 * ms, 6 * ms],
           ["%moe_grouped_matmul.2 = bf16[] custom-call()", 8 * ms, 4 * ms],
           ["%fusion.3 = f32[] fusion()", 13 * ms, 1 * ms],
           ["%fusion.4 = f32[] fusion()", 15 * ms, 1 * ms],
           ["%fusion.5 = f32[] fusion()", 17 * ms, 3 * ms],
           ["%fusion.6 = f32[] fusion()", 21 * ms, 2 * ms],
           ["%fusion.7 = f32[] fusion()", 24 * ms, 3 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 40 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(7))}}


class _H:
    peak = PEAK
    config = NMH

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def metric_args(name):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))["args"]


def test_experts_roofline_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_nemotron_h")
    counters = {"moe_assignments": 28160, "moe_experts_touched": 640}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    args = metric_args("latent_experts_roofline.serve")
    # 7,161,774,080 B / 819e9 = 8.745 ms over 6 + 4 ms of the work
    got = reader.read(data, **args)
    assert got == pytest.approx(100 * (7_161_774_080 / 819e9) / 10e-3)
    assert got < 100
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(10e-3)
    # the parent (no counters), another configuration, no such scope
    assert reader.read({"trace": _trace(), "harness": h}, **args) is None
    assert reader.read(dict(data, counters={"traced": {}}), **args) is None
    assert reader.read(dict(data, harness=_H({"n_layer": 48})),
                       **args) is None
    assert reader.read(data, model="latent_experts",
                       scopes=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"


@pytest.mark.parametrize("name,share", [
    ("moe_latent_share.serve", 2 / 20), ("moe_shared_share.serve", 3 / 20),
    ("moe_experts_share.serve", 10 / 20), ("moe_route_share.serve", 2 / 20)])
def test_the_expert_layers_shares_on_a_hand_made_trace(name, share):
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    reader = bench_run.load_module("readers", spec["reader"])
    got = reader.read({"trace": _trace(), "harness": _H()}, **spec["args"])
    assert got == pytest.approx(100 * share)
    # a program with none of these scopes (the parent): nothing, no raise
    bare = _trace()
    bare["op_scopes"]["table"] = ["jit(f)/mlp"] * 7
    assert reader.read({"trace": bare, "harness": _H()},
                       **spec["args"]) is None


@pytest.mark.parametrize("counters,want", [
    ({"moe_assignments": 1760, "moe_assignments_routed": 7040}, 25.0),
    ({"moe_assignments": 1900, "moe_assignments_routed": 7040},
     100 * 1900 / 7040),
    ({"moe_assignments": 0, "moe_assignments_routed": 7040}, 0.0),
    ({"moe_assignments": 7040}, None),          # the parent's counters
    ({"moe_assignments": 0, "moe_assignments_routed": 0}, None),
    ({}, None)])
def test_held_share_is_landed_over_routed(counters, want):
    reader = bench_run.load_module("readers", "counter_ratio")
    args = metric_args("held_assignment_share.serve")
    got = reader.read({"counters": {"untraced": counters}}, **args)
    assert got == (None if want is None else pytest.approx(want))
    assert reader.read({}, **args) is None


def test_the_accepted_scan_reader_counts_this_familys_mixers():
    """``kernel_roofline_ssm`` through the four derived keys: 64 live
    slots x 5 mixers, a row each."""
    from benchmarks import opcount_ssm_hybrid
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    counters = {"ssm_slot_steps": 320, "ssm_rows": 320, "ssm_chunk_pairs": 0}
    ops, nbytes = opcount_ssm_hybrid.ssm_scan(counters, config)
    assert ops == 320 * 128 * 4 * 64 * 128
    # a slot step: 2 x 128 x 64 x 128 x 4 B = 8,388,608 B, as Falcon-H1's
    assert nbytes == 320 * (8_388_608 + 2 * (2 * 8192 + 2 * 1024) + 512)
