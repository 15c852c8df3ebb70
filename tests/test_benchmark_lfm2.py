"""CPU tests of what ISSUE 34 adds to the benchmark: the runner
``serve_hybrid_moe`` end to end at a small size, the configuration, cell,
traffic and metric entries and their files, ``opcount_hybrid`` against
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

from benchmarks import opcount_hybrid, run as bench_run  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-lfm2-8b-a1b-rag-closed"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
PERIODS = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3
SMALL = dict(vocab_size=257, hidden_size=64, num_hidden_layers=6,
             num_attention_heads=8, num_key_value_heads=2,
             layer_types=["conv", "conv", "full_attention", "conv",
                          "full_attention", "conv"],
             intermediate_size=96, moe_intermediate_size=48, num_experts=8,
             num_experts_per_tok=2, max_position_embeddings=256)
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.6, "lo": 4, "hi": 48,
                             "round_to": 4},
                 output_len={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 64: the order of the sums is all that
    # differs, so the limits are a thousandth of the cell's; a prompt of
    # 3 chunks of 8 is "long" here
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=33,
                prefill_chunk=8, max_seq_len=64, init_scale=0.2,
                check_requests=3, long_prompt_chunks=3, reference_pad=16,
                reference_rows=8, logit_margin=1e-3, tie_margin=1e-6,
                held_rows_min=4, held_over_share_max=0.0,
                over_margin_share_max=0.02)
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
    return bench_run.load_module("runners", "serve_hybrid_moe")


def run_once(runner, h):
    """As ``tests/benchmark``'s ``serve_once``: a run stopped by the
    engine's own 50 ms assertion on a loaded machine is made again."""
    from hetu_tpu.models.moe_decode import HybridMoEConfig
    cfg = HybridMoEConfig.from_hf(h.config)
    for attempt in range(3):
        try:
            return runner.run(h, cfg=cfg)
        except AssertionError as e:
            if "chunk_stall" not in str(e) or attempt == 2:
                raise
            h.out.seek(0)
            h.out.truncate()


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    h = harness()
    out = run_once(runner, h)
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] > 0 and e2e["ttft_p95_ms"] > 0
    lines = {json.loads(l)["line"]: json.loads(l) for l in log.splitlines()}
    eng = lines["serve"]["engine"]
    assert eng["ragged"] and eng["paged"] and eng["stateful"]
    assert eng["state_resets"] >= out["attempted"]
    assert eng["warmed_buckets"] == [4, 8]
    assert lines["setup"]["state_bytes"] == 4 * 4 * 2 * 64 * 4
    assert lines["serve"]["exact_lengths"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 3 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and not ref["lower"]
    assert ref["longest_checked_prompt_chunks"] >= 3
    assert ref["held_rows"] >= 4 and ref["rows_over_margin"] == 0
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "near_tie_share", "over_margin_share",
        "held_rows", "longest_checked_prompt_chunks", "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    assert c["moe_assignments"] == sum(c["moe_load"]) > 0
    assert c["moe_assignments"] == c["wave_rows_live"] * 2 * 4
    assert c["wave_rows_computed"] > c["wave_rows_live"]
    assert c["attn_score_pairs"] >= c["attn_ctx_tokens"] > 0
    assert h.setup_s > 0


def finished(runner, h, sizes, seed=5):
    import jax.numpy as jnp
    from hetu_tpu.models.moe_decode import (
        HybridMoEConfig, init_hybrid_moe_params)
    from hetu_tpu.serving import Request, ServingEngine
    cfg = HybridMoEConfig.from_hf(h.config)
    params = init_hybrid_moe_params(cfg, name="lfm", seed=seed, scale=0.2,
                                    dtype=jnp.float32)
    eng = ServingEngine(params, cfg, slots=4, max_seq_len=64,
                        pool_blocks=33, prefill_chunk=8)
    rng = np.random.default_rng(2)
    out = eng.run([Request(rng.integers(0, 257, n).astype(np.int32), 12,
                           request_id=f"q{i}") for i, n in enumerate(sizes)])
    ref_config = {k: h.config[k] for k in runner.REFERENCE_KEYS}
    return params, ref_config, [{"result": r} for r in out.values()]


def test_lower_precision_reference_fails_the_comparison(tpu_default_paths,
                                                        runner):
    """The nearest precision below (float8 operands) comes out as not
    correct by the limits a float32 small model is held to."""
    h = harness()
    params, ref_config, done = finished(runner, h, (9, 30))
    args = h.config["runner_args"]
    steps = (0.0, 0.01)
    ok, rec = runner.agree(h, params, ref_config, done, args, steps)
    assert ok and rec["widest_logit_gap"] <= 1e-3
    bad, rec = runner.agree(h, params, ref_config, done, args, steps,
                            lower=True)
    assert not bad
    assert rec["held_over_share"] > 0.05         # by either limit alone
    assert rec["over_margin_share"] > 0.02


def test_a_sample_without_a_long_prompt_is_not_correct(tpu_default_paths,
                                                       runner):
    """Every prompt is under ``long_prompt_chunks`` chunks: the conv
    state's carry was not checked, and the run says so."""
    h = harness(long_prompt_chunks=5)
    params, ref_config, done = finished(runner, h, (9, 12, 30))
    ok, rec = runner.agree(h, params, ref_config, done,
                           h.config["runner_args"], (0.0,))
    assert not ok and rec["longest_checked_prompt_chunks"] == 4
    assert rec["widest_logit_gap"] <= 1e-3


@pytest.mark.parametrize("limit,value", [
    ("held_rows_min", 10 ** 6), ("tie_share_max", -1.0),
    ("over_margin_share_max", -1.0), ("held_over_share_max", -1.0)])
def test_each_limit_alone_refuses(tpu_default_paths, runner, limit, value):
    """A sound window is not correct when any ONE limit cannot be met:
    too few rows held to the logit bound, too many near ties, too many
    rows (near ties included) over the margin, too many held rows over
    it."""
    h = harness()
    params, ref_config, done = finished(runner, h, (9, 30))
    ok, rec = runner.agree(h, params, ref_config, done,
                           h.config["runner_args"], (0.0,))
    assert ok and rec["held_rows"] >= 4 and rec["over_margin_share"] == 0
    bad, _ = runner.agree(h, params, ref_config, done,
                          dict(h.config["runner_args"], **{limit: value}),
                          (0.0,))
    assert not bad


def test_sample_holds_a_long_prompt_when_there_is_one(runner):
    class R:
        def __init__(self, p):
            self.prompt_len = p

    class H:
        seed = 11

    done = [{"result": R(p)} for p in [300] * 40 + [2100]]
    args = {"check_requests": 4, "prefill_chunk": 256,
            "long_prompt_chunks": 8}
    picks, longest = runner.sample(H(), done, args)
    assert len(picks) == 4 and len(set(picks)) == 4
    assert 40 in picks and longest == 9
    again, _ = runner.sample(H(), done, args)
    assert again == picks                       # the seed's
    none, longest = runner.sample(H(), done[:40], args)
    assert len(none) == 4 and longest == 2


# ------------------------------------------------------------------ #
# entries and their files
# ------------------------------------------------------------------ #

def test_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b")
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 14
    assert config["layer_types"] == PERIODS
    assert config["published"]["num_hidden_layers"] == 24
    assert config["published"]["layer_types"][:14] == PERIODS
    assert config["published"]["layer_types"].count("full_attention") == 6
    assert "32 of 32 experts" in config["deployment"]
    assert set(config["assumed"]) >= {"tie_embedding", "head_dim",
                                      "topk_norm_epsilon", "weights",
                                      "expert_bias", "max_seq_len"}
    assert config["runner"] == "serve_hybrid_moe"
    assert config["dtype"] == "bfloat16"
    for key in ("logit_margin", "tie_margin", "tie_share_max",
                "held_over_share_max", "held_rows_min",
                "over_margin_share_max", "check_requests"):
        assert config["runner_args"][key] > 0
        assert config["runner_args"][key + "_why"]
    mem = config["memory_analysis"]
    assert mem["slots_32_Q_256_pool_25601"]["peak_GB"] < 13.5
    # the weights as served: 9.33 GB
    runner = bench_run.load_module("runners", "serve_hybrid_moe")
    shapes = runner.model_config(config).param_shapes("lfm")
    nbytes = sum(int(np.prod(s)) * (4 if "_moe_router_" in k else 2)
                 for k, s in shapes.items())
    assert 9.13e9 < nbytes < 9.53e9
    args = config["runner_args"]
    assert (args["pool_blocks"] - 1) * 16 == args["slots"] * args[
        "max_seq_len"]


def test_traffic_file_holds_the_issues_table():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "rag-closed.json"))
    assert mix.pop("note")
    pool = mix.pop("request_pool")
    assert pool % 32 == 0 and pool >= 32
    assert mix == {
        "kind": "requests", "loop": "closed", "clients": 32, "base_seed": 34,
        "prompt_len": {"median": 3072, "sigma": 0.6, "lo": 512, "hi": 12288,
                       "round_to": 128},
        "output_len": {"median": 128, "sigma": 0.6, "lo": 32, "hi": 512},
        "ramp_seconds": 12.0, "drain_limit_seconds": 60.0,
        "trace_seconds": 6.0}
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [128, 256]
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        == config["runner_args"]["max_seq_len"]


NEW_METRICS = ["conv_share.serve", "gqa_kernel_roofline.serve"]
SHARED_METRICS = ["decode_wave_ms", "wave_occupancy", "tpot_p95_ms",
                  "mixed_step_device_ms", "pallas_kernel_share.serve",
                  "device_idle_share.serve", "ragged_kernel_share.serve",
                  "sample_share.serve", "kv_write_share.serve",
                  "wave_host_ms", "idle_in_host_work_share.serve",
                  "moe_experts_share.serve", "moe_experts_roofline.serve",
                  "moe_route_share.serve", "expert_load_imbalance.serve"]


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS)
def test_the_cell_reports_the_metric_and_its_files_are_there(name):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    entry = next(m for m in resolved["per_layer"] if m["name"] == name)
    assert entry["moves"] == "serve_tokens_per_s"
    if name in NEW_METRICS:
        assert entry["workloads"] == [CELL]
    else:
        # appended; later cells are appended after it
        assert CELL in entry["workloads"][1:]
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))


def test_the_cell_is_one_chip_and_the_old_entries_stand():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="lfm2-8b-a1b", traffic="rag-closed",
                        chips=1)
    assert len(cell["why"]) <= 200
    # later cells and configurations are appended after these
    assert [w["name"] for w in BENCH["workloads"]][:4] == [
        "train-gpt2-medium-s1024", "serve-gpt2-xl-batch-closed",
        "serve-glm47flash-reason-closed", CELL]
    assert [c["name"] for c in BENCH["configs"]][3] == "lfm2-8b-a1b"
    assert BENCH["run_seconds"] == 51
    resolved = bench_run.resolve_cell(BENCH, CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert not {m["name"] for m in resolved["per_layer"]} & {
        "mla_kernel_share.serve", "mla_kernel_roofline.serve",
        "mla_absorb_share.serve", "prefill_wave_ms"}
    for old in ("serve-gpt2-xl-batch-closed",
                "serve-glm47flash-reason-closed"):
        names = {m["name"] for m in bench_run.resolve_cell(
            BENCH, old)["per_layer"]}
        assert not set(NEW_METRICS) & names


def test_the_parent_exits_cleanly_on_the_cell(runner, monkeypatch):
    """A program without ``HybridMoEConfig`` (the parent of this PR under
    this PR's benchmark files) stops before anything is built."""
    from hetu_tpu.models import moe_decode
    monkeypatch.delattr(moe_decode, "HybridMoEConfig")
    with pytest.raises(SystemExit, match="no HybridMoEConfig"):
        runner.model_config({})


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

LFM = {"num_hidden_layers": 14, "layer_types": PERIODS,
       "num_attention_heads": 32, "num_key_value_heads": 8,
       "hidden_size": 2048, "num_experts_per_tok": 4, "num_dense_layers": 2}


def test_one_decode_wave_of_32_slots_at_4000_positions():
    """32 rows, each seeing 4000 positions, 3 attention layers."""
    assert opcount_hybrid.attention_layers(LFM) == 3
    counters = {"moe_assignments": 32 * 4 * 12, "attn_ctx_tokens": 128000,
                "attn_score_pairs": 128000}
    ops, nbytes = opcount_hybrid.gqa_attention(counters, LFM)
    # a pair, a query head: 64 + 64 multiply-adds = 256 operations
    assert ops == 128000 * 3 * 32 * 256 == 3_145_728_000
    # K and V rows 128000 x 2 x 512 x 2 B x 3 layers + (q + o) 2 x 2048
    # x 2 B a row x 3
    assert nbytes == 3 * 2 * (128000 * 1024 + 32 * 4096) == 787_218_432
    # bytes bound a decode wave: 0.96 ms against 0.016 ms
    assert nbytes / 819e9 > 50 * ops / 197e12


def test_a_chunk_at_the_end_of_a_long_prompt_is_bound_by_operations():
    """256 rows at positions 12,032..12,287: each row sees about 12,160."""
    pairs = 256 * 12032 + 256 * 257 // 2
    counters = {"moe_assignments": 256 * 4 * 12, "attn_ctx_tokens": 12288,
                "attn_score_pairs": pairs}
    ops, nbytes = opcount_hybrid.gqa_attention(counters, LFM)
    assert ops == pairs * 3 * 32 * 256
    assert ops / 197e12 > nbytes / 819e9


# ------------------------------------------------------------------ #
# the new reader on a hand-made trace
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: two ``ragged_paged_mixed`` calls of 2 and 3 ms,
    a conv fusion of 1 ms, another operation of 10 ms, inside one 30 ms
    benchmark span."""
    ms = 1e6
    ops = [["%ragged_paged_mixed.1 = bf16[1] custom-call()", 1 * ms, 2 * ms],
           ["%multiply_fusion.2 = bf16[1] fusion()", 9 * ms, 1 * ms],
           ["%ragged_paged_mixed.2 = bf16[1] custom-call()", 11 * ms, 3 * ms],
           ["%fusion.9 = bf16[1] fusion(%ragged_paged_mixed.2)", 15 * ms,
            10 * ms]]
    stacks = ["jit(f)/attention/ragged_paged_mixed/pallas_call",
              "jit(f)/conv_mix/mul",
              "jit(f)/attention/ragged_paged_mixed/pallas_call",
              "jit(f)/mlp/dot_general"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 30 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(4))}}


class _H:
    peak = PEAK
    config = LFM

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def test_gqa_roofline_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_hybrid")
    counters = {"moe_assignments": 32 * 4 * 12, "attn_ctx_tokens": 128000,
                "attn_score_pairs": 128000}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    # 787,218,432 B / 819e9 = 0.961 ms over 5 ms of kernel
    got = reader.read(data, model="gqa_attention",
                      ops=["ragged_paged_mixed"])
    assert got == pytest.approx(100 * (787_218_432 / 819e9) / 5e-3)
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(5e-3)
    # the parent, another configuration, or a program without the kernel
    assert reader.read({"trace": _trace(), "harness": h},
                       model="gqa_attention", ops=["x"]) is None
    assert reader.read(dict(data, counters={"traced": {}}),
                       model="gqa_attention", ops=["x"]) is None
    assert reader.read(dict(data, harness=_H({"n_layer": 48})),
                       model="gqa_attention",
                       ops=["ragged_paged_mixed"]) is None
    assert reader.read(data, model="gqa_attention", ops=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    # the conv's share by the accepted reader: 1 of 16 busy ms
    share = bench_run.load_module("readers", "scope_or_op_share")
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "conv_share.serve.json"))
    assert share.read({"trace": _trace(), "harness": _H()},
                      **spec["args"]) == pytest.approx(100 / 16)
