"""CPU tests of what ISSUE 28 adds to the benchmark: the runner
``serve_latent_moe`` end to end at a small size, the configuration, cell,
traffic and metric entries and their files, ``opcount_latent_moe``
against numbers worked by hand, and the two new readers on a hand-made
trace and snapshot.

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

from benchmarks import opcount_latent_moe, run as bench_run  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-glm47flash-reason-closed"
SMALL = dict(vocab_size=257, hidden_size=64, num_hidden_layers=3,
             num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=96, moe_intermediate_size=48,
             n_routed_experts=8, num_experts_per_tok=2,
             max_position_embeddings=256)
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 12, "sigma": 0.6, "lo": 4, "hi": 40,
                             "round_to": 4},
                 output_len={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8})


def harness(seconds=2.0, trace=False, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 64: the order of the sums is all that
    # differs, so the limits are a thousandth of the cell's
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=33,
                prefill_chunk=16, max_seq_len=64, init_scale=0.2,
                check_requests=3, reference_pad=16, reference_rows=8,
                logit_margin=1e-3, tie_margin=1e-6)
    args.update(args_over)
    resolved["config"] = dict(resolved["config"], **SMALL, dtype="float32",
                              runner_args=args)
    resolved["traffic"] = dict(resolved["traffic"], **SMALL_MIX)
    h = bench_run.Harness(resolved, seed=3_000_000_019, seconds=seconds,
                          trace=trace, peak=PEAK, root=ROOT,
                          out=io.StringIO())
    h.count_compiles()
    return h


@pytest.fixture
def tpu_default_paths():
    """The engine's defaults as the runner takes them (mixed wave over
    the paged pool of block 16, on the CPU with masked attention), with
    the collector held off as ``tests/benchmark``'s own fixture does."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "serve_latent_moe")


def run_once(runner, h):
    """As ``tests/benchmark``'s ``serve_once``: a run stopped by the
    engine's own 50 ms assertion on a loaded machine is made again."""
    for attempt in range(3):
        try:
            return runner.run(h)
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
    assert eng["ragged"] and eng["paged"] and eng["latent"]
    assert eng["warmed_buckets"] == [4, 8, 16]
    assert lines["serve"]["exact_lengths"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 3 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and not ref["lower"]
    # an untraced run: one part, the whole window
    counters = out["data"]["counters"]
    assert set(counters) == {"untraced"}
    c = counters["untraced"]
    assert c["moe_assignments"] == sum(c["moe_load"]) > 0
    assert c["moe_assignments"] % (2 * 2) == 0          # rows x top_k x layers
    assert 0 < c["moe_experts_touched"] <= c["steps"] * 2 * 8
    assert c["attn_score_pairs"] >= c["attn_ctx_tokens"] > 0
    snap = out["data"]["snapshot"]
    assert snap["decode_ms_p50"] > 0 and snap["steps"] > 0
    assert h.setup_s > 0


def test_lower_precision_reference_fails_the_comparison(tpu_default_paths,
                                                        runner):
    """The nearest precision below (float8 operands) comes out as not
    correct by the limits a float32 small model is held to."""
    import jax.numpy as jnp
    from hetu_tpu.models.moe_decode import init_latent_moe_params
    from hetu_tpu.serving import Request, ServingEngine
    h = harness()
    cfg = runner.model_config(h.config)
    params = init_latent_moe_params(cfg, name="glm", seed=5, scale=0.2,
                                    dtype=jnp.float32)
    eng = ServingEngine(params, cfg, slots=4, max_seq_len=64,
                        pool_blocks=33, prefill_chunk=16)
    rng = np.random.default_rng(2)
    out = eng.run([Request(rng.integers(0, 257, n).astype(np.int32), 12,
                           request_id=f"q{i}") for i, n in enumerate((9, 20))])
    done = [{"result": r} for r in out.values()]
    args = h.config["runner_args"]
    ok, rec = runner.agree(h, params, runner.reference_config(cfg), done,
                           args)
    assert ok and rec["widest_logit_gap"] <= 1e-3
    bad, rec = runner.agree(h, params, runner.reference_config(cfg), done,
                            args, lower=True)
    assert not bad
    assert rec["widest_logit_gap"] > 1e-3 or rec["near_tie_share"] > 0.05


def test_near_tie_rows_are_counted_apart(tpu_default_paths, runner):
    """With a tie margin wider than every row's, every row is a near-tie
    row: none is held to the logit bound and their share fails alone."""
    h = harness(tie_margin=10.0)
    out = run_once(runner, h)
    assert not out["correct"]
    assert out["notes"]["near_tie_share"] == 1.0
    assert out["notes"]["widest_logit_gap"] == 0.0


def test_marks_split_the_counters_where_the_profiler_starts():
    runner = bench_run.load_module("runners", "serve_latent_moe")

    class Metrics:
        n = 0

        def mark(self):
            return self.n

        def snapshot(self, since):
            return {"moe_assignments": self.n - since}

    class H:
        trace, tracing = True, False
        seed = 1

        def open_window(self):
            return 0.0

        def trace_start(self):
            self.tracing = True

        def mute_spans(self):
            pass

    m, h = Metrics(), H()
    marks = runner.Marks(h, m)
    assert marks.seed == 1
    marks.open_window()
    m.n = 10
    marks.trace_start()
    m.n = 14
    marks.trace_start()                       # already tracing: no new mark
    m.n = 17
    marks.mute_spans()
    m.n = 30                                  # the drain: nobody's
    marks.mute_spans()
    assert marks.counters == {"untraced": {"moe_assignments": 10},
                              "traced": {"moe_assignments": 7}}
    untraced_run = runner.Marks(type("U", (H,), {"trace": False})(), m)
    untraced_run.open_window()
    m.n = 35
    untraced_run.mute_spans()
    assert untraced_run.counters == {"untraced": {"moe_assignments": 5}}


# ------------------------------------------------------------------ #
# entries and their files
# ------------------------------------------------------------------ #

def test_configuration_holds_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash")
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "moe_intermediate_size": 1536, "intermediate_size": 10240,
        "n_routed_experts": 64, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "vocab_size": 154880,
        "routed_scaling_factor": 1.8, "norm_topk_prob": True,
        "first_k_dense_replace": 1, "rope_theta": 1000000,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
        "max_position_embeddings": 202752, "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "rope_scaling": None,
        "partial_rotary_factor": 1, "attention_bias": False,
        "hidden_act": "silu", "model_type": "glm4_moe_lite"}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 7
    assert config["num_nextn_predict_layers"] == 0
    assert config["published"] == {"num_hidden_layers": 47,
                                   "num_nextn_predict_layers": 1}
    assert "64 of 64 experts" in config["deployment"]
    assert set(config["assumed"]) >= {"weights", "e_score_correction_bias",
                                      "rope_pairing", "max_seq_len"}
    assert config["runner"] == "serve_latent_moe"
    assert config["dtype"] == "bfloat16"
    for key in ("logit_margin", "tie_margin", "tie_share_max"):
        assert config["runner_args"][key] > 0
        assert config["runner_args"][key + "_why"]
    assert config["memory_analysis"]
    # the weights as served: 9.06 GB
    runner = bench_run.load_module("runners", "serve_latent_moe")
    shapes = runner.model_config(config).param_shapes("glm")
    nbytes = sum(int(np.prod(s)) * (4 if "_moe_router_" in k else 2)
                 for k, s in shapes.items())
    assert 9.05e9 < nbytes < 9.08e9
    args = config["runner_args"]
    assert (args["pool_blocks"] - 1) * 16 >= args["slots"] * 2.2 * 1400


def test_traffic_file_holds_the_issues_table():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "reason-closed.json"))
    mix.pop("note")
    assert mix == {
        "kind": "requests", "loop": "closed", "clients": 32, "base_seed": 28,
        "request_pool": 96,
        "prompt_len": {"median": 768, "sigma": 0.7, "lo": 128, "hi": 4096,
                       "round_to": 64},
        "output_len": {"median": 320, "sigma": 0.7, "lo": 64, "hi": 1024},
        "ramp_seconds": 12.0, "drain_limit_seconds": 60.0,
        "trace_seconds": 6.0}
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [64, 128, 256]


NEW_METRICS = ["mla_kernel_share.serve", "mla_kernel_roofline.serve",
               "moe_experts_share.serve", "moe_experts_roofline.serve",
               "moe_route_share.serve", "mla_absorb_share.serve",
               "expert_load_imbalance.serve"]
SHARED_METRICS = ["decode_wave_ms", "wave_occupancy",
                  "tpot_p95_ms", "mixed_step_device_ms",
                  "pallas_kernel_share.serve", "device_idle_share.serve",
                  "sample_share.serve", "kv_write_share.serve",
                  "wave_host_ms", "idle_in_host_work_share.serve"]


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS)
def test_the_cell_reports_the_metric_and_its_files_are_there(name):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    entry = next(m for m in resolved["per_layer"] if m["name"] == name)
    assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}
    if name in NEW_METRICS:
        # PR 28's own; a later cell with a routed FFN is appended
        # (PR 34's), never put first
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == "serve_tokens_per_s"
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))


def test_the_cell_is_one_chip_and_the_old_entries_stand():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="glm-4.7-flash",
                        traffic="reason-closed", chips=1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in BENCH["workloads"]][:2] == [
        "train-gpt2-medium-s1024", "serve-gpt2-xl-batch-closed"]
    assert BENCH["run_seconds"] == 51
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # no ttft_p95_ms: its runs spread 4.3 % where half its bound is 2.5
    # (PERF.md section 6, PR 28), and prefill_wave_ms moves nothing else
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert not {"ragged_kernel_share.serve", "prefill_wave_ms"} & {
        m["name"] for m in resolved["per_layer"]}
    old = bench_run.resolve_cell(BENCH, "serve-gpt2-xl-batch-closed")
    assert not set(NEW_METRICS) & {m["name"] for m in old["per_layer"]}


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

GLM = {"num_hidden_layers": 7, "num_attention_heads": 20, "kv_lora_rank": 512,
       "qk_rope_head_dim": 64, "hidden_size": 2048,
       "moe_intermediate_size": 1536, "num_experts_per_tok": 4,
       "first_k_dense_replace": 1}


def test_one_decode_wave_of_32_slots_at_2000_positions():
    """32 rows, each seeing 2000 positions: 768 assignments; say 55
    experts touched in each of 6 layers."""
    counters = {"moe_assignments": 32 * 4 * 6, "moe_experts_touched": 330,
                "attn_ctx_tokens": 64000, "attn_score_pairs": 64000}
    ops, nbytes = opcount_latent_moe.mla_attention(counters, GLM)
    # a pair, a head: 576 + 512 multiply-adds = 2176 operations
    assert ops == 64000 * 7 * 20 * 2176 == 19_496_960_000
    # latents 64000 x 576 x 2 B x 7 layers + (q 576 + o 512) x 2 B a
    # (row, head) x 7
    assert nbytes == 7 * 2 * (64000 * 576 + 32 * 20 * 1088) == 525_844_480
    ops, nbytes = opcount_latent_moe.routed_ffn(counters, GLM)
    assert ops == 768 * 6 * 2048 * 1536 == 14_495_514_624
    # 330 experts x 3 x 2048 x 1536 x 2 B = 6.23 GB; the rows 13.4 MB
    assert nbytes == 2 * (330 * 3 * 2048 * 1536 + 768 * (4096 + 4608))
    assert 6.22e9 < nbytes < 6.25e9
    # bytes bound the experts of a decode wave: 7.6 ms against 0.07 ms
    assert nbytes / 819e9 > 100 * ops / 197e12


def test_one_chunk_wave_is_bound_by_operations():
    rows = 8192
    counters = {"moe_assignments": rows * 4 * 6, "moe_experts_touched": 384,
                "attn_ctx_tokens": 0, "attn_score_pairs": 0}
    ops, nbytes = opcount_latent_moe.routed_ffn(counters, GLM)
    assert ops == rows * 4 * 6 * 6 * 2048 * 1536
    assert ops / 197e12 > nbytes / 819e9


# ------------------------------------------------------------------ #
# the new readers on a hand-made trace and snapshot
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: two ``ragged_paged_mla`` calls of 2 and 3 ms, a
    grouped matmul of 4 ms, an activation of 1 ms under ``moe_experts``,
    another operation of 10 ms, inside one 30 ms benchmark span."""
    ms = 1e6
    ops = [["%ragged_paged_mla.1 = bf16[1] custom-call()", 1 * ms, 2 * ms],
           ["%ragged-dot-none.4 = bf16[1] custom-call()", 4 * ms, 4 * ms],
           ["%multiply_fusion.2 = bf16[1] fusion()", 9 * ms, 1 * ms],
           ["%ragged_paged_mla.2 = bf16[1] custom-call()", 11 * ms, 3 * ms],
           ["%fusion.9 = bf16[1] fusion(%ragged_paged_mla.2)", 15 * ms,
            10 * ms]]
    stacks = ["jit(f)/attention/ragged_paged_mla/pallas_call",
              "ragged-dot-none", "jit(f)/moe_experts/mul",
              "jit(f)/attention/ragged_paged_mla/pallas_call",
              "jit(f)/mlp/dot_general"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 30 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(5))}}


class _H:
    peak = PEAK
    config = GLM

    def __init__(self):
        self.lines = []

    def log(self, **record):
        self.lines.append(record)


def test_kernel_roofline_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline")
    counters = {"moe_assignments": 32 * 4 * 6, "moe_experts_touched": 330,
                "attn_ctx_tokens": 64000, "attn_score_pairs": 64000}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    # 525,844,480 B / 819e9 = 0.642 ms over 5 ms of kernel
    got = reader.read(data, model="mla_attention", ops=["ragged_paged_mla"])
    assert got == pytest.approx(100 * (525_844_480 / 819e9) / 5e-3)
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(5e-3)
    # the experts: the grouped matmul by name AND the activation by scope
    got = reader.read(data, model="routed_ffn", scopes=["moe_experts"],
                      ops=["ragged-dot-none", "ragged-dot-metadata"])
    _, nbytes = opcount_latent_moe.routed_ffn(counters, GLM)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 5e-3)
    # the parent: no counters, or a program without the kernel
    assert reader.read({"trace": _trace(), "harness": h}, model="routed_ffn",
                       ops=["ragged-dot-none"]) is None
    assert reader.read(dict(data, counters={"traced": {}}),
                       model="mla_attention", ops=["x"]) is None
    assert reader.read(data, model="mla_attention", ops=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"


def test_scope_or_op_share_and_load_imbalance():
    share = bench_run.load_module("readers", "scope_or_op_share")
    data = {"trace": _trace(), "harness": _H()}
    # busy 20 of 30 ms; the experts 4 + 1
    assert share.read(data, scopes=["moe_experts"],
                      ops=["ragged-dot-none"]) == pytest.approx(25.0)
    assert share.read(data, scopes=["moe_experts"]) == pytest.approx(5.0)
    assert share.read(data, scopes=["nothing"]) is None
    load = bench_run.load_module("readers", "load_imbalance")
    assert load.read({"counters": {"untraced": {
        "moe_load": [10, 30, 20, 20]}}}) == pytest.approx(1.5)
    assert load.read({"counters": {"untraced": {"moe_load": [0, 0]}}}) is None
    assert load.read({"snapshot": {}}) is None
