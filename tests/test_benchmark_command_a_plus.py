"""CPU tests of what ISSUE 55 adds to the benchmark: the runner
``serve_parallel_moe`` end to end at a small size, the controls on the
reference's side each refused, the configuration, cell, traffic and
metric entries and their files, ``opcount_parallel_moe`` against numbers
worked by hand, the new reader and the accepted readers the new metrics
use on a hand-made trace.

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

from benchmarks import (  # noqa: E402
    loadgen, opcount_parallel_moe, reference_command_a_plus,
    run as bench_run)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-command-a-plus-grounded-closed"
CONFIG = "command-a-plus-05-2026"
SOURCE = ("https://huggingface.co/CohereLabs/command-a-plus-05-2026/"
          "blob/main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's ``config``, key for key
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
# the narrowed model: the file's keys with these in their place; 2 of 8
# experts and 257 of 512 rows held
SMALL = dict(hidden_size=48, num_hidden_layers=4, head_dim=16,
             num_attention_heads=8, num_key_value_heads=2,
             layer_types=PERIOD, intermediate_size=32, num_experts=2,
             vocab_size=257, num_experts_per_tok=3, sliding_window=12,
             max_position_embeddings=256,
             published={"num_experts": 8, "vocab_size": 512},
             deployment={"experts_held": [0, 2],
                         "vocab_rows_held": [0, 257]})
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.8, "lo": 4, "hi": 72,
                             "round_to": 4},
                 output_len={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 48: the order of the sums is all that
    # differs, so the limits are a hundredth of the cell's; a prompt of 52
    # positions has turned the ring of 3 blocks of 16 (the engine's
    # default block), one under 12 lies inside the window
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=129,
                prefill_chunk=8, max_seq_len=96, init_gain={},
                check_requests=4, long_prompt_positions=52,
                short_prompt_positions=12, reference_pad=16,
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
    return bench_run.load_module("runners", "serve_parallel_moe")


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    h = harness()
    cfg = runner.model_config(h.config)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.vocab_size,
            cfg.published_vocab_size) == (8, (0, 2), 257, 512)
    for attempt in range(3):
        try:
            out = runner.run(h, cfg=cfg)
            break
        except AssertionError as e:        # the engine's 50 ms assertion
            if "chunk_stall" not in str(e) or attempt == 2:
                raise
            h.out.seek(0)
            h.out.truncate()
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] > 0 and e2e["ttft_p95_ms"] > 0
    lines = {json.loads(l)["line"]: json.loads(l) for l in log.splitlines()}
    eng = lines["serve"]["engine"]
    assert eng["ragged"] and eng["paged"] and eng["drained"]
    assert eng["window_layers"] == 3 and eng["window_ring"] == 3
    assert eng["window_blocks_recycled"] > 0
    assert eng["warmed_buckets"] == [4, 8]
    setup = lines["setup"]
    assert setup["experts_held"] == [0, 2] and setup["router_experts"] == 8
    assert setup["vocab_rows_held"] == [0, 257]
    assert setup["pool_bytes"] == setup["full_pool_bytes"] \
        + setup["window_pool_bytes"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 4 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and ref["control"] is None
    assert ref["longest_checked_prompt"] >= 52
    assert ref["shortest_checked_prompt"] < 12
    assert 0.5 < ref["logit_std"] < 2.0 and len(ref["rms"]) == 4
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "near_tie_share", "over_margin_share",
        "held_rows", "longest_checked_prompt", "shortest_checked_prompt",
        "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    # every live row routes 3 of 8 in each of 4 layers; 2 are held here
    assert c["moe_assignments_routed"] == c["wave_rows_live"] * 3 * 4
    assert 0 < c["moe_assignments"] == sum(c["moe_load"]) \
        < c["moe_assignments_routed"]
    assert 0 < c["attn_window_ctx_tokens"] < c["attn_ctx_tokens"]
    assert 0 < c["attn_window_bound_rows"] < c["wave_rows_live"]
    # what the accepted readers hand the metrics this cell reports
    share = out["data"]["snapshot"]["window_ctx_share"]
    assert share == c["attn_window_ctx_tokens"] / c["attn_ctx_tokens"]
    ratio = bench_run.load_module("readers", "counter_ratio")
    for metric, over, under in (
            ("window_bound_row_share.serve", "attn_window_bound_rows",
             "wave_rows_live"),
            ("held_assignment_share.serve", "moe_assignments",
             "moe_assignments_routed")):
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{metric}.json"))
        assert ratio.read(out["data"], **spec["args"]) \
            == 100.0 * c[over] / c[under]
    assert h.setup_s > 0


@pytest.fixture(scope="module")
def finished(runner):
    """Three requests served at the small size (one several rings past
    the window, one inside it): (harness, published params, the
    reference's configuration, the finished rows)."""
    import jax.numpy as jnp
    from hetu_tpu.models.parallel_moe import init_parallel_moe_params
    from hetu_tpu.serving import Request, ServingEngine
    h = harness()
    cfg = runner.model_config(h.config)
    pub = init_parallel_moe_params(cfg, name="cmd", seed=5,
                                   dtype=jnp.float32)
    eng = ServingEngine(cfg.permute_rotary(pub, "cmd"), cfg, slots=4,
                        max_seq_len=96, pool_blocks=129, prefill_chunk=8)
    rng = np.random.default_rng(2)
    out = eng.run([Request(rng.integers(0, 257, n).astype(np.int32), 12,
                           request_id=f"q{i}")
                   for i, n in enumerate((9, 30, 61))])
    source, held, _ = runner.published(h.config)
    ref_config = {k: source[k] for k in runner.REFERENCE_KEYS}
    return h, pub, ref_config, held, [{"result": r} for r in out.values()]


def test_the_sound_reference_is_correct(tpu_default_paths, runner,
                                        finished):
    h, pub, ref_config, held, done = finished
    ok, rec = runner.agree(h, pub, ref_config, held, done,
                           h.config["runner_args"])
    assert ok and rec["widest_logit_gap"] <= 1e-3
    assert rec["held_rows"] >= 4 and rec["over_margin_share"] == 0


@pytest.mark.parametrize("control", reference_command_a_plus.CONTROLS)
def test_each_control_is_refused(tpu_default_paths, runner, finished,
                                 control):
    """Each piece has teeth: one thing computed differently on the
    reference's side comes out as not correct by the shares."""
    h, pub, ref_config, held, done = finished
    bad, rec = runner.agree(h, pub, ref_config, held, done,
                            h.config["runner_args"], control=control)
    assert not bad, control
    assert rec["over_margin_share"] > 0.02, (control, rec)


@pytest.mark.parametrize("limit,value", [
    ("held_rows_min", 10 ** 6), ("tie_share_max", -1.0),
    ("over_margin_share_max", -1.0), ("held_over_share_max", -1.0),
    ("long_prompt_positions", 62), ("short_prompt_positions", 9)])
def test_each_limit_alone_refuses(tpu_default_paths, runner, finished,
                                  limit, value):
    h, pub, ref_config, held, done = finished
    bad, _ = runner.agree(h, pub, ref_config, held, done,
                          dict(h.config["runner_args"], **{limit: value}))
    assert not bad


def test_a_program_without_the_family_stops_at_once(runner, monkeypatch):
    """The parent of the PR has no ``hetu_tpu.models.parallel_moe``: the
    cell exits non-zero before anything is built."""
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    cfg = runner.model_config(config)
    assert (cfg.sliding_window, cfg.n_routed_experts, cfg.held_experts,
            cfg.vocab_rows) == (4096, 128, (0, 16), (0, 32768))
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.parallel_moe", None)
    with pytest.raises(SystemExit, match="Nothing was run"):
        runner.model_config(config)
    # a file whose deployment and held counts disagree is refused
    with pytest.raises(SystemExit, match="disagree"):
        runner.published(dict(config, num_experts=32))


# ------------------------------------------------------------------ #
# the configuration, the cell, the traffic, the metric entries
# ------------------------------------------------------------------ #

def test_the_configuration_holds_every_published_number():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert BENCH["configs"].index(entry) == 9          # appended
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    conf = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert conf["source"] == SOURCE
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert conf[key] == value, key
    # every published width
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["sliding_window"], conf["intermediate_size"],
            conf["num_experts_per_tok"], conf["num_shared_experts"]) == (
        4096, 128, 8, 128, 4096, 4096, 8, 4)
    assert conf["num_hidden_layers"] == 4
    assert conf["layer_types"] == PERIOD == PUBLISHED["layer_types"][:4]
    assert (conf["num_experts"], conf["vocab_size"]) == (16, 32768)
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(conf["reduced_why"]) == set(REDUCED) == set(conf["reduced"])
    dep = conf["deployment"]
    assert (dep["chips_a_layer"], dep["rank"], dep["experts_held"],
            dep["vocab_rows_held"]) == (8, 0, [0, 16], [0, 32768])
    assert "not run" in dep["not_here"]
    for key in ("shared_expert_combination_strategy",
                "window_includes_query", "selection_bias", "vision_tower",
                "max_seq_len", "intermediate_size", "rotation"):
        assert key in conf["assumed"], key
    assert "NOT taken" in conf["assumed"][
        "shared_expert_combination_strategy"]
    mem = conf["memory_analysis"]
    assert 0.25 * 16 < mem["slots_32_Q_256"]["peak_GB"] < 15.0
    assert conf["runner"] == "serve_parallel_moe"
    args = conf["runner_args"]
    for key in args:
        if key.endswith("_max") or key.endswith("_margin") \
                or key.endswith("_min"):
            assert f"{key}_why" in args, key
    assert args["long_prompt_positions"] >= 8192
    assert args["short_prompt_positions"] <= 4096
    # the program reads the file as the runner hands it over
    runner = bench_run.load_module("runners", "serve_parallel_moe")
    cfg = runner.model_config(conf)
    blk = cfg.block_spec()
    assert blk.ops == ("window_attention",) * 3 + ("attention",)
    assert (blk.window, blk.residual, blk.norm) == (
        4096, "parallel", "layernorm_nobias")
    assert (blk.routed.num_experts, blk.routed.held, blk.routed.top_k,
            blk.routed.shared_scale) == (128, 16, 8, 0.25)
    shapes = cfg.param_shapes("cmd")
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert 4.72e9 < count < 4.75e9                     # 9.47 GB in bf16


def test_the_cell_and_its_metrics_are_appended_entries():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == 9 and len(cells) >= 10
    cell = BENCH["workloads"][9]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "grounded-closed", 1)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][8] == CELL
    assert e2e["serve_tokens_per_s"]["bound"] == 0.1
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    new = ["wide_window_kernel_roofline.serve",
           "wide_full_kernel_roofline.serve", "held_experts_roofline.serve",
           "par_norm_share.serve", "window_bound_row_share.serve"]
    assert [m["name"] for m in BENCH["per_layer"]][73:78] == new
    for name in new:
        m = metrics[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "readers", f"{spec['reader']}.py"))
    # an accepted list the cell joins has it LAST
    for name in ("window_kernel_share.serve", "ragged_kernel_share.serve",
                 "window_ctx_share.serve", "moe_shared_share.serve",
                 "held_assignment_share.serve", "lm_head_share.serve",
                 "moe_experts_chunk_wave_ms", "attention_chunk_wave_ms",
                 "device_idle_share.serve", "setup_build_s"):
        # (a later PR's cell may follow)
        assert metrics[name]["workloads"].index(CELL) >= 1, name
    # ... and the three shares whose counts read other keys do not
    for name in ("window_kernel_roofline.serve", "gqa_kernel_roofline.serve",
                 "moe_experts_roofline.serve"):
        assert CELL not in metrics[name]["workloads"], name
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "PROGRAM_SPANS.parallel-moe.md"))


def test_the_traffic_is_the_issues():
    mix = bench_run.resolve_cell(BENCH, CELL)["traffic"]
    assert (mix["loop"], mix["clients"]) == ("closed", 32)
    assert (mix["ramp_seconds"], mix["drain_limit_seconds"],
            mix["trace_seconds"]) == (16.0, 60.0, 6.0)
    assert mix["request_pool"] % 32 == 0
    sizes = np.array(loadgen.request_sizes(mix, 0, mix["request_pool"]))
    prompts, answers = sizes[:, 0], sizes[:, 1]
    assert (prompts % 256 == 0).all()
    assert prompts.min() >= 1024 and prompts.max() <= 16384
    assert answers.min() >= 32 and answers.max() <= 384
    assert (prompts >= 8192).any() and (prompts < 4096).any()
    other = np.array(loadgen.request_sizes(mix, 7, mix["request_pool"]))
    assert (np.roll(sizes, -7, 0) == other).all()
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [256]
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert prompts.max() + answers.max() <= config["runner_args"][
        "max_seq_len"]


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

CMD = {"num_hidden_layers": 4, "layer_types": PERIOD,
       "num_attention_heads": 128, "num_key_value_heads": 8,
       "head_dim": 128, "hidden_size": 4096, "intermediate_size": 4096,
       "num_experts_per_tok": 8, "use_parallel_block": True}


def test_one_decode_wave_of_32_slots_at_8192():
    """32 rows at position 8,191: a sliding layer sees the window's 4,096
    positions, the full layer all 8,192; 3 + 1 layers."""
    counters = {"wave_rows_live": 32,
                "attn_window_ctx_tokens": 32 * 4096,
                "attn_window_score_pairs": 32 * 4096,
                "attn_ctx_tokens": 32 * 8192, "attn_score_pairs": 32 * 8192,
                "moe_assignments": 32 * 4, "moe_experts_touched": 16 * 4}
    assert opcount_parallel_moe.layers_of(CMD, "sliding_attention") == 3
    ops, nbytes = opcount_parallel_moe.wide_window_attention(counters, CMD)
    # a pair, a query head: 128 + 128 multiply-adds = 512 operations
    assert ops == 131072 * 3 * 128 * 512 == 25_769_803_776
    # K and V rows 131,072 x 2 x 1,024 x 2 B x 3 layers + (q + o) 2 x
    # 16,384 x 2 B a row x 3
    assert nbytes == 3 * 2 * (131072 * 2048 + 32 * 32768) \
        == 1_616_904_192
    assert nbytes / 819e9 > 10 * ops / 197e12          # bound by bytes
    ops, nbytes = opcount_parallel_moe.wide_full_attention(counters, CMD)
    assert ops == 262144 * 128 * 512
    assert nbytes == 2 * (262144 * 2048 + 32 * 32768)
    # the held experts: three 4,096 x 4,096 matrices each, 16 a layer
    ops, nbytes = opcount_parallel_moe.held_experts(counters, CMD)
    assert ops == 128 * 3 * 2 * 4096 * 4096
    assert nbytes == 2 * (64 * 3 * 4096 * 4096 + 128 * 2 * 4096) \
        == 6_444_548_096
    assert nbytes / 819e9 > 100 * ops / 197e12
    # the file's own keys serve it: no KeyError on the configuration
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert opcount_parallel_moe.held_experts(counters, config) \
        == (ops, nbytes)


def test_a_chunk_deep_in_a_long_prompt():
    """256 rows at positions 8,192..8,447: 4,351 positions in the band,
    each row 4,096 pairs; the full layer has 8,448 in sight and 8,320.5
    pairs a row on average."""
    pairs = sum(8192 + j + 1 for j in range(256))
    counters = {"wave_rows_live": 256,
                "attn_window_ctx_tokens": 4096 + 255,
                "attn_window_score_pairs": 256 * 4096,
                "attn_ctx_tokens": 8448, "attn_score_pairs": pairs}
    ops, nbytes = opcount_parallel_moe.wide_window_attention(counters, CMD)
    assert ops == 256 * 4096 * 3 * 128 * 512
    assert nbytes == 3 * 2 * (4351 * 2048 + 256 * 32768)
    assert ops / 197e12 > nbytes / 819e9           # bound by operations
    ops, nbytes = opcount_parallel_moe.wide_full_attention(counters, CMD)
    assert ops == pairs * 128 * 512 and pairs == 2_129_792 + 256


# ------------------------------------------------------------------ #
# the readers on a hand-made trace
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: two ``ragged_paged_window`` calls of 2 and 3 ms,
    a ``ragged_paged_mixed`` call of 4 ms, a grouped matmul of 10 ms
    under ``moe_experts``, a fusion of 1 ms under ``par_norm``, inside
    one 30 ms benchmark span."""
    ms = 1e6
    ops = [["%ragged_paged_window.1 = bf16[1] custom-call()", 1 * ms, 2 * ms],
           ["%ragged_paged_mixed.2 = bf16[1] custom-call()", 4 * ms, 4 * ms],
           ["%ragged_paged_window.2 = bf16[1] custom-call()", 9 * ms,
            3 * ms],
           ["%moe_grouped_matmul.3 = bf16[1] custom-call()", 13 * ms,
            10 * ms],
           ["%fusion.9 = bf16[1] fusion()", 24 * ms, 1 * ms]]
    stacks = ["jit(f)/wave_decode/attention/ragged_paged_window/pallas_call",
              "jit(f)/wave_decode/attention/ragged_paged_mixed/pallas_call",
              "jit(f)/wave_decode/attention/ragged_paged_window/pallas_call",
              "jit(f)/wave_decode/moe_experts/moe_grouped_matmul/pallas_call",
              "jit(f)/wave_decode/par_norm/mul"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 30 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(5))}}


class _H:
    peak = PEAK
    config = CMD

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def test_the_new_rooflines_and_shares_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_parallel")
    counters = {"wave_rows_live": 32, "attn_window_bound_rows": 32,
                "attn_window_ctx_tokens": 32 * 4096,
                "attn_window_score_pairs": 32 * 4096,
                "attn_ctx_tokens": 32 * 8192, "attn_score_pairs": 32 * 8192,
                "moe_assignments": 32 * 4, "moe_experts_touched": 16 * 4}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters, "untraced": counters}}

    def metric(name):
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        return bench_run.load_module("readers", spec["reader"]), spec["args"]

    # the sliding layers' bytes over the WINDOW kernel's 5 ms, the full
    # layer's over the other kernel's 4 ms, the held experts' over the 10
    # ms under ``moe_experts``
    for name, nbytes, spent in (
            ("wide_window_kernel_roofline.serve", 1_616_904_192, 5e-3),
            ("wide_full_kernel_roofline.serve",
             2 * (262144 * 2048 + 32 * 32768), 4e-3),
            ("held_experts_roofline.serve", 6_444_548_096, 10e-3)):
        mod, args = metric(name)
        assert mod is not None and mod.__name__ == reader.__name__
        got = mod.read(data, **args)
        assert got == pytest.approx(100 * (nbytes / 819e9) / spent), name
        assert 0 < got < 100
        assert h.lines[-1]["bound"] == "bytes"
        assert h.lines[-1]["kernel_s"] == pytest.approx(spent)
    # the norm's share by the accepted reader: 1 of 20 busy ms; the
    # bound rows' by the accepted counter ratio
    mod, args = metric("par_norm_share.serve")
    assert mod.read(data, **args) == pytest.approx(5.0)
    mod, args = metric("window_bound_row_share.serve")
    assert mod.read(data, **args) == pytest.approx(100.0)
    # the parent (no such counter or scope), another family's
    # configuration, a program without the kernel: nothing, and no raise
    mod, args = metric("wide_window_kernel_roofline.serve")
    assert mod.read({"trace": _trace(), "harness": h}, **args) is None
    old = {k: v for k, v in counters.items()
           if k != "attn_window_bound_rows"}
    assert mod.read(dict(data, counters={"traced": old}), **args) is None
    assert mod.read(dict(data, harness=_H({"layer_types": PERIOD})),
                    **args) is None
    assert mod.read(data, model="wide_window_attention",
                    ops=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    bare = _trace()
    bare["op_scopes"]["table"][4] = "jit(f)/wave_decode/attn_qkv/mul"
    mod, args = metric("par_norm_share.serve")
    assert mod.read(dict(data, trace=bare), **args) is None
    mod, args = metric("window_bound_row_share.serve")
    assert mod.read(dict(data, counters={"untraced": old}), **args) is None


def test_the_benchmarks_reference_is_the_programs_equations(runner,
                                                            finished):
    """``benchmarks/reference_command_a_plus.py`` (a K/V head at a time,
    row blocks, an expert at a time) against
    ``hetu_tpu/models/reference_parallel_moe.py`` (one dense forward) on
    one sequence, logits and margins."""
    from hetu_tpu.models import reference_parallel_moe as program_ref
    h, pub, ref_config, held, _ = finished
    tokens = np.random.default_rng(1).integers(0, 257, 48).astype(np.int32)
    stats = {}
    lg, margin = reference_command_a_plus.forward(
        pub, ref_config, tokens, np.arange(48), name="cmd", held=held,
        stats=stats)
    want, want_margin = program_ref.forward(pub, ref_config, tokens,
                                            name="cmd", held=held)
    np.testing.assert_allclose(lg, np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(margin, np.asarray(want_margin), atol=1e-6)
    assert len(stats["layers"]) == 4 and stats["logits"] > 0
    with pytest.raises(ValueError, match="control="):
        reference_command_a_plus.forward(pub, ref_config, tokens, [0],
                                         control="nothing")
