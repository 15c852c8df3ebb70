"""CPU tests of what ISSUE 42 adds to the benchmark: the runner
``serve_window_moe`` end to end at a small size, the configuration, cell,
traffic and metric entries and their files, ``opcount_window_moe``
against numbers worked by hand, the new reader on a hand-made trace, and
the accepted readers and operation counts this cell is listed under on
this configuration's keys.

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
    loadgen, opcount_latent_moe, opcount_window_moe, run as bench_run)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-mellum2-12b-code-closed"
CONFIG = "mellum2-12b-a2.5b"
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's ``config``, number for number
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
SMALL = dict(vocab_size=257, hidden_size=48, num_hidden_layers=4,
             head_dim=16, num_attention_heads=8, num_key_value_heads=2,
             layer_types=PERIOD, mlp_layer_types=["sparse"] * 4,
             intermediate_size=96, moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, sliding_window=12,
             max_position_embeddings=256,
             rope_parameters={
                 "full_attention": {
                     "rope_type": "yarn", "rope_theta": 10000.0,
                     "factor": 4.0, "original_max_position_embeddings": 32,
                     "beta_fast": 4, "beta_slow": 1},
                 "sliding_attention": {"rope_type": "default",
                                       "rope_theta": 10000.0}})
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.8, "lo": 4, "hi": 72,
                             "round_to": 4},
                 output_len={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 48: the order of the sums is all that
    # differs, so the limits are a thousandth of the cell's; a prompt of
    # 52 positions has turned the ring of 3 blocks of 16 (the engine's
    # default block), one under 12 lies inside the window
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=129,
                prefill_chunk=8, max_seq_len=96, init_scale=0.2,
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
    return bench_run.load_module("runners", "serve_window_moe")


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
    assert eng["ragged"] and eng["paged"]
    assert eng["window_layers"] == 3 and eng["window_ring"] == 3
    assert eng["window_blocks_recycled"] > 0
    assert eng["warmed_buckets"] == [4, 8]
    setup = lines["setup"]
    assert setup["window_pool_bytes"] == 2 * 3 * 13 * 16 * 128 * 4
    assert setup["pool_bytes"] == setup["full_pool_bytes"] \
        + setup["window_pool_bytes"]
    assert lines["serve"]["exact_lengths"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 4 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and not ref["lower"]
    assert ref["longest_checked_prompt"] >= 52
    assert ref["shortest_checked_prompt"] < 12
    assert ref["held_rows"] >= 4 and ref["rows_over_margin"] == 0
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "near_tie_share", "over_margin_share",
        "held_rows", "longest_checked_prompt", "shortest_checked_prompt",
        "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    assert c["moe_assignments"] == sum(c["moe_load"]) > 0
    assert c["moe_assignments"] == c["wave_rows_live"] * 2 * 4
    assert c["attn_score_pairs"] >= c["attn_ctx_tokens"] > 0
    assert 0 < c["attn_window_ctx_tokens"] < c["attn_ctx_tokens"]
    assert 0 < c["attn_window_score_pairs"] < c["attn_score_pairs"]
    # what the accepted reader hands ``window_ctx_share.serve``
    share = out["data"]["snapshot"]["window_ctx_share"]
    assert share == c["attn_window_ctx_tokens"] / c["attn_ctx_tokens"]
    reader = bench_run.load_module("readers", "snapshot_key")
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "window_ctx_share.serve.json"))
    assert reader.read(out["data"], **spec["args"]) == 100.0 * share
    assert h.setup_s > 0


def finished(runner, h, sizes, seed=5):
    import jax.numpy as jnp
    from hetu_tpu.models.moe_decode import (
        HybridMoEConfig, init_hybrid_moe_params)
    from hetu_tpu.serving import Request, ServingEngine
    cfg = HybridMoEConfig.from_hf(h.config)
    params = init_hybrid_moe_params(cfg, name="mel", seed=seed, scale=0.2,
                                    dtype=jnp.float32)
    eng = ServingEngine(params, cfg, slots=4, max_seq_len=96,
                        pool_blocks=129, prefill_chunk=8)
    rng = np.random.default_rng(2)
    out = eng.run([Request(rng.integers(0, 257, n).astype(np.int32), 12,
                           request_id=f"q{i}") for i, n in enumerate(sizes)])
    ref_config = {k: h.config[k] for k in runner.REFERENCE_KEYS}
    return params, ref_config, [{"result": r} for r in out.values()]


def test_lower_precision_reference_fails_the_comparison(tpu_default_paths,
                                                        runner):
    """The nearest precision below (float8 operands, a bfloat16 router
    and softmax) comes out as not correct by the limits a float32 small
    model is held to, by either share alone."""
    h = harness()
    params, ref_config, done = finished(runner, h, (9, 30, 61))
    args = h.config["runner_args"]
    steps = (0.0, 0.01)
    ok, rec = runner.agree(h, params, ref_config, done, args, steps)
    assert ok and rec["widest_logit_gap"] <= 1e-3
    bad, rec = runner.agree(h, params, ref_config, done, args, steps,
                            lower=True)
    assert not bad
    assert rec["held_over_share"] > 0.05         # by either limit alone
    assert rec["over_margin_share"] > 0.02


@pytest.mark.parametrize("sizes,long_at,short_at,what", [
    ((9, 12, 20), 52, 12, "longest"),            # the ring never turned
    ((30, 40, 61), 52, 12, "shortest")])         # the window always binds
def test_a_sample_without_both_kinds_of_prompt_is_not_correct(
        tpu_default_paths, runner, sizes, long_at, short_at, what):
    h = harness(long_prompt_positions=long_at,
                short_prompt_positions=short_at)
    params, ref_config, done = finished(runner, h, sizes)
    ok, rec = runner.agree(h, params, ref_config, done,
                           h.config["runner_args"], (0.0,))
    assert not ok and rec["widest_logit_gap"] <= 1e-3
    if what == "longest":
        assert rec["longest_checked_prompt"] < long_at
    else:
        assert rec["shortest_checked_prompt"] >= short_at


@pytest.mark.parametrize("limit,value", [
    ("held_rows_min", 10 ** 6), ("tie_share_max", -1.0),
    ("over_margin_share_max", -1.0), ("held_over_share_max", -1.0)])
def test_each_limit_alone_refuses(tpu_default_paths, runner, limit, value):
    h = harness()
    params, ref_config, done = finished(runner, h, (9, 30, 61))
    ok, rec = runner.agree(h, params, ref_config, done,
                           h.config["runner_args"], (0.0,))
    assert ok and rec["held_rows"] >= 4 and rec["over_margin_share"] == 0
    bad, _ = runner.agree(h, params, ref_config, done,
                          dict(h.config["runner_args"], **{limit: value}),
                          (0.0,))
    assert not bad


def test_sample_holds_a_long_and_a_short_prompt_when_there_are(runner):
    class R:
        def __init__(self, p):
            self.prompt_len = p

    class H:
        seed = 11

    args = {"check_requests": 3, "long_prompt_positions": 2560,
            "short_prompt_positions": 1024}
    mid = [{"result": R(1500)} for _ in range(20)]
    picks, longest, shortest = runner.sample(H, mid, args)
    assert len(picks) == 3 and (longest, shortest) == (1500, 1500)
    both = mid + [{"result": R(4096)}, {"result": R(256)}]
    picks, longest, shortest = runner.sample(H, both, args)
    assert len(set(picks)) == 3 and (longest, shortest) == (4096, 256)
    # the same seed picks the same requests
    assert runner.sample(H, both, args)[0] == picks
    # whichever is swapped in never takes the place of the only pick of
    # the other kind, wherever the seeded choice put that one
    for seed in range(40):
        H.seed = seed
        for have, lack in ((4096, 256), (256, 4096)):
            some = [{"result": R(have if k % 4 == 0 else 1500)}
                    for k in range(12)] + [{"result": R(lack)}]
            _, longest, shortest = runner.sample(H, some, args)
            assert (longest, shortest) == (4096, 256), (seed, have)


def test_a_program_without_the_family_stops_at_once(runner, monkeypatch):
    """The parent of the PR: its ``HybridMoEConfig`` has no ``FAMILIES``
    (or none named "mellum"), so the cell exits non-zero before anything
    is built."""
    from hetu_tpu.models import moe_decode
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert runner.model_config(config).sliding_window == 1024
    monkeypatch.setattr(moe_decode.HybridMoEConfig, "FAMILIES",
                        {"lfm2_moe": {}})
    with pytest.raises(SystemExit, match="does not run model_type 'mellum'"):
        runner.model_config(config)
    monkeypatch.delattr(moe_decode.HybridMoEConfig, "FAMILIES")
    with pytest.raises(SystemExit, match="Nothing was run"):
        runner.model_config(config)


# ------------------------------------------------------------------ #
# the configuration, the cell, the traffic, the metric entries
# ------------------------------------------------------------------ #

def test_the_configuration_holds_every_published_number():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert BENCH["configs"].index(entry) == 5          # appended
    assert entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    conf = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert conf["source"] == SOURCE
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            continue
        assert conf[key] == value, key
    # cut in depth alone: the first three whole periods
    assert conf["num_hidden_layers"] == 12
    assert conf["layer_types"] == PERIOD * 3 == PUBLISHED["layer_types"][:12]
    assert conf["mlp_layer_types"] == ["sparse"] * 12
    assert conf["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    assert set(conf["reduced_why"]) == set(entry["reduced"]) \
        == set(conf["reduced"])
    for key in ("router_scoring", "qk_norm", "window_includes_query",
                "next_token_module", "weights", "max_seq_len"):
        assert key in conf["assumed"], key
    assert "pipeline" in conf["deployment"]
    mem = conf["memory_analysis"]
    assert mem["slots_32_Q_256"]["peak_GB"] < 15.0     # the issue's line
    assert mem["slots_32_Q_256"]["peak_GB"] > 0.25 * 16
    assert conf["runner"] == "serve_window_moe"
    args = conf["runner_args"]
    for key in args:
        if key.endswith("_max") or key.endswith("_margin"):
            assert f"{key}_why" in args, key
    # the program reads the file as it stands
    from hetu_tpu.models.moe_decode import HybridMoEConfig
    cfg = HybridMoEConfig.from_hf(conf)
    blk = cfg.block_spec()
    assert blk.ops.count("window_attention") == 9
    assert blk.ops.count("attention") == 3 and blk.window == 1024
    assert (blk.head_dim, blk.kv_heads, blk.head) == (128, 4, "untied")
    assert blk.routed.scoring == "softmax" and blk.routed.top_k == 8
    shapes = cfg.param_shapes("mel")
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 5_465_956_608                          # 10.93 GB in bf16
    layer = sum(int(np.prod(s)) for k, s in shapes.items() if "_h0_" in k)
    assert layer == 21_233_664 + 64 * 3 * 2304 * 896 + 2304 * 64 + 2 * 2304
    assert mem["weights_GB"] == pytest.approx(2 * n / 1e9, abs=0.01)


def test_the_cell_and_its_metrics_are_appended_entries():
    # appended: the sixth cell and configuration, after the five there
    # were; later PRs append after them
    cell = BENCH["workloads"][5]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == (CONFIG, "code-closed")
    assert len(cell["why"]) <= 200
    assert len(BENCH["configs"][5]["why"]) <= 200
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("window_kernel_share.serve")
    assert names[first:first + 3] == ["window_kernel_share.serve",
                                      "window_kernel_roofline.serve",
                                      "window_ctx_share.serve"]
    assert names[first - 1] == "admit_p95_ms"      # the last there was
    new = {m["name"]: m for m in BENCH["per_layer"][first:first + 3]}
    for m in new.values():
        assert m["workloads"][0] == CELL and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", f"{m['name']}.json"))
    assert new["window_kernel_roofline.serve"]["better"] == "higher"
    assert new["window_kernel_share.serve"]["source"] == "device_trace"
    assert new["window_ctx_share.serve"]["source"] == "program_counter"
    resolved = bench_run.resolve_cell(BENCH, CELL)
    e2e = {m["name"] for m in resolved["end_to_end"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}    # no ttft_p95_ms
    reported = {m["name"] for m in resolved["per_layer"]}
    assert {"ragged_kernel_share.serve", "moe_experts_roofline.serve",
            "moe_route_share.serve", "attention_chunk_wave_ms",
            "kv_write_share.serve", "chunk_wave_device_ms",
            "expert_load_imbalance.serve"} <= reported
    # a count that would be wrong here is not listed: LFM2's reads
    # hidden / heads as the head and ``num_dense_layers``
    assert "gqa_kernel_roofline.serve" not in reported
    assert "conv_share.serve" not in reported
    # every metric keeps a workloads list, so no later cell inherits one
    assert all("workloads" in m for m in BENCH["per_layer"])
    # every reader and metric file the cell reports exists
    for m in resolved["per_layer"]:
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{m['name']}.json"))
        bench_run.load_module("readers", spec["reader"])


def test_the_traffic_is_the_issues():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "code-closed.json"))
    assert (mix["loop"], mix["clients"], mix["base_seed"]) == (
        "closed", 32, 42)
    assert mix["prompt_len"] == {"median": 1536, "sigma": 1.0, "lo": 128,
                                 "hi": 12288, "round_to": 64}
    assert mix["output_len"] == {"median": 96, "sigma": 0.6, "lo": 16,
                                 "hi": 384}
    assert (mix["ramp_seconds"], mix["drain_limit_seconds"],
            mix["trace_seconds"]) == (12.0, 60.0, 6.0)
    assert mix["request_pool"] % 32 == 0
    sizes = np.array(loadgen.request_sizes(mix, 0, mix["request_pool"]))
    prompts, answers = sizes[:, 0], sizes[:, 1]
    assert (prompts % 64 == 0).all()
    assert prompts.min() >= 128 and prompts.max() <= 12288
    assert answers.min() >= 16 and answers.max() <= 384
    # short and long in ONE queue: the issue's shares, within the draw
    assert 2000 < prompts.mean() < 2800
    assert 0.05 < (prompts < 512).mean() < 0.2
    assert 0.1 < (prompts > 4096).mean() < 0.25
    assert (prompts >= 2560).any() and (prompts < 1024).any()
    # another seed is a rotation of the same sizes
    other = np.array(loadgen.request_sizes(mix, 7, mix["request_pool"]))
    assert (np.roll(sizes, -7, 0) == other).all()
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [64, 128, 256]


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

MEL = {"num_hidden_layers": 12, "layer_types": PERIOD * 3,
       "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
       "hidden_size": 2304, "moe_intermediate_size": 896,
       "num_experts_per_tok": 8}


def test_one_decode_wave_of_32_slots_past_the_window():
    """32 rows, each seeing the window's 1,024 positions whatever the
    slot holds, 9 window layers."""
    assert opcount_window_moe.window_layers(MEL) == 9
    counters = {"moe_assignments": 32 * 8 * 12,
                "attn_window_ctx_tokens": 32 * 1024,
                "attn_window_score_pairs": 32 * 1024}
    assert opcount_window_moe.live_rows(counters, MEL) == 32
    ops, nbytes = opcount_window_moe.window_attention(counters, MEL)
    # a pair, a query head: 128 + 128 multiply-adds = 512 operations
    assert ops == 32768 * 9 * 32 * 512 == 4_831_838_208
    # K and V rows 32768 x 2 x 512 x 2 B x 9 layers + (q + o) 2 x 4096
    # x 2 B a row x 9
    assert nbytes == 9 * 2 * (32768 * 1024 + 32 * 8192) == 608_698_368
    # bytes bound a decode wave: 0.74 ms against 0.025 ms
    assert nbytes / 819e9 > 25 * ops / 197e12


def test_a_chunk_deep_in_a_long_prompt_reads_its_window_alone():
    """256 rows at positions 8,192..8,447: 1,279 positions in sight, each
    row 1,024 pairs; a full layer would have 8,448 and 8,320 a row."""
    counters = {"moe_assignments": 256 * 8 * 12,
                "attn_window_ctx_tokens": 1024 + 255,
                "attn_window_score_pairs": 256 * 1024}
    ops, nbytes = opcount_window_moe.window_attention(counters, MEL)
    assert ops == 262144 * 9 * 32 * 512
    assert nbytes == 9 * 2 * (1279 * 1024 + 256 * 8192)
    assert ops / 197e12 > nbytes / 819e9           # bound by operations


def test_the_accepted_routed_count_reads_this_configuration():
    """``moe_experts_roofline.serve`` counts through
    ``opcount_latent_moe.routed_ffn``, which reads ``hidden_size`` and
    ``moe_intermediate_size``: the source's key names here too.  A chunk
    wave of 1,024 rows x top-8 over 64 experts, every expert touched."""
    counters = {"moe_assignments": 8192 * 12, "moe_experts_touched": 64 * 12}
    ops, nbytes = opcount_latent_moe.routed_ffn(counters, MEL)
    assert ops == 8192 * 12 * 3 * 2 * 2304 * 896
    assert nbytes == 2 * (768 * 3 * 2304 * 896
                          + 8192 * 12 * (2 * 2304 + 3 * 896))
    # 12 layers of expert weights: 9.51 GB, 11.6 ms at 819 GB/s
    assert 768 * 3 * 2304 * 896 * 2 == 9_512_681_472
    # the file's own keys serve it: no KeyError on the configuration
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    assert opcount_latent_moe.routed_ffn(counters, config) == (ops, nbytes)
    assert opcount_window_moe.window_layers(config) == 9


# ------------------------------------------------------------------ #
# the new reader on a hand-made trace
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: two ``ragged_paged_window`` calls of 2 and 3 ms,
    a ``ragged_paged_mixed`` call of 4 ms, another operation of 11 ms,
    inside one 30 ms benchmark span."""
    ms = 1e6
    ops = [["%ragged_paged_window.1 = bf16[1] custom-call()", 1 * ms, 2 * ms],
           ["%ragged_paged_mixed.2 = bf16[1] custom-call()", 4 * ms, 4 * ms],
           ["%ragged_paged_window.2 = bf16[1] custom-call()", 9 * ms,
            3 * ms],
           ["%fusion.9 = bf16[1] fusion(%ragged_paged_window.2)", 15 * ms,
            11 * ms]]
    stacks = ["jit(f)/attention/ragged_paged_window/pallas_call",
              "jit(f)/attention/ragged_paged_mixed/pallas_call",
              "jit(f)/attention/ragged_paged_window/pallas_call",
              "jit(f)/moe_experts/dot_general"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 30 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(4))}}


class _H:
    peak = PEAK
    config = MEL

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def test_window_roofline_and_share_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_window")
    counters = {"moe_assignments": 32 * 8 * 12,
                "attn_window_ctx_tokens": 32 * 1024,
                "attn_window_score_pairs": 32 * 1024}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "window_kernel_roofline.serve.json"))
    assert spec["reader"] == "kernel_roofline_window"
    # 608,698,368 B / 819e9 = 0.743 ms over the 5 ms of the WINDOW
    # kernel's two calls (the full layers' 4 ms are another kernel's)
    got = reader.read(data, **spec["args"])
    assert got == pytest.approx(100 * (608_698_368 / 819e9) / 5e-3)
    assert 0 < got < 100
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(5e-3)
    # the parent (no such counter), a configuration without a window
    # layer, a program without the kernel: nothing, and no raise
    assert reader.read({"trace": _trace(), "harness": h},
                       **spec["args"]) is None
    assert reader.read(dict(data, counters={"traced": {
        "attn_score_pairs": 5, "moe_assignments": 8}}),
        **spec["args"]) is None
    assert reader.read(dict(data, harness=_H({
        "layer_types": ["conv", "full_attention"]})), **spec["args"]) is None
    assert reader.read(dict(data, harness=_H({"n_layer": 48})),
                       **spec["args"]) is None
    assert reader.read(data, model="window_attention",
                       ops=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    # the kernel's share by the accepted reader: 5 of 20 busy ms; the
    # full layers' kernel keeps its own metric: 4 of 20
    share = bench_run.load_module("readers", "op_share")
    for metric, want in (("window_kernel_share.serve", 25.0),
                         ("ragged_kernel_share.serve", 20.0)):
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{metric}.json"))
        assert share.read({"trace": _trace(), "harness": _H()},
                          **spec["args"]) == pytest.approx(want)
    # a trace without the window kernel (the parent's): nothing
    other = _trace()
    for e in other["planes"][0]["lines"][0]["events"]:
        e[0] = e[0].replace("ragged_paged_window", "ragged_paged_mixed")
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "window_kernel_share.serve.json"))
    assert share.read({"trace": other, "harness": _H()},
                      **spec["args"]) is None


def test_the_benchmarks_reference_is_the_programs_equations():
    """``benchmarks/reference_mellum2.py`` (row blocks, an expert at a
    time, the head in column blocks) against
    ``hetu_tpu/models/reference_window_moe.py`` (the whole sequence at
    once) on seeded weights: two writings of one set of equations."""
    import jax.numpy as jnp
    from benchmarks import reference_mellum2
    from hetu_tpu.models import reference_window_moe
    from hetu_tpu.models.moe_decode import (
        HybridMoEConfig, init_hybrid_moe_params)
    config = dict(SMALL, rms_norm_eps=1e-6, norm_topk_prob=True,
                  tie_word_embeddings=False, model_type="mellum")
    cfg = HybridMoEConfig.from_hf(config)
    params = init_hybrid_moe_params(cfg, name="mel", seed=9, scale=0.2,
                                    dtype=jnp.float32)
    tokens = np.random.default_rng(4).integers(0, 257, 48).astype(np.int32)
    want, margin = reference_window_moe.forward(params, config, tokens)
    rows = np.arange(40, 48)
    got, got_margin = reference_mellum2.forward(params, config, tokens, rows)
    np.testing.assert_allclose(got, np.asarray(want)[rows], rtol=0,
                               atol=2e-5)
    np.testing.assert_allclose(got_margin, np.asarray(margin), rtol=0,
                               atol=1e-6)
    low, _ = reference_mellum2.forward(params, config, tokens, rows,
                                       lower=True)
    assert np.abs(low - got).max() > 1e-2
    # the closed form is one form
    a = reference_mellum2.rope_frequencies(
        128, **PUBLISHED["rope_parameters"]["full_attention"])
    b = reference_window_moe.inv_freq(
        128, **PUBLISHED["rope_parameters"]["full_attention"])
    np.testing.assert_allclose(a[0], b[0], rtol=1e-12)
    assert a[1] == b[1] == 1.2772588722239782
