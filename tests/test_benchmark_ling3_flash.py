"""CPU tests of what ISSUE 58 adds to the benchmark: the runner
``serve_kda_latent`` end to end at a small size (logits AND the state the
drained requests left), the controls on the reference's side each
refused, the configuration, cell, traffic and metric entries and their
files, ``opcount_kda_latent`` against numbers worked by hand, the new
reader and the accepted readers the new metrics use on a hand-made trace,
the benchmark's reference against the program's.

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
    loadgen, opcount_kda_latent, reference_ling3_flash, run as bench_run)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-ling3-flash-vl-digest-closed"
CONFIG = "ling-3.0-flash-vl"
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
          "config.json")
# the catalog row's ``config``, key for key (the two clamp lists apart)
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
# the narrowed model: the file's keys with these in their place: groups of
# 3 layers (KDA, KDA, MLA, KDA), 4 of 16 experts (group 1 of 4) and 96 of
# 128 rows held
SMALL = dict(hidden_size=32, num_hidden_layers=4, layer_group_size=3,
             num_attention_heads=2, num_key_value_heads=2, head_dim=16,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rotary_dim=8, intermediate_size=48,
             moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
             first_k_dense_replace=1, num_experts=4, num_experts_per_tok=2,
             n_group=4, topk_group=2, vocab_size=96, rope_theta=100,
             max_position_embeddings=256,
             expert_swiglu_limit_list=[0, 0, 0, 0],
             share_expert_swiglu_limit_list=[0, 0, 0, 0],
             published={"num_experts": 16, "vocab_size": 128},
             deployment={"experts_held": [4, 4], "vocab_rows_held": [0, 96]})
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 20, "sigma": 0.8, "lo": 4, "hi": 72,
                             "round_to": 4},
                 output_len={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 32: the order of the sums is all that
    # differs, so the limits are a hundredth of the cell's; a prompt of 40
    # positions is five chunks of 8, one under 12 is short
    args = dict(resolved["config"]["runner_args"], slots=4, pool_blocks=65,
                prefill_chunk=8, max_seq_len=96, init_gain={},
                check_requests=4, state_requests=2, state_probes=3,
                long_prompt_chunks=5, short_prompt_tokens=12,
                logit_margin=1e-3, tie_margin=1e-6, held_rows_min=4,
                held_over_share_max=0.0, over_margin_share_max=0.02,
                state_margin=1e-3, deep_state_margin=1e-3)
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
    return bench_run.load_module("runners", "serve_kda_latent")


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    h = harness()
    cfg = runner.model_config(h.config)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.vocab_size,
            cfg.published_vocab_size) == (16, (4, 4), 96, 128)
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
    assert eng["stateful"] and eng["latent_pool"] and eng["slots"] == 4
    assert eng["warmed_buckets"] == [4, 8]
    setup = lines["setup"]
    assert setup["experts_held"] == [4, 4] and setup["router_experts"] == 16
    assert setup["vocab_rows_held"] == [0, 96]
    assert setup["pool_bytes"] > 0 and setup["state_bytes"] > 0
    assert setup["state_dtypes"] == ["float32"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 4 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and ref["control"] is None
    assert ref["state_requests_checked"] == 2
    assert ref["widest_state_error"] < 1e-3
    assert ref["longest_checked_prompt_chunks"] >= 5
    assert 0 < ref["shortest_checked_prompt_tokens"] < 12
    assert 0.5 < ref["logit_std"] < 2.0 and len(ref["rms"]) == 4
    assert [l["kind"] for l in ref["rms"]] == ["kda", "kda", "mla", "kda"]
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "near_tie_share", "over_margin_share",
        "leading_state_error", "widest_state_error", "held_rows",
        "state_requests_checked",
        "longest_checked_prompt_chunks", "shortest_checked_prompt_tokens",
        "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    # every live row routes 2 of 16 in each of 3 layers; 4 are held here
    assert c["moe_assignments_routed"] == c["wave_rows_live"] * 2 * 3
    assert 0 < c["moe_assignments"] == sum(c["moe_load"]) \
        < c["moe_assignments_routed"]
    # every live row is one of a one-row slot or of a wider q-block, in
    # each of 3 KDA layers
    assert c["kda_slot_steps"] + c["kda_chunk_rows"] \
        == 3 * c["wave_rows_live"]
    assert c["kda_slot_steps"] > 0 and c["kda_chunk_rows"] > 0
    ratio = bench_run.load_module("readers", "counter_ratio")
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "held_assignment_share.serve.json"))
    assert ratio.read(out["data"], **spec["args"]) \
        == 100.0 * c["moe_assignments"] / c["moe_assignments_routed"]
    assert h.setup_s > 0


@pytest.fixture(scope="module")
def finished(runner):
    """Four requests served at the small size on two slots, the last two
    the last on their slots: (harness, params, the reference's
    configuration, held, the finished rows, what the probes read, the
    probes)."""
    import jax.numpy as jnp
    from hetu_tpu.models.kda_latent import init_kda_latent_params
    from hetu_tpu.serving import Request, ServingEngine
    h = harness()
    cfg = runner.model_config(h.config)
    params = init_kda_latent_params(cfg, name="lng", seed=5,
                                    dtype=jnp.float32, dt_range=(0.05, 2.0))
    eng = ServingEngine(params, cfg, slots=2, max_seq_len=96, pool_blocks=65,
                        prefill_chunk=8)
    rng = np.random.default_rng(2)
    first = eng.run([Request(rng.integers(0, 96, n).astype(np.int32), 10,
                             request_id=f"q{i}")
                     for i, n in enumerate((9, 30))])
    last = eng.run([Request(rng.integers(0, 96, n).astype(np.int32), 10,
                            request_id=f"q{i + 2}")
                    for i, n in enumerate((61, 17))])
    probes = runner.probe_queries(7, 3, 2, 16)
    read = runner.read_states(eng.kv.states, probes)
    source, held, _ = runner.published_source(h.config)
    # (``done`` past the window marks a request of the drain)
    done = [{"result": r, "done": 1.0} for r in first.values()] \
        + [{"result": r, "done": 9.0} for r in last.values()]
    return h, params, source, held, done, read, probes


def test_the_sound_reference_is_correct(tpu_default_paths, runner,
                                        finished):
    h, params, source, held, done, read, probes = finished
    ok, rec = runner.agree(h, params, source, held, done,
                           h.config["runner_args"], 2.0, read=read,
                           probes=probes)
    assert ok, rec
    assert rec["widest_logit_gap"] <= 1e-3 and rec["held_rows"] >= 4
    assert rec["state_requests_checked"] == 2
    assert rec["widest_state_error"] < 1e-4
    # without what the probes read the state's check is not made
    bad, rec = runner.agree(h, params, source, held, done,
                            h.config["runner_args"], 2.0)
    assert not bad and rec["state_requests_checked"] == 0


STATE_CONTROLS = ("no_decay", "no_delta", "softplus_gate", "conv_cut")


@pytest.mark.parametrize("control", reference_ling3_flash.CONTROLS)
def test_each_control_is_refused(tpu_default_paths, runner, finished,
                                 control, monkeypatch):
    """Each piece has teeth: one thing computed differently on the
    reference's side comes out as not correct, by the shares or, of what
    moves the state, by the state's own check."""
    if control == "group_max":
        pytest.skip("two groups kept and two experts chosen: the largest "
                    "two experts' groups ARE plain top-k's; the chip's "
                    "probe reads this control at 8 groups, 4 kept")
    h, params, source, held, done, read, probes = finished
    limits = h.config["runner_args"]
    # (the small model's chunks are 8 rows)
    monkeypatch.setattr(reference_ling3_flash, "CONV_CUT", 8)
    bad, rec = runner.agree(h, params, source, held, done, limits, 2.0,
                            read=read, probes=probes, control=control)
    assert not bad, control
    if control in STATE_CONTROLS:
        assert rec["leading_state_error"] > 10 * limits["state_margin"], rec
        assert rec["widest_state_error"] >= rec["leading_state_error"]
    else:
        assert rec["over_margin_share"] > 0.02, rec


@pytest.mark.parametrize("limit,value", [
    ("held_rows_min", 10 ** 6), ("tie_share_max", -1.0),
    ("over_margin_share_max", -1.0), ("held_over_share_max", -1.0),
    ("state_margin", 0.0), ("deep_state_margin", 0.0),
    ("state_requests", 3),
    ("long_prompt_chunks", 9), ("short_prompt_tokens", 9)])
def test_each_limit_alone_refuses(tpu_default_paths, runner, finished,
                                  limit, value):
    h, params, source, held, done, read, probes = finished
    bad, _ = runner.agree(h, params, source, held, done,
                          dict(h.config["runner_args"], **{limit: value}),
                          2.0, read=read, probes=probes)
    assert not bad


def test_a_program_without_the_family_stops_at_once(runner, monkeypatch):
    """The parent of the PR has no ``hetu_tpu.models.kda_latent``: the
    cell exits non-zero before anything is built."""
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    cfg = runner.model_config(config)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.vocab_rows,
            cfg.num_hidden_layers) == (512, (0, 128), (0, 39296), 6)
    assert runner.model_config(config, state_dtype="bfloat16"
                               ).kda.state_dtype == "bfloat16"
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.kda_latent", None)
    with pytest.raises(SystemExit, match="Nothing was run"):
        runner.model_config(config)
    # a file whose deployment and held counts disagree is refused
    with pytest.raises(SystemExit, match="disagree"):
        runner.published_source(dict(config, num_experts=64))


# ------------------------------------------------------------------ #
# the configuration, the cell, the traffic, the metric entries
# ------------------------------------------------------------------ #

def test_the_configuration_holds_every_published_number():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert BENCH["configs"].index(entry) == 10         # appended
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    conf = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert conf["source"] == SOURCE and conf["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        want = {"num_hidden_layers": 6, "num_experts": 128,
                "vocab_size": 39296}.get(key, value)
        assert conf[key] == want, key
    assert conf["expert_swiglu_limit_list"] == [0] * 6
    assert conf["share_expert_swiglu_limit_list"] == [0] * 6
    pub = conf["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (42, 512, 157184)
    assert pub["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert pub["share_expert_swiglu_limit_list"] \
        == [0] * 34 + [5] * 6 + [7] * 2
    assert set(conf["reduced_why"]) == set(REDUCED)
    dep = conf["deployment"]
    assert (dep["chips_a_layer"], dep["rank"], dep["experts_held"],
            dep["vocab_rows_held"]) == (4, 0, [0, 128], [0, 39296])
    for key in ("layer_pattern", "kda_qk", "rotary", "kda_projections",
                "kda_gate", "kda_constants", "kda_out", "mla", "router",
                "head", "state"):
        assert conf["assumed"][key]
    assert set(conf["not_served"]) == {"vision_tower", "next_token_module",
                                       "clamped_swiglu"}
    mem = conf["memory_analysis"]
    assert 4.0 < mem["slots_48_Q_256"]["peak_GB"] < 12.0     # over 25 %
    # what the program builds from it
    runner = bench_run.load_module("runners", "serve_kda_latent")
    cfg = runner.model_config(conf)
    blk = cfg.block_spec()
    assert blk.ops == ("kda",) * 5 + ("latent_attention",)
    assert (blk.latent.q_lora_rank, blk.latent.gate,
            blk.latent.row_width) == (0, True, 640)
    assert (blk.kda.heads, blk.kda.head_dim, blk.kda.conv_kernel,
            blk.kda.lower_bound, blk.kda.state_dtype) == (
                32, 128, 4, -5.0, "float32")
    assert (blk.routed.num_experts, blk.routed.held, blk.routed.top_k,
            blk.routed.n_group, blk.routed.topk_group, blk.routed.scale,
            blk.leading_dense) == (512, 128, 8, 8, 4, 2.5, 2)
    shapes = cfg.param_shapes("lng")
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert 3.62e9 < count < 3.66e9                     # 7.28 GB in bf16
    # the class raises on a clamp among the layers it is given
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        runner.model_config(dict(
            conf, expert_swiglu_limit_list=[0, 0, 0, 0, 0, 4]))


def test_the_cell_and_its_metrics_are_appended_entries():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == 10 and len(cells) >= 11
    cell = BENCH["workloads"][10]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "digest-closed", 1)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][9] == CELL
    assert e2e["serve_tokens_per_s"]["bound"] == 0.1
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    new = ["kda_share.serve", "kda_scan_roofline.serve",
           "kda_scan_chunk_wave_ms", "kda_conv_share.serve",
           "moe_group_select_share.serve"]
    assert [m["name"] for m in BENCH["per_layer"]][78:83] == new
    for name in new:
        m = metrics[name]
        assert m["workloads"][0] == CELL and m["layer"] == "serving cores"
        assert m["moves"] == "serve_tokens_per_s"
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "readers", f"{spec['reader']}.py"))
    # an accepted list the cell joins has it behind the cells accepted
    # before it (a later PR's cell may follow)
    for name in ("mla_kernel_share.serve", "mla_absorb_share.serve",
                 "mla_gate_share.serve", "moe_route_share.serve",
                 "moe_experts_share.serve", "moe_experts_roofline.serve",
                 "moe_shared_share.serve", "held_assignment_share.serve",
                 "lm_head_share.serve", "moe_experts_chunk_wave_ms",
                 "attention_chunk_wave_ms", "chunk_wave_device_ms",
                 "device_idle_share.serve", "setup_build_s"):
        assert metrics[name]["workloads"].index(CELL) >= 1, name
    # ... and the shares whose counts would read this configuration wrong
    # (every layer latent; a parallel block; a state-space scan) do not
    for name in ("mla_kernel_roofline.serve", "held_experts_roofline.serve",
                 "ssm_scan_roofline.serve", "retention_scan_roofline.serve",
                 "decode_wave_device_ms"):
        assert CELL not in metrics[name]["workloads"], name
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "PROGRAM_SPANS.kda-latent.md"))


def test_the_traffic_is_the_issues():
    mix = bench_run.resolve_cell(BENCH, CELL)["traffic"]
    assert (mix["loop"], mix["clients"]) == ("closed", 48)
    assert (mix["ramp_seconds"], mix["drain_limit_seconds"],
            mix["trace_seconds"]) == (16.0, 60.0, 6.0)
    assert mix["request_pool"] % 64 == 0
    assert mix["prompt_len"] == {"median": 4096, "sigma": 0.5, "lo": 512,
                                 "hi": 16384, "round_to": 256}
    assert mix["output_len"] == {"median": 192, "sigma": 0.5, "lo": 32,
                                 "hi": 768}
    sizes = np.array(loadgen.request_sizes(mix, 0, mix["request_pool"]))
    prompts, answers = sizes[:, 0], sizes[:, 1]
    assert (prompts % 256 == 0).all()
    assert prompts.min() >= 512 and prompts.max() <= 16384
    assert answers.min() >= 32 and answers.max() <= 768
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    args = config["runner_args"]
    # the check's long prompt and its short one are in every pool
    assert (prompts >= 256 * args["long_prompt_chunks"]).sum() >= 16
    short = np.flatnonzero(prompts < args["short_prompt_tokens"])
    assert len(short) >= 12
    # ... at most a quarter of the cycle apart: a run that finishes most
    # of the pool finishes one
    assert np.diff(np.r_[short, short[0] + len(prompts)]).max() <= 80
    other = np.array(loadgen.request_sizes(mix, 7, mix["request_pool"]))
    assert (np.roll(sizes, -7, 0) == other).all()
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [256]
    assert prompts.max() + answers.max() <= args["max_seq_len"]
    # the pool reserves every slot's longest sequence
    assert args["pool_blocks"] == args["slots"] * args["max_seq_len"] // 16 \
        + 1


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

LNG = {"num_attention_heads": 32, "head_dim": 128, "kda_lower_bound": -5,
       "runner_args": {"prefill_chunk": 256}}


def test_one_chunk_wave_by_hand():
    """Three 256-row chunks beside 36 decoding slots, five KDA layers."""
    counters = {"kda_slot_steps": 36 * 5, "kda_chunk_rows": 768 * 5}
    assert opcount_kda_latent.state_bytes(LNG) == 2_097_152
    ops, nbytes = opcount_kda_latent.kda_scan(counters, LNG)
    # 39 slot states a layer read and written; 804 rows' q, k, v, output
    # (bfloat16), decay and beta (float32) a head
    assert nbytes == (180 + 15) * 2 * 2_097_152 \
        + (180 + 3840) * 32 * (2 * 4 * 128 + 4 * 129)
    assert ops == 180 * 32 * 7 * 128 * 128 \
        + 3840 * 32 * (6 * 128 * 128 + 5 * 64 * 128)
    # bytes bind it: 1.0 GB at 819 GB/s against 18 GFLOP at 197 TFLOP/s
    assert nbytes / 819e9 > 10 * ops / 197e12
    # a decode wave of 48 slots
    ops, nbytes = opcount_kda_latent.kda_scan({"kda_slot_steps": 240,
                                               "kda_chunk_rows": 0}, LNG)
    assert nbytes == 240 * (2 * 2_097_152 + 32 * 1540)


# ------------------------------------------------------------------ #
# the readers on a hand-made trace
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: the delta rule's front end (2 ms), its conv (1
    ms), its scan (a ``while`` of 6 ms), a state store (1 ms), its output
    (1 ms), the group choice (0.5 ms) and the latent kernel (3 ms) inside
    one 20 ms benchmark span."""
    ms = 1e6
    ops = [["%fusion.1 = bf16[1] fusion()", 1 * ms, 2 * ms],
           ["%fusion.2 = bf16[1] fusion()", 3 * ms, 1 * ms],
           ["%while.3 = f32[1] while()", 4 * ms, 6 * ms],
           ["%fusion.4 = f32[1] fusion()", 10 * ms, 1 * ms],
           ["%fusion.5 = bf16[1] fusion()", 11 * ms, 1 * ms],
           ["%fusion.6 = f32[1] fusion()", 12 * ms, 0.5 * ms],
           ["%ragged_paged_mla.7 = bf16[1] custom-call()", 13 * ms, 3 * ms]]
    stacks = ["jit(f)/wave_chunk/kda_qkvg/dot_general",
              "jit(f)/wave_chunk/kda_conv/mul",
              "jit(f)/wave_chunk/kda_scan/while",
              "jit(f)/wave_chunk/state_write/convert",
              "jit(f)/wave_chunk/kda_out/dot_general",
              "jit(f)/wave_chunk/moe_route/moe_group_select/top_k",
              "jit(f)/wave_chunk/attention/ragged_paged_mla/pallas_call"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 20 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(7))}}


class _H:
    peak = PEAK
    config = LNG

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def test_the_new_roofline_and_shares_on_a_hand_made_trace():
    counters = {"kda_slot_steps": 36 * 5, "kda_chunk_rows": 768 * 5}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters, "untraced": counters}}

    def metric(name):
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        return bench_run.load_module("readers", spec["reader"]), spec["args"]

    # the scan's bytes over the 7 ms under ``kda_scan`` and
    # ``state_write``
    _, nbytes = opcount_kda_latent.kda_scan(counters, LNG)
    mod, args = metric("kda_scan_roofline.serve")
    got = mod.read(data, **args)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 7e-3)
    assert 0 < got < 100 and h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(7e-3)
    # the shares by the accepted readers: of 14.5 busy ms
    for name, ms in (("kda_share.serve", 11.0), ("kda_conv_share.serve", 1.0),
                     ("moe_group_select_share.serve", 0.5)):
        mod, args = metric(name)
        assert mod.read(data, **args) == pytest.approx(100 * ms / 14.5), name
    # the parent (no such counter or scope), another family's
    # configuration: nothing, and no raise
    mod, args = metric("kda_scan_roofline.serve")
    assert mod.read({"trace": _trace(), "harness": h}, **args) is None
    assert mod.read(dict(data, counters={"traced": {"steps": 3}}),
                    **args) is None
    assert mod.read(dict(data, harness=_H({"head_dim": 128})),
                    **args) is None
    assert mod.read(data, model="kda_scan", scopes=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    bare = _trace()
    bare["op_scopes"]["table"] = [
        s.replace("kda_", "ssm_").replace("moe_group_select/", "")
        for s in bare["op_scopes"]["table"]]
    for name in ("kda_conv_share.serve", "moe_group_select_share.serve"):
        mod, args = metric(name)
        assert mod.read(dict(data, trace=bare), **args) is None


def test_the_benchmarks_reference_is_the_programs_equations(runner,
                                                            finished):
    """``benchmarks/reference_ling3_flash.py`` (head blocks, row blocks,
    an expert at a time, a padded sequence) against
    ``hetu_tpu/models/reference_kda_latent.py`` (one dense forward) on one
    sequence: logits and the states the probes read."""
    import jax.numpy as jnp
    from hetu_tpu.models import reference_kda_latent as program_ref
    h, params, source, held, _, _, probes = finished
    cfg = runner.model_config(h.config)
    tokens = np.random.default_rng(1).integers(0, 96, 70).astype(np.int32)
    stats = {}
    lg, margin, read = reference_ling3_flash.forward(
        params, source, tokens, np.arange(70), name="lng", held=held,
        stats=stats, probes=probes, pad_to=64)
    want, states = program_ref.forward(params, cfg, jnp.asarray(tokens),
                                       name="lng", states=True)
    np.testing.assert_allclose(lg, np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(
        read, np.einsum("mhk,lhkv->lmhv", probes, np.asarray(states)),
        atol=2e-5)
    assert margin.shape == (70,) and (margin >= 0).all()
    assert len(stats["layers"]) == 4 and stats["logits"] > 0
    with pytest.raises(ValueError, match="control="):
        reference_ling3_flash.forward(params, source, tokens, [0],
                                      control="nothing")
