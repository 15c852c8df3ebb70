"""CPU tests of what ISSUE 62 adds to the benchmark: the runner
``serve_kda_gqa`` end to end at a small size (logits AND the state the
drained requests left), the controls on the reference's side each
refused, the configuration, cell, traffic and metric entries and their
files, ``opcount_kda_gqa`` against numbers worked by hand, the new
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
    loadgen, opcount_kda_gqa, reference_solar_open2, run as bench_run)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-solar-open2-longdoc-closed"
CONFIG = "solar-open2-250b"
SOURCE = ("https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
          "config.json")
# the catalog row's ``config``, key for key
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts",
           "vocab_size"]
HELD_HERE = {"num_hidden_layers": 4, "gqa_layers": [0],
             "n_routed_experts": 40, "vocab_size": 24576}
# the narrowed model: the file's keys with these in their place: GQA then
# three KDA layers, 2 of 16 experts and 96 of 128 rows held
SMALL = dict(hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16,
             linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                 "num_heads": 4, "num_kv_heads": None},
             intermediate_size=48, moe_intermediate_size=16,
             n_routed_experts=2, num_experts_per_tok=2, vocab_size=96,
             max_position_embeddings=256,
             published={"n_routed_experts": 16, "vocab_size": 128},
             deployment={"experts_held": [2, 2], "vocab_rows_held": [0, 96]})
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
                state_margin=1e-3, deep_state_margin=1e-3,
                state_lead_layers=1, state_slow_heads=2)
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
    return bench_run.load_module("runners", "serve_kda_gqa")


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner):
    h = harness()
    cfg = runner.model_config(h.config)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.vocab_size,
            cfg.published_vocab_size) == (16, (2, 2), 96, 128)
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
    assert eng["stateful"] and eng["kv_pool_layers"] == 1
    assert eng["slots"] == 4
    assert eng["warmed_buckets"] == [4, 8]
    setup = lines["setup"]
    assert setup["experts_held"] == [2, 2] and setup["router_experts"] == 16
    assert setup["vocab_rows_held"] == [0, 96]
    assert setup["pool_bytes"] > 0 and setup["state_bytes"] > 0
    assert setup["state_dtypes"] == ["float32"]
    ref = lines["reference"]
    assert ref["requests_checked"] == 4 and ref["rows_checked"] > 0
    assert ref["widest_logit_gap"] <= 1e-3 and ref["control"] is None
    assert ref["state_requests_checked"] >= 2
    assert ref["widest_state_error"] < 1e-3
    assert ref["longest_checked_prompt_chunks"] >= 5
    assert 0 < ref["shortest_checked_prompt_tokens"] < 12
    assert 0.5 < ref["logit_std"] < 2.0 and len(ref["rms"]) == 4
    assert [l["kind"] for l in ref["rms"]] == ["gqa", "kda", "kda", "kda"]
    assert {c["name"] for c in out["compared"]} == {
        "held_over_share", "near_tie_share", "over_margin_share",
        "leading_state_error", "widest_state_error", "held_rows",
        "state_requests_checked",
        "longest_checked_prompt_chunks", "shortest_checked_prompt_tokens",
        "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    # every live row routes 2 of 16 in each of 4 layers; 2 are held here
    assert c["moe_assignments_routed"] == c["wave_rows_live"] * 2 * 4
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
    from hetu_tpu.models.kda_gqa import init_kda_gqa_params
    from hetu_tpu.serving import Request, ServingEngine
    h = harness()
    cfg = runner.model_config(h.config)
    params = init_kda_gqa_params(cfg, name="slr", seed=5,
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
    probes = runner.kda_latent().probe_queries(7, 3, 4, 16)
    read = runner.kda_latent().read_states(eng.kv.states, probes)
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


STATE_CONTROLS = ("no_decay", "no_delta", "safe_gate", "conv_cut",
                  "beta_one")


@pytest.mark.parametrize("control", reference_solar_open2.CONTROLS)
def test_each_control_is_refused(tpu_default_paths, runner, finished,
                                 control, monkeypatch):
    """Each piece has teeth: one thing computed differently on the
    reference's side comes out as not correct, by the shares or, of what
    moves the state, by the state's own check."""
    h, params, source, held, done, read, probes = finished
    limits = h.config["runner_args"]
    # (the small model's chunks are 8 rows)
    monkeypatch.setattr(reference_solar_open2, "CONV_CUT", 8)
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
    """The parent of the PR has no ``hetu_tpu.models.kda_gqa``: the cell
    exits non-zero before anything is built."""
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    cfg = runner.model_config(config)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.vocab_rows,
            cfg.num_hidden_layers) == (320, (0, 40), (0, 24576), 4)
    assert runner.model_config(config, state_dtype="bfloat16"
                               ).kda.state_dtype == "bfloat16"
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.kda_gqa", None)
    with pytest.raises(SystemExit, match="Nothing was run"):
        runner.model_config(config)
    # a file whose deployment and held counts disagree is refused
    with pytest.raises(SystemExit, match="disagree"):
        runner.published_source(dict(config, n_routed_experts=64))


# ------------------------------------------------------------------ #
# the configuration, the cell, the traffic, the metric entries
# ------------------------------------------------------------------ #

def test_the_configuration_holds_every_published_number():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert BENCH["configs"].index(entry) == 11         # appended
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    conf = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert conf["source"] == SOURCE and conf["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        assert conf[key] == HELD_HERE.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(conf["reduced_why"]) == set(REDUCED)
    dep = conf["deployment"]
    assert (dep["chips_a_layer"], dep["rank"], dep["experts_held"],
            dep["vocab_rows_held"]) == (8, 0, [0, 40], [0, 24576])
    for key in ("layer_pattern", "kda_decay", "kda_projections",
                "kda_neg_eigval", "kda_out", "gqa", "router", "unused",
                "head", "state", "max_seq_len"):
        assert conf["assumed"][key]
    assert set(conf["not_served"]) == {"next_token_module", "long_context"}
    mem = conf["memory_analysis"]
    assert 4.0 < mem["slots_48_Q_256"]["peak_GB"] < 14.5     # over 25 %
    assert mem["slots_48_Q_1"]["peak_GB"] <= mem["slots_48_Q_256"]["peak_GB"]
    # what the program builds from it
    runner = bench_run.load_module("runners", "serve_kda_gqa")
    cfg = runner.model_config(conf)
    blk = cfg.block_spec()
    assert blk.ops == ("attention", "kda", "kda", "kda")
    assert (blk.positions, blk.kv_heads, blk.attn_gate, blk.head_dim) == (
        "none", 8, True, 128)
    assert (blk.kda.heads, blk.kda.head_dim, blk.kda.conv_kernel,
            blk.kda.decay, blk.kda.rank, blk.kda.gate_by,
            blk.kda.beta_scale, blk.kda.state_dtype) == (
                64, 128, 4, "softplus", 128, "channel", 2.0, "float32")
    assert (blk.routed.num_experts, blk.routed.held, blk.routed.top_k,
            blk.routed.n_group, blk.routed.scale, blk.routed.n_shared,
            blk.leading_dense) == (320, 40, 8, 1, 1.0, 1, 0)
    shapes = cfg.param_shapes("slr")
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert 3.29e9 < count < 3.33e9                     # 6.62 GB in bf16
    # the class raises by name on what it cannot run
    with pytest.raises(ValueError, match="use_rope"):
        runner.model_config(dict(conf, use_rope=True))


def test_the_cell_and_its_metrics_are_appended_entries():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == 11 and len(cells) >= 12
    cell = BENCH["workloads"][11]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed", 1)
    assert len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][10] == CELL
    assert e2e["serve_tokens_per_s"]["bound"] == 0.1
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    new = ["gqa_gate_share.serve", "kda_free_scan_roofline.serve",
           "kda_gqa_attention_roofline.serve"]
    assert [m["name"] for m in BENCH["per_layer"]][83:86] == new
    for name in new:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "readers", f"{spec['reader']}.py"))
    # an accepted list the cell joins has it behind the cells accepted
    # before it (a later PR's cell may follow)
    for name in ("kda_share.serve", "kda_conv_share.serve",
                 "kda_scan_chunk_wave_ms", "moe_route_share.serve",
                 "moe_experts_share.serve", "moe_shared_share.serve",
                 "held_assignment_share.serve",
                 "expert_load_imbalance.serve", "ragged_kernel_share.serve",
                 "chunk_wave_device_ms", "chunk_wave_time_share.serve",
                 "device_idle_share.serve", "setup_build_s"):
        assert metrics[name]["workloads"].index(CELL) >= 1, name
    # ... and the shares whose counts would read this configuration wrong
    # (the bounded gate's roofline; latent attention; other scans) do not
    for name in ("kda_scan_roofline.serve", "mla_kernel_share.serve",
                 "mla_gate_share.serve", "moe_group_select_share.serve",
                 "gqa_kernel_roofline.serve", "ssm_scan_roofline.serve",
                 "retention_scan_roofline.serve"):
        assert CELL not in metrics[name]["workloads"], name
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "PROGRAM_SPANS.kda-gqa.md"))


def test_the_traffic_is_the_issues():
    mix = bench_run.resolve_cell(BENCH, CELL)["traffic"]
    assert (mix["loop"], mix["clients"], mix["base_seed"]) == (
        "closed", 48, 62)
    assert (mix["ramp_seconds"], mix["drain_limit_seconds"],
            mix["trace_seconds"]) == (16.0, 60.0, 6.0)
    assert mix["request_pool"] % mix["clients"] == 0
    assert mix["prompt_len"]["median"] in (8192, 6144)   # the fallback
    assert {k: v for k, v in mix["prompt_len"].items() if k != "median"} \
        == {"sigma": 0.5, "lo": 2048, "hi": 24576, "round_to": 256}
    assert mix["output_len"] == {"median": 128, "sigma": 0.5, "lo": 32,
                                 "hi": 512}
    sizes = np.array(loadgen.request_sizes(mix, 0, mix["request_pool"]))
    prompts, answers = sizes[:, 0], sizes[:, 1]
    assert (prompts % 256 == 0).all()
    assert prompts.min() >= 2048 and prompts.max() <= 24576
    assert answers.min() >= 32 and answers.max() <= 512
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    args = config["runner_args"]
    # the check's long prompt and its short one are in every pool
    assert (prompts >= 256 * args["long_prompt_chunks"]).sum() >= 16
    short = np.flatnonzero(prompts < args["short_prompt_tokens"])
    assert len(short) >= 8
    # every checked request can be one the drain finished
    assert args["state_requests"] <= args["check_requests"] <= 48
    # ... at most a third of the cycle apart: a run that finishes most of
    # the pool finishes one
    assert np.diff(np.r_[short, short[0] + len(prompts)]).max() \
        <= len(prompts) // 3
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

SLR = {"num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
       "gqa_layers": [0],
       "linear_attn_config": {"num_heads": 64, "head_dim": 128},
       "runner_args": {"prefill_chunk": 256}}


def test_one_chunk_wave_by_hand():
    """Three 256-row chunks beside 45 decoding slots, three KDA layers
    and one GQA layer."""
    counters = {"kda_slot_steps": 45 * 3, "kda_chunk_rows": 768 * 3,
                "attn_ctx_tokens": 48 * 8192, "attn_score_pairs": 813 * 8000,
                "wave_rows_live": 813}
    assert opcount_kda_gqa.state_bytes(SLR) == 4_194_304
    ops, nbytes = opcount_kda_gqa.kda_free_scan(counters, SLR)
    # 48 slot states a layer read and written; 813 rows' q, k, v, output
    # (bfloat16), decay and beta (float32) a head
    assert nbytes == (135 + 9) * 2 * 4_194_304 \
        + (135 + 2304) * 64 * (2 * 4 * 128 + 4 * 129)
    assert ops == 135 * 64 * 7 * 128 * 128 \
        + 2304 * 64 * (6 * 128 * 128 + 5 * 64 * 128)
    # the exact pairing's exponentials are NOT in the count: it is the
    # bounded gate's count at this configuration's heads
    from benchmarks import opcount_kda_latent
    assert (ops, nbytes) == opcount_kda_latent.kda_scan(
        counters, dict(SLR, num_attention_heads=64))
    # bytes bind it
    assert nbytes / 819e9 > 5 * ops / 197e12
    ops, nbytes = opcount_kda_gqa.kda_gqa_attention(counters, SLR)
    # K and V of 8 heads of 128 a cached position once; q in, o out
    assert nbytes == 2 * (48 * 8192 * 2 * 8 * 128 + 813 * 2 * 64 * 128)
    assert ops == 813 * 8000 * 64 * 2 * 128 * 2


# ------------------------------------------------------------------ #
# the readers on a hand-made trace
# ------------------------------------------------------------------ #

def _trace():
    """One device plane: the delta rule's front end (2 ms), its conv (1
    ms), its scan kernel (6 ms), a state store (1 ms), its output (1 ms),
    the attention's gate (0.5 ms) and the K/V kernel (3 ms) inside one 20
    ms benchmark span."""
    ms = 1e6
    ops = [["%fusion.1 = bf16[1] fusion()", 1 * ms, 2 * ms],
           ["%fusion.2 = bf16[1] fusion()", 3 * ms, 1 * ms],
           ["%kda_chunk_scan.3 = f32[1] custom-call()", 4 * ms, 6 * ms],
           ["%fusion.4 = f32[1] fusion()", 10 * ms, 1 * ms],
           ["%fusion.5 = bf16[1] fusion()", 11 * ms, 1 * ms],
           ["%fusion.6 = bf16[1] fusion()", 12 * ms, 0.5 * ms],
           ["%ragged_paged_mixed.7 = bf16[1] custom-call()", 13 * ms,
            3 * ms]]
    stacks = ["jit(f)/wave_chunk/kda_qkvg/dot_general",
              "jit(f)/wave_chunk/kda_conv/mul",
              "jit(f)/wave_chunk/kda_scan/kda_chunk_scan/pallas_call",
              "jit(f)/wave_chunk/state_write/convert",
              "jit(f)/wave_chunk/kda_out/dot_general",
              "jit(f)/wave_chunk/gqa_gate/mul",
              "jit(f)/wave_chunk/attention/ragged_paged_mixed/pallas_call"]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 20 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(7))}}


class _H:
    peak = PEAK
    config = SLR

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def test_the_new_rooflines_and_shares_on_a_hand_made_trace():
    counters = {"kda_slot_steps": 45 * 3, "kda_chunk_rows": 768 * 3,
                "attn_ctx_tokens": 48 * 8192, "attn_score_pairs": 813 * 8000,
                "wave_rows_live": 813}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters, "untraced": counters}}

    def metric(name):
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "metrics", f"{name}.json"))
        return bench_run.load_module("readers", spec["reader"]), spec["args"]

    # the scan's bytes over the 7 ms under ``kda_scan`` and
    # ``state_write``
    _, nbytes = opcount_kda_gqa.kda_free_scan(counters, SLR)
    mod, args = metric("kda_free_scan_roofline.serve")
    got = mod.read(data, **args)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 7e-3)
    assert 0 < got < 100 and h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(7e-3)
    # the K/V kernel's 3 ms, by its name
    ops, nbytes = opcount_kda_gqa.kda_gqa_attention(counters, SLR)
    mod, args = metric("kda_gqa_attention_roofline.serve")
    got = mod.read(data, **args)
    assert got == pytest.approx(
        100 * max(nbytes / 819e9, ops / PEAK["bf16_flops_per_s"]) / 3e-3)
    assert h.lines[-1]["kernel_s"] == pytest.approx(3e-3)
    # the shares by the accepted readers: of 14.5 busy ms
    for name, ms in (("kda_share.serve", 11.0), ("kda_conv_share.serve", 1.0),
                     ("gqa_gate_share.serve", 0.5)):
        mod, args = metric(name)
        assert mod.read(data, **args) == pytest.approx(100 * ms / 14.5), name
    # the parent (no such counter or scope), another family's
    # configuration: nothing, and no raise
    for name in ("kda_free_scan_roofline.serve",
                 "kda_gqa_attention_roofline.serve"):
        mod, args = metric(name)
        assert mod.read({"trace": _trace(), "harness": h}, **args) is None
        assert mod.read(dict(data, counters={"traced": {"steps": 3}}),
                        **args) is None
        assert mod.read(dict(data, harness=_H({"head_dim": 128})),
                        **args) is None
    assert mod.read(data, model="kda_free_scan", scopes=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    # the bounded gate's reader reads nothing of this configuration
    mod, args = metric("kda_scan_roofline.serve")
    assert mod.read(data, **args) is None
    bare = _trace()
    bare["op_scopes"]["table"] = [s.replace("gqa_gate", "attn_out")
                                  for s in bare["op_scopes"]["table"]]
    mod, args = metric("gqa_gate_share.serve")
    assert mod.read(dict(data, trace=bare), **args) is None


def test_the_benchmarks_reference_is_the_programs_equations(runner,
                                                            finished):
    """``benchmarks/reference_solar_open2.py`` (head blocks, row blocks,
    an expert at a time, a padded sequence) against
    ``hetu_tpu/models/reference_kda_gqa.py`` (one dense forward) on one
    sequence: logits and the states the probes read."""
    import jax.numpy as jnp
    from hetu_tpu.models import reference_kda_gqa as program_ref
    h, params, source, held, _, _, probes = finished
    cfg = runner.model_config(h.config)
    tokens = np.random.default_rng(1).integers(0, 96, 70).astype(np.int32)
    stats = {}
    lg, margin, read = reference_solar_open2.forward(
        params, source, tokens, np.arange(70), name="slr", held=held,
        stats=stats, probes=probes, pad_to=64)
    want, states = program_ref.forward(params, cfg, jnp.asarray(tokens),
                                       name="slr", states=True)
    np.testing.assert_allclose(lg, np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(
        read, np.einsum("mhk,lhkv->lmhv", probes, np.asarray(states)),
        atol=2e-5)
    assert margin.shape == (70,) and (margin >= 0).all()
    assert len(stats["layers"]) == 4 and stats["logits"] > 0
    with pytest.raises(ValueError, match="control="):
        reference_solar_open2.forward(params, source, tokens, [0],
                                      control="nothing")
    # each control the two references share computes the same wrong thing
    for control, wrong in (("beta_one", "beta_one"), ("no_gate", "no_gate"),
                           ("safe_gate", "safe_gate"), ("rope", "rope"),
                           ("gate_head", "gate_head")):
        lg, _, _ = reference_solar_open2.forward(
            params, source, tokens, np.arange(70), name="slr", held=held,
            control=control, pad_to=64)
        want = program_ref.forward(params, cfg, jnp.asarray(tokens),
                                   name="slr", wrong=(wrong,))
        np.testing.assert_allclose(lg, np.asarray(want), atol=2e-4)
