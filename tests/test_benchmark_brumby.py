"""CPU tests of what ISSUE 44 adds to the benchmark: the runner
``serve_retention`` end to end at a small size with both parts of its
comparison, each control coming out not correct, the configuration, cell,
traffic and metric entries and their files, ``opcount_retention`` against
numbers worked by hand, the new reader on a hand-made trace, and the
benchmark's reference against the program's.

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
    loadgen, opcount_retention, reference_brumby, run as bench_run)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = bench_run.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))[
    "TPU v5 lite"]
CELL = "serve-brumby-14b-docs-closed"
CONFIG = "brumby-14b"
SOURCE = ("https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
          "config.json")
# the catalog row's ``config``, number for number
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
SMALL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=96, max_position_embeddings=512,
             retention_chunk=8)
SMALL_MIX = dict(clients=4, request_pool=64, ramp_seconds=0.3,
                 prompt_len={"median": 24, "sigma": 0.6, "lo": 8, "hi": 64,
                             "round_to": 8},
                 output_len={"median": 6, "sigma": 0.5, "lo": 2, "hi": 12})


def harness(seconds=2.0, **args_over):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    # float32 weights at a width of 64: the order of the sums is all that
    # differs, so the limits are a thousandth of the cell's; a prompt of
    # 96 tokens (12 chunks of 8) is "long" here, memories are 4-64 steps
    args = dict(resolved["config"]["runner_args"], slots=4, prefill_chunk=8,
                max_seq_len=128, init_memory_range=[4.0, 64.0],
                long_prompts=2, long_prompt_tokens=96, long_prompt_answer=4,
                check_requests=5, state_requests=2, state_probes=4,
                long_prompt_chunks=3, reference_pad=16, reference_rows=8,
                logit_margin=2e-4, state_margin=2e-4, normaliser_margin=2e-4)
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
    return bench_run.load_module("runners", "serve_retention")


@pytest.fixture(scope="module")
def small_blocks():
    """The reference's blocks at the small size (its row block is 1,024
    on the chip: a sequence is padded to a multiple of it)."""
    old = reference_brumby.ROW_BLOCK
    reference_brumby.ROW_BLOCK = 16
    yield
    reference_brumby.ROW_BLOCK = old


@pytest.fixture(scope="module")
def window(runner, small_blocks):
    """One served window at the small size, shared by the tests that
    read it again under a control."""
    gc.collect()
    h = harness()
    w = runner.serve_window(h, cfg=runner.model_config(h.config))
    return h, w


def read_again(runner, window, args=None, **kw):
    h, w = window
    kw.setdefault("read", w["read"])
    return runner.agree(h, w["params"], w["ref_config"], w["out"]["done"],
                        args or h.config["runner_args"], h.seconds,
                        probes=w["probes"], long_done=w["long_done"], **kw)


def test_runner_end_to_end_at_a_small_size(tpu_default_paths, runner,
                                           small_blocks):
    h = harness()
    out = runner.run(h, cfg=runner.model_config(h.config))
    log = h.out.getvalue()
    assert out["correct"], log
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    lines = {json.loads(l)["line"]: json.loads(l) for l in log.splitlines()}
    eng = lines["serve"]["engine"]
    assert eng["ragged"] and eng["paged"] and eng["stateful"]
    assert not eng["pool"] and eng["slots"] == 4
    assert eng["drained"] and eng["state_resets"] >= out["attempted"]
    assert eng["warmed_buckets"] == [8]
    # a layer's S [1, 4, 2, 136, 16] and z [1, 4, 2, 136], three layers
    # of each, float32; no pool
    assert lines["setup"]["state_bytes"] == 3 * 4 * 2 * 136 * 17 * 4
    assert lines["setup"]["pool_bytes"] == 0
    assert lines["setup"]["state_dtypes"] == ["float32"]
    assert lines["setup"]["long_prompts_s"] > 0
    ref = lines["reference"]
    assert ref["control"] is None
    # five of the window's and the two long prompts
    assert ref["requests_checked"] == 7 and ref["rows_checked"] > 0
    assert ref["state_requests_checked"] == 2
    assert ref["widest_logit_gap"] <= 2e-4
    assert ref["widest_state_error"] <= 2e-4
    assert ref["widest_normaliser_error"] <= 2e-4
    assert ref["longest_checked_prompt_tokens"] == 96
    # both branches move the residual, and the logits are of order one
    for layer in ref["rms"]:
        for branch in ("retention", "mlp"):
            assert layer[branch] > 0.05 * layer["residual"]
    assert 0.3 < ref["logit_std"] < 3.0
    assert {c["name"] for c in out["compared"]} == {
        "widest_logit_gap", "widest_state_error", "widest_normaliser_error",
        "state_requests_checked", "longest_checked_prompt_tokens",
        "exact_lengths"}
    assert all(c["within"] for c in out["compared"])
    c = out["data"]["counters"]["untraced"]
    assert c["ret_rows"] == c["wave_rows_live"] * 3
    assert 0 < c["ret_slot_steps"] <= c["ret_rows"]
    assert c["ret_chunk_pairs"] > 0
    assert c["attn_score_pairs"] == c["attn_ctx_tokens"] == 0
    assert h.setup_s > 0


@pytest.mark.parametrize("control", reference_brumby.CONTROLS)
def test_each_control_comes_out_not_correct(window, runner, control):
    """The same served window read against the reference computed another
    way: every control is outside one of the limits.  At this width the
    limits are float32's (2e-4); the cell's are set between what the
    bfloat16 engine reads and what each control reads on the chip
    (PERF.md section 6, PR 44)."""
    ok, record = read_again(runner, window)
    assert ok, record
    ok, other = read_again(runner, window, control=control)
    args = window[0].config["runner_args"]
    assert not ok, other
    assert other["widest_logit_gap"] > 10 * args["logit_margin"] \
        or other["widest_state_error"] > 10 * args["state_margin"]


def test_a_state_kept_in_bfloat16_comes_out_not_correct(
        tpu_default_paths, runner, small_blocks):
    """The control on the PROGRAM's side, as the probe runs it: the same
    weights served with ``state_dtype`` bfloat16."""
    h = harness()
    cfg = runner.model_config(h.config, state_dtype="bfloat16")
    w = runner.serve_window(h, cfg=cfg)
    assert w["engine"]["drained"] and w["read"] is not None
    ok, record = read_again(runner, (h, w))
    assert not ok
    assert record["widest_normaliser_error"] > 5 * 2e-4
    assert record["widest_state_error"] > 5 * 2e-4


@pytest.mark.parametrize("limit,value", [
    ("logit_margin", -1.0), ("state_margin", -1.0),
    ("normaliser_margin", -1.0), ("state_requests", 99),
    ("long_prompt_tokens", 9999)])
def test_each_limit_alone_refuses(window, runner, limit, value):
    args = dict(window[0].config["runner_args"], **{limit: value})
    ok, _ = read_again(runner, window, args=args)
    assert not ok


def test_without_the_states_the_run_is_not_correct(window, runner):
    ok, record = read_again(runner, window, read=None)
    assert not ok and record["state_requests_checked"] == 0


def test_the_profiler_is_switched_at_a_retirement_alone(runner):
    """``drive`` asks once an iteration whether ``no_token_yet`` is empty:
    only at the first look after some request's last token landed."""
    class Req:
        def __init__(self, n):
            self.max_new_tokens = n

    serve = bench_run.load_module("runners", "serve")
    mix = dict(SMALL_MIX, base_seed=1)
    load = serve.Load(mix, 3, 256, 8)
    load.no_token_yet = gate = runner.AfterARetirement(load.rows)
    assert gate                                   # nothing retired yet
    a, b = load.next_request(0.0, 0.0), load.next_request(0.0, 0.0)
    need = {r.request_id: r.max_new_tokens for r in (a, b)}
    looks = []
    for _ in range(max(need.values())):
        for r in (a, b):
            if need[r.request_id]:
                need[r.request_id] -= 1
                load.on_token(r, 7)
        looks.append(not gate)                    # drive's one look a step
    # one look is open after each of the two last tokens, no other
    assert sum(looks) == len({r.max_new_tokens for r in (a, b)})
    assert looks[min(a.max_new_tokens, b.max_new_tokens) - 1]
    assert looks[-1] and gate and load.emitted == sum(
        r.max_new_tokens for r in (a, b))
    load.rejected(load.next_request(0.0, 0.0))    # no token, no retirement
    assert gate


def test_state_error_finds_the_slot_and_reads_the_widest_head(runner):
    rng = np.random.default_rng(0)
    num = rng.normal(size=(2, 5, 4, 3, 6))     # layers, slots, M, g, d
    den = rng.uniform(1, 2, size=(2, 5, 4, 3))
    want = (num[:, 3].copy(), den[:, 3].copy())
    assert runner.state_error((num, den), want) == (0.0, 0.0, 3)
    want[0][1, :, 2] *= 1.1                    # one head of layer 1
    want[1][0, :, 1] *= 1.02
    rel_n, rel_d, slot = runner.state_error((num, den), want)
    assert slot == 3
    assert rel_n == pytest.approx(0.1 / 1.1, rel=1e-6)
    assert rel_d == pytest.approx(0.02 / 1.02, rel=1e-6)


def test_the_probes_read_the_state_the_reference_describes(runner):
    """``read_states`` through the program's phi against the attention
    form's sums, for a state built by hand."""
    from hetu_tpu.models.retention_decode import sympow2
    rng = np.random.default_rng(1)
    T, g, d = 9, 2, 16
    k = rng.normal(size=(T, g, d)).astype(np.float32)
    v = rng.normal(size=(T, g, d)).astype(np.float32)
    w = rng.uniform(0.2, 1.0, size=(T, g)).astype(np.float32)
    pk = np.asarray(sympow2(k))
    S = np.einsum("tg,tgD,tgd->gDd", w, pk, v)[None, None]
    z = np.einsum("tg,tgD->gD", w, pk)[None, None]
    probes = runner.probe_queries(5, 3, g, d)
    assert probes.shape == (3, g, d)
    np.testing.assert_array_equal(probes, runner.probe_queries(5, 3, g, d))
    num, den = runner.read_states((S, z), probes)
    a = w[None] * np.einsum("mgd,tgd->mtg", probes, k) ** 2
    np.testing.assert_allclose(num[0, 0], np.einsum("mtg,tgd->mgd", a, v),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(den[0, 0], a.sum(1), rtol=2e-4)


# ------------------------------------------------------------------ #
# entries and their files
# ------------------------------------------------------------------ #

def test_the_configuration_holds_every_published_number(runner):
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Brumby-14B-Base")
        assert row["config"] == PUBLISHED and row["source_url"] == SOURCE
    changed = {k for k in PUBLISHED if config[k] != PUBLISHED[k]}
    assert changed == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 40}
    assert set(config["reduced_why"]) == {"num_hidden_layers"}
    assert "six further chips" in config["deployment"]
    assert "state form" in config["deployment"]
    assert set(config["assumed"]) >= {
        "retention_degree", "gate", "qk_norm_and_rope", "state_dtype",
        "gate_bias_init", "weights", "switch_over_seq_len"}
    assert config["retention_degree"] == 2
    assert config["state_dtype"] == "float32"
    assert config["runner"] == "serve_retention"
    assert config["dtype"] == "bfloat16"
    args = config["runner_args"]
    for key in ("logit_margin", "state_margin", "normaliser_margin",
                "check_requests", "long_prompts", "long_prompt_chunks",
                "state_probes"):
        assert args[key] > 0 and args[key + "_why"], key
    assert args["slots"] in (20, 24) and args["prefill_chunk"] == 256
    assert args["long_prompts"] == 2 and args["long_prompt_tokens"] >= 12288
    assert args["max_seq_len"] == 16896 and "pool_blocks" not in args
    assert args["init_memory_range"] == [16.0, 16384.0]
    # the arithmetic of the cut, from the file
    cfg = runner.model_config(config)
    shapes = cfg.param_shapes("bru")
    layer = sum(int(np.prod(s)) for k, s in shapes.items()
                if k.startswith("bru_h0_"))
    assert layer == 2 * 26_214_400 + 2 * 5_242_880 + 40_968 + 10_496 \
        + 3 * 89_128_960 == 330_352_904
    vocab = sum(int(np.prod(shapes[k])) for k in
                ("bru_wte_table", "bru_lm_head_weight"))
    assert vocab == 2 * 151_936 * 5120 == 1_555_824_640
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 6 * layer + vocab + 5120
    assert 7.07e9 < total * 2 < 7.09e9
    # a slot's state a layer: S 33,816,576 B and z 264,192 B, float32
    blk = cfg.block_spec()
    assert blk.op_layers(6, "pool") == 0
    states = blk.state_shapes(6, 5120)
    assert len(states) == 12
    (S, S_dtype), (z, z_dtype) = states[0], states[6]
    assert S == (1, 8, 8256, 128) and z == (1, 8, 8256)
    assert str(np.dtype(S_dtype)) == str(np.dtype(z_dtype)) == "float32"
    one = int(np.prod(S)) * 4 + int(np.prod(z)) * 4
    assert one == 33_816_576 + 264_192 == 34_080_768 \
        == opcount_retention.state_bytes(config)
    # ... which is the K/V of 8,320 positions at 4,096 B a position
    assert one // (2 * 8 * 128 * 2) == 8320
    assert 4.90e9 < 6 * args["slots"] * one <= 4.91e9 or args["slots"] == 20
    mem = config["memory_analysis"]
    for q in (1, 256):
        m = mem[f"slots_{args['slots']}_Q_{q}"]
        assert m["peak_GB"] < 14.6
        # the states in place
        assert m["aliased_GB"] > 6 * args["slots"] * one / 1e9 - 0.01


def test_the_cell_and_its_metrics_are_appended_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="docs-closed", chips=1)
    assert len(cell["why"]) <= 200
    # (later PRs append after it: PR 48 one cell, one configuration and
    # four metrics)
    assert [w["name"] for w in BENCH["workloads"]][:7] == [
        "train-gpt2-medium-s1024", "serve-gpt2-xl-batch-closed",
        "serve-glm47flash-reason-closed", "serve-lfm2-8b-a1b-rag-closed",
        "serve-falcon-h1-34b-chat-closed", "serve-mellum2-12b-code-closed",
        CELL]
    assert [c["name"] for c in BENCH["configs"]][6] == CONFIG
    assert len(BENCH["configs"]) >= 7 and BENCH["run_seconds"] == 51
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("retention_share.serve")
    assert names[at:at + 2] == [
        "retention_share.serve", "retention_scan_roofline.serve"]
    resolved = bench_run.resolve_cell(BENCH, CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in resolved["per_layer"]}
    assert not names & {
        "pallas_kernel_share.serve", "ragged_kernel_share.serve",
        "kv_write_share.serve", "kv_write_chunk_wave_ms",
        "attention_chunk_wave_ms", "gqa_kernel_roofline.serve",
        "decode_wave_device_ms", "ssm_share.serve", "prefill_wave_ms"}
    for old in [w["name"] for w in BENCH["workloads"]][:6]:
        assert not {"retention_share.serve",
                    "retention_scan_roofline.serve"} & {
            m["name"] for m in bench_run.resolve_cell(
                BENCH, old)["per_layer"]}


NEW_METRICS = ["retention_share.serve", "retention_scan_roofline.serve"]
SHARED_METRICS = [
    "decode_wave_ms", "wave_occupancy", "tpot_p95_ms", "wave_host_ms",
    "mixed_step_device_ms", "chunk_wave_device_ms",
    "chunk_wave_time_share.serve", "device_idle_share.serve",
    "sample_share.serve", "lm_head_share.serve",
    "idle_in_host_work_share.serve", "idle_in_inorder_share.serve",
    "idle_in_admit_share.serve", "idle_in_assemble_share.serve",
    "idle_in_dispatch_share.serve", "idle_in_unpack_share.serve",
    "inorder_step_share.serve", "admit_p95_ms"]


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS)
def test_the_cell_reports_the_metric_and_its_files_are_there(name):
    resolved = bench_run.resolve_cell(BENCH, CELL)
    entry = next(m for m in resolved["per_layer"] if m["name"] == name)
    assert entry["moves"] == "serve_tokens_per_s"
    if name in NEW_METRICS:
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["source"], entry["unit"]) == (
            "serving cores", "device_trace", "%")
        assert entry["better"] == ("lower" if "share" in name else "higher")
    else:
        # appended after the cells accepted before it
        assert CELL in entry["workloads"][1:]
    spec = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))
    # (set-up's seven, PR 53, move ``setup_s`` and list every cell)
    assert len([m for m in resolved["per_layer"]
                if m["moves"] == "serve_tokens_per_s"]) \
        == len(NEW_METRICS + SHARED_METRICS)


def test_the_traffic_is_the_issues():
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "docs-closed.json"))
    assert mix.pop("note")
    pool = mix.pop("request_pool")
    assert pool % 24 == 0 and pool >= 24
    assert mix == {
        "kind": "requests", "loop": "closed", "clients": 24, "base_seed": 44,
        "prompt_len": {"median": 2048, "sigma": 0.7, "lo": 256, "hi": 16384,
                       "round_to": 256},
        "output_len": {"median": 64, "sigma": 0.6, "lo": 16, "hi": 256},
        "ramp_seconds": 12.0, "drain_limit_seconds": 60.0,
        "trace_seconds": 6.0}
    serve = bench_run.load_module("runners", "serve")
    assert serve.chunk_buckets(mix, 256) == [256]
    config = bench_run.resolve_cell(BENCH, CELL)["config"]
    args = config["runner_args"]
    assert mix["clients"] == args["slots"] or args["slots"] == 20
    assert mix["prompt_len"]["hi"] + mix["output_len"]["hi"] \
        <= args["max_seq_len"] <= config["max_position_embeddings"]
    assert args["long_prompt_tokens"] + args["long_prompt_answer"] \
        <= args["max_seq_len"]
    # the draw: the same sizes for every seed, rotated
    sizes = loadgen.request_sizes(mix, 0, pool)
    assert sorted(sizes) == sorted(loadgen.request_sizes(mix, 2 ** 31 + 5,
                                                         pool))
    prompts = np.array([p for p, _ in sizes])
    answers = np.array([n for _, n in sizes])
    assert np.all(prompts % 256 == 0) and prompts.min() >= 256
    assert 2200 < prompts.mean() < 3100 and 60 < answers.mean() < 95
    # about 34 prompt rows an answer token; a few prompts lie past the
    # 8,320 positions at which K/V would be the smaller form
    assert 25 < prompts.sum() / answers.sum() < 45
    assert 1 <= (prompts > 8320).sum() <= 12
    assert (prompts >= 256 * args["long_prompt_chunks"]).mean() > 0.1


def test_a_program_without_the_family_stops_at_once(runner, monkeypatch):
    """A program without ``retention_decode`` (the parent of this PR under
    this PR's benchmark files) stops before anything is built."""
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.retention_decode",
                        None)
    with pytest.raises(SystemExit, match="no RetentionConfig"):
        runner.model_config({})


# ------------------------------------------------------------------ #
# operations and bytes, against numbers worked by hand
# ------------------------------------------------------------------ #

BRU = {"num_attention_heads": 40, "num_key_value_heads": 8, "head_dim": 128,
       "num_hidden_layers": 6, "retention_degree": 2}
TINY = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 3, "retention_degree": 2}


def test_one_decode_wave_of_24_slots_is_bound_by_the_states_bytes():
    """24 live slots x 6 layers, a row each: every slot's 34.08 MB state
    in and out once a layer."""
    counters = {"ret_slot_steps": 24 * 6, "ret_rows": 24 * 6,
                "ret_chunk_pairs": 0}
    ops, nbytes = opcount_retention.retention_scan(counters, BRU)
    # a row: (8 + 40) x 2 x 8256 x 129 for update and read-out, 48 x 8256
    # for phi
    assert ops == 144 * (48 * 2 * 8256 * 129 + 48 * 8256) \
        == 144 * 102_638_592
    # a slot step: 2 x 34,080,768 B; a row: q and y 2 x 5120 x 2 B, k and
    # v 2 x 1024 x 2 B, the gate 8 x 4 B = 24,608 B
    assert nbytes == 144 * (68_161_536 + 24_608) == 9_818_804_736
    assert nbytes / 819e9 > 100 * ops / 197e12      # 12.0 ms against 75 us


def test_a_chunk_of_256_rows_adds_its_pairs_and_moves_the_state_once():
    counters = {"ret_slot_steps": 1, "ret_rows": 256,
                "ret_chunk_pairs": 256 * 257 // 2}
    ops, nbytes = opcount_retention.retention_scan(counters, BRU)
    assert ops == 256 * 102_638_592 + 32_896 * 40 * (256 + 258) \
        == 26_951_821_312
    assert nbytes == 68_161_536 + 256 * 24_608 == 74_461_184
    assert ops / 197e12 > nbytes / 819e9            # 137 us against 91 us


def test_the_count_at_the_tiny_size_by_hand():
    """Hidden 64, 4 heads over 2 of 16: D 136.  One slot, one layer: a
    chunk of 8 rows (36 pairs), then a row."""
    assert opcount_retention.state_bytes(TINY) == 2 * 136 * 17 * 4 == 18_496
    counters = {"ret_slot_steps": 2, "ret_rows": 9, "ret_chunk_pairs": 36}
    ops, nbytes = opcount_retention.retention_scan(counters, TINY)
    row = 6 * 2 * 136 * 17 + 6 * 136               # 27,744 + 816
    assert ops == 9 * row + 36 * 4 * (32 + 34) == 266_544
    assert nbytes == 2 * 2 * 18_496 + 9 * (2 * 16 * 12 + 4 * 2) == 77_512


def _trace():
    """A hand-made trace: 40 ms window; phi's fusion 2 ms, the scan's 10
    ms, the state's write 4 ms, the front end 3 ms, the out-projection 1
    ms, the head 4 ms, another operation 6 ms: 30 ms busy."""
    ms = 1e6
    stacks = ["jit(f)/wave_decode/ret_scan/ret_expand",
              "jit(f)/wave_decode/ret_scan", "jit(f)/wave_decode/state_write",
              "jit(f)/wave_decode/ret_qkvg", "jit(f)/wave_decode/ret_out",
              "jit(f)/wave_decode/lm_head", "jit(f)/wave_decode/mlp"]
    at, ops = 1.0, []
    for i, dur in enumerate((2, 10, 4, 3, 1, 4, 6)):
        ops.append([f"%fusion.{i + 1} = f32[] fusion()", at * ms, dur * ms])
        at += dur + 1
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3",
                    "events": [["bench.engine_step", 0.0, 40 * ms]]}]}],
        "op_scopes": {"table": stacks, "index": list(range(7))}}


class _H:
    peak = PEAK
    config = BRU

    def __init__(self, config=None):
        self.lines = []
        if config is not None:
            self.config = config

    def log(self, **record):
        self.lines.append(record)


def metric_args(name):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", name + ".json"))["args"]


def test_scan_roofline_and_share_on_a_hand_made_trace():
    reader = bench_run.load_module("readers", "kernel_roofline_retention")
    counters = {"ret_slot_steps": 144, "ret_rows": 144, "ret_chunk_pairs": 0}
    h = _H()
    data = {"trace": _trace(), "harness": h,
            "counters": {"traced": counters}}
    args = metric_args("retention_scan_roofline.serve")
    # 9,818,804,736 B / 819e9 = 11.99 ms over 2 + 10 + 4 ms of the work
    got = reader.read(data, **args)
    assert got == pytest.approx(100 * (9_818_804_736 / 819e9) / 16e-3)
    assert 0 < got < 100
    assert h.lines[-1]["bound"] == "bytes"
    assert h.lines[-1]["kernel_s"] == pytest.approx(16e-3)
    # the parent (no counters), another configuration, no such scope
    assert reader.read({"trace": _trace(), "harness": h}, **args) is None
    assert reader.read(dict(data, counters={"traced": {}}), **args) is None
    assert reader.read(dict(data, counters={"traced": {
        "ret_slot_steps": None, "ret_rows": None}}), **args) is None
    assert reader.read(dict(data, harness=_H({"n_layer": 48})),
                       **args) is None
    assert reader.read(data, model="retention_scan",
                       scopes=["nothing"]) is None
    assert h.lines[-1]["line"] == "metric_missing"
    # the layer's share by the accepted reader: 2 + 10 + 4 + 3 + 1 of 30
    share = bench_run.load_module("readers", "scope_or_op_share")
    assert share.read({"trace": _trace(), "harness": _H()},
                      **metric_args("retention_share.serve")) \
        == pytest.approx(100 * 20 / 30)
    head = bench_run.load_module("readers", "scope_share")
    assert head.read({"trace": _trace(), "harness": _H()},
                     **metric_args("lm_head_share.serve")) \
        == pytest.approx(100 * 4 / 30)


# ------------------------------------------------------------------ #
# the benchmark's reference is the program's equations
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("length,n", [(16, 16), (48, 41)])
def test_the_benchmarks_reference_is_the_programs_equations(small_blocks,
                                                            length, n):
    """``benchmarks/reference_brumby.py`` (blocked, one compiled function
    for every length) equals ``hetu_tpu/models/reference_retention.py``
    (one whole sequence) on seeded weights, and its probes read what the
    attention form sums."""
    from hetu_tpu.models import reference_retention as program
    from hetu_tpu.models import retention_decode as rd
    config = dict(PUBLISHED, **SMALL)
    cfg = rd.RetentionConfig.from_hf(config)
    params = rd.init_retention_params(cfg, "bru", seed=7,
                                      memory_range=(4.0, 64.0))
    tokens = np.random.default_rng(length).integers(0, 256, length)
    padded = np.where(np.arange(length) < n, tokens, 0)
    rows = np.arange(n - 8, n)
    probes = np.random.default_rng(3).normal(size=(4, 2, 16)).astype(
        np.float32)
    got, (num, den) = reference_brumby.forward(
        params, config, padded, rows, n=n, probes=probes)
    want = np.asarray(program.forward(params, cfg, tokens[:n], "bru"))
    np.testing.assert_allclose(got, want[rows], atol=2e-4 * want.std())
    assert num.shape == (3, 4, 2, 16) and den.shape == (3, 4, 2)
    assert np.all(den > 0)
    for control in reference_brumby.CONTROLS:
        other, _ = reference_brumby.forward(params, config, padded, rows,
                                            n=n, control=control)
        assert np.abs(other - want[rows]).max() > 20 * 2e-4 * want.std()
    with pytest.raises(ValueError, match="control"):
        reference_brumby.forward(params, config, padded, rows, control="x")
