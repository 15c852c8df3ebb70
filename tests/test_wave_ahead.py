"""One wave ahead (ISSUE 38): a full engine launches wave t+1 before it
lands wave t, and nothing a request can observe changes but the time.

Two oracles.  ALONE: every request served by itself in an engine with
free slots (such an engine never runs ahead) must give the tokens the
full engine gave it.  IN ORDER: the same engine with the rule switched
off in the test, driven by the same loop, must give every request's
first chunk the same wave number.
"""

import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.models import gpt_decode as gd
from hetu_tpu.models import ssm_decode as sd
from hetu_tpu.models.moe_decode import (
    HybridMoEConfig, LatentMoEConfig, MoEDecodeConfig,
    init_hybrid_moe_params, init_latent_moe_params, init_moe_params)
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.telemetry.trace import main as trace_main

import test_hybrid_moe
import test_latent_moe
import test_ssm_hybrid
from test_serving import _rand_gpt

pytestmark = pytest.mark.smoke


# --------------------------------------------------------------------- #
# small engines of every kind the wave runs
# --------------------------------------------------------------------- #

def _gpt():
    return _rand_gpt(name="wa", V=61, S=64)


def _latent():
    cfg = LatentMoEConfig(**test_latent_moe.SMALL)
    return init_latent_moe_params(cfg, seed=3, scale=0.2), cfg


def _hybrid():
    cfg = HybridMoEConfig.from_hf(test_hybrid_moe.SMALL)
    return init_hybrid_moe_params(cfg, seed=3, scale=0.2), cfg


def _ssm():
    cfg = sd.SSMHybridConfig.from_hf(test_ssm_hybrid.SMALL)
    return sd.init_ssm_hybrid_params(cfg, test_ssm_hybrid.NAME, seed=3), cfg


def _capacity_moe():
    cfg = MoEDecodeConfig(vocab_size=61, hidden_size=16, num_hidden_layers=2,
                          num_attention_heads=2, max_position_embeddings=64,
                          batch_size=1, seq_len=64, dropout_rate=0.0,
                          num_experts=4, top_k=2, capacity_factor=4.0)
    return init_moe_params(cfg, name="moe", seed=0), cfg


PAGED = dict(kv_block=4, prefill_chunk=8, max_seq_len=64,
             prefix_share=False)
KINDS = {
    "gpt2-paged": (_gpt, dict(PAGED, fast_path=False)),
    "gpt2-paged-kernel": (_gpt, dict(PAGED, fast_path=True)),
    "gpt2-int8": (_gpt, dict(PAGED, fast_path=False, kv_quant="int8")),
    "gpt2-shared-prefix": (_gpt, dict(PAGED, fast_path=False,
                                      prefix_share=True)),
    "latent-routed": (_latent, dict(PAGED, fast_path=False)),
    "latent-routed-kernel": (_latent, dict(PAGED, fast_path=True)),
    "conv-gqa-routed": (_hybrid, dict(PAGED, fast_path=False)),
    "attention-ssm": (_ssm, dict(PAGED, fast_path=False, prefill_chunk=16)),
}
_MODELS = {}


def build(kind, slots=4, **kw):
    make, base = KINDS[kind] if isinstance(kind, str) else kind
    if make not in _MODELS:
        _MODELS[make] = make()
    params, cfg = _MODELS[make]
    return ServingEngine(params, cfg, slots=slots, queue_limit=64,
                         **dict(base, **kw))


def requests(vocab, sizes, seed=0, shared=0, **kw):
    """``shared``: that many leading prompt tokens are the same in every
    request (a system prompt)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, vocab, shared).tolist()
    return [Request(head + rng.integers(1, vocab, n - shared).tolist(), m,
                    request_id=f"r{i}", seed=i, **kw)
            for i, (n, m) in enumerate(sizes)]


def again(req, **kw):
    """The same request, fresh (an engine stamps the object)."""
    return Request(req.prompt, req.max_new_tokens, eos_id=req.eos_id,
                   seed=req.seed, request_id=req.request_id,
                   temperature=req.temperature, top_k=req.top_k, **kw)


def closed_loop(eng, reqs, clients):
    """``clients`` requests at a time; a completion is answered with the
    next request at once, before the next step (the benchmark's loop)."""
    todo = [again(r) for r in reqs]
    out = {}
    for _ in range(min(clients, len(todo))):
        eng.submit(todo.pop(0))
    while eng.pending:
        for res in eng.step():
            out[res.request_id] = res
            if todo:
                eng.submit(todo.pop(0))
    return out


def alone(kind, reqs, **kw):
    """Each request by itself in an engine with free slots."""
    eng = build(kind, **kw)
    out = {}
    for r in reqs:
        out.update(eng.run([again(r)]))
    assert eng.metrics.snapshot()["waves_ahead"] == 0
    return out


def in_order(eng):
    """The engine with the rule switched off: the parent's order."""
    eng._may_run_ahead = lambda flying: False
    return eng


def first_wave(eng):
    """request id -> the number of the first wave it rode."""
    first = {}
    for e in eng.metrics.events:
        if e["event"] == "serve_step":
            for rid in e["requests"]:
                first.setdefault(rid, e["step"])
    return first


# answers by count, longer than a prompt's chunks last, on prompts of one
# to three chunks: arrivals join mid-decode, decoders ride chunk waves
SIZES = [(5, 9), (17, 12), (8, 7), (11, 14), (3, 5), (20, 10), (9, 6),
         (14, 8), (6, 11), (12, 4)]


# --------------------------------------------------------------------- #
# token for token
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_full_engine_emits_what_each_request_alone_emits(kind):
    eng = build(kind)
    vocab = eng.params[f"{eng._name}_wte_table"].shape[0]
    shared = 9 if kind == "gpt2-shared-prefix" else 0
    sizes = [(n + shared, m) for n, m in SIZES]
    reqs = requests(vocab, sizes, seed=1, shared=shared)
    for r in reqs[1::2]:
        # a small random model's greedy answer soon repeats one token:
        # every other request samples, so that a token or a key handed
        # over wrongly shows
        r.temperature = 1.0
    got = closed_loop(eng, reqs, clients=4)
    want = alone(kind, reqs)
    assert sorted(got) == sorted(want)
    for rid, res in got.items():
        assert res.tokens.tolist() == want[rid].tokens.tolist(), rid
        assert res.finish_reason == "length"
    snap = eng.metrics.snapshot()
    assert snap["waves_ahead"] > snap["steps"] // 4
    assert snap["rows_dead_ahead"] == 0
    assert eng._flying is None and not eng.pending
    if shared:
        assert eng.kv.prefix_hits > 0


def test_a_greedy_gpt2_matches_the_offline_decode():
    eng = build("gpt2-paged")
    params, cfg = _MODELS[_gpt]
    reqs = requests(61, SIZES, seed=2)
    got = closed_loop(eng, reqs, clients=4)
    assert eng.metrics.snapshot()["waves_ahead"] > 0
    for r in reqs:
        want = gd.generate_fast(params, cfg, [r.prompt], r.max_new_tokens)
        assert got[r.request_id].tokens.tolist() == \
            [int(t) for t in np.asarray(want)[0]]


def test_sampled_requests_keep_their_own_rng_stream():
    """The key after a sample stays on the device with the token."""
    reqs = requests(61, SIZES[:6], seed=3, temperature=0.9, top_k=7)
    eng = build("gpt2-paged")
    got = closed_loop(eng, reqs, clients=4)
    assert eng.metrics.snapshot()["waves_ahead"] > 0
    want = alone("gpt2-paged", reqs)
    for rid, res in got.items():
        assert res.tokens.tolist() == want[rid].tokens.tolist(), rid


def test_capacity_routed_engine_accounts_at_landing():
    """A ``MoESpec``'s capacity follows the rows of its wave, so its
    tokens follow the company: the oracle is the same loop in order.
    Its statistics are fetched when the wave lands."""
    kind = (_capacity_moe, dict(PAGED, fast_path=False))
    reqs = requests(61, SIZES, seed=4)
    eng, twin = build(kind), in_order(build(kind))
    got = closed_loop(eng, reqs, clients=4)
    want = closed_loop(twin, reqs, clients=4)
    assert eng.metrics.snapshot()["waves_ahead"] > 0
    assert twin.metrics.snapshot()["waves_ahead"] == 0
    for rid, res in got.items():
        assert res.tokens.tolist() == want[rid].tokens.tolist(), rid
    assert eng.moe_tokens == twin.moe_tokens > 0
    assert eng.expert_load.tolist() == twin.expert_load.tolist()
    steps = [e for e in eng.metrics.events if e["event"] == "serve_step"]
    assert all(e["moe_routed"] + e["moe_dropped"]
               == e["moe_tokens"] * e["moe_k"] * e["moe_layers"]
               for e in steps)


# --------------------------------------------------------------------- #
# the rule
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", ["gpt2-paged", "conv-gqa-routed"])
def test_every_first_chunk_rides_the_parents_wave(kind):
    """A closed loop that answers every completion at once: each
    request's first chunk is in the wave an engine that never runs
    ahead gives it, and each wave holds the same requests."""
    eng, twin = build(kind), in_order(build(kind))
    vocab = eng.params[f"{eng._name}_wte_table"].shape[0]
    reqs = requests(vocab, SIZES + SIZES[:4], seed=5)
    got = closed_loop(eng, reqs, clients=4)
    want = closed_loop(twin, reqs, clients=4)
    assert first_wave(eng) == first_wave(twin)
    assert len(first_wave(eng)) == len(reqs)
    assert eng.steps == twin.steps
    snap = eng.metrics.snapshot()
    assert snap["waves_ahead"] > 0 == twin.metrics.snapshot()["waves_ahead"]
    for rid, res in got.items():
        assert res.tokens.tolist() == want[rid].tokens.tolist(), rid


def test_an_engine_with_a_free_slot_stays_in_order():
    eng = build("gpt2-paged")
    closed_loop(eng, requests(61, SIZES, seed=6), clients=3)
    snap = eng.metrics.snapshot()
    assert snap["steps"] > 20 and snap["waves_ahead"] == 0


def test_a_result_comes_one_step_after_its_last_wave_was_launched():
    """By count: the step that lands the last wave launches nothing and
    hands the Result back, so the caller refills the slot first."""
    eng = build("gpt2-paged", slots=2)
    a, b = requests(61, [(4, 2), (4, 6)], seed=7)
    eng.submit(a)
    eng.submit(b)
    assert eng.step() == [] and eng._flying.id == 1      # prompts fly
    assert eng.step() == [] and eng._flying.id == 2      # ahead: full
    assert eng._flying.ahead and eng._flying.ends        # a's second token
    launched = eng._launched
    [res] = eng.step()               # lands 2 first: a leaves by count
    assert res.request_id == "r0" and res.n_generated == 2
    assert eng._flying is None and eng._launched == launched
    assert eng.kv.free_slots == 1 and eng.pending == 1
    out = eng.run()
    assert out["r1"].n_generated == 6 and eng._flying is None


def test_a_speculative_engine_never_runs_ahead():
    eng = build("gpt2-paged", spec=2)
    got = closed_loop(eng, requests(61, SIZES, seed=8), clients=4)
    assert len(got) == len(SIZES) and eng.spec_emitted > eng.spec_waves
    assert eng.metrics.snapshot()["waves_ahead"] == 0
    want = alone("gpt2-paged", requests(61, SIZES, seed=8))
    for rid, res in got.items():
        assert res.tokens.tolist() == want[rid].tokens.tolist(), rid


# --------------------------------------------------------------------- #
# an ending nobody could foresee
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", ["gpt2-shared-prefix", "attention-ssm"])
def test_an_eos_ending_leaves_one_dead_row_and_harms_nobody(kind):
    """A request that ends on ``eos_id`` has a row in the wave already
    launched: the row is dropped and counted, its write touches no other
    request and no registered prefix block, and the successor (which
    shares the prompt's prefix where the engine shares prefixes) is
    admitted one wave late."""
    telemetry.reset()
    eng = build(kind)
    vocab = eng.params[f"{eng._name}_wte_table"].shape[0]
    sizes = [(13, 20), (9, 20), (17, 20), (6, 20)]
    # sampled: a small random model's greedy answer repeats one token
    reqs = requests(vocab, sizes, seed=9, temperature=1.0)
    free = alone(kind, reqs)
    # r1 ends where its 4th answer token comes round, if nowhere before
    ans = free["r1"].tokens.tolist()[9:]
    k = next(i for i in range(3, 20) if ans[i] not in ans[:i])
    reqs[1].eos_id = ans[k]
    # the successor continues r1's prompt: it attaches r1's registered
    # blocks, the partial tail block r1 decoded into among them
    tail = Request(reqs[1].prompt + [3, 4, 5, 6, 7], 6, request_id="tail",
                   temperature=1.0, seed=5)
    [want_tail] = alone(kind, [tail]).values()
    hooked = []
    eng.retire_hook = lambda req, slot: hooked.append(
        (req.request_id, int(eng.kv.lengths[slot]), len(eng._gen[slot])))
    got = closed_loop(eng, reqs + [tail], clients=4)
    assert got["r1"].finish_reason == "eos"
    assert got["r1"].tokens.tolist() == free["r1"].tokens.tolist()[:9 + k + 1]
    for rid in ("r0", "r2", "r3"):
        assert got[rid].tokens.tolist() == free[rid].tokens.tolist(), rid
    assert got["tail"].tokens.tolist() == want_tail.tokens.tolist()
    snap = eng.metrics.snapshot()
    assert snap["rows_dead_ahead"] == 1 and snap["waves_ahead"] > 0
    assert telemetry.snapshot()["counters"][
        "serve.wave.rows_dead_ahead"] == 1
    # the hook saw the slot as long as what had LANDED: every position
    # but the last token's
    assert ("r1", 9 + k, k + 1) in hooked
    # one wave late: the wave run ahead had left without it
    twin = in_order(build(kind))
    closed_loop(twin, reqs + [tail], clients=4)
    assert first_wave(eng)["tail"] == first_wave(twin)["tail"] + 1
    if kind == "gpt2-shared-prefix":
        assert got["tail"].prompt_len == 14 and eng.kv.prefix_hits >= 1


def test_everybody_ending_on_eos_leaves_nothing_in_flight():
    eng = build("gpt2-paged", slots=1)
    [probe] = alone("gpt2-paged", requests(61, [(5, 12)], seed=10,
                                            temperature=1.0)).values()
    ans = probe.tokens.tolist()[5:]
    k = next(i for i in range(2, 12) if ans[i] not in ans[:i])
    [req] = requests(61, [(5, 12)], seed=10, temperature=1.0, eos_id=ans[k])
    [res] = eng.run([req]).values()
    assert res.finish_reason == "eos" and res.n_generated == k + 1
    assert eng._flying is None and not eng.pending
    snap = eng.metrics.snapshot()
    assert snap["rows_dead_ahead"] == 1
    assert snap["steps"] == k + 2        # the prompt, k tokens, the dead wave


# --------------------------------------------------------------------- #
# who else meets a wave in flight
# --------------------------------------------------------------------- #

def test_swap_params_lands_the_wave_in_flight():
    eng = build("gpt2-paged", slots=2)
    params, _ = _MODELS[_gpt]
    eng.set_weight_version(1)
    a, b = requests(61, [(4, 2), (4, 9)], seed=11)
    eng.submit(a)
    eng.submit(b)
    eng.step()
    eng.step()                        # wave 2 flies: a's last token
    assert eng._flying is not None and eng.steps == 1
    eng.swap_params(params, version=2)
    assert eng._flying is None and eng.steps == 2
    assert eng.pending == 2           # b, and a's Result held for step()
    [res] = eng.step()
    assert res.request_id == "r0" and res.weight_version == 1
    out = eng.run()
    assert out["r1"].n_generated == 9 and not eng.pending


def test_retire_hook_sees_a_landed_slot():
    eng = build("gpt2-paged")
    seen = []
    eng.retire_hook = lambda req, slot: seen.append(
        (int(eng.kv.lengths[slot]), len(req.prompt) + len(eng._gen[slot]) - 1,
         eng._flying))
    closed_loop(eng, requests(61, SIZES, seed=12), clients=4)
    assert len(seen) == len(SIZES)
    # by count the rule lands first: nothing flies at any retirement
    assert all(held == landed and flying is None
               for held, landed, flying in seen)


def test_hetu_trace_check_passes_on_a_run_that_ran_ahead(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    log = str(tmp_path / "telemetry.jsonl")
    monkeypatch.setenv("HETU_TELEMETRY_LOG", log)
    telemetry.reset()
    serve_log = str(tmp_path / "serve.jsonl")
    eng = build("gpt2-paged", log_path=serve_log)
    closed_loop(eng, requests(61, SIZES, seed=13), clients=4)
    counters = telemetry.snapshot()["counters"]
    snap = eng.metrics.snapshot()
    assert counters["serve.wave.ahead"] == snap["waves_ahead"] > 0
    assert "serve.lifecycle_residue" not in counters
    assert trace_main([log, serve_log, "--check"]) == 0
    telemetry.reset()


# --------------------------------------------------------------------- #
# the counters and the clock
# --------------------------------------------------------------------- #

def test_counters_in_snapshot_and_since_a_mark():
    eng = build("gpt2-paged")
    closed_loop(eng, requests(61, SIZES[:5], seed=14), clients=4)
    whole = eng.metrics.snapshot()
    assert 0 < whole["waves_ahead"] < whole["steps"]
    mark = eng.metrics.mark()
    assert eng.metrics.snapshot(since=mark)["waves_ahead"] == 0
    closed_loop(eng, requests(61, SIZES[:5], seed=15), clients=4)
    tail, after = eng.metrics.snapshot(since=mark), eng.metrics.snapshot()
    assert tail["waves_ahead"] == after["waves_ahead"] - whole["waves_ahead"]
    assert tail["waves_ahead"] > 0 and tail["rows_dead_ahead"] == 0
    landed = [e for e in eng.metrics.events if e["event"] == "serve_step"]
    assert len(landed) == after["steps"]


def test_dt_of_a_wave_run_ahead_is_its_period():
    """``dt_s`` is what a wave ADDED: landing to landing where waves
    follow one another, so the waves' times never sum past the wall."""
    eng = build("gpt2-paged")
    rows = []
    record = eng.metrics.record_step

    def spy(**kw):
        rows.append((kw["dt_s"], kw["end_perf"], eng._flying is not None))
        return record(**kw)
    eng.metrics.record_step = spy
    t_start = time.perf_counter()
    closed_loop(eng, requests(61, SIZES, seed=16), clients=4)
    wall = time.perf_counter() - t_start
    assert sum(dt for dt, _, _ in rows) <= wall
    assert all(dt > 0 for dt, _, _ in rows)
    n_ahead = 0
    for (_, before, _), (dt, end, _) in zip(rows, rows[1:]):
        assert dt <= end - before + 1e-9
        # a wave that lands while its successor flies was itself
        # launched no later than the landing before it
        n_ahead += dt == pytest.approx(end - before, abs=1e-9)
    assert n_ahead >= eng.metrics.snapshot()["waves_ahead"] > 0
    assert eng.metrics.snapshot()["decode_total_s"] <= wall


def test_lifecycle_wall_is_not_counted_twice():
    """A prompt of three chunks whose waves run ahead: its prefill
    credit stays within its prefill wall, with no residue."""
    eng = build("gpt2-paged")
    closed_loop(eng, requests(61, [(24, 6)] * 6, seed=17), clients=4)
    assert eng.metrics.snapshot()["waves_ahead"] > 0
    assert not [e for e in eng.metrics.events
                if e["event"] == "serve_lifecycle_residue"]
    spans = [e for e in eng.metrics.events
             if e["event"] == "req_span" and e["phase"] == "prefill"]
    assert len(spans) == 6
    for e in spans:
        assert e["compute_ms"] <= e["ms"] + 1e-6 and e["dispatches"] == 3


# --------------------------------------------------------------------- #
# no program after the warm-up
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", ["gpt2-paged", "conv-gqa-routed",
                                  "attention-ssm"])
def test_a_burst_after_a_lone_warm_up_builds_no_program(kind):
    """The benchmark's warm-up serves one request alone a bucket, two
    tokens each, so it never runs ahead; the burst that fills the engine
    does, through the programs the warm-up built."""
    eng = build(kind, prefill_chunk=16)
    vocab = eng.params[f"{eng._name}_wte_table"].shape[0]
    for n in (8, 16):
        eng.run([Request(((np.arange(n) + n) % vocab).tolist(), 2)])
    assert eng.metrics.snapshot()["waves_ahead"] == 0
    rng = np.random.default_rng(18)
    reqs = [Request(rng.integers(1, vocab, 8 * int(rng.integers(1, 5))
                                 ).tolist(), int(rng.integers(3, 9)),
                    request_id=f"b{i}") for i in range(12)]
    built = telemetry.counter("compile.programs")    # the program's watch
    before = built.get()
    out = closed_loop(eng, reqs, clients=4)
    assert len(out) == 12 and built.get() == before
    assert eng.metrics.snapshot()["waves_ahead"] > 0
