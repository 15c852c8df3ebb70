"""Request-lifecycle observability (ISSUE 7 tentpole).

The acceptance spine: a trace-replay run (seeded mixed-length requests
through the continuous-batching engine) exports a Perfetto trace where
each request has its OWN track showing its queue/kv_alloc/prefill/decode lifecycle with
flow arrows into the engine's fused-step wave spans;
``explain_tail()`` names the component that dominates p99 TTFT; a
deliberately-undersized SLO flips ``engine.health()`` to "breach" and
emits ``slo_violation`` events; and the flight recorder dumps
contract-valid JSONL on engine exceptions and QueueFull storms (the
chaos kill/reset dump lives in tests/test_faults.py, next to the rest
of the HETU_CHAOS suite).

Satellites pinned here too: the one interpolating percentile helper
(registry Histogram and ServingMetrics now agree, p95 included),
bounded ``ServingMetrics.events``, gauge records exporting as Chrome
"C" counter tracks, the ``hetu_trace --check`` span-balance rule, and
the ``hetu_top`` dashboard.

All CPU-harness, all smoke-tier.
"""

import json
import os

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu import telemetry
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import (
    COMPONENTS, QueueFull, Request, ServingEngine, ServingMetrics, SLO,
    SLOMonitor,
)
from hetu_tpu.telemetry import top
from hetu_tpu.telemetry.flight import RECORDER
from hetu_tpu.telemetry.metrics import Histogram, percentile
from hetu_tpu.telemetry.trace import (
    check_span_balance, check_wave_pairing, main as trace_main, read_events,
)

pytestmark = pytest.mark.smoke


def _rand_gpt(name="rt", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    """Deterministic random params in generate_fast's naming contract."""
    rng = np.random.RandomState(seed)
    hd = H * Dh
    p = {f"{name}_wte_table": rng.randn(V, hd) * 0.05,
         f"{name}_wpe": rng.randn(S, hd) * 0.05,
         f"{name}_ln_f_scale": np.ones(hd),
         f"{name}_ln_f_bias": np.zeros(hd)}
    for i in range(L):
        us = f"{name}_h{i}"
        for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                       ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                       ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
            p[f"{us}_{w}_weight"] = rng.randn(*shp) * 0.05
            p[f"{us}_{w}_bias"] = np.zeros(shp[1])
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = np.ones(hd)
            p[f"{us}_{ln}_bias"] = np.zeros(hd)
    cfg = GPTConfig(vocab_size=V, hidden_size=hd, num_hidden_layers=L,
                    num_attention_heads=H, max_position_embeddings=S,
                    batch_size=1, seq_len=S, dropout_rate=0.0)
    return p, cfg


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    yield
    telemetry.reset()


def _mixed_trace(n_req=10, seed=1234, vocab=61):
    """Seeded mixed-length trace: mostly short requests, a longer
    straggler every 5th."""
    rng = np.random.RandomState(seed)
    trace = []
    for i in range(n_req):
        P = int(rng.randint(2, 7))
        gen = 12 if i % 5 == 4 else int(rng.randint(2, 7))
        trace.append(([int(t) for t in rng.randint(0, vocab, P)], gen))
    return trace


@pytest.fixture(scope="module")
def replay(model, tmp_path_factory):
    """ONE trace-replay run with the merged telemetry log configured;
    read-only tests (export / tail / balance / top) share it."""
    d = tmp_path_factory.mktemp("reqtrace")
    log = str(d / "merged.jsonl")
    old = os.environ.get("HETU_TELEMETRY_LOG")
    os.environ["HETU_TELEMETRY_LOG"] = log
    os.environ.setdefault("HETU_TELEMETRY", "1")
    telemetry.reset()
    try:
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, queue_limit=64,
                            fast_path=False)
        reqs = [Request(prompt=pr, max_new_tokens=g)
                for pr, g in _mixed_trace()]
        res = eng.run(reqs)
    finally:
        if old is None:
            os.environ.pop("HETU_TELEMETRY_LOG", None)
        else:
            os.environ["HETU_TELEMETRY_LOG"] = old
    assert len(res) == 10
    return {"eng": eng, "results": res, "log": log, "dir": str(d)}


def _export(log, out):
    rc = trace_main([log, "--export", str(out)])
    assert rc == 0
    with open(out) as f:
        return json.load(f)


def _track(trace, name):
    for e in trace["traceEvents"]:
        if e.get("ph") == "M" and e["args"].get("name") == name:
            return e["pid"], e["tid"]
    return None


# --------------------------------------------------------------------- #
# tentpole (a): lifecycle tracing -> per-request Perfetto tracks
# --------------------------------------------------------------------- #

class TestLifecycleTrace:
    def test_every_request_gets_a_track(self, replay, tmp_path):
        trace = _export(replay["log"], tmp_path / "t.json")
        for rid in replay["results"]:
            assert _track(trace, f"req:{rid}") is not None, rid

    def test_request_track_shows_lifecycle_phases(self, replay,
                                                  tmp_path):
        """Acceptance: an individual request's track reads queue ->
        kv_alloc -> prefill -> decode, start-ordered."""
        trace = _export(replay["log"], tmp_path / "t.json")
        rid = next(r for r, res in replay["results"].items()
                   if res.n_generated > 1)
        pid, tid = _track(trace, f"req:{rid}")
        xs = sorted((e for e in trace["traceEvents"]
                     if e.get("ph") == "X" and e["pid"] == pid
                     and e["tid"] == tid), key=lambda e: e["ts"])
        names = [e["name"] for e in xs]
        assert set(names) == {"queue", "kv_alloc", "prefill", "decode"}
        order = {n: i for i, n in enumerate(names)}
        assert order["queue"] < order["prefill"] < order["decode"]
        for e in xs:
            assert e["dur"] >= 0

    def test_flow_arrows_into_wave_spans(self, replay, tmp_path):
        """The decode span flows (s -> t* -> f) into the engine's
        fused-step wave spans the request actually rode."""
        trace = _export(replay["log"], tmp_path / "t.json")
        evs = trace["traceEvents"]
        waves = [e for e in evs
                 if e.get("ph") == "X" and e["name"] == "serve.decode"]
        assert waves
        rid = next(r for r, res in replay["results"].items()
                   if res.n_generated > 2)
        flows = sorted((e for e in evs if e.get("cat") == "req"
                        and e.get("id") == str(rid)),
                       key=lambda e: e["ts"])
        assert flows, "no flow events for the request"
        assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
        steps = [e for e in flows if e["ph"] == "t"]
        assert steps
        # a step is tied to its wave by the wave's number, not by where
        # two host stamps of one loaded iteration happened to fall: the
        # exporter clamps a step into the request's decode span, which
        # can move it out of the wave's slice by that overhead
        by_step = {w["args"]["step"]: w for w in waves}
        rode = {r["step"] for r in read_events([replay["log"]])[0]
                if r["event"] == "serve_step"
                and rid in r.get("requests", ())}
        assert {s["args"]["wave"] for s in steps} == rode
        lo, hi = flows[0]["ts"], flows[-1]["ts"]
        for s in steps:
            w = by_step[s["args"]["wave"]]
            assert (w["pid"], w["tid"]) == (s["pid"], s["tid"])
            assert lo <= s["ts"] <= hi
            assert (w["ts"] <= s["ts"] <= w["ts"] + w["dur"]
                    or s["ts"] in (lo, hi)), "flow step outside its wave"

    def test_all_records_contract_valid(self, replay):
        events, bad = read_events([replay["log"]])
        assert bad == 0 and events
        for rec in events:
            assert telemetry.validate_record(rec) == [], rec
        kinds = {r["event"] for r in events}
        assert {"serve_submit", "serve_admit", "req_span", "req_retire",
                "serve_finish", "gauge"} <= kinds

    def test_req_retire_carries_breakdown(self, replay):
        events, _ = read_events([replay["log"]])
        retires = [r for r in events if r["event"] == "req_retire"]
        assert len(retires) == len(replay["results"])
        for r in retires:
            for c in COMPONENTS:
                assert isinstance(r.get(c), (int, float)), (c, r)
            assert r["ttft_ms"] > 0


# --------------------------------------------------------------------- #
# tentpole (b): tail-latency decomposition
# --------------------------------------------------------------------- #

class TestTailDecomposition:
    def test_components_in_snapshot(self, replay):
        snap = replay["eng"].metrics.snapshot()
        comps = snap["components"]
        assert set(comps) == set(COMPONENTS)
        for c, agg in comps.items():
            assert set(agg) == {"p50_ms", "p95_ms", "p99_ms", "mean_ms"}
            assert agg["p50_ms"] <= agg["p95_ms"] <= agg["p99_ms"]
        assert snap["ttft_p95_s"] is not None
        assert snap["tpot_p50_s"] is not None and snap["tpot_p50_s"] > 0

    def test_explain_tail_names_dominant_component(self, replay):
        """Acceptance: explain_tail() attributes p99 TTFT to a NAMED
        component."""
        et = replay["eng"].metrics.explain_tail()
        assert et is not None
        assert et["dominant_component"] in COMPONENTS
        assert et["dominant_component"] != "decode_ms"   # TTFT only
        assert 0 < et["dominant_share"] <= 1.0
        assert et["n_tail"] >= 1
        assert et["dominant_component"].replace("_ms", "") \
            in et["summary"]
        assert et["ttft_p_ms"] >= et["ttft_p50_ms"]

    def test_explain_tail_empty_engine(self, model):
        m = ServingMetrics(log_path=None)
        assert m.explain_tail() is None

    def test_paged_requeue_component(self, model):
        """A paged pool that fits ONE request at a time: the second
        request's wait shows up as requeue_ms, not queue_ms."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, kv_block=4,
                            pool_blocks=4, prefix_share=False,
                            fast_path=False)
        reqs = [Request(prompt=[1, 2, 3], max_new_tokens=8),
                Request(prompt=[4, 5, 6], max_new_tokens=8)]
        res = eng.run(reqs)
        assert len(res) == 2
        bd = {b["request"]: b for b in eng.metrics.breakdowns}
        assert bd[reqs[1].request_id]["requeue_ms"] > 0
        assert bd[reqs[0].request_id]["requeue_ms"] == 0

    def test_chunked_prefill_stall_component(self, model):
        """Chunked prefill interleaves with decode waves: the prefill
        phase records >1 dispatch and a non-negative stall share."""
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, kv_block=4,
                            prefill_chunk=4, fast_path=False)
        long_req = Request(prompt=list(range(1, 13)), max_new_tokens=3)
        res = eng.run([Request(prompt=[7, 8], max_new_tokens=10),
                       long_req])
        assert len(res) == 2
        spans = [e for e in eng.metrics.events
                 if e["event"] == "req_span"
                 and e["request"] == long_req.request_id
                 and e["phase"] == "prefill"]
        assert len(spans) == 1
        assert spans[0]["dispatches"] >= 2        # chunked
        assert spans[0]["stall_ms"] >= 0
        bd = {b["request"]: b for b in eng.metrics.breakdowns}
        assert bd[long_req.request_id]["chunk_stall_ms"] >= 0
        assert bd[long_req.request_id]["prefill_ms"] > 0


# --------------------------------------------------------------------- #
# tentpole (c): SLO classes + engine health()
# --------------------------------------------------------------------- #

class TestSLOHealth:
    def _run(self, model, **kw):
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, fast_path=False, **kw)
        eng.run([Request(prompt=[1 + i, 2 + i], max_new_tokens=4)
                 for i in range(4)])
        return eng

    def test_undersized_slo_breaches(self, model):
        """Acceptance: a deliberately-undersized SLO flips health() to
        breach and emits slo_violation events."""
        mon = SLOMonitor([SLO("ttft", "latency", 1e-6)])
        eng = self._run(model, slo=mon)
        assert eng.health() == "breach"
        viol = [e for e in eng.metrics.events
                if e["event"] == "slo_violation"]
        assert len(viol) == 4
        for v in viol:
            assert v["slo"] == "ttft" and v["value"] > v["target"]
            assert telemetry.validate_record(v) == []
        trans = [e for e in eng.metrics.events
                 if e["event"] == "slo_health"]
        assert trans and trans[-1]["state"] == "breach"
        snap = mon.snapshot()
        assert snap["slos"]["ttft"]["burn_rate"] >= 2.0

    def test_generous_slo_stays_ok(self, model):
        eng = self._run(model, slo=[SLO("ttft", "latency", 1e9)])
        assert eng.health() == "ok"
        assert not [e for e in eng.metrics.events
                    if e["event"] == "slo_violation"]

    def test_throughput_slo(self, model):
        """Per-stream decode rate: an impossible tok/s target breaches,
        a trivial one passes."""
        bad = self._run(model, slo=[SLO("tps", "throughput", 1e12)])
        assert bad.health() == "breach"
        ok = self._run(model, slo=[SLO("tps", "throughput", 1e-9)])
        assert ok.health() == "ok"

    def test_env_declared_slo(self, model, monkeypatch):
        monkeypatch.setenv("HETU_SLO_TTFT_MS", "0.000001")
        eng = self._run(model)
        assert eng.health() == "breach"
        assert eng.slo.violations == 4

    def test_no_slo_always_ok(self, model, monkeypatch):
        monkeypatch.delenv("HETU_SLO_TTFT_MS", raising=False)
        monkeypatch.delenv("HETU_SLO_TPS", raising=False)
        eng = self._run(model)
        assert eng.health() == "ok" and eng.slo.slos == []

    def test_degraded_between_ok_and_breach(self):
        """Burn in [1, breach_burn) reads degraded: 2 bad of 100 at a
        0.95 objective is burn 0.4 (ok); 6 bad is burn 1.2
        (degraded); 11 bad is burn 2.2 (breach)."""
        for n_bad, want in ((2, "ok"), (6, "degraded"), (11, "breach")):
            mon = SLOMonitor([SLO("ttft", "latency", 10.0,
                                  objective=0.95)], window=100)
            for i in range(100):
                mon.observe(ttft_ms=100.0 if i < n_bad else 1.0)
            assert mon.health() == want, (n_bad, mon.health())

    def test_bad_slo_kind_rejected(self):
        with pytest.raises(ValueError):
            SLO("x", "availability", 1.0)
        with pytest.raises(ValueError):
            SLO("x", "latency", 1.0, objective=1.5)


# --------------------------------------------------------------------- #
# tentpole (d): flight recorder (engine triggers; chaos kill/reset
# live in tests/test_faults.py)
# --------------------------------------------------------------------- #

class TestFlightRecorder:
    def test_dump_on_engine_exception(self, model, tmp_path,
                                      monkeypatch):
        flog = str(tmp_path / "flight.jsonl")
        monkeypatch.setenv("HETU_FLIGHT_LOG", flog)
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, fast_path=False)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))

        def boom(*a, **k):
            raise RuntimeError("injected decode fault")
        monkeypatch.setattr(eng, "_launch", boom)
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
        recs = [json.loads(ln) for ln in open(flog) if ln.strip()]
        assert recs[0]["event"] == "flight_dump"
        assert recs[0]["reason"] == "engine_exception"
        assert "injected decode fault" in recs[0]["error"]
        assert recs[0]["records"] == len(recs) - 1
        for rec in recs:
            assert telemetry.validate_record(rec) == [], rec
        # the records leading up to the fault are there
        kinds = {r["event"] for r in recs}
        assert "serve_submit" in kinds

    def test_dump_on_queue_storm(self, model, tmp_path, monkeypatch):
        flog = str(tmp_path / "storm.jsonl")
        monkeypatch.setenv("HETU_FLIGHT_LOG", flog)
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=1, queue_limit=1,
                            fast_path=False)
        eng.submit(Request(prompt=[1], max_new_tokens=2))
        for i in range(9):
            with pytest.raises(QueueFull):
                eng.submit(Request(prompt=[2], max_new_tokens=2))
        recs = [json.loads(ln) for ln in open(flog) if ln.strip()]
        headers = [r for r in recs if r["event"] == "flight_dump"]
        assert len(headers) == 1          # once per storm, not per reject
        assert headers[0]["reason"] == "queue_storm"
        assert headers[0]["rejects"] == 8
        assert any(r["event"] == "serve_queue_reject" for r in recs)

    def test_queue_full_does_not_dump_engine_exception(self, model,
                                                       tmp_path,
                                                       monkeypatch):
        flog = str(tmp_path / "qf.jsonl")
        monkeypatch.setenv("HETU_FLIGHT_LOG", flog)
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=1, queue_limit=1,
                            fast_path=False)
        eng.submit(Request(prompt=[1], max_new_tokens=2))
        with pytest.raises(QueueFull):
            eng.submit(Request(prompt=[2], max_new_tokens=2))
        assert not os.path.exists(flog)   # one reject != a storm

    def test_no_sink_is_noop(self, monkeypatch):
        monkeypatch.delenv("HETU_FLIGHT_LOG", raising=False)
        telemetry.emit("span", name="x", ms=1.0)
        assert RECORDER.dump("test") is None

    def test_ring_is_bounded_and_always_on(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HETU_FLIGHT_DEPTH", "4")
        monkeypatch.setenv("HETU_TELEMETRY", "0")   # recorder ignores it
        telemetry.reset()                           # picks up the depth
        for i in range(10):
            telemetry.emit("worker_exit", _stream="failure", rank=i,
                           rc=1)
        assert len(RECORDER) == 4
        flog = str(tmp_path / "ring.jsonl")
        assert RECORDER.dump("test", path=flog) == flog
        recs = [json.loads(ln) for ln in open(flog) if ln.strip()]
        assert recs[0]["records"] == 4
        assert [r["rank"] for r in recs[1:]] == [6, 7, 8, 9]


# --------------------------------------------------------------------- #
# satellite: gauge/counter export as Chrome "C" tracks
# --------------------------------------------------------------------- #

class TestCounterExport:
    def test_serve_step_and_gauges_render_as_counters(self, replay,
                                                      tmp_path):
        trace = _export(replay["log"], tmp_path / "t.json")
        cs = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
        names = {e["name"] for e in cs}
        assert {"serve.queue_depth", "serve.live",
                "serve.occupancy", "serve.blocks_free"} <= names
        for e in cs:
            assert isinstance(e["args"]["value"], (int, float))

    def test_paged_pool_gauges_export(self, model, tmp_path,
                                      monkeypatch):
        log = str(tmp_path / "paged.jsonl")
        monkeypatch.setenv("HETU_TELEMETRY_LOG", log)
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, kv_block=4,
                            fast_path=False)
        eng.run([Request(prompt=[1, 2, 3], max_new_tokens=3)])
        trace = _export(log, tmp_path / "t.json")
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "C"}
        assert {"serve.blocks_free", "serve.blocks_shared"} <= names


# --------------------------------------------------------------------- #
# satellite: hetu_trace --check span-balance rule
# --------------------------------------------------------------------- #

class TestSpanBalance:
    def test_balanced_replay_passes(self, replay, capsys):
        assert trace_main([replay["log"], "--check"]) == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(out)["span_balance_violations"] == 0

    def test_admit_without_finish_fails(self, tmp_path, capsys):
        log = tmp_path / "unbalanced.jsonl"
        recs = [
            telemetry.make_record("serve_submit", request="r-9",
                                  queue_depth=0),
            telemetry.make_record("serve_admit", request="r-9", slot=0,
                                  ttft_s=0.01),
        ]
        log.write_text("".join(json.dumps(r) + "\n" for r in recs))
        assert trace_main([str(log), "--check"]) == 1
        out = capsys.readouterr().out
        assert "span-balance" in out and "r-9" in out

    def test_finish_without_admit_fails(self):
        evs = [telemetry.make_record("serve_finish", request="r-3",
                                     reason="length", n_generated=2)]
        problems = check_span_balance(evs)
        assert problems and "without a matching admit" in problems[0]

    def test_flight_dump_snapshot_is_exempt(self):
        evs = [
            telemetry.make_record("flight_dump", reason="chaos_kill"),
            telemetry.make_record("serve_admit", request="r-1", slot=0,
                                  ttft_s=0.01),
        ]
        assert check_span_balance(evs) == []


def _wave_span(name, wave, kind, us, tid="MainThread"):
    return dict(telemetry.make_record("span", t=us / 1e6, name=name,
                                      ms=0.5), us=us, pid=1, tid=tid,
                parent="serve.wave", wave=wave, kind=kind)


class TestWavePairing:
    """ISSUE 40: a wave's dispatch and its sync share ``wave=`` and
    ``kind=``, in different roots once the engine runs a wave ahead;
    ``--check`` holds the engine to one sync a dispatch and ``--export``
    draws the pair as a flow arrow."""

    def test_the_replay_pairs_every_wave_and_draws_its_arrow(
            self, replay, tmp_path, capsys):
        assert trace_main([replay["log"], "--check"]) == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(out)["wave_pairing_violations"] == 0
        evs = _export(replay["log"], tmp_path / "t.json")["traceEvents"]
        spans = {n: sorted((e for e in evs if e.get("ph") == "X"
                            and e["name"] == n), key=lambda e: e["ts"])
                 for n in ("serve.wave.dispatch", "serve.wave.sync")}
        n_waves = replay["eng"].steps
        assert len(spans["serve.wave.dispatch"]) \
            == len(spans["serve.wave.sync"]) == n_waves
        flows = [e for e in evs if e.get("cat") == "wave"]
        starts = {e["id"]: e for e in flows if e["ph"] == "s"}
        ends = {e["id"]: e for e in flows if e["ph"] == "f"}
        assert len(starts) == len(ends) == n_waves
        by_wave = {n: {e["args"]["wave"]: e for e in spans[n]}
                   for n in spans}
        for fid, s in starts.items():
            f, wave = ends[fid], s["args"]["wave"]
            d = by_wave["serve.wave.dispatch"][wave]
            sy = by_wave["serve.wave.sync"][wave]
            # each end binds inside its own slice, on that slice's track
            assert (s["pid"], s["tid"], s["ts"]) == (d["pid"], d["tid"],
                                                     d["ts"])
            assert (f["pid"], f["tid"], f["ts"]) == (sy["pid"], sy["tid"],
                                                     sy["ts"])
            assert s["ts"] < f["ts"] and f["bp"] == "e"
            assert s["args"]["kind"] == d["args"]["kind"] \
                == sy["args"]["kind"]

    @pytest.mark.parametrize("fault,finding", [
        (None, None),
        ("never_synced", "dispatched and never synced"),
        ("synced_twice", "synced twice"),
        ("kind_changed", "dispatched as 'chunk' and synced as 'decode'"),
        ("no_dispatch", "has no dispatch"),
        ("dispatched_twice", "dispatched twice"),
    ])
    def test_check_holds_a_dispatch_to_exactly_one_sync(self, fault,
                                                        finding):
        # one wave ahead: dispatch t+1, then sync t; the last in flight
        evs = [_wave_span("serve.wave.dispatch", 1, "chunk", 100),
               _wave_span("serve.wave.dispatch", 2, "decode", 200),
               _wave_span("serve.wave.sync", 1, "chunk", 300),
               _wave_span("serve.wave.dispatch", 3, "decode", 400),
               _wave_span("serve.wave.sync", 2, "decode", 500)]
        if fault == "never_synced":
            del evs[2]
        elif fault == "synced_twice":
            evs.append(_wave_span("serve.wave.sync", 2, "decode", 600))
        elif fault == "kind_changed":
            evs[2]["kind"] = "decode"
        elif fault == "no_dispatch":
            evs.append(_wave_span("serve.wave.sync", 9, "decode", 600))
        elif fault == "dispatched_twice":
            evs.insert(2, _wave_span("serve.wave.dispatch", 1, "chunk", 250))
        problems = check_wave_pairing(evs)
        if finding is None:
            assert problems == []
        else:
            assert len(problems) == 1 and finding in problems[0]

    def test_a_cut_stream_and_a_second_engine_are_no_findings(self):
        # the head lost wave 4's dispatch; another thread's engine and a
        # later engine on this thread both count from 1
        evs = [_wave_span("serve.wave.sync", 4, "decode", 50),
               _wave_span("serve.wave.dispatch", 1, "chunk", 100),
               _wave_span("serve.wave.dispatch", 1, "chunk", 110, tid="r1"),
               _wave_span("serve.wave.sync", 1, "chunk", 200),
               _wave_span("serve.wave.sync", 1, "chunk", 210, tid="r1"),
               _wave_span("serve.wave.dispatch", 1, "decode", 300),
               _wave_span("serve.wave.sync", 1, "decode", 400)]
        assert check_wave_pairing(evs) == []
        dump = [telemetry.make_record("flight_dump", reason="chaos_kill"),
                _wave_span("serve.wave.sync", 7, "decode", 10),
                _wave_span("serve.wave.dispatch", 8, "decode", 5)]
        assert check_wave_pairing(dump) == []


# --------------------------------------------------------------------- #
# tentpole (e): hetu_top dashboard
# --------------------------------------------------------------------- #

class TestHetuTop:
    def test_summarize_replay(self, replay):
        events, _ = read_events([replay["log"]])
        stats = top.summarize(events, window=0)
        assert stats["requests"]["submitted"] == 10
        assert stats["requests"]["finished"] == 10
        assert stats["ttft_p50_ms"] is not None
        assert stats["ttft_p50_ms"] <= stats["ttft_p99_ms"]
        assert stats["tpot_p50_ms"] is not None
        assert stats["occupancy"] is not None
        assert stats["queue_depth"] is not None
        assert stats["slots"] == 2
        assert stats["slo"]["state"] == "ok"

    def test_render_frame(self, replay):
        events, _ = read_events([replay["log"]])
        frame = top.render(top.summarize(events, window=0), clock=0.0)
        for needle in ("hetu_top", "occupancy", "TTFT", "TPOT", "SLO",
                       "[ OK ]"):
            assert needle in frame, needle

    def test_cli_once(self, replay, capsys):
        # the whole replay: a wave's seven spans push the ten submits
        # out of the default window of 512 records
        assert top.main([replay["log"], "--once", "--window", "0"]) == 0
        out = capsys.readouterr().out
        assert "hetu_top" in out and "submitted 10" in out

    def test_cli_requires_paths(self, monkeypatch):
        for env in ("HETU_TELEMETRY_LOG", "HETU_SERVE_LOG",
                    "HETU_FAILURE_LOG", "HETU_VALIDATE_LOG"):
            monkeypatch.delenv(env, raising=False)
        with pytest.raises(SystemExit):
            top.main(["--once"])


# --------------------------------------------------------------------- #
# satellite: ONE percentile implementation (+ p95 in Histogram)
# --------------------------------------------------------------------- #

class TestPercentileUnification:
    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.RandomState(7)
        xs = list(rng.randn(173) * 10)
        for q in (50, 90, 95, 99):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)))

    def test_edge_cases(self):
        assert percentile([], 50) is None
        assert percentile([5.0], 99) == 5.0
        assert percentile([1, 2], 50) == 1.5

    def test_histogram_summary_has_p95(self):
        h = Histogram("x")
        for v in range(1, 101):
            h.observe(float(v))
        s = h.summary()
        assert s["p95"] == pytest.approx(float(np.percentile(
            np.arange(1.0, 101.0), 95)))
        assert s["p50"] <= s["p95"] <= s["p99"]

    def test_serving_metrics_uses_same_helper(self, model):
        """Registry histograms and serving snapshots now agree on what
        a percentile is (they used to differ: nearest-rank vs numpy)."""
        m = ServingMetrics(log_path=None)
        m.ttfts = [float(v) for v in range(1, 51)]
        snap_p99 = m.snapshot()["ttft_p99_s"]
        assert snap_p99 == pytest.approx(percentile(m.ttfts, 99))
        assert snap_p99 == pytest.approx(
            float(np.percentile(m.ttfts, 99)))


# --------------------------------------------------------------------- #
# satellite: bounded ServingMetrics.events
# --------------------------------------------------------------------- #

class TestBoundedEvents:
    def test_ring_without_log_path(self, monkeypatch):
        monkeypatch.delenv("HETU_SERVE_LOG", raising=False)
        monkeypatch.setenv("HETU_TELEMETRY_BUFFER", "8")
        m = ServingMetrics()
        for i in range(50):
            m.record_submit(f"r-{i}", i)
        assert m.submitted == 50          # aggregates keep counting
        assert len(m.events) == 8         # memory stays bounded
        assert m.events[-1]["request"] == "r-49"

    def test_full_history_with_log_path(self, tmp_path):
        m = ServingMetrics(log_path=str(tmp_path / "s.jsonl"))
        for i in range(50):
            m.record_submit(f"r-{i}", i)
        assert len(m.events) == 50        # deliberate observation
