"""The program's spans, names and request timelines (ISSUE 25).

One span primitive on the profiler's clock (``telemetry.span`` enters a
``TraceAnnotation("hetu.<name>", **entry fields)``, records its parent
and passes ``wave=``/``step=`` through; since ISSUE 40 a wave's spans
say its ``kind=`` and a root its ``order=``); a fixed number of spans a
serving wave and
a training step whatever is live; a ``Result``'s queue wait and
per-token stamps; ``ServingMetrics.mark()``/``snapshot(since=)``; and a
host pause mid-prefill that is counted instead of raising.
"""

import gc
import json
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.serving import Request, ServingEngine
from hetu_tpu.telemetry import events as tevents
from hetu_tpu.telemetry.trace import main as trace_main
from hetu_tpu.telemetry.trace import read_events, to_chrome_trace

from test_serving import _rand_gpt

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture()
def merged_log(tmp_path, monkeypatch):
    log = str(tmp_path / "telemetry.jsonl")
    monkeypatch.setenv("HETU_TELEMETRY_LOG", log)
    return log


@pytest.fixture(scope="module")
def model():
    return _rand_gpt()


# once an engine, a manager or an executor, not once a wave or a step:
# ``tests/test_compile_watch.py`` holds them
BUILD_SPANS = ("serve.engine.build", "serve.kv.build", "exec.build")


def _spans(path):
    with open(path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    return [r for r in recs if r["event"] == "span"
            and r["name"] not in BUILD_SPANS]


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what was
    entered (``fields``: the keyword arguments each was built with) and
    left."""

    def __init__(self):
        self.entered, self.left, self.fields = [], [], []
        self.built = 0

    def __call__(self, name, **fields):
        outer = self
        self.built += 1
        self.fields.append(fields)

        class _One:
            def __enter__(self):
                outer.entered.append(name)

            def __exit__(self, *exc):
                outer.left.append(name)
        return _One()


# --------------------------------------------------------------------- #
# the primitive
# --------------------------------------------------------------------- #

class TestSpanPrimitive:
    def test_parent_and_identifiers_reach_the_record(self, merged_log):
        with telemetry.span("serve.wave", wave=7) as root:
            with telemetry.span("serve.admit", wave=7):
                with telemetry.span("serve.kv_alloc", queue=2):
                    pass
            with telemetry.span("serve.wave.sync", wave=7):
                pass
            root.set(live=3)
        with telemetry.span("exec.step", step=4):
            pass
        by = {r["name"]: r for r in _spans(merged_log)}
        assert by["serve.wave"]["parent"] is None
        assert by["serve.wave"]["wave"] == 7 and by["serve.wave"]["live"] == 3
        assert by["serve.admit"]["parent"] == "serve.wave"
        assert by["serve.kv_alloc"]["parent"] == "serve.admit"
        assert by["serve.wave.sync"]["parent"] == "serve.wave"
        assert by["serve.wave.sync"]["wave"] == 7
        assert by["exec.step"]["parent"] is None and by["exec.step"]["step"] == 4
        # the start to the microsecond, beside the contract's rounded t
        assert all(abs(r["us"] / 1e6 - r["t"]) < 2e-3 for r in by.values())

    def test_a_span_is_a_trace_annotation_named_hetu(self, monkeypatch):
        ann = _Annotations()
        monkeypatch.setattr(tevents, "TraceAnnotation", ann)
        with telemetry.span("serve.wave", wave=1):
            with telemetry.span("serve.admit", wave=1):
                pass
        assert ann.entered == ["hetu.serve.wave", "hetu.serve.admit"]
        assert ann.left == ["hetu.serve.admit", "hetu.serve.wave"]

    def test_entry_fields_reach_the_annotation_and_set_fields_do_not(
            self, monkeypatch, merged_log):
        """What a span is OPENED with rides the annotation (the host
        event's stats in the profiler's trace); what ``set()`` adds is
        known too late and stays in the JSONL record, which keeps both."""
        ann = _Annotations()
        monkeypatch.setattr(tevents, "TraceAnnotation", ann)
        with telemetry.span("serve.wave", order="ahead") as root:
            with telemetry.span("serve.wave.dispatch", wave=7, kind="chunk",
                                q=128, ahead=True):
                pass
            root.set(launched=7, landed=6)
        assert ann.entered == ["hetu.serve.wave", "hetu.serve.wave.dispatch"]
        assert ann.fields == [
            {"order": "ahead"},
            {"wave": 7, "kind": "chunk", "q": 128, "ahead": True}]
        by = {r["name"]: r for r in _spans(merged_log)}
        assert by["serve.wave"]["order"] == "ahead"
        assert (by["serve.wave"]["launched"], by["serve.wave"]["landed"]) \
            == (7, 6)
        assert by["serve.wave.dispatch"]["kind"] == "chunk"

    def test_disabled_enters_no_annotation(self, monkeypatch):
        """A disabled span builds nothing: no annotation object, no
        record, the shared no-op."""
        ann = _Annotations()
        monkeypatch.setattr(tevents, "TraceAnnotation", ann)
        monkeypatch.setenv("HETU_TELEMETRY", "0")
        with telemetry.span("serve.wave", order="first") as root:
            root.set(live=1)
        assert telemetry.span("a", wave=1) is telemetry.span("b", kind="x")
        assert ann.built == 0
        assert ann.entered == [] and ann.left == []

    def test_parent_stack_survives_an_exception(self, merged_log):
        with pytest.raises(ValueError):
            with telemetry.span("outer"):
                with telemetry.span("inner"):
                    raise ValueError("boom")
        with telemetry.span("after"):
            pass
        by = {r["name"]: r for r in _spans(merged_log)}
        assert by["inner"]["parent"] == "outer"
        assert by["after"]["parent"] is None

    def test_export_draws_the_nesting_and_check_reads_it(self, merged_log,
                                                         tmp_path, capsys):
        with telemetry.span("serve.wave", wave=1):
            with telemetry.span("serve.wave.assemble", wave=1):
                time.sleep(0.001)
            with telemetry.span("serve.wave.sync", wave=1):
                time.sleep(0.002)
        events, bad = read_events([merged_log])
        trace, n = to_chrome_trace(events)
        assert n == 3 and not bad
        xs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
        root = xs["serve.wave"]
        for child in ("serve.wave.assemble", "serve.wave.sync"):
            c = xs[child]
            assert c["args"]["parent"] == "serve.wave"
            assert c["args"]["wave"] == 1
            assert root["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= root["ts"] + root["dur"]
        assert trace_main([merged_log, "--check"]) == 0
        capsys.readouterr()
        # a span whose parent never appears is a contract violation
        telemetry.emit("span", name="orphan", ms=1.0, parent="nowhere",
                       us=int(time.time() * 1e6), pid=1, tid="t")
        telemetry.emit("span", name="late.root", ms=1.0, parent=None,
                       us=int(time.time() * 1e6) + 5000, pid=1, tid="t")
        assert trace_main([merged_log, "--check"]) == 1
        out = capsys.readouterr().out
        assert "span-nesting" in out and "'orphan'" in out


# --------------------------------------------------------------------- #
# the serving wave
# --------------------------------------------------------------------- #

# a wave's spans, tagged ``wave=``: its launch in one iteration, its
# landing in the next (``serve.kv_alloc`` carries no tag and lies in
# ``serve.admit``); and one root ``serve.wave`` an iteration
LAUNCH_SPANS = ["serve.admit", "serve.kv_alloc", "serve.wave.assemble",
                "serve.wave.dispatch"]
LAND_SPANS = ["serve.wave.sync", "serve.wave.unpack"]


def _engine(model, **kw):
    p, cfg = model
    kw.setdefault("slots", 8)
    return ServingEngine(p, cfg, kv_block=8, **kw)


class TestWaveSpans:
    @pytest.mark.parametrize("n_requests", [1, 3, 8])
    def test_one_wave_emits_each_span_once(self, model, merged_log,
                                           n_requests):
        """A fixed number of spans a WAVE whatever is live, in order
        (1 and 3 requests on 8 slots) and one wave ahead (8 on 8): four
        at its launch, two at its landing one iteration later, six in
        all under the iterations' roots."""
        eng = _engine(model)
        for i in range(n_requests):
            eng.submit(Request(prompt=[3 + i, 5, 7], max_new_tokens=6,
                               seed=i))
        for _ in range(4):
            eng.step()
        spans = [r for r in _spans(merged_log)
                 if r["name"].startswith("serve.")]
        for wave in (1, 2, 3):
            assert sorted(r["name"] for r in spans
                          if r.get("wave") == wave) == \
                sorted(LAUNCH_SPANS + LAND_SPANS)
        # the fourth is in flight: launched, not landed
        assert sorted(r["name"] for r in spans if r.get("wave") == 4) == \
            sorted(LAUNCH_SPANS)
        assert sorted(r["name"] for r in spans) == sorted(
            LAUNCH_SPANS * 4 + LAND_SPANS * 3 + ["serve.wave"] * 4)
        roots = [r for r in spans if r["name"] == "serve.wave"]
        assert [(r.get("launched"), r.get("landed")) for r in roots] == \
            [(1, None), (2, 1), (3, 2), (4, 3)]
        assert all("wave" not in r for r in roots)
        assert roots[1]["live"] == n_requests
        assert roots[1]["q_prefill"] == 3 * n_requests
        assert roots[2]["q_decode"] == n_requests
        assert all(r["parent"] == "serve.wave" for r in spans
                   if r["name"].startswith("serve.wave.")
                   or r["name"] == "serve.admit")
        assert all(r["parent"] == "serve.admit" for r in spans
                   if r["name"] == "serve.kv_alloc")
        # which half of the second iteration came first
        at = {(r["name"], r.get("wave")): r["us"] for r in spans}
        ahead = n_requests == 8
        assert (at["serve.wave.dispatch", 2]
                < at["serve.wave.sync", 1]) is ahead
        assert eng.metrics.snapshot()["waves_ahead"] == (2 if ahead else 0)

    @pytest.mark.parametrize("arrangement", ["inorder", "ahead", "spec"])
    def test_a_wave_says_its_kind_number_and_order_on_the_annotation(
            self, model, monkeypatch, arrangement):
        """What the profiler's trace gets (the annotations' arguments):
        every dispatch has one sync of the same ``wave=`` and ``kind=``
        (in different roots once the engine runs ahead), a kind is what
        the wave held, a root says its ``order=`` at entry, and a wave
        is six spans as before."""
        ann = _Annotations()
        monkeypatch.setattr(tevents, "TraceAnnotation", ann)
        kw = {"inorder": dict(slots=8, prefill_chunk=4),
              "ahead": dict(slots=4, prefill_chunk=4),
              "spec": dict(slots=4, spec=2)}[arrangement]
        eng = _engine(model, **kw)
        eng.run([Request(prompt=[3 + i, 5, 7, 9, 11, 13], max_new_tokens=5,
                         seed=i) for i in range(4)])
        seen = [(n[len("hetu."):], f) for n, f in zip(ann.entered, ann.fields)]
        by_wave = {}
        for name, f in seen:
            if "wave" in f:
                by_wave.setdefault(f["wave"], []).append((name, f))
        assert sorted(by_wave) == list(range(1, len(by_wave) + 1))
        kinds = {}
        for wave, spans in by_wave.items():
            names = sorted(n for n, _ in spans)
            assert names == sorted(
                LAUNCH_SPANS + LAND_SPANS
                + ["serve.wave.draft"] * names.count("serve.wave.draft"))
            fields = dict(spans)
            d, sy = fields["serve.wave.dispatch"], fields["serve.wave.sync"]
            assert set(d) == {"wave", "kind", "q", "ahead"}
            assert set(sy) == {"wave", "kind", "ahead"}
            assert (d["kind"], d["ahead"]) == (sy["kind"], sy["ahead"])
            assert fields["serve.wave.assemble"] == \
                fields["serve.wave.unpack"] == {"wave": wave,
                                                "kind": d["kind"]}
            assert set(fields["serve.admit"]) == \
                set(fields["serve.kv_alloc"]) == {"wave", "queue"}
            assert ("serve.wave.draft" in fields) == (d["kind"] == "verify")
            kinds[wave] = d["kind"]
        # the prompts' chunk waves first (two where six tokens go in
        # chunks of four), then the answers' decode (or verify) waves
        assert kinds[1] == "chunk"
        assert arrangement == "spec" or kinds[2] == "chunk"
        assert set(kinds.values()) == {
            "chunk", "verify" if arrangement == "spec" else "decode"}
        orders = [f["order"] for n, f in seen if n == "serve.wave"]
        assert orders[0] == "first"
        assert set(orders[1:]) == (
            {"ahead", "inorder"} if arrangement == "ahead" else {"inorder"})
        ahead = [f["ahead"] for n, f in seen if n == "serve.wave.dispatch"]
        assert sum(ahead) == eng.metrics.snapshot()["waves_ahead"] \
            == orders.count("ahead")

    def test_a_step_with_nothing_live_stops_after_admission(self, model,
                                                            merged_log):
        eng = _engine(model)
        assert eng.step() == []
        assert sorted(r["name"] for r in _spans(merged_log)) == \
            ["serve.admit", "serve.kv_alloc", "serve.wave"]

    def test_speculative_wave_adds_the_draft_span(self, model, merged_log):
        eng = _engine(model, slots=4, spec=2)
        eng.submit(Request(prompt=[3, 5, 7], max_new_tokens=6))
        for _ in range(3):
            eng.step()
        second = [r["name"] for r in _spans(merged_log)
                  if r.get("wave") == 2]
        assert sorted(second) == sorted(
            LAUNCH_SPANS + LAND_SPANS + ["serve.wave.draft"])


# --------------------------------------------------------------------- #
# the request's timeline
# --------------------------------------------------------------------- #

class TestResultTimeline:
    @pytest.mark.parametrize("kw", [
        dict(kv_block=8, prefill_chunk=4),
        dict(kv_block=8, spec=2),
        dict(kv_block=8),
    ], ids=["paged-chunked", "paged-spec", "paged"])
    def test_token_times_and_queue_wait(self, model, kw):
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, **kw)
        reqs = [Request(prompt=list(range(2, 2 + n)), max_new_tokens=m,
                        seed=i)
                for i, (n, m) in enumerate([(9, 7), (3, 1), (5, 12),
                                            (2, 4)])]
        out = eng.run(reqs)
        assert len(out) == 4
        for r in out.values():
            t = r.token_times_s
            assert len(t) == r.n_generated
            assert t[0] == pytest.approx(r.ttft_s, abs=1e-9)
            assert all(b >= a for a, b in zip(t, t[1:]))
            assert t[-1] <= r.latency_s + 1e-9
            assert 0.0 <= r.queue_wait_s <= r.ttft_s
        # two slots, four requests: the later two waited for a slot
        waits = sorted(r.queue_wait_s for r in out.values())
        assert waits[-1] > waits[0]

    def test_a_speculative_wave_shares_one_stamp(self, model):
        p, cfg = model
        eng = ServingEngine(p, cfg, slots=2, kv_block=8,
                            spec=2)
        [r] = eng.run([Request(prompt=[4, 5, 6], max_new_tokens=12)]
                      ).values()
        # 12 tokens in fewer waves than tokens: some stamps repeat
        assert eng.spec_emitted > eng.spec_waves
        assert len(set(r.token_times_s)) < r.n_generated


class TestMetricsMark:
    def test_since_a_mark_excludes_what_came_before(self, model):
        eng = _engine(model, slots=2)
        m0 = eng.metrics.mark()
        assert eng.metrics.snapshot(since=m0) == eng.metrics.snapshot()
        eng.run([Request(prompt=[7, 8], max_new_tokens=4),
                 Request(prompt=[9], max_new_tokens=6)])
        whole = eng.metrics.snapshot()
        assert eng.metrics.snapshot(since=m0) == whole
        m1 = eng.metrics.mark()
        empty = eng.metrics.snapshot(since=m1)
        assert empty["steps"] == 0 and empty["requests_finished"] == 0
        assert empty["decode_ms_p50"] is None and empty["components"] == {}
        eng.run([Request(prompt=[1, 2, 3], max_new_tokens=5)])
        tail = eng.metrics.snapshot(since=m1)
        after = eng.metrics.snapshot()
        assert tail["requests_finished"] == 1
        assert tail["tokens_generated"] == 5
        assert tail["steps"] == after["steps"] - whole["steps"]
        assert tail["steps"] == 5       # one prefill wave + four decode
        assert tail["mean_batch_occupancy"] == pytest.approx(0.5)
        assert after["requests_finished"] == 3
        assert tail["components"]["decode_ms"]["p50_ms"] > 0
        assert tail["wall_s"] < after["wall_s"]


# --------------------------------------------------------------------- #
# a paused host
# --------------------------------------------------------------------- #

class TestLifecycleResidue:
    def test_a_pause_mid_prefill_is_counted_not_raised(self, model,
                                                       tmp_path, capsys):
        assert gc.isenabled()
        log = str(tmp_path / "serve.jsonl")
        eng = _engine(model, slots=2, log_path=log)
        # warm the programs: the paused request's wave must be short
        eng.run([Request(prompt=[5, 6, 7], max_new_tokens=2)])
        admit = eng._admit_paged

        def paused_admit():
            claimed = admit()
            if claimed:
                time.sleep(0.2)         # between the claim and the wave
            return claimed
        eng._admit_paged = paused_admit
        [res] = eng.run([Request(prompt=[8, 6, 7], max_new_tokens=2,
                                 request_id="paused")]).values()
        assert res.n_generated == 2
        assert telemetry.snapshot()["counters"][
            "serve.lifecycle_residue"] == 1
        [ev] = [e for e in eng.metrics.events
                if e["event"] == "serve_lifecycle_residue"]
        assert ev["request"] == "paused" and ev["residue_ms"] >= 150
        assert ev["wall_ms"] >= ev["residue_ms"]
        # reported as chunk_stall_ms, not folded to 0
        stalls = eng.metrics.components["chunk_stall_ms"]
        assert stalls[0] == 0.0 and stalls[1] >= 150
        assert trace_main([log, "--check"]) == 1
        assert "lifecycle-residue" in capsys.readouterr().out


# --------------------------------------------------------------------- #
# the trainer's step
# --------------------------------------------------------------------- #

class TestExecutorSpans:
    def test_step_span_holds_its_children(self, merged_log):
        x = ht.placeholder_op("x")
        w = ht.init.xavier_uniform((16, 16), name=f"ps_w_{time.time_ns()}")
        loss = ht.reduce_mean_op(ht.reduce_mean_op(
            ht.relu_op(ht.matmul_op(x, w)), axes=1), axes=0)
        train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        ex = ht.Executor({"train": [loss, train]})
        feed = {x: np.ones((4, 16), np.float32)}
        ex.run("train", feed_dict=feed)
        ex.run("train", feed_dict=feed)
        out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        assert isinstance(out[0], np.ndarray) and out[1] is None
        spans = [r for r in _spans(merged_log)
                 if r["name"].startswith("exec.")]
        steps = {s: sorted(r["name"] for r in spans if r["step"] == s)
                 for s in (1, 2, 3)}
        base = ["exec.dispatch", "exec.feed", "exec.phase_a", "exec.step"]
        assert steps[1] == sorted(base + ["exec.compile"])
        assert steps[2] == base
        assert steps[3] == sorted(base + ["exec.fetch"])
        for r in spans:
            assert r["parent"] == (None if r["name"] == "exec.step"
                                   else "exec.step")
            assert r["subgraph"] == "train"

    def test_nodes_trace_under_their_own_scope(self):
        from hetu_tpu.executor import scope_name
        x = ht.placeholder_op("x")
        w = ht.init.xavier_uniform((8, 8), name=f"ps_s_{time.time_ns()}")
        y = ht.matmul_op(x, w)
        loss = ht.reduce_mean_op(ht.reduce_mean_op(y, axes=1), axes=0)
        opt = ht.optim.SGDOptimizer(learning_rate=0.1)
        train = opt.minimize(loss)
        ex = ht.Executor({"train": [loss, train]})
        sub = ex.subexecutor["train"]
        names = {scope_name(n) for n in sub.topo}
        assert "optimizer" in names
        assert any(n.startswith("grad_") for n in names)
        assert not any(c.isdigit() for n in names for c in n
                       if not n.startswith("ps_s_")), names
        feeds = {"x": np.ones((4, 8), np.float32)}
        import jax
        text = jax.jit(lambda p, f: sub._trace(
            p, ex.opt_states, 0, jax.random.PRNGKey(0), f)
        ).lower(ex.var_values, feeds).as_text(debug_info=True)
        assert "optimizer" in text and scope_name(y) in text
