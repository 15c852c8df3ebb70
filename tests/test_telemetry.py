"""Unified telemetry subsystem (hetu_tpu/telemetry): the one event
pipeline, spans/metrics, and trace export.

The acceptance spine (ISSUE 5): a training step, a serving request, and
a validate failure all land in ONE merged JSONL stream via the sink;
``bin/hetu_trace.py`` exports a loadable Perfetto trace from it; with
``HETU_TELEMETRY=0`` the instrumentation is a no-op.  Plus the shared
EVENT CONTRACT test covering all four
streams — ``{"t", "event"}`` + per-kind required fields as a single
schema instead of four conventions.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.telemetry.trace import (
    main as trace_main, read_events, to_chrome_trace,
)

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def _fresh_registry(monkeypatch):
    # instrumentation on for this file regardless of the ambient env
    # (the disabled-path tests set HETU_TELEMETRY=0 themselves, which
    # wins over this autouse default)
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture()
def merged_log(tmp_path, monkeypatch):
    log = str(tmp_path / "telemetry.jsonl")
    monkeypatch.setenv("HETU_TELEMETRY_LOG", log)
    return log


def _read(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# --------------------------------------------------------------------- #
# the sink + event contract
# --------------------------------------------------------------------- #

class TestSink:
    def test_emit_shape_and_buffer(self):
        rec = telemetry.emit("worker_exit", _stream="failure", rank=0,
                             rc=1)
        assert isinstance(rec["t"], float) and rec["event"] == "worker_exit"
        assert telemetry.get_sink().recent(kind="worker_exit") == [rec]

    def test_stream_lands_in_legacy_and_merged(self, tmp_path,
                                               monkeypatch, merged_log):
        legacy = str(tmp_path / "failures.jsonl")
        monkeypatch.setenv("HETU_FAILURE_LOG", legacy)
        telemetry.emit("worker_exit", _stream="failure", rank=0, rc=-9)
        assert [r["event"] for r in _read(legacy)] == ["worker_exit"]
        assert [r["event"] for r in _read(merged_log)] == ["worker_exit"]

    def test_explicit_path_overrides_stream_env(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("HETU_SERVE_LOG", str(tmp_path / "env.jsonl"))
        override = str(tmp_path / "explicit.jsonl")
        telemetry.emit("serve_submit", _stream="serve", _path=override,
                       request="r0", queue_depth=0)
        assert not os.path.exists(str(tmp_path / "env.jsonl"))
        assert len(_read(override)) == 1

    def test_unwritable_log_never_raises(self, monkeypatch):
        monkeypatch.setenv("HETU_TELEMETRY_LOG",
                           "/nonexistent-dir/x/y.jsonl")
        telemetry.emit("span", name="x", ms=1.0)   # must not raise
        assert telemetry.snapshot()["dropped_writes"] >= 1

    def test_contract_validates_known_kinds(self):
        good = telemetry.make_record("serve_step", live=2, queue_depth=0,
                                     decode_ms=1.2)
        assert telemetry.validate_record(good) == []
        bad = telemetry.make_record("serve_step", live=2)
        assert any("queue_depth" in p
                   for p in telemetry.validate_record(bad))
        assert telemetry.validate_record({"event": "x"})  # missing t
        # unknown kinds only need the base shape
        assert telemetry.validate_record(
            telemetry.make_record("some_new_kind", foo=1)) == []

    def test_event_contract_all_streams(self, merged_log, model):
        """THE shared schema test: generate real records from all four
        streams and validate every one against the single contract."""
        # failure stream: a launcher-family record
        telemetry.emit("ps_shard_failover", _stream="failure", shard=0,
                       backup=1)
        # serve stream: a real engine request (fixture below)
        params, cfg = model
        from hetu_tpu.serving import Request, ServingEngine
        eng = ServingEngine(params, cfg, slots=2, fast_path=False)
        eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2, seed=0)])
        # validate stream: a real verifier report
        from hetu_tpu.analysis.report import emit_records, make_record
        emit_records([make_record("graph_verified", subgraph="train",
                                  phase="build", nodes=3, verified=3,
                                  findings=[])])
        # telemetry stream: a span
        with telemetry.span("exec.step", subgraph="train"):
            pass
        recs = _read(merged_log)
        kinds = {r["event"] for r in recs}
        assert {"ps_shard_failover", "serve_submit", "serve_finish",
                "graph_verified", "span"} <= kinds
        for rec in recs:
            assert telemetry.validate_record(rec) == [], rec


# --------------------------------------------------------------------- #
# metrics + spans + the disabled no-op contract
# --------------------------------------------------------------------- #

class TestMetrics:
    def test_counter_gauge_histogram_snapshot(self):
        telemetry.inc("a.count", 3)
        telemetry.inc("a.count")
        telemetry.set_gauge("a.depth", 7)
        for v in (1.0, 2.0, 9.0):
            telemetry.observe("a.ms", v)
        s = telemetry.snapshot()
        assert s["counters"]["a.count"] == 4
        assert s["gauges"]["a.depth"] == 7
        h = s["histograms"]["a.ms"]
        assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 9.0

    def test_thread_safety(self):
        def work():
            for _ in range(1000):
                telemetry.inc("t.count")
        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert telemetry.snapshot()["counters"]["t.count"] == 8000

    def test_type_collision_raises(self):
        telemetry.counter("x.y")
        with pytest.raises(TypeError):
            telemetry.gauge("x.y")

    def test_span_records_histogram_and_jsonl(self, merged_log):
        with telemetry.span("exec.phase_a", subgraph="train"):
            time.sleep(0.002)
        h = telemetry.snapshot()["histograms"]["span.exec.phase_a"]
        assert h["count"] == 1 and h["min"] >= 1.0   # >= 1 ms
        [rec] = _read(merged_log)
        assert rec["event"] == "span" and rec["name"] == "exec.phase_a"
        assert rec["subgraph"] == "train" and rec["ms"] >= 1.0
        assert "pid" in rec and "tid" in rec

    def test_disabled_is_noop(self, monkeypatch, merged_log):
        monkeypatch.setenv("HETU_TELEMETRY", "0")
        with telemetry.span("exec.step"):
            pass
        telemetry.inc("c")
        telemetry.observe("h", 1.0)
        telemetry.set_gauge("g", 1)
        s = telemetry.snapshot()
        assert s["counters"] == {} and s["histograms"] == {} \
            and s["gauges"] == {}
        assert not os.path.exists(merged_log)

    def test_disabled_span_overhead_tiny(self, monkeypatch):
        """The HETU_TELEMETRY=0 contract: a disabled span is an env
        read + a shared no-op object — generous bound of 50us each so
        the assertion never flakes while still catching an accidental
        always-on JSONL write (orders of magnitude slower)."""
        monkeypatch.setenv("HETU_TELEMETRY", "0")
        t0 = time.perf_counter()
        for _ in range(1000):
            with telemetry.span("x"):
                pass
        dt = time.perf_counter() - t0
        assert dt < 0.05, f"1000 disabled spans took {dt * 1e3:.1f} ms"


# --------------------------------------------------------------------- #
# instrumentation integration: one merged stream, end to end
# --------------------------------------------------------------------- #

def _rand_gpt(name="tl", L=1, H=2, Dh=8, V=61, S=32, seed=0):
    from hetu_tpu.models import GPTConfig
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


def _tiny_train_step(n_steps=2):
    x = ht.placeholder_op("x")
    w = ht.init.xavier_uniform((16, 16), name=f"tl_w_{time.time_ns()}")
    h = ht.relu_op(ht.matmul_op(x, w))
    loss = ht.reduce_mean_op(ht.reduce_mean_op(h, axes=1), axes=0)
    train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
    ex = ht.Executor({"train": [loss, train]})
    for _ in range(n_steps):
        ex.run("train", feed_dict={x: np.ones((4, 16), np.float32)})
    return ex


class TestMergedStream:
    def test_train_serve_validate_one_stream(self, merged_log, model,
                                             monkeypatch):
        """ISSUE acceptance: a training step, a serving request, and a
        validate failure all land in a single merged JSONL stream."""
        monkeypatch.setenv("HETU_VALIDATE", "1")
        _tiny_train_step()
        params, cfg = model
        from hetu_tpu.serving import Request, ServingEngine
        eng = ServingEngine(params, cfg, slots=2, fast_path=False)
        eng.run([Request(prompt=[1, 2], max_new_tokens=2, seed=1)])
        # a validate FAILURE (shape mismatch fails the pre-trace check)
        x = ht.placeholder_op("x")
        w = ht.init.xavier_uniform((8, 8), name="tl_bad_w")
        bad = ht.matmul_op(x, w)
        from hetu_tpu.analysis import GraphVerifyError
        ex = ht.Executor({"bad": [bad]})
        with pytest.raises(GraphVerifyError):
            ex.run("bad", feed_dict={x: np.ones((4, 5), np.float32)})
        kinds = {r["event"] for r in _read(merged_log)}
        assert "span" in kinds                  # training step spans
        assert "serve_finish" in kinds          # serving request
        assert "graph_verify_error" in kinds    # validate failure
        assert "graph_verified" in kinds

    def test_executor_spans_and_counters(self, merged_log):
        _tiny_train_step(n_steps=3)
        s = telemetry.snapshot()
        assert s["counters"]["exec.steps"] == 3
        assert s["counters"]["exec.compile_cache_miss"] == 1
        names = {r.get("name") for r in _read(merged_log)
                 if r["event"] == "span"}
        assert {"exec.phase_a", "exec.compile",
                "exec.dispatch"} <= names
        # the cache-miss step's dispatch is marked compiled=True
        dispatches = [r for r in _read(merged_log)
                      if r.get("name") == "exec.dispatch"]
        assert dispatches[0]["compiled"] is True
        assert all(d["compiled"] is False for d in dispatches[1:])

    def test_ps_rpc_metrics_local(self):
        from hetu_tpu.ps.client import PSClient
        from hetu_tpu.ps.server import PSServer
        PSServer._instance = None
        c = PSClient()
        try:
            c.parameter_init("tl_table", (8, 4), "constant", 0.0)
            c.push("tl_table", np.ones((8, 4), np.float32))
            c.pull("tl_table")
            s = telemetry.snapshot()
            assert s["counters"]["ps.rpc.calls[local]"] >= 3
            assert "ps.rpc_ms.pull" in s["histograms"]
        finally:
            PSServer._instance = None

    def test_ps_rpc_metrics_tcp_bytes(self):
        import socket
        from hetu_tpu.ps.client import PSClient, _TCPTransport
        from hetu_tpu.ps.server import PSServer
        s_ = socket.socket()
        s_.bind(("", 0))
        port = s_.getsockname()[1]
        s_.close()
        srv = PSServer()
        srv.serve_tcp(port, block=False)
        c = None
        try:
            c = PSClient(transport=_TCPTransport("127.0.0.1", port))
            c.parameter_init("tl_tcp", (4, 4), "constant", 0.0)
            c.pull("tl_tcp")
            s = telemetry.snapshot()
            shard = f"127.0.0.1:{port}"
            assert s["counters"][f"ps.rpc.calls[{shard}]"] >= 2
            assert s["counters"]["ps.rpc.bytes_sent"] > 0
            assert s["counters"]["ps.rpc.bytes_recv"] > 0
            assert s["counters"]["ps.server.requests"] >= 2
            assert s["counters"]["ps.server.bytes_in"] > 0
            assert "ps.server.handle_ms.pull" in s["histograms"]
        finally:
            srv.shutdown()

    def test_cache_counters(self):
        from hetu_tpu.cache.cstable import CacheSparseTable
        from hetu_tpu.ps.server import PSServer
        srv = PSServer()
        srv.param_init("tl_emb", (64, 4), init_type="constant", arg1=0.5)
        t = CacheSparseTable(limit=8, vocab_size=64, width=4,
                             key="tl_emb", comm=srv,
                             prefer_native=False)
        t.embedding_lookup(np.arange(8))           # 8 misses
        t.embedding_lookup(np.arange(8))           # 8 hits
        t.embedding_lookup(np.arange(8, 12))       # evictions begin
        t.embedding_update(np.arange(8, 12), np.ones((4, 4)))
        t.flush()
        s = telemetry.snapshot()["counters"]
        assert s["cache.hits"] >= 8
        assert s["cache.misses"] >= 12
        assert s["cache.evictions"] >= 4
        assert s["cache.writeback_rows"] >= 4

    def test_dataloader_ring_metrics(self):
        from hetu_tpu.dataloader import Dataloader
        dl = Dataloader(np.arange(64).reshape(16, 4), 4, "tl")
        dl.start_prefetch(depth=2)
        try:
            for _ in range(4):
                dl.get_arr()
        finally:
            dl.stop_prefetch()
        s = telemetry.snapshot()
        assert s["histograms"]["dataloader.wait_ms"]["count"] == 4
        assert s["gauges"]["dataloader.ring_depth"] is not None

    def test_serving_engine_wave_counter_and_stream(self, merged_log,
                                                    model):
        params, cfg = model
        from hetu_tpu.serving import Request, ServingEngine
        eng = ServingEngine(params, cfg, slots=2, fast_path=False)
        eng.run([Request(prompt=[1, 2], max_new_tokens=2, seed=s)
                 for s in range(3)])
        assert telemetry.snapshot()["counters"]["serve.admission_waves"] \
            >= 2
        kinds = [r["event"] for r in _read(merged_log)]
        assert "serve_step" in kinds and "serve_prefill" in kinds


# --------------------------------------------------------------------- #
# every name has a reader to point at (ISSUE 40)
# --------------------------------------------------------------------- #

def _instrumented_names():
    """(call, name, "file:line") for every name handed to
    ``telemetry.span/spanned/inc/observe/set_gauge`` under the serving stack, the
    executor and the gradient nodes; ``name`` is None where it is not a
    literal (or a choice between literals)."""
    import ast
    import glob
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = sorted(glob.glob(os.path.join(root, "hetu_tpu", "serving",
                                          "*.py")))
    files += [os.path.join(root, "hetu_tpu", "executor.py"),
              os.path.join(root, "hetu_tpu", "graph", "ops_misc.py")]
    assert len(files) > 10
    found = []
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("span", "spanned", "inc",
                                           "observe", "set_gauge")
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "telemetry" and node.args):
                continue
            first = node.args[0]
            where = f"{os.path.relpath(path, root)}:{node.lineno}"
            for n in ([first.body, first.orelse]
                      if isinstance(first, ast.IfExp) else [first]):
                literal = isinstance(n, ast.Constant) \
                    and isinstance(n.value, str)
                found.append((node.func.attr, n.value if literal else None,
                              where))
    return root, found


def test_every_serving_and_executor_name_is_documented():
    """A span, counter, histogram or gauge that no document names has no
    reader anybody can find: it is deleted or written down, in README's
    telemetry section (operators, ``hetu_top``, ``hetu_trace``) or in
    ``benchmarks/PROGRAM_SPANS*.md`` (the benchmark's readers)."""
    import glob
    root, found = _instrumented_names()
    with open(os.path.join(root, "README.md")) as f:
        readme = f.read()
    start = readme.index("\n## Telemetry (")
    docs = readme[start:readme.index("\n## ", start + 1)]
    for path in glob.glob(os.path.join(root, "benchmarks",
                                       "PROGRAM_SPANS*.md")):
        with open(path) as f:
            docs += f.read()
    assert {"serve.wave", "serve.wave.dispatch", "exec.step",
            "serve.wave.ahead", "exec.grad.retraced",
            "serve.tokens_per_step", "serve.occupancy"} \
        <= {name for _, name, _ in found}
    computed = sorted(where for _, name, where in found if name is None)
    assert not computed, f"names built at run time, unreadable: {computed}"
    unread = sorted({f"{name} ({call}, {where})"
                     for call, name, where in found
                     if f"`{name}`" not in docs and f"`{name}(" not in docs})
    assert not unread, f"documented nowhere: {unread}"


# --------------------------------------------------------------------- #
# trace merge/export CLI
# --------------------------------------------------------------------- #

class TestTraceExport:
    def _populate(self, merged_log):
        with telemetry.span("exec.dispatch", subgraph="train"):
            time.sleep(0.001)
        telemetry.emit("serve_step", _stream="serve", live=2,
                       queue_depth=0, prefill_ms=0.5, decode_ms=2.0)
        telemetry.emit("worker_exit", _stream="failure", rank=0, rc=1)

    def test_merge_is_time_sorted_across_files(self, tmp_path):
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        a.write_text(json.dumps({"t": 2.0, "event": "late"}) + "\n")
        b.write_text(json.dumps({"t": 1.0, "event": "early"}) + "\n"
                     + "not json\n")
        events, bad = read_events([str(a), str(b)])
        assert [e["event"] for e in events] == ["early", "late"]
        assert bad == 1

    def test_chrome_trace_spans_and_instants(self, merged_log):
        self._populate(merged_log)
        events, _ = read_events([merged_log])
        trace, n_spans = to_chrome_trace(events)
        assert n_spans == 2       # the span + serve_step(decode_ms)
        xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        names = {e["name"] for e in xs}
        assert "exec.dispatch" in names and "serve.decode" in names
        for e in xs:
            assert e["dur"] > 0 and isinstance(e["ts"], float)
        # instants for the point events
        assert any(e.get("ph") == "i" and e["name"] == "worker_exit"
                   for e in trace["traceEvents"])

    def test_compile_records_draw_on_a_track_of_their_own(self, tmp_path):
        """A canned merged log of a cold start: the first chunk wave's
        dispatch span and the program it built.  The phases sit on
        ``compile:<thread>``, named by phase, with ``fun`` and the span
        that built them as arguments; the span stays on its thread."""
        log = tmp_path / "cold.jsonl"
        base = {"pid": 7, "tid": "MainThread"}
        parent = {"wave": 3, "kind": "chunk", "q": 256, "ahead": False,
                  "name": "serve.wave.dispatch"}
        recs = [
            dict(base, t=100.0, event="span", name="serve.wave.dispatch",
                 ms=9000.0, us=100_000_000, parent="serve.wave", wave=3,
                 kind="chunk", q=256),
            dict(base, t=100.5, event="compile", phase="trace",
                 fun="_serve_mixed_paged", ms=4000.0, us=100_500_000,
                 t0=100.5, t1=104.5, parent=parent,
                 under=["serve.wave", "serve.wave.dispatch"]),
            dict(base, t=101.0, event="compile", phase="trace",
                 fun="_kv_rows_kernel", ms=2000.0, us=101_000_000,
                 t0=101.0, t1=103.0, parent=parent,
                 under=["serve.wave", "serve.wave.dispatch"]),
            dict(base, t=104.5, event="compile", phase="lower",
                 fun="jit(_serve_mixed_paged)", ms=1500.0,
                 us=104_500_000, t0=104.5, t1=106.0, parent=parent,
                 under=["serve.wave", "serve.wave.dispatch"]),
            dict(base, t=106.0, event="compile", phase="backend",
                 fun="jit(_serve_mixed_paged)", ms=3000.0, cache="miss",
                 us=106_000_000, t0=106.0, t1=109.0, parent=None,
                 under=[]),
        ]
        log.write_text("".join(json.dumps(r) + "\n" for r in recs))
        events, bad = read_events([str(log)])
        assert bad == 0
        assert all(telemetry.validate_record(e) == [] for e in events)
        trace, n_spans = to_chrome_trace(events)
        assert n_spans == 5
        names = {e["tid"]: e["args"]["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "M"}
        assert sorted(names.values()) == ["MainThread",
                                          "compile:MainThread"]
        drawn = [e for e in trace["traceEvents"]
                 if e.get("cat") == "compile"]
        assert [(e["name"], e["args"]["fun"], e["ts"], e["dur"])
                for e in drawn] == [
            ("trace", "_serve_mixed_paged", 100.5e6, 4e6),
            ("trace", "_kv_rows_kernel", 101e6, 2e6),
            ("lower", "jit(_serve_mixed_paged)", 104.5e6, 1.5e6),
            ("backend", "jit(_serve_mixed_paged)", 106e6, 3e6)]
        assert {names[e["tid"]] for e in drawn} == {"compile:MainThread"}
        assert drawn[0]["args"]["parent"] == \
            "serve.wave.dispatch(wave=3, kind=chunk, q=256, ahead=False)"
        assert drawn[3]["args"]["cache"] == "miss" \
            and "parent" not in drawn[3]["args"]
        span = next(e for e in trace["traceEvents"]
                    if e.get("cat") == "span")
        assert names[span["tid"]] == "MainThread"
        assert trace_main([str(log), "--check"]) == 0

    def test_cli_export_loadable(self, merged_log, tmp_path, capsys):
        self._populate(merged_log)
        out = str(tmp_path / "trace.json")
        rc = trace_main([merged_log, "--export", out])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out.strip())
        assert summary["spans"] >= 2
        trace = json.load(open(out))     # loadable = the acceptance bar
        assert trace["traceEvents"]
        assert trace["displayTimeUnit"] == "ms"

    def test_cli_merge_and_filters(self, merged_log, capsys):
        self._populate(merged_log)
        rc = trace_main([merged_log, "--events", "worker_exit"])
        assert rc == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        assert len(lines) == 1 and lines[0]["event"] == "worker_exit"

    def test_cli_contract_check(self, merged_log, tmp_path, capsys):
        self._populate(merged_log)
        assert trace_main([merged_log, "--check"]) == 0
        capsys.readouterr()
        bad = tmp_path / "bad.jsonl"
        bad.write_text(json.dumps({"t": 1.0, "event": "serve_step",
                                   "live": 1}) + "\n")
        assert trace_main([str(bad), "--check"]) == 1

    def test_cli_default_paths_from_env(self, merged_log, capsys):
        self._populate(merged_log)
        rc = trace_main([])          # falls back to HETU_TELEMETRY_LOG
        assert rc == 0
        assert capsys.readouterr().out.strip()


# --------------------------------------------------------------------- #
# launcher/report compatibility (the migrated emitters keep their
# contracts: in-memory lists + legacy files)
# --------------------------------------------------------------------- #

class TestMigratedEmitters:
    def test_serving_metrics_keeps_event_list(self, tmp_path):
        from hetu_tpu.serving import ServingMetrics
        log = str(tmp_path / "serve.jsonl")
        m = ServingMetrics(log_path=log)
        m.record_submit("r1", 0)
        assert m.events[0]["event"] == "serve_submit"
        assert _read(log)[0]["event"] == "serve_submit"

    def test_report_emit_records_path_override(self, tmp_path):
        from hetu_tpu.analysis.report import emit_records, make_record
        p = str(tmp_path / "v.jsonl")
        recs = [make_record("graph_verified", subgraph="s", phase="build")]
        emit_records(recs, path=p)
        assert _read(p) == recs

    def test_sharded_event_reaches_failure_stream(self, tmp_path,
                                                  monkeypatch):
        legacy = str(tmp_path / "fail.jsonl")
        monkeypatch.setenv("HETU_FAILURE_LOG", legacy)
        from hetu_tpu.ps import sharded
        c = sharded.ShardedPSClient.__new__(sharded.ShardedPSClient)
        c.failure_events = []
        c._event("ps_shard_failover", shard=1, backup=2, error="x")
        assert c.failure_events[0]["event"] == "ps_shard_failover"
        assert _read(legacy)[0]["shard"] == 1
