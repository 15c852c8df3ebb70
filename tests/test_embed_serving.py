"""Embedding serving engine (ISSUE 14 tentpole): the model-agnostic
serving substrate's second workload.

The acceptance spine: EmbedServingEngine scores (user, item, dense)
requests through the HET cache + one jitted dense-tower wave and its
scores match a pure-numpy oracle forward for all three towers
(wdl/dcn/ncf); a zipf-skewed trace against a capacity-limited cache
clears a hit-rate floor; the fleet router hosts embedding replicas and
sheds throughput-class traffic first; a mid-trace PS kill loses ZERO
requests (stale/zero degradation, replay on recovery); and the serve
stream stays span- AND gather-balanced.  Around it: the regression that
matters most — the GPT engine + router are token-identical to offline
``generate_fast`` across paged/int8/spec configs AFTER the
model-agnostic refactor.

All CPU-harness, all smoke-tier (tiny random-weight towers — the
contract is scheduling, caching and degradation, not model quality).
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu import telemetry
from hetu_tpu.cache.cstable import CacheSparseTable
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.ps.client import PSConnectionError
from hetu_tpu.ps.server import PSServer
from hetu_tpu.serving import (
    EmbedRequest, EmbedServingEngine, QueueFull, Request, RouterShed,
    ServingEngine, ServingRouter, SLO,
)
from hetu_tpu.telemetry import top
from hetu_tpu.telemetry.trace import (check_gather_balance,
                                      check_span_balance, read_events)

pytestmark = pytest.mark.smoke

E = 4          # embedding width of the CTR tables under test
NCF_W = 8      # user/item latent width (embed_dim=4 GMF + 4 MLP)
VOCAB = 64


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    telemetry.reset()
    yield
    telemetry.reset()


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _server(tables):
    """PSServer with one embedding matrix per (key, vocab, width)."""
    server = PSServer()
    for key, vocab, width in tables:
        server.param_init(key, (vocab, width), "normal", 0.0, 1.0,
                          seed=3)
    return server


def _table(server, key, vocab=VOCAB, width=E, limit=256, **kw):
    return CacheSparseTable(limit=limit, vocab_size=vocab, width=width,
                            key=key, comm=server, policy="LRU", **kw)


def _rng(seed=0):
    return np.random.RandomState(seed)


# --------------------------------------------------------------------- #
# tower params + numpy oracles (the engine's jax towers must match)
# --------------------------------------------------------------------- #

def _wdl_params(rng, h=8):
    return {"W1": rng.randn(13, h) * 0.3, "W2": rng.randn(h, h) * 0.3,
            "W3": rng.randn(h, h) * 0.3,
            "W4": rng.randn(26 * E + h, 1) * 0.3}


def _dcn_params(rng, h=8):
    D = 26 * E + 13
    p = {"W1": rng.randn(D, h) * 0.1, "W2": rng.randn(h, h) * 0.1,
         "W3": rng.randn(h, h) * 0.1, "W4": rng.randn(D + h, 1) * 0.1}
    for i in range(3):
        p[f"cross{i}_weight"] = rng.randn(D, 1) * 0.1
        p[f"cross{i}_bias"] = rng.randn(D) * 0.1
    return p


def _ncf_params(rng, h=8):
    # embed_dim=4 GMF factors; MLP input = 2 * (NCF_W - 4) = 8
    return {"W1": rng.randn(8, h) * 0.3, "W2": rng.randn(h, h) * 0.3,
            "W3": rng.randn(h, h) * 0.3, "W4": rng.randn(4 + h, 1) * 0.3}


def _np_tower(x, p):
    y = np.maximum(x @ p["W1"], 0.0)
    y = np.maximum(y @ p["W2"], 0.0)
    return y @ p["W3"]


def _np_wdl(p, emb_flat, dense):
    y3 = _np_tower(dense, p)
    return _sigmoid(np.concatenate([emb_flat, y3], axis=1)
                    @ p["W4"])[:, 0]


def _np_dcn(p, emb_flat, dense):
    x = np.concatenate([emb_flat, dense], axis=1)
    cross = x
    for i in range(3):
        cross = x * (cross @ p[f"cross{i}_weight"]) + cross \
            + p[f"cross{i}_bias"]
    y3 = _np_tower(x, p)
    return _sigmoid(np.concatenate([cross, y3], axis=1) @ p["W4"])[:, 0]


def _np_ncf(p, u_lat, i_lat, ed=4):
    gmf = u_lat[:, :ed] * i_lat[:, :ed]
    x = np.concatenate([u_lat[:, ed:], i_lat[:, ed:]], axis=1)
    for i in range(1, 4):
        x = np.maximum(x @ p[f"W{i}"], 0.0)
    return _sigmoid(np.concatenate([gmf, x], axis=1) @ p["W4"])[:, 0]


def _f32(params):
    return {k: np.asarray(v, np.float32) for k, v in params.items()}


def _ctr_requests(rng, n, pairs=(1, 4), vocab=VOCAB, cls=None):
    out = []
    for i in range(n):
        np_ = int(rng.randint(pairs[0], pairs[1] + 1))
        out.append(EmbedRequest(
            item_ids=rng.randint(0, vocab, (np_, 26)),
            dense_features=rng.randn(np_, 13).astype(np.float32),
            slo_class=cls or "throughput"))
    return out


def _mk_ctr_engine(model="wdl", seed=0, **kw):
    server = _server([("snd_order_embedding", VOCAB, E)])
    table = _table(server, "snd_order_embedding")
    params = _f32((_wdl_params if model == "wdl"
                   else _dcn_params)(_rng(seed)))
    eng = EmbedServingEngine(params,
                             {"snd_order_embedding": table},
                             model=model, **kw)
    return eng, server, params


# --------------------------------------------------------------------- #
# tower parity vs the numpy oracle
# --------------------------------------------------------------------- #

class TestOracleParity:
    @pytest.mark.parametrize("model", ["wdl", "dcn"])
    def test_ctr_engine_matches_numpy(self, model):
        """Engine scores (cache gather + jitted padded wave) equal the
        oracle forward over exact PS rows, across ragged wave sizes."""
        eng, server, params = _mk_ctr_engine(model, wave=3)
        rng = _rng(7)
        reqs = _ctr_requests(rng, 7)
        res = eng.run(reqs)
        assert len(res) == 7
        oracle = _np_wdl if model == "wdl" else _np_dcn
        for r in reqs:
            emb = np.asarray(
                server.sparse_pull("snd_order_embedding",
                                   r.item_ids.reshape(-1)),
                np.float32).reshape(r.n_pairs, 26 * E)
            want = oracle(params, emb, r.dense_features)
            got = res[r.request_id]
            assert got.finish_reason == "scored"
            assert got.scores.shape == (r.n_pairs,)
            np.testing.assert_allclose(got.scores, want,
                                       rtol=1e-4, atol=1e-6)

    def test_ncf_engine_matches_numpy(self):
        server = _server([("user_embed", VOCAB, NCF_W),
                          ("item_embed", VOCAB, NCF_W)])
        tables = {"user_embed": _table(server, "user_embed",
                                       width=NCF_W),
                  "item_embed": _table(server, "item_embed",
                                       width=NCF_W)}
        params = _f32(_ncf_params(_rng(5)))
        eng = EmbedServingEngine(params, tables, model="ncf",
                                 embed_dim=4, mlp_layers=(8, 8, 8, 8),
                                 wave=4)
        rng = _rng(11)
        reqs = [EmbedRequest(user_ids=rng.randint(0, VOCAB, n),
                             item_ids=rng.randint(0, VOCAB, n))
                for n in (1, 3, 2, 4, 1)]
        res = eng.run(reqs)
        for r in reqs:
            u = np.asarray(server.sparse_pull("user_embed", r.user_ids),
                           np.float32)
            it = np.asarray(server.sparse_pull("item_embed", r.item_ids),
                            np.float32)
            np.testing.assert_allclose(res[r.request_id].scores,
                                       _np_ncf(params, u, it),
                                       rtol=1e-4, atol=1e-6)

    def test_results_identical_across_wave_sizes(self):
        """Bucket padding + wave batching never change a score: the
        same trace through wave=1 and wave=8 engines agrees exactly."""
        rng = _rng(3)
        ids = rng.randint(0, VOCAB, (6, 2, 26))
        dense = rng.randn(6, 2, 13).astype(np.float32)
        outs = []
        for wave in (1, 8):
            eng, _, _ = _mk_ctr_engine("wdl", wave=wave)
            reqs = [EmbedRequest(item_ids=ids[i], dense_features=dense[i])
                    for i in range(6)]
            res = eng.run(reqs)
            outs.append(np.concatenate(
                [res[r.request_id].scores for r in reqs]))
        np.testing.assert_array_equal(outs[0], outs[1])


# --------------------------------------------------------------------- #
# cache behavior under load
# --------------------------------------------------------------------- #

class TestCacheBehavior:
    def test_zipf_hit_rate_floor(self):
        """The bench regime in miniature: zipf(1.05) ids against a
        cache holding 25% of the vocabulary keep the hit rate above a
        floor — the HET cache thesis applied to serving."""
        vocab = 256
        server = _server([("snd_order_embedding", vocab, E)])
        table = _table(server, "snd_order_embedding", vocab=vocab,
                       limit=128)
        eng = EmbedServingEngine(
            _f32(_wdl_params(_rng(0))),
            {"snd_order_embedding": table}, model="wdl", wave=8,
            queue_limit=256)
        rng = _rng(42)
        raw = rng.zipf(1.05, size=(96, 2, 26))
        reqs = [EmbedRequest(item_ids=((raw[i] - 1) % vocab))
                for i in range(96)]
        res = eng.run(reqs)
        assert len(res) == 96
        s = table.perf_summary()
        assert s["hit_rate"] >= 0.3
        assert s["pull_bytes"] > 0
        # per-result + snapshot surfacing of the same signal
        assert eng.metrics.snapshot()["cache_hit_rate_mean"] >= 0.3
        assert any(r.cache_hit_rate > 0.3 for r in res.values())
        assert "snd_order_embedding" in eng.cache_summary()

    def test_queue_full_backpressure(self):
        eng, _, _ = _mk_ctr_engine("wdl", wave=2, queue_limit=2)
        rng = _rng(1)
        for r in _ctr_requests(rng, 2):
            eng.submit(r)
        with pytest.raises(QueueFull):
            eng.submit(_ctr_requests(rng, 1)[0])
        assert eng.metrics.rejected == 1
        eng.run()
        assert eng.pending == 0


# --------------------------------------------------------------------- #
# PS outage: zero request loss (the chaos spine)
# --------------------------------------------------------------------- #

class _FlakyPS:
    """PSServer wrapper whose every verb raises while ``down`` — the
    serving-side twin of tests/test_faults.py's comm failure rig."""

    def __init__(self, server):
        self._server = server
        self.down = False

    def __getattr__(self, name):
        fn = getattr(self._server, name)

        def wrapper(*a, **kw):
            if self.down:
                raise PSConnectionError("PS down (test)")
            return fn(*a, **kw)
        return wrapper


class TestPSOutage:
    def test_ps_kill_zero_request_loss(self, tmp_path):
        """Mid-trace PS kill: warm requests serve stale, cold requests
        serve zeros, NOTHING is lost, and recovery resumes pulls — the
        training degradation protocol doing serving duty."""
        log = str(tmp_path / "serve.jsonl")
        server = _server([("snd_order_embedding", VOCAB, E)])
        flaky = _FlakyPS(server)
        table = CacheSparseTable(limit=64, vocab_size=VOCAB, width=E,
                                 key="snd_order_embedding", comm=flaky,
                                 policy="LRU")
        eng = EmbedServingEngine(
            _f32(_wdl_params(_rng(0))),
            {"snd_order_embedding": table}, model="wdl", wave=2,
            log_path=log)
        rng = _rng(9)
        warm = [EmbedRequest(item_ids=rng.randint(0, 32, (2, 26)))
                for _ in range(4)]
        res = eng.run(warm)

        flaky.down = True           # ---- the kill ----
        hot = [EmbedRequest(item_ids=rng.randint(0, 32, (2, 26)))
               for _ in range(2)]   # ids seen above -> stale hits
        cold = [EmbedRequest(item_ids=rng.randint(32, VOCAB, (2, 26)))
                for _ in range(2)]  # never cached -> zero vectors
        res.update(eng.run(hot + cold))

        flaky.down = False          # ---- recovery ----
        again = [EmbedRequest(item_ids=c.item_ids) for c in cold]
        res.update(eng.run(again))

        all_reqs = warm + hot + cold + again
        assert len(res) == len(all_reqs)          # ZERO loss
        for r in all_reqs:
            assert res[r.request_id].finish_reason == "scored"
        s = table.perf_summary()
        assert s["ps_failures"] > 0
        assert s["stale_served_rows"] > 0
        assert s["zero_served_rows"] > 0
        # cold scores during the outage came from zero embeddings;
        # after recovery the same ids score through real rows
        for c, a in zip(cold, again):
            assert not np.array_equal(res[c.request_id].scores,
                                      res[a.request_id].scores)
        # the serve stream stayed contract-clean through the chaos
        events, _ = read_events([log])
        assert check_span_balance(events) == []
        assert check_gather_balance(events) == []

    def test_outage_past_budget_surfaces(self, monkeypatch):
        """Degradation is BOUNDED: past HETU_CACHE_MAX_STALE failed
        RPCs the outage escapes (and the engine dumps its black box)."""
        monkeypatch.setenv("HETU_CACHE_MAX_STALE", "1")
        server = _server([("snd_order_embedding", VOCAB, E)])
        flaky = _FlakyPS(server)
        table = CacheSparseTable(limit=16, vocab_size=VOCAB, width=E,
                                 key="snd_order_embedding", comm=flaky)
        eng = EmbedServingEngine(
            _f32(_wdl_params(_rng(0))),
            {"snd_order_embedding": table}, model="wdl", wave=1)
        flaky.down = True
        rng = _rng(2)
        with pytest.raises(ConnectionError):
            eng.run(_ctr_requests(rng, 3))


# --------------------------------------------------------------------- #
# fleet: embedding replicas behind the router
# --------------------------------------------------------------------- #

def _embed_factory(seed=0, **kw):
    params = _f32(_wdl_params(_rng(seed)))
    server = _server([("snd_order_embedding", VOCAB, E)])

    def factory(i):
        return EmbedServingEngine(
            params, {"snd_order_embedding": _table(
                server, "snd_order_embedding")},
            model="wdl", **kw)
    return factory


class TestEmbedFleet:
    def test_router_hosts_embed_replicas(self):
        router = ServingRouter(_embed_factory(wave=2, queue_limit=16),
                               replicas=2)
        rng = _rng(4)
        reqs = _ctr_requests(rng, 8)
        res = router.run(reqs)
        assert len(res) == 8
        for r in reqs:
            assert res[r.request_id].finish_reason == "scored"
        snap = router.snapshot()
        assert snap["finished"] == 8 and snap["lost"] == 0

    def test_throughput_sheds_first(self):
        """The GPT shed ordering holds verbatim for the embedding
        workload: throughput-class waves are shed under pressure while
        latency-class requests all admit and finish."""
        factory = _embed_factory(wave=1, queue_limit=2,
                                 slo=[SLO("ttft", "latency", 60000.0)])
        router = ServingRouter(factory, replicas=2, shed_queue=0.5)
        rng = _rng(6)
        lat, shed, res = [], 0, {}
        for i in range(16):
            cls = "latency" if i % 4 == 0 else "throughput"
            req = _ctr_requests(rng, 1, cls=cls)[0]
            try:
                router.submit(req)
                if cls == "latency":
                    lat.append(req)
            except RouterShed:
                shed += 1
                assert cls == "throughput"   # sheds throughput FIRST
            except QueueFull:
                # embed waves retire synchronously: keep what the
                # backpressure step scores
                for out in router.step():
                    res[out.request_id] = out
        res.update(router.run())
        snap = router.snapshot()
        assert shed > 0 and snap["shed"] == shed
        assert snap["classes"]["latency"]["shed"] == 0
        assert snap["classes"]["throughput"]["shed"] == shed
        for r in lat:
            assert r.request_id in res
        assert snap["classes"]["latency"]["finished"] == len(lat)


# --------------------------------------------------------------------- #
# telemetry: the embed stream speaks the fleet vocabulary
# --------------------------------------------------------------------- #

class TestEmbedTelemetry:
    def test_stream_balanced_and_workload_tagged(self, tmp_path):
        log = str(tmp_path / "serve.jsonl")
        eng, _, _ = _mk_ctr_engine("wdl", wave=2, log_path=log)
        eng.run(_ctr_requests(_rng(8), 5))
        events, bad = read_events([log])
        assert not bad
        assert check_span_balance(events) == []
        assert check_gather_balance(events) == []
        kinds = {e["event"] for e in events}
        assert {"serve_submit", "serve_gather", "serve_admit",
                "serve_step", "serve_finish", "req_span",
                "req_retire"} <= kinds
        # every retire carries the gather/forward breakdown
        for e in events:
            if e["event"] == "req_retire":
                assert "gather_ms" in e and "forward_ms" in e
        stats = top.summarize(events, window=0)
        assert stats["workload"] == "embed"
        frame = top.render(stats, clock=0.0)
        assert "workload embed" in frame

    def test_snapshot_explains_the_wave(self):
        eng, _, _ = _mk_ctr_engine("wdl", wave=4)
        eng.run(_ctr_requests(_rng(12), 8))
        snap = eng.metrics.snapshot()
        assert snap["requests_finished"] == 8
        assert snap["requests_rejected"] == 0
        assert snap["pairs_per_sec"] > 0
        assert snap["gather_ms_p50"] is not None
        assert "gather_ms" in snap["components"]
        tail = eng.metrics.explain_tail()
        assert tail is not None
        assert eng.health() in ("ok", "degraded", "breach")


# --------------------------------------------------------------------- #
# the refactor regression: GPT serving is token-identical to offline
# across paged / int8-KV / speculative configs
# --------------------------------------------------------------------- #

def _rand_gpt(name="em", L=2, H=2, Dh=8, V=61, S=32, seed=0):
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
def gpt_model():
    return _rand_gpt()


class TestGPTByteIdentity:
    @pytest.mark.parametrize("kw", [
        dict(),
        dict(kv_block=4),
        dict(kv_quant="int8"),
        dict(spec=3, spec_adapt=False, spec_draft_layers=1),
    ], ids=["default", "paged", "int8", "spec"])
    def test_router_matches_offline(self, gpt_model, kw):
        """Every token the refactored substrate serves equals offline
        ``generate_fast`` — per config, through the fleet router."""
        p, cfg = gpt_model
        factory = lambda i: ServingEngine(   # noqa: E731
            p, cfg, slots=2, queue_limit=16, fast_path=False, **kw)
        router = ServingRouter(factory, replicas=2)
        rng = np.random.RandomState(17)
        reqs = [Request(prompt=[int(t) for t in
                                rng.randint(0, 61, rng.randint(1, 5))],
                        max_new_tokens=int(rng.randint(3, 7)))
                for _ in range(4)]
        res = router.run(reqs)
        for r in reqs:
            want = generate_fast(p, cfg, [r.prompt],
                                 num_tokens=r.max_new_tokens)[0]
            assert res[r.request_id].tokens.tolist() == want.tolist()
        assert router.snapshot()["lost"] == 0

    def test_mixed_request_types_rejected_cleanly(self, gpt_model):
        """Workload mismatch is a TypeError at submit, not a corrupted
        wave: the GPT engine refuses EmbedRequests and vice versa."""
        p, cfg = gpt_model
        eng = ServingEngine(p, cfg, slots=1, fast_path=False)
        with pytest.raises((TypeError, AttributeError)):
            eng.submit(EmbedRequest(
                item_ids=np.zeros((1, 26), np.int64)))
        emb, _, _ = _mk_ctr_engine("wdl")
        with pytest.raises(TypeError):
            emb.submit(Request(prompt=[1, 2], max_new_tokens=2))
