"""Fleet-wide KV intelligence (ISSUE 12): the prefix-cache directory,
cache-hit-maximizing routing, and prefill/decode disaggregation with
int8 KV handoff.

The acceptance spine: a role-split fleet (prefill-heavy + decode-heavy
replicas) moves every long prompt's KV from the prefill replica to its
decode home through ``PagedKVManager.export_blocks`` /
``import_blocks`` — token-identical to offline ``generate_fast``, with
paired ``kv_handoff_out``/``kv_handoff_in`` events (the
``check_handoff_balance`` trace rule), ``handoff_ms`` lifecycle
attribution on the destination engine, and ~4x cheaper bytes when the
wire rides the PR 9 int8 codec.  Around it: export/import round-trip
properties on both managers (f32 + int8 pools, COW-shared blocks,
truncate-after-import, byte budgets), the PrefixDirectory unit
surface (register/lookup/TTL/evict/drop), directory-first routing
(hit/steal/miss/stale verdicts, back-compat ``prefix_misses``), chaos
directory-kill degradation to exact PR 8 affinity behavior with zero
token loss, and the ``hetu_top --fleet`` role + directory columns.

All CPU-harness, all smoke-tier (tiny random-weight GPTs — the
contract is placement and data movement, not model quality).
"""

import os

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax.numpy as jnp
from hetu_tpu import quant, telemetry
from hetu_tpu.kv_layout import kv_heads
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.ps import faults
from hetu_tpu.serving import (
    PagedKVManager, PrefixDirectory, Request, ServingEngine, ServingRouter,
    prefix_hash, resolve_handoff_quant,
)
from hetu_tpu.telemetry import top
from hetu_tpu.telemetry.trace import (
    check_handoff_balance, check_span_balance, read_events,
)

pytestmark = pytest.mark.smoke


def _rand_gpt(name="fk", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    """Deterministic random params in generate_fast's naming contract
    (mirrors test_router's helper; kept local so the files stay
    independently runnable)."""
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
    monkeypatch.delenv("HETU_CHAOS", raising=False)
    monkeypatch.delenv("HETU_HANDOFF_QUANT", raising=False)
    faults.reset_plans()
    telemetry.reset()
    yield
    faults.reset_plans()
    telemetry.reset()


def _factory(model, **kw):
    p, cfg = model
    kw.setdefault("slots", 2)
    kw.setdefault("queue_limit", 16)
    kw.setdefault("fast_path", False)
    kw.setdefault("kv_block", 8)
    kw.setdefault("prefix_share", True)
    return lambda i: ServingEngine(p, cfg, **kw)


def _offline(model, req):
    p, cfg = model
    return generate_fast(p, cfg, [req.prompt],
                         num_tokens=req.max_new_tokens)[0].tolist()


def _mgr(**kw):
    base = dict(layers=2, heads=2, head_dim=8, slots=2, max_seq_len=32,
                block=8, prefix_share=True)
    base.update(kw)
    return PagedKVManager(**base)


def _fill(m, seed=0):
    """Random content into EVERY pool block so gathered spans are
    distinguishable (int8 pools get a (payload, scales) pair)."""
    rng = np.random.RandomState(seed)

    def one(cache):
        if isinstance(cache, tuple):
            q = rng.randint(-127, 128, cache[0].shape).astype(np.int8)
            s = (rng.rand(*cache[1].shape) + 0.01).astype(np.float32)
            return (jnp.asarray(q), jnp.asarray(s))
        return jnp.asarray(rng.randn(*cache.shape).astype(np.float32))

    m.cache_k = one(m.cache_k)
    m.cache_v = one(m.cache_v)


def _span_f32(m, slot):
    """The slot's filled span as dequantized f32 host arrays
    ``[L, n, block, H, Dh]``: a float pool's rows seen through
    ``kv_heads`` (``_fill`` leaves garbage in the pad columns, which the
    wire strips and an importing pool zeroes)."""
    n = m.blocks_needed(int(m.lengths[slot]))
    idx = [int(b) for b in m.tables[slot, :n]]

    def one(cache):
        if isinstance(cache, tuple):
            return np.asarray(quant.kv_decode(
                jnp.asarray(np.asarray(cache[0])[:, idx]),
                jnp.asarray(np.asarray(cache[1])[:, idx])))
        return kv_heads(np.asarray(cache)[:, idx], m.heads, m.head_dim)

    return one(m.cache_k), one(m.cache_v)


# --------------------------------------------------------------------- #
# export/import round-trip properties (satellite 1)
# --------------------------------------------------------------------- #

class TestHandoffWire:
    def test_resolve_handoff_quant_modes(self, monkeypatch):
        assert resolve_handoff_quant("auto") == "auto"
        assert resolve_handoff_quant("int8") == "int8"
        assert resolve_handoff_quant("off") is None
        assert resolve_handoff_quant("0") is None
        monkeypatch.setenv("HETU_HANDOFF_QUANT", "int8")
        assert resolve_handoff_quant() == "int8"
        with pytest.raises(ValueError):
            resolve_handoff_quant("fp4")

    def test_paged_f32_round_trip_bit_identical(self):
        """Exact pool, auto wire: the imported span is bit-identical,
        the source untouched (pure read), and the byte budget adds up
        on both sides."""
        src, dst = _mgr(), _mgr()
        _fill(src, seed=1)
        prompt = list(range(1, 12))                       # 11 tokens
        slot, _ = src.alloc("r0", prompt, len(prompt))
        src.advance(slot, len(prompt))
        ref_before = src.ref.copy()
        pay = src.export_blocks(slot)
        assert pay["layout"] == "paged" and pay["quant"] is None
        assert pay["length"] == 11 and pay["k"].shape[1] == 2
        assert np.array_equal(src.ref, ref_before)        # pure read
        assert src.exports == 1 and src.export_bytes == pay["nbytes"]
        slot2 = dst.import_blocks(pay, "r0", prompt=prompt)
        assert slot2 is not None
        assert int(dst.lengths[slot2]) == 11
        k0, v0 = _span_f32(src, slot)
        k1, v1 = _span_f32(dst, slot2)
        assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
        assert dst.imports == 1 and dst.import_bytes == pay["nbytes"]
        assert dst.stats()["imports"] == 1
        assert src.stats()["export_bytes"] == pay["nbytes"]

    def test_paged_int8_pool_native_wire(self):
        """int8 pool to int8 pool: the native (payload, scales) pair IS
        the wire — no requantization, bit-identical on arrival."""
        src, dst = _mgr(dtype=jnp.int8), _mgr(dtype=jnp.int8)
        _fill(src, seed=2)
        prompt = list(range(1, 10))
        slot, _ = src.alloc("r0", prompt, len(prompt))
        src.advance(slot, len(prompt))
        pay = src.export_blocks(slot)
        assert pay["quant"] == "int8"
        assert isinstance(pay["k"], tuple) and pay["k"][0].dtype == np.int8
        assert pay["nbytes"] < pay["raw_nbytes"] / 2
        slot2 = dst.import_blocks(pay, "r0")
        k0, v0 = _span_f32(src, slot)
        k1, v1 = _span_f32(dst, slot2)
        assert np.array_equal(k0, k1) and np.array_equal(v0, v1)

    def test_paged_forced_int8_wire_cheap_and_close(self):
        """f32 pools with a forced int8 wire: ~4x fewer bytes (scale
        planes ride along), small quantization error, and the mixed
        direction (int8 wire -> exact pool) dequantizes."""
        src, dst = _mgr(head_dim=16), _mgr(head_dim=16)
        _fill(src, seed=3)
        prompt = list(range(1, 14))
        slot, _ = src.alloc("r0", prompt, len(prompt))
        src.advance(slot, len(prompt))
        pay = src.export_blocks(slot, quant_mode="int8")
        assert pay["quant"] == "int8"
        assert pay["nbytes"] < pay["raw_nbytes"] / 3
        slot2 = dst.import_blocks(pay, "r0")
        k0, v0 = _span_f32(src, slot)
        k1, v1 = _span_f32(dst, slot2)
        assert float(np.abs(k0 - k1).max()) < 0.05
        assert float(np.abs(v0 - v1).max()) < 0.05

    def test_cow_shared_blocks_survive_export_and_reregister(self):
        """A COW-shared prefix stays shared on the source after export
        (refcounts untouched), and ``import_blocks(prompt=...)``
        re-registers it on the destination so the next admission there
        attaches the imported blocks refcounted."""
        src, dst = _mgr(), _mgr()
        _fill(src, seed=4)
        p16 = list(range(1, 17))                          # 2 full blocks
        slot, _ = src.alloc("a", p16 + [40], 20)
        src.advance(slot, 17)
        src.register_prefix(p16 + [40], slot)
        shared = src.blocks_shared
        assert shared >= 2                                # prefix holds refs
        pay = src.export_blocks(slot)
        assert src.blocks_shared == shared                # untouched
        slot2 = dst.import_blocks(pay, "a", prompt=p16 + [40])
        assert dst.stats()["prefix_entries"] >= 1
        dst.release(slot2)                                # prefix keeps blocks
        free_before = dst.free_blocks
        slot3, cached = dst.alloc("b", p16 + [41], 20)
        assert cached == 16                               # warm attach
        assert dst.free_blocks == free_before - 1         # only the tail
        k0, _ = _span_f32(src, slot)
        k1, _ = _span_f32(dst, slot3)
        assert np.array_equal(k0[:, :2], k1[:, :2])       # shared blocks

    def test_truncate_after_import(self):
        """Speculative rollback composes with a handoff: an imported
        slot truncates at refcount discipline — the reservation is
        KEPT (a replay holds the same blocks), the surviving span's
        content is intact, and release returns everything."""
        src, dst = _mgr(), _mgr()
        _fill(src, seed=5)
        prompt = list(range(1, 18))                       # 3 blocks
        slot, _ = src.alloc("r", prompt, len(prompt))
        src.advance(slot, len(prompt))
        pay = src.export_blocks(slot)
        slot2 = dst.import_blocks(pay, "r", reserve=24)
        free_after_import = dst.free_blocks
        dst.truncate(slot2, 9)                            # roll back 8
        assert int(dst.lengths[slot2]) == 9
        assert dst.free_blocks == free_after_import       # reservation kept
        k0, _ = _span_f32(src, slot)
        k1, _ = _span_f32(dst, slot2)
        assert np.array_equal(k0[:, :1], k1[:, :1])
        dst.release(slot2)
        assert dst.free_blocks == dst.capacity_blocks

    def test_import_backpressure_and_validation(self):
        src = _mgr()
        _fill(src, seed=6)
        prompt = list(range(1, 10))
        slot, _ = src.alloc("r", prompt, len(prompt))
        src.advance(slot, len(prompt))
        pay = src.export_blocks(slot)
        tiny = _mgr(slots=1, pool_blocks=2)               # 1 usable block
        assert tiny.import_blocks(pay, "r") is None       # blocks short
        with pytest.raises(ValueError):
            _mgr(block=16).import_blocks(pay, "r")        # block mismatch
        with pytest.raises(ValueError):
            _mgr().import_blocks(pay, "r", reserve=4)     # below length
        with pytest.raises(ValueError):
            _mgr().import_blocks(dict(pay, layout="contiguous"), "r")


# --------------------------------------------------------------------- #
# the directory (tentpole unit surface)
# --------------------------------------------------------------------- #

class TestPrefixDirectory:
    def test_register_lookup_longest_cut(self):
        d = PrefixDirectory()
        kv = _mgr()
        d.attach(0, kv)
        _fill(kv)
        p16 = list(range(1, 17))
        slot, _ = kv.alloc("a", p16 + [40], 20)
        kv.advance(slot, 17)
        kv.register_prefix(p16 + [40], slot)              # feeds the map
        assert d.registrations > 0
        hint, outcome = d.lookup(p16 + [41, 42])
        assert outcome is None and hint == (0, 16)        # longest cut
        hint, outcome = d.lookup(list(range(50, 60)))
        assert hint is None and outcome == "miss"
        assert d.hit_rate == 0.0                          # router stamps hits
        assert d.misses == 1

    def test_short_prompt_never_hints(self):
        d = PrefixDirectory()
        assert d.lookup([1, 2, 3]) == (None, "miss")

    def test_eviction_and_drop_replica_clear_entries(self):
        d = PrefixDirectory()
        kv = _mgr(slots=2, pool_blocks=5)                 # tight pool
        d.attach(0, kv)
        _fill(kv)
        p8 = list(range(1, 9))
        slot, _ = kv.alloc("a", p8 + [30], 10)
        kv.advance(slot, 9)
        kv.register_prefix(p8 + [30], slot)
        assert d.snapshot()["entries"] > 0
        kv.release(slot)
        # churn until the LRU eviction fires and the callback drains
        for i in range(3):
            s, _ = kv.alloc("b%d" % i, [40 + i] * 9, 18)
            if s is None:
                break
            kv.advance(s, 9)
            kv.release(s)
        assert d.evictions > 0
        d2 = PrefixDirectory()
        d2.attach(1, _mgr())
        d2.register(1, (1, 2, 3, 4, 5, 6, 7, 8))
        assert d2.snapshot()["entries"] == 1
        d2.drop_replica(1)
        assert d2.snapshot()["entries"] == 0

    def test_ttl_staleness(self):
        clock = [0.0]
        d = PrefixDirectory(ttl=5.0, now=lambda: clock[0])
        d.attach(0, _mgr())                               # fleet block size
        d.register(0, tuple(range(8)))
        hint, outcome = d.lookup(list(range(8)) + [9])
        assert hint == (0, 8) and outcome is None
        clock[0] = 10.0                                   # past the TTL
        hint, outcome = d.lookup(list(range(8)) + [9])
        assert hint is None and outcome == "stale"
        assert d.stale == 1
        # re-registration refreshes the stamp
        d.register(0, tuple(range(8)))
        hint, outcome = d.lookup(list(range(8)) + [9])
        assert hint == (0, 8) and outcome is None

    def test_prefix_hash_stable(self):
        assert prefix_hash([1, 2, 3]) == prefix_hash((1, 2, 3))
        assert prefix_hash([1, 2, 3]) != prefix_hash([1, 2, 4])


# --------------------------------------------------------------------- #
# directory-first routing
# --------------------------------------------------------------------- #

class TestDirectoryRouting:
    def test_warm_wave_hits_and_snapshot_surface(self, model):
        """Wave 1 warms a shared system prompt; wave 2 (different
        sessions) gets directory hits, the hit rate lands in
        ``snapshot()``, and the route events carry the verdicts."""
        router = ServingRouter(_factory(model), replicas=2)
        sys_p = list(range(1, 18))
        w1 = [Request(prompt=sys_p + [20 + i], max_new_tokens=3,
                      session_id=f"a{i}") for i in range(3)]
        res1 = router.run(w1)
        w2 = [Request(prompt=sys_p + [30 + i], max_new_tokens=3,
                      session_id=f"b{i}") for i in range(4)]
        res2 = router.run(w2)
        snap = router.snapshot()
        assert snap["directory"]["hits"] > 0
        assert snap["directory_hit_rate"] > 0
        assert snap["directory_killed"] is False
        # back-compat: the split counter still answers to the old key
        assert snap["prefix_misses"] == snap["affinity_prefix_misses"]
        assert router.prefix_misses == snap["affinity_prefix_misses"]
        routes = [e for e in telemetry.get_sink().recent()
                  if e.get("event") == "router_route"]
        verdicts = {e.get("directory") for e in routes} - {None}
        assert "hit" in verdicts
        for r in w1 + w2:
            got = (res1 if r in w1 else res2)[r.request_id]
            assert got.tokens.tolist() == _offline(model, r)

    def test_directory_off_is_pr8_fleet(self, model):
        """``directory=False`` (or a kill) is exactly the PR 8 fleet:
        no directory in the snapshot, affinity-only routing."""
        router = ServingRouter(_factory(model), replicas=2,
                               directory=False)
        res = router.run([Request(prompt=list(range(1, 18)),
                                  max_new_tokens=3)])
        snap = router.snapshot()
        assert snap["directory"] is None
        assert snap["directory_hit_rate"] is None
        assert len(res) == 1

    def test_chaos_kill_degrades_with_zero_loss(self, model,
                                                monkeypatch, tmp_path):
        """A seeded chaos kill of the DIRECTORY mid-trace: the fleet
        degrades to plain affinity, loses zero requests, stays
        token-identical to offline, and records the kill (failure
        event + flight dump + snapshot flag)."""
        flog = str(tmp_path / "failure.jsonl")
        flt = str(tmp_path / "flight.jsonl")
        monkeypatch.setenv("HETU_FAILURE_LOG", flog)
        monkeypatch.setenv("HETU_FLIGHT_LOG", flt)
        monkeypatch.setenv("HETU_CHAOS", "seed=5,kill=3,role=directory")
        faults.reset_plans()
        router = ServingRouter(_factory(model), replicas=2)
        sys_p = list(range(1, 18))
        reqs = [Request(prompt=sys_p + [50 + i], max_new_tokens=3,
                        session_id=f"c{i}") for i in range(8)]
        res = router.run(reqs)
        snap = router.snapshot()
        assert snap["directory_killed"] is True
        assert snap["directory"] is None
        assert snap["lost"] == 0 and len(res) == 8
        for r in reqs:
            assert res[r.request_id].tokens.tolist() == _offline(model, r)
        events, bad = read_events([flog])
        assert bad == 0
        kills = [e for e in events
                 if e.get("event") == "directory_killed"]
        assert len(kills) == 1 and "reason" in kills[0]
        assert os.path.exists(flt)                        # black box dumped

    def test_roles_validation(self, model):
        with pytest.raises(ValueError):
            ServingRouter(_factory(model), replicas=2, roles="warp")


# --------------------------------------------------------------------- #
# prefill/decode disaggregation (tentpole)
# --------------------------------------------------------------------- #

class TestHandoffRouting:
    def test_roles_handoff_token_identical(self, model):
        """The full disaggregated path: long prompts prefill on the
        prefill-heavy replica, the KV span hands off to a decode-heavy
        home, outputs stay token-identical to offline, events pair,
        and the destination engine carries handoff_ms attribution.
        Eight slots a replica: an import needs a free slot as its write
        vehicle, and a two-slot decode replica is full of the earlier
        arrivals' waves when the later ones land (those then admit
        cold: ``handoff_failed``, never an error)."""
        router = ServingRouter(_factory(model, slots=8), replicas=2,
                               roles="prefill,decode")
        assert router.roles == ["prefill", "decode"]
        assert router.replicas[0].kind == "prefill"
        sys_p = list(range(1, 18))
        reqs = [Request(prompt=sys_p + [20 + i], max_new_tokens=4,
                        session_id=f"s{i}") for i in range(6)]
        res = router.run(reqs)
        snap = router.snapshot()
        assert snap["handoffs"] == 6
        assert snap["handoff_failed"] == 0
        assert snap["handoffs_skipped"] == 0    # affinity yields to roles
        assert snap["handoff_bytes"] > 0
        for r in reqs:
            assert res[r.request_id].tokens.tolist() == _offline(model, r)
        ev = telemetry.get_sink().recent()
        outs = [e for e in ev if e.get("event") == "kv_handoff_out"]
        ins = [e for e in ev if e.get("event") == "kv_handoff_in"]
        assert len(outs) == 6 and len(ins) == 6
        assert all(e["replica"] == 0 and e["to_replica"] == 1
                   for e in outs)
        assert check_handoff_balance(ev) == []
        assert check_span_balance(ev) == []
        # both phases route-logged, hop-free
        routes = [e for e in ev if e.get("event") == "router_route"]
        phases = {e.get("phase") for e in routes}
        assert phases == {"prefill", "decode"}
        comp = router.replicas[1].engine.metrics.snapshot()["components"]
        assert comp["handoff_ms"]["p99_ms"] > 0
        # the decode replica admits warm: its pool saw real imports
        assert router.replicas[1].engine.kv.stats()["imports"] == 6

    def test_short_prompts_skip_the_detour(self, model):
        """Prompts at or under one block never disaggregate — the
        detour only pays for itself when a real prefix span moves."""
        router = ServingRouter(_factory(model), replicas=2,
                               roles="prefill,decode")
        res = router.run([Request(prompt=[3, 4, 5], max_new_tokens=3)
                          for _ in range(3)])
        snap = router.snapshot()
        assert snap["handoffs"] == 0 and len(res) == 3

    def test_int8_wire_cheaper_than_auto(self, model):
        """Forcing the int8 wire moves ~4x fewer bytes than the exact
        f32 wire on the same trace (scale planes included)."""
        sys_p = list(range(1, 18))

        def run_one(hq):
            telemetry.reset()
            router = ServingRouter(_factory(model), replicas=2,
                                   roles="prefill,decode",
                                   handoff_quant=hq)
            reqs = [Request(prompt=sys_p + [20 + i], max_new_tokens=3)
                    for i in range(3)]
            res = router.run(reqs)
            assert len(res) == 3
            snap = router.snapshot()
            assert snap["handoffs"] == 3
            return snap["handoff_bytes"]

        exact = run_one("off")
        cheap = run_one("int8")
        # Dh=8 here: (8 + 4) / 32 per value — bigger heads do better
        assert cheap < exact / 2

    def test_mixed_fleet_roles_inactive(self, model):
        """A roles string without both phases never disaggregates."""
        router = ServingRouter(_factory(model), replicas=2,
                               roles="prefill,mixed")
        assert router._roles_active is False
        res = router.run([Request(prompt=list(range(1, 18)),
                                  max_new_tokens=3)])
        assert router.snapshot()["handoffs"] == 0 and len(res) == 1


# --------------------------------------------------------------------- #
# the trace rule (satellite 2)
# --------------------------------------------------------------------- #

class TestHandoffTraceRule:
    def _rec(self, kind, **f):
        return {"t": 1.0, "event": kind, **f}

    def _pair(self, rid="r1"):
        return [self._rec("kv_handoff_out", request=rid, replica=0,
                          to_replica=1),
                self._rec("kv_handoff_in", request=rid, replica=1,
                          from_replica=0)]

    def _finishes(self, rid="r1", n=2):
        return [self._rec("serve_finish", request=rid, reason="length",
                          n_generated=2, replica=i % 2)
                for i in range(n)]

    def test_paired_stream_clean(self):
        assert check_handoff_balance(
            self._pair() + self._finishes()) == []

    def test_out_without_in_flagged(self):
        stream = [self._rec("kv_handoff_out", request="r1", replica=0,
                            to_replica=1)]
        problems = check_handoff_balance(stream)
        assert len(problems) == 1 and "never landed" in problems[0]

    def test_in_without_out_flagged(self):
        stream = [self._rec("kv_handoff_in", request="r1", replica=1,
                            from_replica=0)]
        problems = check_handoff_balance(stream)
        assert len(problems) == 1 and "never exported" in problems[0]

    def test_double_retire_flagged_hop_exempt(self):
        bad = self._pair() + self._finishes(n=3)
        problems = check_handoff_balance(bad)
        assert len(problems) == 1 and "retired 3" in problems[0]
        exempt = bad + [self._rec("router_hop", request="r1",
                                  to_replica=1)]
        assert check_handoff_balance(exempt) == []

    def test_flight_dump_stream_exempt(self):
        stream = [self._rec("flight_dump", reason="x"),
                  self._rec("kv_handoff_out", request="r1", replica=0,
                            to_replica=1)]
        assert check_handoff_balance(stream) == []

    def test_drop_records_not_paired(self):
        stream = [self._rec("kv_handoff_drop", request="r1", replica=0)]
        assert check_handoff_balance(stream) == []

    def test_cli_check_reports_handoff_violations(self, model,
                                                  tmp_path,
                                                  monkeypatch, capsys):
        """``hetu_trace --check`` over a real disaggregated run is
        green and counts handoff violations in the summary."""
        from hetu_tpu.telemetry.trace import main as trace_main
        slog = str(tmp_path / "serve.jsonl")
        monkeypatch.setenv("HETU_SERVE_LOG", slog)
        router = ServingRouter(_factory(model), replicas=2,
                               roles="prefill,decode")
        router.run([Request(prompt=list(range(1, 18)) + [30 + i],
                            max_new_tokens=3) for i in range(2)])
        assert router.snapshot()["handoffs"] == 2
        rc = trace_main([slog, "--check"])
        out = capsys.readouterr().out
        assert rc == 0
        assert '"handoff_violations": 0' in out


# --------------------------------------------------------------------- #
# hetu_top --fleet columns (satellite 3)
# --------------------------------------------------------------------- #

class TestFleetTopKV:
    def test_fleet_rows_carry_role_and_directory(self, model, tmp_path,
                                                 monkeypatch, capsys):
        slog = str(tmp_path / "serve.jsonl")
        monkeypatch.setenv("HETU_SERVE_LOG", slog)
        # a free slot for every import, as in TestHandoffRouting
        router = ServingRouter(_factory(model, slots=8), replicas=2,
                               roles="prefill,decode")
        sys_p = list(range(1, 18))
        router.run([Request(prompt=sys_p + [20 + i], max_new_tokens=3,
                            session_id=f"s{i}") for i in range(4)])
        stats = top.summarize_fleet(read_events([slog])[0])
        rows = {r["replica"]: r for r in stats["replicas"]}
        assert rows[0]["role"] == "prefill"
        assert rows[1]["role"] == "decode"
        assert stats["handoffs"] == 4
        pre = stats["prefix"]
        assert pre["misses"] > 0                  # cold storm, all misses
        rc = top.main([slog, "--fleet", "--once"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "breaker" in out and "requeued" in out
        assert "dir%" in out and "prefix" in out and "handoffs" in out
        assert "prefill" in out and "decode" in out
        assert "\n  0 " in out and "\n  1 " in out

    def test_directory_hit_rate_column(self, model, tmp_path,
                                       monkeypatch):
        slog = str(tmp_path / "serve.jsonl")
        monkeypatch.setenv("HETU_SERVE_LOG", slog)
        router = ServingRouter(_factory(model), replicas=2)
        sys_p = list(range(1, 18))
        router.run([Request(prompt=sys_p + [20], max_new_tokens=3,
                            session_id="a")])
        router.run([Request(prompt=sys_p + [30 + i], max_new_tokens=3,
                            session_id=f"b{i}") for i in range(3)])
        stats = top.summarize_fleet(read_events([slog])[0])
        hit_rates = [r["dir_hit_rate"] for r in stats["replicas"]
                     if r["dir_hit_rate"] is not None]
        assert stats["prefix"]["hits"] > 0
        assert any(h > 0 for h in hit_rates)
