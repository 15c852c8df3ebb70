"""Tiered KV (ISSUE 17): the HBM -> host-RAM ring -> sharded-PS cold
store ladder behind the paged pool (serving/kv_tiers.py).

The acceptance spine: evicting a refcount-zero prefix SPILLS its int8
handoff wire payload down the ladder instead of dropping it, an
admission miss FETCHES it back up through ``import_blocks`` token-
identically, the directory's tier column keeps demoted prefixes
routable, and a chaos PS kill mid-traffic degrades the whole ladder to
today's drop-on-evict with zero request loss.  Around it: ring LRU
eviction order and host->PS demotion, the refresh-no-double-spill
ledger rule and its ``hetu_trace --check`` tier-balance twin
(synthetic violations + clean pass), the retire-path spill fallback
when no peer can absorb a hot prefix, ShardedPSClient kv_* round
trips, and both-knobs-off == byte-identical drop-on-evict.

All CPU-harness, all smoke-tier (tiny random-weight GPTs — the
contract is data movement and accounting, not model quality).
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
import jax.numpy as jnp
from hetu_tpu import telemetry
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.ps import faults
from hetu_tpu.ps.server import PSServer
from hetu_tpu.ps.sharded import ShardedPSClient
from hetu_tpu.serving import (
    PagedKVManager, PrefixDirectory, Request, ServingEngine,
    ServingRouter, TieredKVStore, prefix_hash,
)
from hetu_tpu.serving.kv_tiers import PS_NAMESPACE
from hetu_tpu.serving.replica import RETIRED
from hetu_tpu.telemetry.trace import check_tier_balance, read_events

pytestmark = pytest.mark.smoke


def _rand_gpt(name="kt", L=2, H=2, Dh=8, V=61, S=32, seed=0):
    """Deterministic random params in generate_fast's naming contract
    (mirrors test_fleet_kv's helper; kept local so the files stay
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
    monkeypatch.delenv("HETU_KV_HOST_BYTES", raising=False)
    monkeypatch.delenv("HETU_KV_PS_TIER", raising=False)
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
    distinguishable."""
    rng = np.random.RandomState(seed)

    def one(cache):
        if isinstance(cache, tuple):
            q = rng.randint(-127, 128, cache[0].shape).astype(np.int8)
            s = (rng.rand(*cache[1].shape) + 0.01).astype(np.float32)
            return (jnp.asarray(q), jnp.asarray(s))
        return jnp.asarray(rng.randn(*cache.shape).astype(np.float32))

    m.cache_k = one(m.cache_k)
    m.cache_v = one(m.cache_v)


def _register(m, prompt, rid="r0"):
    """Alloc + register + release so the prefix sits refcount-held in
    the pool's prefix cache with no live slot (the spillable state)."""
    slot, _ = m.alloc(rid, prompt, len(prompt))
    assert slot is not None
    m.advance(slot, len(prompt))
    m.register_prefix(prompt, slot)
    m.release(slot)
    return tuple(int(t) for t in prompt)


def _store(m, replica=0, **kw):
    """A wired store over one manager (attach sets the spill hook)."""
    st = TieredKVStore(**kw)
    st.attach(replica, m)
    return st


def _pay_eq(a, b):
    ka = a["k"][0] if isinstance(a["k"], tuple) else a["k"]
    kb = b["k"][0] if isinstance(b["k"], tuple) else b["k"]
    return (a["length"] == b["length"]
            and np.array_equal(np.asarray(ka), np.asarray(kb)))


# --------------------------------------------------------------------- #
# the ladder: spill/fetch round trips (tentpole)
# --------------------------------------------------------------------- #

class TestLadder:
    def test_evict_spills_to_host_and_fetch_is_identical(self):
        """LRU pressure spills the evicted prefix's payload into the
        host ring; the fetched payload is the byte-identical wire form
        the pool would have exported, and the ledger pairs one spill
        with one fetch."""
        m = _mgr(slots=2, max_seq_len=32)
        _fill(m, seed=1)
        st = _store(m, host_bytes=1 << 20)
        p1 = list(range(1, 9))
        toks = _register(m, p1, "a")
        ref = m.export_prefix(toks, count=False)
        # fill the pool with fresh prompts until p1's blocks evict
        nxt = 30
        while tuple(toks) in m._prefix:
            _register(m, [nxt + i for i in range(8)], f"f{nxt}")
            nxt += 10
        assert m.spills == 1 and st.spills["host"] == 1
        hit = st.lookup(p1 + [99], m.block)
        assert hit is not None and hit[0] == toks and hit[2] == "host"
        pay = st.fetch(toks)
        assert pay is not None and _pay_eq(pay, ref)
        assert st.fetches["host"] == 1
        assert st.lookup(p1 + [99], m.block) is None     # popped
        ev = [e for e in telemetry.get_sink().recent()
              if e.get("event") in ("kv_spill", "kv_fetch")]
        assert [e["event"] for e in ev] == ["kv_spill", "kv_fetch"]
        assert ev[0]["prefix"] == ev[1]["prefix"] == prefix_hash(toks)

    def test_ring_overflow_demotes_to_ps_in_lru_order(self):
        """A byte-capped ring demotes its OLDEST resident to the PS
        rung (insertion-ordered LRU); the demoted payload fetches back
        from the cold store intact, and the demotion is a counter, not
        a second ledger entry."""
        m = _mgr(slots=4, max_seq_len=32, pool_blocks=16)
        _fill(m, seed=2)
        probe = _register(m, list(range(1, 9)), "p")
        one_bytes = m.export_prefix(probe, count=False)["nbytes"]
        srv = PSServer()
        st = _store(m, host_bytes=2 * one_bytes, ps_tier=True,
                    ps=ShardedPSClient(servers=[srv]))
        pays, toks = {}, []
        for j in range(3):
            t = tuple(range(10 * j + 1, 10 * j + 9))
            pays[t] = m._export_span(
                np.asarray([j], np.int32), 8, None, count=False)
            assert st.spill(t, pays[t])
            toks.append(t)
        # oldest (toks[0]) demoted; two newest still in the ring
        assert st.demotes == 1 and st.spills == {"host": 3, "ps": 0}
        assert st.lookup(list(toks[0]) + [99], m.block)[2] == "ps"
        assert st.lookup(list(toks[1]) + [99], m.block)[2] == "host"
        assert srv.kv_keys() == [PS_NAMESPACE + prefix_hash(toks[0])]
        got = st.fetch(toks[0])
        assert got is not None and _pay_eq(got, pays[toks[0]])
        assert st.fetches == {"host": 0, "ps": 1}
        assert srv.kv_keys() == []                       # popped cold too
        st.close()
        bal = check_tier_balance(
            [e for e in telemetry.get_sink().recent()])
        assert bal == []                                 # demote != event

    def test_refresh_is_one_residency_one_ledger_entry(self):
        """Re-spilling a resident prefix refreshes its LRU stamp —
        refreshed entries outlive older unrefreshed ones — and emits
        NO second kv_spill (the tier-balance rule would flag it)."""
        m = _mgr()
        _fill(m, seed=3)
        probe = _register(m, list(range(1, 9)), "p")
        pay = m.export_prefix(probe, count=False)
        st = _store(m, host_bytes=2 * pay["nbytes"])
        a, b = tuple(range(1, 9)), tuple(range(11, 19))
        assert st.spill(a, pay) and st.spill(b, pay)
        assert st.spill(a, pay)                          # refresh a
        assert st.refreshes == 1 and st.spills["host"] == 2
        st.spill(tuple(range(21, 29)), pay)              # overflow: b dies
        assert st.lookup(list(a) + [99], m.block) is not None
        assert st.lookup(list(b) + [99], m.block) is None
        assert st.drops["host"] == 1                     # no PS rung
        st.close()
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []

    def test_host_bytes_zero_is_byte_identical_drop_on_evict(self, model):
        """Both knobs off: from_env wires NOTHING — no store, no spill
        hook, no tier events, counters byte-identical to the pre-tier
        fleet."""
        assert TieredKVStore.from_env() is None
        router = ServingRouter(_factory(model), replicas=1)
        assert router.kv_tiers is None
        kv = router.replicas[0].engine.kv
        assert kv.on_prefix_spill is None and kv.tier_store is None
        res = router.run([Request(prompt=list(range(1, 12)) + [20 + i],
                                  max_new_tokens=3, request_id=f"z{i}")
                          for i in range(6)])
        assert len(res) == 6 and router.snapshot()["lost"] == 0
        assert router.snapshot()["kv_tiers"] is None
        assert kv.spills == 0
        assert not [e for e in telemetry.get_sink().recent()
                    if e.get("event", "").startswith("kv_spill")]


# --------------------------------------------------------------------- #
# fleet integration: storm -> spill -> tier fetch, token identity
# --------------------------------------------------------------------- #

class TestFleetTiering:
    def test_storm_tier_fetch_token_identical(self, model):
        """A working set larger than the pool: wave 1's prefixes evict
        to the host ring under wave 2's pressure; re-asking wave 1
        routes through the directory's tier column, admission fetches
        the span back, and outputs stay token-identical to offline."""
        store = TieredKVStore(host_bytes=8 << 20)
        router = ServingRouter(_factory(model, slots=2, pool_blocks=8),
                               replicas=1, kv_tiers=store)
        assert router.directory.tiered is True
        heads = [list(range(1, 9)),
                 [9, 10, 11, 12, 13, 14, 15, 16],
                 [17, 18, 19, 20, 21, 22, 23, 24],
                 [25, 26, 27, 28, 29, 30, 31, 32]]
        w1 = [Request(prompt=h + [40 + i], max_new_tokens=3,
                      request_id=f"s{i}", session_id=f"s{i}")
              for i, h in enumerate(heads)]
        res = dict(router.run(w1))
        # wave 2 re-asks the same heads from NEW sessions: the pool is
        # far too small to still hold them all, so the directory's
        # tier column routes at least one through the ladder
        w2 = [Request(prompt=h + [50 + i], max_new_tokens=3,
                      request_id=f"t{i}", session_id=f"t{i}")
              for i, h in enumerate(heads)]
        res.update(router.run(w2))
        reqs = w1 + w2
        assert router.snapshot()["lost"] == 0
        st = router.snapshot()["kv_tiers"]
        assert st["spills"]["host"] > 0
        assert st["fetches"]["host"] > 0                 # warmth came back
        assert router.directory.tier_hits > 0
        routes = [e for e in telemetry.get_sink().recent()
                  if e.get("event") == "router_route"]
        assert "tier" in {e.get("directory") for e in routes}
        for r in reqs:
            assert res[r.request_id].tokens.tolist() == _offline(model, r)
        kv = router.replicas[0].engine.kv
        assert kv.prefix_hit_tokens > 0                  # recompute saved
        store.close()
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []

    def test_retire_with_no_peer_room_spills_not_drops(self, model):
        """The retire-path fix (satellite): when the best UP peer's
        pool has no room for the retiring replica's hot prefixes, the
        export falls back to a tier SPILL instead of dropping them —
        pre-tier behavior lost the warmth — and the replica_retired
        event counts the spills."""
        store = TieredKVStore(host_bytes=8 << 20)
        router = ServingRouter(_factory(model, slots=2, pool_blocks=8),
                               replicas=2, kv_tiers=store)
        head = list(range(1, 9))
        router.run([Request(prompt=head + [20 + i], max_new_tokens=3,
                            session_id="same") for i in range(3)])
        victim = next(r for r in router.replicas
                      if r.engine.kv._prefix)
        peer = next(r for r in router.replicas
                    if r.index != victim.index)
        # wedge the peer's pool: live slots pin every block and slot,
        # so the retire-path prefix ship cannot land there
        kvp = peer.engine.kv
        pin = 0
        while kvp._free_slots:
            slot, _ = kvp.alloc(f"pin{pin}", [100 + pin], 8)
            if slot is None:
                break
            pin += 1
        assert not kvp._free_slots
        router.retire_replica(victim.index, reason="scale_down")
        assert router.replicas[victim.index].state == RETIRED
        assert store.spills["host"] > 0
        assert store.lookup(head + [99], 8) is not None  # still warm
        retired = [e for e in telemetry.get_sink().recent()
                   if e.get("event") == "replica_retired"]
        assert retired and retired[-1]["spilled_prefixes"] > 0
        assert retired[-1]["exported_prefixes"] == 0

    def test_ps_chaos_kill_degrades_to_drop_with_zero_loss(
            self, model, monkeypatch, tmp_path):
        """A seeded chaos kill at the PS rung mid-storm: resident cold
        entries take their terminal drops, future spills stop at the
        host ring, the fleet loses ZERO requests and stays token-
        identical, and the kill is recorded (failure event + flight
        dump + ps_dead in the snapshot)."""
        flog = str(tmp_path / "failure.jsonl")
        monkeypatch.setenv("HETU_FAILURE_LOG", flog)
        monkeypatch.setenv("HETU_FLIGHT_LOG",
                           str(tmp_path / "flight.jsonl"))
        monkeypatch.setenv("HETU_CHAOS", "seed=3,kill=2,role=kvtier")
        faults.reset_plans()
        srv = PSServer()
        store = TieredKVStore(host_bytes=1, ps_tier=True,
                              ps=ShardedPSClient(servers=[srv]))
        router = ServingRouter(_factory(model, slots=2, pool_blocks=8),
                               replicas=1, kv_tiers=store)
        heads = [list(range(8 * j + 1, 8 * j + 9)) for j in range(4)]
        reqs = [Request(prompt=h + [40 + i], max_new_tokens=3,
                        request_id=f"c{i}", session_id=f"c{i}")
                for i, h in enumerate(heads * 2)]
        res = router.run(reqs)
        assert router.snapshot()["lost"] == 0 and len(res) == len(reqs)
        for r in reqs:
            assert res[r.request_id].tokens.tolist() == _offline(model, r)
        st = router.snapshot()["kv_tiers"]
        assert st["ps_dead"] is True and st["ps_entries"] == 0
        # the kill must degrade the TIER, not crash the engine it was
        # spilling for — no replica death/respawn rides along
        assert all(x["restarts"] == 0
                   for x in router.snapshot()["replicas"])
        events, bad = read_events([flog])
        assert bad == 0
        assert [e for e in events
                if e.get("event") == "kvtier_ps_killed"]
        store.close()
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []

    def test_ring_corruption_degrades_to_cold_admit(self, monkeypatch):
        """A drawn drop at the ring-read seam: the corrupted entry is
        dropped (never landed into a pool), counted, and the fetch
        degrades to a miss — the ledger still balances."""
        monkeypatch.setenv("HETU_CHAOS", "seed=1,drop=1.0,role=kvtier")
        faults.reset_plans()
        m = _mgr()
        _fill(m, seed=4)
        toks = _register(m, list(range(1, 9)), "p")
        pay = m.export_prefix(toks, count=False)
        st = _store(m, host_bytes=1 << 20)
        assert st.spill(toks, pay)
        assert st.fetch(toks) is None                    # corrupted
        assert st.corruptions == 1 and st.drops["host"] == 1
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []


# --------------------------------------------------------------------- #
# directory tier column (satellite)
# --------------------------------------------------------------------- #

class TestDirectoryTierColumn:
    def test_evict_demotes_then_clear_deletes(self):
        """With tiering on, the last holder's eviction DEMOTES a tier-
        stamped entry (still routable via the tier verdict) instead of
        deleting it; clear_tier restores delete semantics."""
        d = PrefixDirectory()
        d.tiered = True
        d._block = 8
        toks = tuple(range(1, 9))
        d.register(0, toks)
        d.set_tier(toks, "host")
        d.evict(0, toks)
        assert d.demotions == 1 and d.known(toks)
        hint, outcome = d.lookup(list(toks) + [99])
        assert outcome == "tier" and hint == (None, 8)
        assert d.tier_hits == 1
        snap = d.snapshot()
        assert snap["tiered"] is True and snap["tier_entries"] == 1
        d.clear_tier(toks)
        assert not d.known(toks)
        assert d.lookup(list(toks) + [99])[1] == "miss"

    def test_tiering_off_keeps_delete_semantics(self):
        """The stock directory (tiered=False) deletes on last-holder
        eviction even when a tier stamp exists — satellite back-compat
        guarantee."""
        d = PrefixDirectory()
        d._block = 8
        toks = tuple(range(1, 9))
        d.register(0, toks)
        d.set_tier(toks, "host")
        d.evict(0, toks)
        assert not d.known(toks) and d.demotions == 0

    def test_fresh_holder_beats_tier_column(self):
        """A live replica claim wins over the tier column — the tier
        verdict only fires when NO pool holds the cut."""
        d = PrefixDirectory()
        d.tiered = True
        d._block = 8
        toks = tuple(range(1, 9))
        d.register(1, toks)
        d.set_tier(toks, "ps")
        hint, outcome = d.lookup(list(toks) + [99])
        assert outcome is None and hint == (1, 8)
        d.drop_replica(1)
        assert d.known(toks)                             # tier survives
        assert d.lookup(list(toks) + [99])[1] == "tier"


# --------------------------------------------------------------------- #
# the trace rule (satellite)
# --------------------------------------------------------------------- #

def _ev(kind, h, tier="host"):
    e = {"event": kind, "prefix": h, "tier": tier, "t": 0.0}
    if kind != "kv_tier_drop":
        e["length"] = 8
    return e


class TestTierBalanceRule:
    def test_clean_ledger_passes(self):
        evs = [_ev("kv_spill", "a"), _ev("kv_fetch", "a"),
               _ev("kv_spill", "b"), _ev("kv_tier_drop", "b"),
               _ev("kv_spill", "a"), _ev("kv_fetch", "a")]
        assert check_tier_balance(evs) == []

    def test_double_spill_is_violation(self):
        evs = [_ev("kv_spill", "a"), _ev("kv_spill", "a"),
               _ev("kv_fetch", "a"), _ev("kv_fetch", "a")]
        out = check_tier_balance(evs)
        assert len(out) == 1 and "already tier-resident" in out[0]

    def test_fetch_without_spill_is_violation(self):
        out = check_tier_balance([_ev("kv_fetch", "a")])
        assert len(out) == 1 and "no open tier residency" in out[0]

    def test_open_residency_at_end_is_violation(self):
        out = check_tier_balance([_ev("kv_spill", "a")])
        assert len(out) == 1 and "still tier-resident" in out[0]

    def test_flight_dump_stream_exempt(self):
        evs = [{"event": "flight_dump", "reason": "x", "t": 0.0},
               _ev("kv_fetch", "a")]
        assert check_tier_balance(evs) == []

    def test_cli_reports_tier_violations(self, tmp_path, capsys):
        import json
        from hetu_tpu.telemetry import trace
        log = tmp_path / "serve.jsonl"
        log.write_text(json.dumps(
            {"event": "kv_spill", "prefix": "a", "tier": "host",
             "length": 8, "t": 0.0}) + "\n")
        rc = trace.main([str(log), "--check"])
        out = capsys.readouterr().out
        assert rc == 1
        assert json.loads(out.strip().splitlines()[-1])[
            "tier_balance_violations"] == 1


# --------------------------------------------------------------------- #
# PS cold store plumbing (sharded client)
# --------------------------------------------------------------------- #

class TestPSColdStore:
    def test_sharded_kv_round_trip_and_keys(self):
        """kv_put/get/del route whole by key hash across two local
        servers; kv_keys unions the shards without replica keys."""
        servers = [PSServer(), PSServer()]
        cli = ShardedPSClient(servers=servers)
        pay = {"nbytes": 4, "length": 8, "k": [1], "v": [2]}
        assert cli.kv_put("__kvcold__x", pay, version=3)
        got = cli.kv_get("__kvcold__x")
        assert got is not None
        assert got[0]["k"] == [1] and int(got[1]) == 3
        assert cli.kv_get("__kvcold__missing") is None
        assert cli.kv_keys() == ["__kvcold__x"]
        assert sum(len(s.kv_cold) for s in servers) == 1  # one home
        assert cli.kv_del("__kvcold__x") is True
        assert cli.kv_del("__kvcold__x") is False
        assert cli.kv_keys() == []

    def test_version_skew_refuses_stale_cold_entry(self):
        """A cold entry overwritten behind the store's back (version
        mismatch) is refused at fetch — dropped, never landed."""
        m = _mgr()
        _fill(m, seed=5)
        toks = _register(m, list(range(1, 9)), "p")
        pay = m.export_prefix(toks, count=False)
        srv = PSServer()
        st = _store(m, host_bytes=0, ps_tier=True,
                    ps=ShardedPSClient(servers=[srv]))
        assert st.spill(toks, pay)
        assert st.spills["ps"] == 1
        key = PS_NAMESPACE + prefix_hash(toks)
        srv.kv_put(key, pay, version=999)                # intruder write
        assert st.fetch(toks) is None
        assert st.drops["ps"] == 1 and st.fetches["ps"] == 0
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []

    def test_close_terminates_all_residencies(self):
        """close() gives every resident its terminal drop (host + PS)
        and best-effort deletes the cold blobs — a completed run's
        ledger balances by construction."""
        m = _mgr()
        _fill(m, seed=6)
        probe = _register(m, list(range(1, 9)), "p")
        pay = m.export_prefix(probe, count=False)
        srv = PSServer()
        st = _store(m, host_bytes=pay["nbytes"], ps_tier=True,
                    ps=ShardedPSClient(servers=[srv]))
        # two spills through the public path: the second overflows the
        # one-entry ring, demoting the first to the cold store
        assert st.spill(tuple(range(1, 9)), pay)
        assert st.spill(tuple(range(11, 19)), pay)
        assert st.demotes == 1 and st.stats()["ps_entries"] == 1
        assert srv.kv_keys() != []
        st.close()
        assert st.stats()["host_entries"] == 0
        assert st.stats()["ps_entries"] == 0
        assert srv.kv_keys() == []
        assert check_tier_balance(
            [e for e in telemetry.get_sink().recent()]) == []
