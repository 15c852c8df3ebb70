"""Elastic fleet (ISSUE 16 tentpole): SLO-burn-driven autoscaler with
chaos-gated live scale-up/scale-down and the seeded traffic generator.

The acceptance spine: a ServingRouter's membership is DYNAMIC —
``add_replica`` brings a replica up gated on committed-version
admission, prefix warming, and a half-open probe decode;
``retire_replica`` drains one out with zero request loss (its in-flight
requests requeue onto peers, its hot prefixes export first).  The
FleetAutoscaler rides ``router.step()`` and drives both off SLO burn +
queue pressure with tick-counted hysteresis and a cooldown window, and
``enabled=False`` is byte-identical to a router with no autoscaler at
all (the degradation contract).  Chaos (``HETU_CHAOS role=autoscale``)
kills the busiest peer mid-scale-up or the draining replica mid-drain:
zero loss must hold anyway.

All CPU-harness, all smoke-tier (tiny random-weight GPTs — the
contract under test is elasticity orchestration, not model quality).
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu import telemetry
from hetu_tpu.models import GPTConfig
from hetu_tpu.ps import faults
from hetu_tpu.serving import (
    SLO, FleetAutoscaler, Request, ServingEngine, ServingRouter,
    TrafficGenerator, WeightSyncCoordinator, replay,
)
from hetu_tpu.serving.replica import RETIRED, UP

pytestmark = pytest.mark.smoke


def _rand_gpt(name="as", L=1, H=2, Dh=8, V=61, S=32, seed=0):
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
    # v1 and v2 share shapes/keys but not values, so version-stamped
    # admission is observable in the committed-version test
    p1, cfg = _rand_gpt(seed=0)
    p2, _ = _rand_gpt(seed=1)
    return p1, p2, cfg


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("HETU_TELEMETRY", "1")
    monkeypatch.delenv("HETU_CHAOS", raising=False)
    faults.reset_plans()
    telemetry.reset()
    yield
    faults.reset_plans()
    telemetry.reset()


def _mk_router(p, cfg, *, replicas=2, slo_ms=None, **rkw):
    """The verified elastic-fleet harness config: paged prefix-share
    engines, directory on, deterministic shedding OFF (shed_on_slo
    reads wall-clock TTFT, which is noise on a virtual-clock replay)."""

    def factory(i):
        slo = ([SLO("ttft", "latency", slo_ms)]
               if slo_ms is not None else None)
        return ServingEngine(p, cfg, slots=4, queue_limit=8,
                             max_seq_len=32, kv_block=4,
                             prefix_share=True, slo=slo)

    rkw.setdefault("shed_on_slo", False)
    rkw.setdefault("restart_backoff", 0.01)
    rkw.setdefault("directory", True)
    return ServingRouter(factory, replicas=replicas, **rkw)


def _traffic(**kw):
    kw.setdefault("seed", 7)
    kw.setdefault("vocab", 61)
    kw.setdefault("s_max", 32)
    kw.setdefault("horizon_s", 2.0)
    kw.setdefault("base_rps", 2.0)
    kw.setdefault("peak_rps", 40.0)
    kw.setdefault("cycle_s", 2.0)
    kw.setdefault("n_sessions", 4)
    kw.setdefault("prefix_len", 8)
    return TrafficGenerator(**kw)


# --------------------------------------------------------------------- #
# the control loop: hysteresis, cooldown, rollout deferral
# --------------------------------------------------------------------- #

class TestControlLoop:
    def test_hysteresis_and_cooldown(self, model):
        """Scale-up needs UP_TICKS consecutive hot ticks, every action
        opens a cooldown window that absorbs the signal, the fleet
        clamps to [min, max], and a sustained idle signal walks it back
        down one replica per cooldown."""
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=1)
        auto = FleetAutoscaler(r, fleet_min=1, fleet_max=3, up_ticks=3,
                               down_ticks=4, cooldown=4)
        auto.worst_burn = lambda: 5.0    # hot from burn alone
        r.queue_pressure = lambda: 0.0
        t = [0.0]

        def tk(n=1):
            for _ in range(n):
                t[0] += 0.01
                auto.tick(now=t[0])

        tk(2)
        assert auto.scale_ups == 0 and auto.actual() == 1
        tk()   # third consecutive hot tick
        assert auto.scale_ups == 1 and auto.actual() == 2
        assert auto.last_action["action"] == "scale_up"
        assert auto.last_action["reason"] == "burn"
        tk(4)  # the cooldown window absorbs 4 hot ticks
        assert auto.scale_ups == 1
        tk(3)  # streak rebuilds from zero after the action
        assert auto.scale_ups == 2 and auto.actual() == 3
        tk(10)  # at fleet_max: hot forever, no further growth
        assert auto.scale_ups == 2 and auto.peak_replicas == 3
        auto.worst_burn = lambda: 0.0   # now sustained idle
        tk(40)
        # 4 idle ticks -> retire, 4 cooldown + 4 idle -> retire again,
        # then the fleet_min floor holds
        assert auto.scale_downs == 2 and auto.actual() == 1
        assert auto.last_action["action"] == "scale_down"
        assert sum(1 for x in r.replicas if x.state == RETIRED) == 2
        snap = auto.snapshot()
        assert snap["min"] == 1 and snap["max"] == 3
        assert snap["replica_ticks"] > 0
        assert len(auto.timeline) == 4

    def test_scale_down_deferred_mid_rollout(self, model):
        """A scale-down never fires while a weight rollout is in
        flight (the commit is defined over the fleet), and a replica
        added mid-rollout admits on the COMMITTED version and is
        adopted into the rollout order — the fleet still lands on v2."""
        p1, p2, cfg = model
        r = _mk_router(p1, cfg, replicas=2)
        coord = WeightSyncCoordinator(r, p1, version=1)
        auto = FleetAutoscaler(r, fleet_min=1, fleet_max=4,
                               up_ticks=100, down_ticks=1, cooldown=0)
        auto.worst_burn = lambda: 0.0
        r.queue_pressure = lambda: 0.0
        assert coord.begin(p2, 2)
        auto.tick(now=0.01)
        assert auto.deferred_rollout == 1 and auto.scale_downs == 0
        idx = r.add_replica()
        assert idx == 2
        assert r.replicas[idx].engine.weight_version \
            == coord.committed_version == 1
        auto.enabled = False   # the drain below is the rollout's story
        coord.drain()
        assert coord.state == "done"
        assert coord.fleet_versions() == {0: 2, 1: 2, 2: 2}


# --------------------------------------------------------------------- #
# membership changes under live traffic
# --------------------------------------------------------------------- #

class TestElasticity:
    def test_scale_up_down_zero_loss_under_traffic(self, model):
        """One diurnal cycle through a pressure-driven autoscaler: the
        fleet grows at the peak, shrinks in the idle tail, loses
        nothing, and every finished request is token-identical to a
        lone offline engine decoding the same specs."""
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=1)
        auto = FleetAutoscaler(r, fleet_min=1, fleet_max=2,
                               up_pressure=0.2, up_ticks=2,
                               down_pressure=0.1, down_ticks=30,
                               cooldown=10)
        specs = _traffic(seed=2024, horizon_s=3.0, peak_rps=80.0,
                         cycle_s=3.0, n_sessions=8).trace(dt=0.05)
        res, rep = replay(r, specs, step_s=0.01, tail_s=3.0)
        snap = r.snapshot()
        assert snap["lost"] == 0
        assert auto.scale_ups >= 1 and auto.scale_downs >= 1
        assert auto.peak_replicas == 2
        # every admitted request retired exactly once
        assert len(res) + len(rep["shed"]) + len(rep["rejected"]) \
            == len(specs)
        eng = ServingEngine(p1, cfg, slots=4,
                            queue_limit=len(specs) + 1, max_seq_len=32)
        off = eng.run([sp.to_request() for sp in specs
                       if sp.request_id in res])
        for rid, x in res.items():
            assert list(x.tokens) == list(off[rid].tokens), rid

    def test_warm_prefix_handoff_on_scale_up(self, model):
        """A joining replica prefix-warms from its peers through the
        export/import handoff codec BEFORE taking traffic: the peers'
        hottest directory-known prefixes exist in its paged pool the
        moment it is ready."""
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=1)
        head = [3, 4, 5, 6, 7, 8, 9, 10]   # two full kv blocks
        r.run([Request(prompt=head + [11 + i], max_new_tokens=4,
                       request_id=f"w{i}") for i in range(4)])
        assert r.replicas[0].engine.kv._prefix
        before = r.handoffs
        idx = r.add_replica(warm_prefixes=4)
        rep = r.replicas[idx]
        assert rep.lifecycle == "serving"
        warmed = list(rep.engine.kv._prefix)
        assert warmed, "no prefix warmed onto the joining replica"
        assert any(list(k) == head[:len(k)] for k in warmed)
        assert r.handoffs > before

    def test_retire_requeues_in_flight_zero_loss(self, model):
        """Retiring a replica with requests in flight requeues them
        onto peers through the drain path: every request retires
        exactly once, the victim ends RETIRED (not respawned — intent,
        not failure), and its directory entries are gone."""
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=2)
        reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=6,
                        request_id=f"d{i}") for i in range(8)]
        for q in reqs:
            r.submit(q)
        out = {}
        for _ in range(3):
            for res in r.step():
                out[res.request_id] = res
        requeued = r.retire_replica(1, reason="scale_down")
        for _ in range(4000):
            if not r.pending:
                break
            for res in r.step():
                out[res.request_id] = res
        snap = r.snapshot()
        assert snap["lost"] == 0
        assert set(out) == {q.request_id for q in reqs}
        assert r.replicas[1].state == RETIRED
        assert r.replicas[1].restarts == 0
        assert snap["requeued"] == requeued
        # the victim's directory claims are purged with it
        assert all(1 not in e.replicas
                   for e in r.directory._entries.values())

    def test_retire_last_up_replica_refused(self, model):
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=1)
        with pytest.raises(ValueError, match="no UP peer"):
            r.retire_replica(0)


# --------------------------------------------------------------------- #
# chaos: the seams fire, zero loss holds anyway
# --------------------------------------------------------------------- #

class TestChaos:
    def test_kill_busiest_peer_mid_scale_up(self, model, monkeypatch):
        """role=autoscale kill during bring-up takes out the BUSIEST
        peer: the joining replica absorbs the requeued load and the
        trace still retires every admitted request exactly once."""
        p1, _, cfg = model
        monkeypatch.setenv("HETU_CHAOS", "seed=11,kill=1,role=autoscale")
        faults.reset_plans()
        # a tight TTFT budget makes any traffic burn the error budget,
        # so scale-up is burn-driven and fires early in the trace
        r = _mk_router(p1, cfg, replicas=2, slo_ms=0.001)
        auto = FleetAutoscaler(r, fleet_min=1, fleet_max=3, up_ticks=2,
                               down_ticks=10_000, cooldown=3)
        specs = _traffic().trace(dt=0.05)
        res, rep = replay(r, specs, step_s=0.01, tail_s=1.0)
        snap = r.snapshot()
        assert auto.scale_ups >= 1
        assert snap["lost"] == 0
        assert len(res) + len(rep["shed"]) + len(rep["rejected"]) \
            == len(specs)
        # the seam fired and the supervisor respawned the victim
        assert any(row["restarts"] >= 1 for row in snap["replicas"])

    def test_kill_draining_replica_mid_drain(self, model, monkeypatch):
        """role=autoscale kill during a drain takes out the retiring
        replica itself: the requeue reads the router's own assignment
        records, never the corpse, so zero loss holds anyway."""
        p1, _, cfg = model
        r = _mk_router(p1, cfg, replicas=2)
        reqs = [Request(prompt=[2 + i, 5, 9], max_new_tokens=6,
                        request_id=f"c{i}") for i in range(8)]
        for q in reqs:
            r.submit(q)
        out = {}
        for _ in range(3):
            for res in r.step():
                out[res.request_id] = res
        monkeypatch.setenv("HETU_CHAOS", "seed=12,kill=1,role=autoscale")
        faults.reset_plans()
        r.retire_replica(1, reason="scale_down")
        assert "chaos autoscale kill" in (r.replicas[1].exit_error or "")
        for _ in range(4000):
            if not r.pending:
                break
            for res in r.step():
                out[res.request_id] = res
        assert r.snapshot()["lost"] == 0
        assert set(out) == {q.request_id for q in reqs}


# --------------------------------------------------------------------- #
# the traffic generator
# --------------------------------------------------------------------- #

class TestTraffic:
    def test_trace_is_a_pure_function_of_the_seed(self):
        kw = dict(seed=5, horizon_s=1.0, base_rps=10.0, peak_rps=30.0,
                  cycle_s=1.0, n_sessions=4, prefix_len=6)
        t1 = _traffic(**kw).trace(dt=0.05)
        t2 = _traffic(**kw).trace(dt=0.05)
        assert len(t1) > 0

        def key(s):
            return (s.t, s.request_id, tuple(s.prompt),
                    s.max_new_tokens, s.workload, s.slo_class,
                    s.session_id, s.seed)

        assert [key(s) for s in t1] == [key(s) for s in t2]
        t3 = _traffic(**dict(kw, seed=6)).trace(dt=0.05)
        assert [key(s) for s in t1] != [key(s) for s in t3]

    def test_diurnal_flash_and_sessions(self):
        g = _traffic(seed=5, horizon_s=1.0, base_rps=10.0,
                     peak_rps=30.0, cycle_s=1.0)
        gf = _traffic(seed=5, horizon_s=1.0, base_rps=10.0,
                      peak_rps=30.0, cycle_s=1.0,
                      flash=((0.5, 0.2, 4.0),))
        # the diurnal curve spans base..peak
        assert g.rate(0.0) < g.rate(0.25)
        # the flash crowd multiplies the curve inside its window only
        assert gf.rate(0.6) == pytest.approx(g.rate(0.6) * 4.0)
        assert gf.rate(0.1) == pytest.approx(g.rate(0.1))
        # zipf sessions share a seeded prefix head (the prefix-cache
        # workload shape): same session => same first tokens
        specs = g.trace(dt=0.05)
        by_sess = {}
        for s in specs:
            by_sess.setdefault(s.session_id, []).append(s)
        multi = [v for v in by_sess.values() if len(v) >= 2]
        assert multi
        for group in multi:
            heads = {tuple(s.prompt[:g.prefix_len]) for s in group}
            assert len(heads) == 1
        # workload classes carry their SLO class end to end
        assert {s.slo_class for s in specs} <= {"latency", "throughput"}

    def test_describe_is_jsonable_provenance(self):
        import json
        d = _traffic().describe()
        assert json.loads(json.dumps(d))["seed"] == 7


# --------------------------------------------------------------------- #
# the degradation contract
# --------------------------------------------------------------------- #

def test_disabled_autoscaler_is_byte_identical_to_static(model):
    """enabled=False is a STRICT no-op: same results, same tokens, same
    counters, same step count as a router with no autoscaler at all."""
    p1, _, cfg = model
    specs = _traffic(seed=9, horizon_s=1.0, peak_rps=30.0,
                     cycle_s=1.0).trace(dt=0.05)

    def run(with_auto):
        r = _mk_router(p1, cfg, replicas=2)
        auto = (FleetAutoscaler(r, fleet_min=1, fleet_max=3,
                                enabled=False) if with_auto else None)
        res, rep = replay(r, specs, step_s=0.01, tail_s=0.2)
        return res, rep, r.snapshot(), auto

    r1, rep1, s1, _ = run(False)
    r2, rep2, s2, auto = run(True)
    assert set(r1) == set(r2)
    for rid in r1:
        assert list(r1[rid].tokens) == list(r2[rid].tokens), rid
    for k in ("finished", "lost", "shed", "requeued", "submitted",
              "handoffs"):
        assert s1[k] == s2[k], k
    assert rep1["steps"] == rep2["steps"]
    assert auto.ticks == 0 and auto.scale_ups == 0
    assert s1["autoscaler"] is None
    assert s2["autoscaler"]["enabled"] is False
