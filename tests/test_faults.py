"""Chaos harness + failure-survival suite (ISSUE 1 tentpole).

Layers under test:

- ``ps/faults.py``: seed-deterministic FaultPlan + the HETU_CHAOS env
  activation at the transport seam;
- exactly-once under loss/duplication: the (client_id, seq) replay
  cache absorbs injected drop/dup faults on the real TCP wire;
- ``ps/sharded.py`` replica groups: primary loss mid-training fails
  over to the ring backup with a trajectory equal to the fault-free
  run; a restarted primary re-syncs from its replica before rejoining;
- ``launcher.run_cluster`` supervisor: dead workers restart from the
  latest checkpoint with an exponential-backoff budget and a structured
  failure-event log; dead PS servers respawn;
- ``cache/cstable.py`` graceful degradation: bounded-stale serving and
  push replay across a PS outage.

All CPU-harness; nothing here needs a chip or a cluster.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np
import pytest

from hetu_tpu.ps import faults
from hetu_tpu.ps.faults import FaultPlan
from hetu_tpu.ps.client import (PSClient, PSConnectionError,
                                _TCPTransport)
from hetu_tpu.ps.server import PSServer
from hetu_tpu.ps.sharded import (ShardedPSClient, REPLICA_PREFIX,
                                 _LocalServerTransport)


@pytest.fixture(autouse=True)
def _fresh_plans():
    """Per-test decision streams: a cached plan's counter must not leak
    across tests reusing a spec string."""
    faults.reset_plans()
    yield
    faults.reset_plans()


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.smoke
class TestFaultPlan:
    def test_spec_parse(self):
        p = FaultPlan.from_spec(
            "seed=7,drop=0.1,dup=0.05,delay=0.02:0.5,reset=0.01,"
            "slow=0.1:0.2,kill=9,role=server")
        assert p.seed == 7 and p.drop == 0.1 and p.dup == 0.05
        assert p.delay == (0.02, 0.5) and p.reset == 0.01
        assert p.slow == (0.1, 0.2) and p.kill == 9
        assert p.role == "server"

    def test_reorder_is_dup_alias(self):
        p = FaultPlan.from_spec("dup=0.1,reorder=0.2")
        assert p.dup == pytest.approx(0.3)

    def test_bad_spec_raises(self):
        with pytest.raises(ValueError):
            FaultPlan.from_spec("drop")
        with pytest.raises(ValueError):
            FaultPlan.from_spec("warp=0.1")

    def test_deterministic_stream(self):
        mk = lambda: FaultPlan(seed=5, drop=0.3, dup=0.2,  # noqa: E731
                               delay=(0.1, 0.0))
        a = [mk().draw().kind for _ in range(1)]  # fresh plan each draw
        p1, p2 = mk(), mk()
        s1 = [p1.draw().kind for _ in range(300)]
        s2 = [p2.draw().kind for _ in range(300)]
        assert s1 == s2
        assert a[0] == s1[0]
        p3 = FaultPlan(seed=6, drop=0.3, dup=0.2, delay=(0.1, 0.0))
        assert [p3.draw().kind for _ in range(300)] != s1

    def test_rates_approximate_probabilities(self):
        p = FaultPlan(seed=1, drop=0.25)
        kinds = [p.draw().kind for _ in range(4000)]
        frac = kinds.count("drop") / 4000
        assert 0.2 < frac < 0.3
        assert p.fired["drop"] == kinds.count("drop")

    def test_kinds_filter_masks_but_advances(self):
        p1 = FaultPlan(seed=2, drop=0.5)
        masked = [p1.draw(kinds=("slow",)).kind for _ in range(100)]
        assert set(masked) == {"none"}
        # the restricted caller consumed the same stream positions
        p2 = FaultPlan(seed=2, drop=0.5)
        assert sum(k.kind == "drop" for k in
                   (p2.draw() for _ in range(100))) > 30

    def test_role_gating(self, monkeypatch):
        p = FaultPlan(seed=0, drop=1.0, role="server")
        monkeypatch.delenv("HETU_CHAOS_ROLE", raising=False)
        assert p.draw().kind == "none"          # wrong role: inert
        monkeypatch.setenv("HETU_CHAOS_ROLE", "server:3")
        assert p.draw().kind == "drop"          # prefix match fires

    def test_env_activation_caches_one_plan(self, monkeypatch):
        monkeypatch.setenv("HETU_CHAOS", "seed=4,drop=0.5")
        a, b = faults.plan_from_env(), faults.plan_from_env()
        assert a is b and a.drop == 0.5
        monkeypatch.delenv("HETU_CHAOS")
        assert faults.plan_from_env() is None


@pytest.mark.smoke
class TestReplicaScopedChaos:
    """The serving-fleet seam additions (ISSUE 8): wedge events, the
    explicit draw(role=...) override for seams hosting several roles in
    one process, and inline kills that return instead of SIGKILLing the
    whole fleet."""

    def test_wedge_spec_parse(self):
        p = FaultPlan.from_spec("seed=2,wedge=3,role=replica0")
        assert p.wedge == 3 and p.role == "replica0"

    def test_wedge_fires_only_at_optin_seams(self):
        """A transport (no "wedge" in kinds) walks straight past the
        wedge position; a replica step seam draws it exactly once."""
        p1 = FaultPlan(seed=0, wedge=2)
        kinds = [p1.draw(kinds=("drop",)).kind for _ in range(5)]
        assert "wedge" not in kinds and p1.fired["wedge"] == 0
        p2 = FaultPlan(seed=0, wedge=2)
        kinds = [p2.draw(kinds=("kill", "wedge")).kind
                 for _ in range(5)]
        assert kinds[1] == "wedge" and kinds.count("wedge") == 1
        assert p2.fired["wedge"] == 1

    def test_explicit_role_overrides_env(self, monkeypatch):
        """draw(role=...) gates the plan per call — the fleet's
        replicas share one process, so HETU_CHAOS_ROLE cannot tell
        them apart."""
        monkeypatch.setenv("HETU_CHAOS_ROLE", "replica1")
        p = FaultPlan(seed=0, drop=1.0, role="replica0")
        assert p.draw().kind == "none"               # env role: no match
        assert p.draw(role="replica0").kind == "drop"   # explicit: fires
        assert p.draw(role="replica1").kind == "none"

    def test_nonmatching_role_never_advances_counter(self):
        """Each replica's step stream is independently deterministic:
        other replicas' draws must not consume positions."""
        p = FaultPlan(seed=9, kill=2, role="replica1")
        for _ in range(10):   # replica0 hammers the plan — inert
            assert p.draw(role="replica0", kinds=("kill", "wedge"),
                          inline=True).kind == "none"
        assert p._n == 0
        # replica1's own 2nd step is still the kill
        assert p.draw(role="replica1", kinds=("kill",),
                      inline=True).kind == "none"
        assert p.draw(role="replica1", kinds=("kill",),
                      inline=True).kind == "kill"

    def test_inline_kill_returns_instead_of_sigkill(self):
        """inline=True hands the death to the caller (the replica
        harness) — the test process surviving IS the assertion."""
        p = FaultPlan(seed=0, kill=1)
        f = p.draw(kinds=("kill",), inline=True)
        assert f.kind == "kill" and p.fired["kill"] == 1
        # one-shot: the position is consumed
        assert p.draw(kinds=("kill",), inline=True).kind == "none"


@pytest.mark.smoke
class TestChaosLocalTier:
    def test_local_transport_drops_retry_exactly_once(self, monkeypatch):
        """In-process tier under loss: every push applies exactly once
        (drops retry immediately; there is no response to lose)."""
        srv = PSServer()
        c = PSClient(transport=_LocalServerTransport(srv))
        c.param_set("w", np.zeros(4, np.float32), opt="sgd",
                    opt_args={"learning_rate": 1.0})
        # seed picked so no call loses all 3 attempts (deterministic)
        monkeypatch.setenv("HETU_CHAOS", "seed=3,drop=0.1")
        for _ in range(60):
            c.push("w", -np.ones(4, np.float32))
        plan = faults.plan_from_env()
        monkeypatch.delenv("HETU_CHAOS")
        np.testing.assert_allclose(np.asarray(c.pull("w")), 60.0)
        assert plan.fired["drop"] > 0   # the chaos actually fired

    def test_local_transport_surfaces_total_loss(self, monkeypatch):
        srv = PSServer()
        c = PSClient(transport=_LocalServerTransport(srv))
        c.param_set("w2", np.zeros(2, np.float32))
        monkeypatch.setenv("HETU_CHAOS", "seed=0,drop=1.0")
        with pytest.raises(PSConnectionError):
            c.pull("w2")


class TestChaosTCPExactlyOnce:
    def test_drop_dup_replay_cache_applies_once(self, monkeypatch):
        """The acceptance fault mix on the REAL wire: ~10% dropped
        requests and ~10% lost-after-apply responses.  The retries and
        the server's (client_id, seq) replay cache must deliver every
        push exactly once."""
        srv = PSServer()
        port = _free_port()
        tcp = srv.serve_tcp(port, block=False)
        try:
            t = _TCPTransport("127.0.0.1", port, timeout=5,
                              connect_timeout=2, retries=8)
            c = PSClient(transport=t)
            c.param_set("w", np.zeros(4, np.float32), opt="sgd",
                        opt_args={"learning_rate": 1.0})
            monkeypatch.setenv("HETU_CHAOS", "seed=11,drop=0.1,dup=0.1")
            for _ in range(40):
                c.push("w", -np.ones(4, np.float32))
            plan = faults.plan_from_env()
            monkeypatch.delenv("HETU_CHAOS")
            np.testing.assert_allclose(np.asarray(c.pull("w")), 40.0)
            assert plan.fired["drop"] > 0 and plan.fired["dup"] > 0
        finally:
            tcp.shutdown()


def _train_steps(client, key, steps, rng_seed=0, rows=8, width=3,
                 skip=0):
    """Deterministic sd_pushpull workload shared by the failover tests
    and their fault-free baselines."""
    rng = np.random.RandomState(rng_seed)
    out = []
    for i in range(steps):
        ids = rng.randint(0, rows, 5).astype(np.int64)
        grads = rng.randn(5, width).astype(np.float32)
        if i >= skip:
            out.append(np.asarray(client.sd_pushpull(key, ids, grads)))
    return out


class TestShardFailoverLocal:
    ROWS, WIDTH = 8, 3

    def _mk(self, replicate):
        servers = [PSServer(), PSServer()]
        c = ShardedPSClient(servers=servers, replicate=replicate)
        table = np.zeros((self.ROWS, self.WIDTH), np.float32)
        c.param_set("t", table, opt="sgd",
                    opt_args={"learning_rate": 0.5})
        return servers, c

    def test_replica_tracks_primary(self):
        servers, c = self._mk(True)
        _train_steps(c, "t", 6)
        c.drain_replication()
        # each backup's replica equals its partner shard exactly
        np.testing.assert_allclose(
            np.asarray(servers[1].pull(REPLICA_PREFIX + "t")),
            np.asarray(servers[0].pull("t")))
        np.testing.assert_allclose(
            np.asarray(servers[0].pull(REPLICA_PREFIX + "t")),
            np.asarray(servers[1].pull("t")))

    def test_failover_matches_fault_free_and_resync_rejoins(self):
        _, base = self._mk(False)
        _train_steps(base, "t", 12)
        want = base.pull("t")

        servers, c = self._mk(True)
        _train_steps(c, "t", 6)                       # healthy half
        c.drain_replication()

        class _Dead:
            def call(self, method, *a, **kw):
                raise PSConnectionError("server gone (test)")

            def close(self):
                pass
        live_transport = c.clients[0].t
        c.clients[0].t = _Dead()                      # primary 0 dies
        _train_steps(c, "t", 12, skip=6)              # failed-over half
        assert c.failed_shards() == [0]
        assert any(e["event"] == "ps_shard_failover"
                   for e in c.failure_events)
        np.testing.assert_allclose(c.pull("t"), want, atol=1e-5)

        # "restart" the primary empty and re-seed it from the replica
        fresh = PSServer()
        c.clients[0].t = _LocalServerTransport(fresh)
        restored = c.resync_shard(0)
        assert "t" in restored and c.failed_shards() == []
        np.testing.assert_allclose(c.pull("t"), want, atol=1e-5)
        # the restored primary really holds its shard again...
        np.testing.assert_allclose(np.asarray(fresh.pull("t")),
                                   np.asarray(want)[0::2], atol=1e-5)
        # ...including its hosted replica of the OTHER shard
        np.testing.assert_allclose(
            np.asarray(fresh.pull(REPLICA_PREFIX + "t")),
            np.asarray(want)[1::2], atol=1e-5)
        del live_transport

    def test_unreplicated_group_still_surfaces_loss(self):
        _, c = self._mk(False)

        class _Dead:
            def call(self, *a, **kw):
                raise PSConnectionError("gone")

            def close(self):
                pass
        c.clients[0].t = _Dead()
        with pytest.raises(PSConnectionError):
            c.pull("t")


class TestShardFailoverSIGKILL:
    """The acceptance scenario: a 2-shard replicated TCP group, the
    shard-0 primary SIGKILLed by a seeded FaultPlan mid-training while
    ~10% of the client's requests are dropped/duplicated.  The run must
    complete with a final table matching the fault-free trajectory, and
    the restarted primary must re-sync and rejoin."""

    STEPS = 12

    def test_sigkill_failover_equivalence(self, monkeypatch):
        from hetu_tpu.launcher import _start_ps_process, _wait_ps

        # fault-free baseline, in-process
        base_servers = [PSServer(), PSServer()]
        base = ShardedPSClient(servers=base_servers, replicate=False)
        base.param_set("t", np.zeros((8, 3), np.float32), opt="sgd",
                       opt_args={"learning_rate": 0.5})
        _train_steps(base, "t", self.STEPS)
        want = base.pull("t")

        ports = [_free_port(), _free_port()]
        addrs = [f"localhost:{p}" for p in ports]
        # the seeded plan SIGKILLs the shard-0 primary at its 13th
        # served request (~mid-training: setup costs ~3 requests, each
        # step costs ~2 — its own shard op + the shard-1 replica write)
        procs = [
            _start_ps_process(ports[0], {
                "HETU_CHAOS": "seed=1,kill=13,role=server:0",
                "HETU_CHAOS_ROLE": "server:0"}),
            _start_ps_process(ports[1], {"HETU_CHAOS_ROLE": "server:1"}),
        ]
        try:
            # (a spawned server imports the package first: seconds on an
            # idle machine, and nobody knows how many beside six workers)
            for p in ports:
                _wait_ps("localhost", p, timeout=120)
            # fast failure detection: short timeouts, generous retries
            # (chaos losses retry without backoff)
            monkeypatch.setenv("HETU_PS_TIMEOUT", "5")
            monkeypatch.setenv("HETU_PS_CONNECT_TIMEOUT", "1")
            monkeypatch.setenv("HETU_PS_RETRIES", "6")
            c = ShardedPSClient(addrs=addrs, replicate=True)
            c.param_set("t", np.zeros((8, 3), np.float32), opt="sgd",
                        opt_args={"learning_rate": 0.5})
            monkeypatch.setenv("HETU_CHAOS", "seed=2,drop=0.1,dup=0.1")
            _train_steps(c, "t", self.STEPS)
            monkeypatch.delenv("HETU_CHAOS")
            c.drain_replication()

            assert c.failed_shards() == [0], \
                "the seeded kill did not fire (or hit the wrong shard)"
            np.testing.assert_allclose(c.pull("t"), want, atol=1e-4)

            # restart the dead primary (no kill this time) + resync
            procs.append(_start_ps_process(
                ports[0], {"HETU_CHAOS_ROLE": "server:0"}))
            _wait_ps("localhost", ports[0], timeout=120)
            restored = c.resync_shard(0)
            assert "t" in restored
            assert c.failed_shards() == []
            np.testing.assert_allclose(c.pull("t"), want, atol=1e-4)
            # traffic really returned to the primary: its python tier
            # serves the shard again
            direct = PSClient(transport=_TCPTransport(
                "localhost", ports[0], retries=2))
            np.testing.assert_allclose(
                np.asarray(direct.pull("t")), np.asarray(want)[0::2],
                atol=1e-4)
            direct.finalize()
            c.finalize()
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
                p.join(timeout=10)


class TestSupervisorWorkerRestart:
    """Acceptance: kill a worker mid-epoch under run_cluster; it must
    resume from the latest checkpoint and finish with the expected step
    count, with the restart budget and backoff visible in the
    failure-event log."""

    def test_worker_sigkill_resumes_from_checkpoint(self, monkeypatch):
        from hetu_tpu.context import DistConfig
        from hetu_tpu.launcher import run_cluster

        d = tempfile.mkdtemp()
        script = os.path.join(d, "train.py")
        with open(script, "w") as f:
            f.write("""
import os, json, signal
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import hetu_tpu as ht

D = %r
TOTAL = 6
x = ht.placeholder_op("x")
y = ht.placeholder_op("y")
w1 = ht.Variable("w1", value=np.eye(4, dtype=np.float32))
loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
    ht.matmul_op(x, w1), y), axes=0)
train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
ex = ht.Executor({"train": [loss, train]})
if os.path.exists(os.path.join(D, "ckpt", "checkpoint.pkl")):
    ex.load(os.path.join(D, "ckpt"))
rng = np.random.RandomState(0)
batches = [(rng.randn(8, 4).astype(np.float32),
            np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)])
           for _ in range(TOTAL)]
losses = []
for step in range(int(ex.step), TOTAL):
    a, b = batches[step]
    out = ex.run("train", feed_dict={x: a, y: b})
    losses.append(float(np.asarray(out[0])))
    ex.save(os.path.join(D, "ckpt"))
    if step == 2 and os.environ.get("HETU_RESTART_COUNT", "0") == "0":
        os.kill(os.getpid(), signal.SIGKILL)   # die mid-epoch
with open(os.path.join(D, "out.json"), "w") as f:
    json.dump({"final_step": int(ex.step),
               "restart_count": os.environ.get("HETU_RESTART_COUNT"),
               "losses_this_life": losses}, f)
""" % d)
            f.flush()
        log = os.path.join(d, "failures.jsonl")
        monkeypatch.setenv("HETU_FAILURE_LOG", log)
        monkeypatch.setenv("HETU_RESTART_BACKOFF", "0.3")
        codes = run_cluster(DistConfig(num_servers=0, num_workers=1),
                            [sys.executable, script])
        assert codes == [0]
        with open(os.path.join(d, "out.json")) as f:
            out = json.load(f)
        # the resumed incarnation continued at step 3 and finished 6
        assert out["final_step"] == 6
        assert out["restart_count"] == "1"
        assert len(out["losses_this_life"]) == 3
        events = [json.loads(ln) for ln in open(log)]
        kinds = [e["event"] for e in events]
        assert "worker_exit" in kinds and "worker_restart" in kinds
        exit_ev = next(e for e in events if e["event"] == "worker_exit")
        assert exit_ev["rc"] == -9
        sched = next(e for e in events
                     if e["event"] == "worker_restart_scheduled")
        assert sched["backoff_s"] == pytest.approx(0.3)
        assert sched["attempt"] == 1

    def test_restart_budget_exhausts(self, monkeypatch):
        """A worker that always fails consumes the budget and surfaces
        its exit code — the supervisor must not loop forever."""
        from hetu_tpu.context import DistConfig
        from hetu_tpu.launcher import run_cluster, last_failure_events

        monkeypatch.setenv("HETU_RESTART_LIMIT", "2")
        monkeypatch.setenv("HETU_RESTART_BACKOFF", "0.05")
        monkeypatch.delenv("HETU_FAILURE_LOG", raising=False)
        codes = run_cluster(DistConfig(num_servers=0, num_workers=1),
                            [sys.executable, "-c", "raise SystemExit(3)"])
        assert codes == [3]
        from hetu_tpu import launcher
        kinds = [e["event"] for e in launcher.last_failure_events]
        assert kinds.count("worker_exit") == 3      # 1 first + 2 retries
        assert "worker_failed" in kinds


class TestSupervisorPSRestart:
    def test_ps_server_sigkill_is_respawned(self, monkeypatch):
        """A chaos-killed PS server is respawned by the supervisor and
        the cluster still completes (the worker rides through or is
        itself restarted within budget)."""
        from hetu_tpu.context import DistConfig
        from hetu_tpu.launcher import run_cluster

        d = tempfile.mkdtemp()
        script = os.path.join(d, "worker.py")
        with open(script, "w") as f:
            f.write("""
import os, time
import numpy as np
from hetu_tpu.ps.client import PSClient
c = PSClient.get()
c.param_set("w", np.zeros(4, np.float32), opt="sgd",
            opt_args={"learning_rate": 1.0})
for i in range(40):
    c.push("w", -np.ones(4, np.float32))
    time.sleep(0.02)
open(os.path.join(%r, "done"), "w").write("1")
""" % d)
        log = os.path.join(d, "failures.jsonl")
        port = _free_port()
        monkeypatch.setenv("HETU_PS_PORT", str(port))
        monkeypatch.setenv("HETU_FAILURE_LOG", log)
        monkeypatch.setenv("HETU_RESTART_BACKOFF", "0.3")
        monkeypatch.setenv("HETU_RESTART_LIMIT", "5")
        monkeypatch.setenv("HETU_PS_TIMEOUT", "3")
        monkeypatch.setenv("HETU_PS_CONNECT_TIMEOUT", "1")
        monkeypatch.setenv("HETU_PS_RETRIES", "3")
        # the kill plan reaches the server child through the launcher's
        # env inheritance; role-scoping keeps every other process inert
        monkeypatch.setenv("HETU_CHAOS", "seed=5,kill=25,role=server:0")
        codes = run_cluster(DistConfig(num_servers=1, num_workers=1),
                            [sys.executable, script])
        monkeypatch.delenv("HETU_CHAOS")
        assert codes == [0]
        assert os.path.exists(os.path.join(d, "done"))
        events = [json.loads(ln) for ln in open(log)]
        kinds = [e["event"] for e in events]
        assert "ps_server_exit" in kinds
        assert "ps_restart" in kinds


class _FlakyComm:
    """PSServer facade whose RPCs fail while ``down`` (PS outage
    stand-in).  ``down_methods`` restricts the outage to a method
    subset (e.g. only the push seam)."""

    def __init__(self, srv, down_methods=None):
        self._srv = srv
        self.down = False
        self._down_methods = down_methods

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        fn = getattr(self._srv, name)

        def call(*a, **kw):
            if self.down and (self._down_methods is None
                              or name in self._down_methods):
                raise PSConnectionError("PS down (test)")
            return fn(*a, **kw)
        return call


@pytest.mark.smoke
class TestCacheOutage:
    def _mk(self):
        from hetu_tpu.cache.cstable import CacheSparseTable
        srv = PSServer()
        table = np.arange(64, dtype=np.float32).reshape(16, 4)
        srv.param_set("emb", table)
        comm = _FlakyComm(srv)
        ct = CacheSparseTable(limit=8, vocab_size=16, width=4,
                              key="emb", comm=comm, policy="LRU",
                              prefer_native=False)
        return srv, comm, ct, table

    def test_stale_hits_and_zero_misses_during_outage(self):
        srv, comm, ct, table = self._mk()
        warm = np.arange(6)
        np.testing.assert_allclose(ct.embedding_lookup(warm),
                                   table[warm])
        comm.down = True
        # hits: served from cache (stale within the budget)
        got = ct.embedding_lookup(warm)
        np.testing.assert_allclose(got, table[warm])
        assert ct.num_stale_served > 0
        # misses: zero vectors, not inserted
        got = ct.embedding_lookup(np.array([9]))
        np.testing.assert_allclose(got, 0.0)
        assert ct.num_zero_served == 1
        comm.down = False
        # recovery: the miss re-fetches for real
        np.testing.assert_allclose(ct.embedding_lookup(np.array([9])),
                                   table[[9]])

    def test_pushes_buffer_and_replay(self):
        srv, comm, ct, table = self._mk()
        warm = np.arange(4)
        ct.embedding_lookup(warm)
        comm.down = True
        # cold-id updates can't reach the PS: they buffer
        ct.embedding_update(np.array([12, 12, 13]),
                            np.ones((3, 4), np.float32))
        assert ct.perf_summary()["backlog_rows"] == 2   # merged dup id
        # flush during the outage buffers the dirty warm lines too
        ct.embedding_update(warm, np.full((4, 4), 0.5, np.float32))
        ct.flush()
        assert ct.perf_summary()["backlog_rows"] >= 2
        before = np.asarray(srv.pull("emb")).copy()
        comm.down = False
        ct.flush()                                      # replays
        assert ct.perf_summary()["backlog_rows"] == 0
        assert ct.num_replayed_rows > 0
        after = np.asarray(srv.pull("emb"))
        np.testing.assert_allclose(after[12], before[12] + 2.0)
        np.testing.assert_allclose(after[13], before[13] + 1.0)
        np.testing.assert_allclose(after[:4], before[:4] + 0.5)

    def test_outage_budget_bounds_degradation(self):
        srv, comm, ct, table = self._mk()
        ct.embedding_lookup(np.arange(4))
        ct.max_stale = 3
        comm.down = True
        for _ in range(3):
            ct.embedding_lookup(np.arange(4))   # within budget
        with pytest.raises(ConnectionError):
            for _ in range(5):
                ct.embedding_lookup(np.arange(4))


class TestExecutorOutageBacklog:
    def test_direct_path_buffers_pushes_across_outage(self):
        import hetu_tpu as ht

        srv = PSServer()
        # outage on the PUSH seam only: phase A's reads stay up, so the
        # backlog (not the read path) is what carries the step
        comm = _FlakyComm(srv, down_methods={"sparse_push", "push"})
        ids = ht.placeholder_op("fo_ids")
        y = ht.placeholder_op("fo_y")
        emb = ht.layers.Embedding(16, 4, name="fo_emb")
        h = ht.embedding_lookup_op(emb.embedding_table, ids)
        h = ht.reduce_mean_op(h, [1])
        logits = ht.matmul_op(h, ht.init.xavier_uniform(
            (4, 2), name="fo_head"))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, y), axes=0)
        train = ht.optim.SGDOptimizer(learning_rate=0.2).minimize(loss)
        ex = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid",
                         ps_comm=comm)
        rng = np.random.RandomState(0)

        def step():
            a = rng.randint(0, 16, (8, 4)).astype(np.int32)
            b = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)]
            out = ex.run("train", feed_dict={ids: a, y: b})
            ex.join_ps_push()
            return float(np.asarray(out[0]))

        assert np.isfinite(step())
        before = np.asarray(srv.pull("fo_emb_table")).copy()
        comm.down = True
        assert np.isfinite(step())              # push buffered, no raise
        assert len(ex._ps_push_backlog) >= 1
        np.testing.assert_allclose(np.asarray(srv.pull("fo_emb_table")),
                                   before)     # nothing landed while down
        comm.down = False
        assert np.isfinite(step())              # replays + current push
        assert ex._ps_push_backlog == []
        assert not np.allclose(
            np.asarray(srv.pull("fo_emb_table")), before)


class TestValidatorEventLogContract:
    """The verifier's JSONL report (HETU_VALIDATE_LOG) shares the
    failure log's record shape, keeping PR 1's event-log contract
    uniform: one ``tail | jq 'select(.event == ...)'`` pipeline reads
    launcher failures, serving telemetry, and validation reports."""

    def _record_shape_ok(self, rec):
        return isinstance(rec.get("t"), float) \
            and isinstance(rec.get("event"), str)

    def test_verifier_records_match_failure_log_shape(self, tmp_path,
                                                      monkeypatch):
        import hetu_tpu as ht
        log = tmp_path / "validate.jsonl"
        monkeypatch.setenv("HETU_VALIDATE", "1")
        monkeypatch.setenv("HETU_VALIDATE_LOG", str(log))
        a = ht.Variable("vc_a", value=np.ones((4, 3), np.float32))
        b = ht.Variable("vc_b", value=np.ones((3, 2), np.float32))
        ht.Executor({"eval": [ht.reduce_mean_op(
            ht.matmul_op(a, b), axes=0)]})
        recs = [json.loads(line)
                for line in log.read_text().splitlines()]
        assert recs and all(self._record_shape_ok(r) for r in recs)
        assert {r["event"] for r in recs} <= {
            "graph_verified", "graph_verify_error"}

    def test_verify_error_record_lands_like_a_failure_event(
            self, tmp_path, monkeypatch):
        import hetu_tpu as ht
        from hetu_tpu.analysis import GraphVerifyError
        log = tmp_path / "validate.jsonl"
        monkeypatch.setenv("HETU_VALIDATE", "1")
        monkeypatch.setenv("HETU_VALIDATE_LOG", str(log))
        a = ht.Variable("vc_c", value=np.ones((4, 3), np.float32))
        b = ht.Variable("vc_d", value=np.ones((5, 2), np.float32))
        bad = ht.matmul_op(a, b)
        with pytest.raises(GraphVerifyError):
            ht.Executor({"eval": [bad]})
        recs = [json.loads(line)
                for line in log.read_text().splitlines()]
        err = [r for r in recs if r["event"] == "graph_verify_error"]
        assert err and self._record_shape_ok(err[0])
        # the record carries the same attribution the exception does
        assert err[0]["node"] == bad.name
        assert err[0]["kind"] == "shape"

    def test_uniform_with_launcher_failure_records(self, tmp_path,
                                                   monkeypatch):
        # one merged stream: a launcher failure event and a verifier
        # record filter through the same (t, event) pipeline
        from hetu_tpu.analysis.report import emit_records, make_record
        log = tmp_path / "merged.jsonl"
        launcher_rec = {"t": round(time.time(), 3),
                        "event": "worker_exit", "rank": 0, "code": -9}
        with open(log, "a") as f:
            f.write(json.dumps(launcher_rec) + "\n")
        emit_records([make_record("graph_verified", subgraph="train",
                                  nodes=12)], path=str(log))
        recs = [json.loads(line)
                for line in log.read_text().splitlines()]
        assert len(recs) == 2
        assert all(self._record_shape_ok(r) for r in recs)


@pytest.mark.smoke
class TestFlightRecorderChaos:
    """ISSUE 7 tentpole (d): the chaos flight recorder under
    ``HETU_CHAOS``.  A ``kill=`` event must write the black box to
    ``$HETU_FLIGHT_LOG`` BEFORE the SIGKILL lands (the process gets no
    other chance), and a reset storm that exhausts the client's retries
    dumps from the ``PSConnectionError`` failure path — in both cases a
    contract-valid JSONL file holding the records that led up to the
    fault."""

    def _read_dump(self, path):
        with open(path) as f:
            return [json.loads(ln) for ln in f if ln.strip()]

    def test_chaos_kill_dumps_flight_log(self, tmp_path):
        import subprocess
        flog = str(tmp_path / "flight.jsonl")
        script = (
            "from hetu_tpu import telemetry\n"
            "from hetu_tpu.ps import faults\n"
            "for i in range(6):\n"
            "    telemetry.emit('worker_exit', _stream='failure',\n"
            "                   rank=i, rc=0)\n"
            "plan = faults.plan_from_env()\n"
            "for _ in range(10):\n"
            "    plan.draw('push')   # the 4th evaluated event SIGKILLs\n"
            "raise SystemExit('kill never fired')\n")
        env = dict(os.environ, HETU_CHAOS="seed=1,kill=4",
                   HETU_CHAOS_ROLE="", HETU_RESTART_COUNT="0",
                   HETU_FLIGHT_LOG=flog, HETU_FLIGHT_DEPTH="32",
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, timeout=120)
        assert proc.returncode == -9, (proc.returncode, proc.stderr)
        recs = self._read_dump(flog)
        assert recs[0]["event"] == "flight_dump"
        assert recs[0]["reason"] == "chaos_kill"
        assert recs[0]["chaos_event"] == 4
        assert recs[0]["records"] == len(recs) - 1
        # the records leading up to the kill are all there, in order
        assert [r["rank"] for r in recs[1:]] == list(range(6))
        from hetu_tpu.telemetry import validate_record
        for rec in recs:
            assert validate_record(rec) == [], rec
        # and hetu_trace --check accepts the dump as a stream
        from hetu_tpu.telemetry.trace import main as trace_main
        assert trace_main([flog, "--check"]) == 0

    def test_reset_storm_dumps_on_retry_exhaustion(self, tmp_path,
                                                   monkeypatch):
        from hetu_tpu import telemetry
        flog = str(tmp_path / "reset.jsonl")
        monkeypatch.setenv("HETU_FLIGHT_LOG", flog)
        telemetry.reset()
        telemetry.emit("worker_exit", _stream="failure", rank=7, rc=0)
        srv = PSServer()
        c = PSClient(transport=_LocalServerTransport(srv))
        c.param_set("fw", np.zeros(2, np.float32))
        monkeypatch.setenv("HETU_CHAOS", "seed=0,reset=1.0")
        with pytest.raises(PSConnectionError):
            c.pull("fw")
        recs = self._read_dump(flog)
        headers = [r for r in recs if r["event"] == "flight_dump"]
        assert headers and headers[0]["reason"] == "ps_connection_error"
        assert headers[0]["shard"] == "local"
        # the pre-fault marker made it into the black box
        assert any(r["event"] == "worker_exit" and r.get("rank") == 7
                   for r in recs)
        from hetu_tpu.telemetry import validate_record
        for rec in recs:
            assert validate_record(rec) == [], rec

    def test_no_flight_log_never_blocks_the_kill_path(self, monkeypatch):
        # HETU_FLIGHT_LOG unset: dump is a no-op returning None (the
        # chaos kill and error paths must not grow a new failure mode)
        from hetu_tpu.telemetry.flight import RECORDER
        monkeypatch.delenv("HETU_FLIGHT_LOG", raising=False)
        assert RECORDER.dump("chaos_kill") is None
