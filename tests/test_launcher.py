"""Launcher tests (reference: runner.py spawns PS+workers from yaml;
tests/pstests/test_apis.py exercises multi-worker push/pull through a
launched local cluster)."""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from hetu_tpu.context import DistConfig
from hetu_tpu.launcher import launch, main, run_cluster


class TestDistConfigYaml:
    def test_yaml_parse(self):
        d = tempfile.mkdtemp()
        p = os.path.join(d, "cluster.yml")
        with open(p, "w") as f:
            f.write("""
nodes:
  - host: localhost
    chief: true
    servers: 1
    workers: 2
""")
        c = DistConfig(file=p)
        assert c.chief == "localhost"
        assert c.enable_PS and c.num_servers == 1 and c.num_workers == 2


def test_launcher_and_ps_roles_never_initialise_a_backend():
    """One process per chip (ISSUE 22): ``heturun``'s parent and the PS /
    scheduler children it spawns only IMPORT jax — a parent that had
    initialised a backend would hold the chip its workers need."""
    code = (
        "import hetu_tpu.launcher, hetu_tpu.ps.server, hetu_tpu.ps.client\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]


class TestLaunch:
    def test_launch_runs_target_against_fresh_ps(self):
        def target():
            from hetu_tpu.ps.client import PSClient
            c = PSClient.get()
            c.parameter_init("w", (4,), init_type="constant", arg1=1.0)
            c.push("w", np.ones(4, np.float32))
            return np.asarray(c.pull("w"))

        out = launch(target)
        # constant-1 init, one push of ones with default server opt
        assert out.shape == (4,)
        assert np.all(np.isfinite(out))

    def test_launch_restores_env(self):
        before = os.environ.get("HETU_PS_ADDR")
        launch(lambda: None)
        assert os.environ.get("HETU_PS_ADDR") == before


class TestRunCluster:
    def test_two_workers_accumulate_on_shared_ps(self):
        """The reference's tier-3 pattern (test_apis.py:22-50): N worker
        processes push to one PS; total reflects both."""
        d = tempfile.mkdtemp()
        script = os.path.join(d, "worker.py")
        with open(script, "w") as f:
            f.write("""
import os, numpy as np
from hetu_tpu.ps.client import PSClient
c = PSClient.get()
rank = c.rank
c.parameter_init("acc", (2,), init_type="constant", arg1=0.0,
                 opt="sgd", opt_args={"learning_rate": 1.0})
c.BarrierWorker("init")
c.push("acc", -np.ones(2, np.float32))   # sgd lr=1: value += 1 per push
c.BarrierWorker("pushed")
val = np.asarray(c.pull("acc"))
assert np.allclose(val, 2.0), val
open(os.path.join(%r, f"ok{rank}"), "w").write("1")
""" % d)
        os.environ["HETU_PS_PORT"] = "23981"
        try:
            config = DistConfig(num_servers=1, num_workers=2)
            codes = run_cluster(config, [sys.executable, script])
        finally:
            os.environ.pop("HETU_PS_PORT", None)
        assert codes == [0, 0]
        assert os.path.exists(os.path.join(d, "ok0"))
        assert os.path.exists(os.path.join(d, "ok1"))


class TestCLI:
    def test_cli_no_command_errors(self):
        with pytest.raises(SystemExit):
            main(["-s", "0"])

    def test_cli_runs_local_worker(self):
        d = tempfile.mkdtemp()
        marker = os.path.join(d, "ran")
        code = main(["-w", "1", sys.executable, "-c",
                     f"open({marker!r}, 'w').write('1')"])
        assert code == 0
        assert os.path.exists(marker)


class TestHeturnTrainEndToEnd:
    """The full reference tier-3 flow: `heturun -c cluster.yml python
    train.py` — yaml cluster config, launcher spawns the PS and two
    worker processes, each worker builds an Executor in Hybrid mode and
    TRAINS against the shared PS with a BSP barrier per step; both
    workers' embedding updates land in the one table."""

    @pytest.mark.parametrize("bsp,van", [(0, False), (1, False),
                                         (0, True)],
                             ids=["bsp", "ssp1", "bsp-van"])
    def test_cluster_yaml_hybrid_training(self, bsp, van):
        from hetu_tpu.launcher import _free_port
        d = tempfile.mkdtemp()
        yml = os.path.join(d, "cluster.yml")
        with open(yml, "w") as f:
            f.write("""
nodes:
  - host: localhost
    chief: true
    servers: 1
    workers: 2
""")
        script = os.path.join(d, "train.py")
        with open(script, "w") as f:
            f.write("""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import hetu_tpu as ht
from hetu_tpu.ps.client import PSClient

OUT = %r
BSP = %d
V, D, B, STEPS = 16, 8, 8, 4
rank = int(os.environ["HETU_PS_RANK"])

ids_node = ht.placeholder_op("ids")
y = ht.placeholder_op("y")
emb = ht.layers.Embedding(V, D, name="e2e_table")
h = ht.embedding_lookup_op(emb.embedding_table, ids_node)
h = ht.reduce_mean_op(h, [1])
logits = ht.matmul_op(h, ht.init.xavier_uniform((D, 2), name="e2e_head"))
loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y), axes=0)
train = ht.optim.SGDOptimizer(learning_rate=0.5).minimize(loss)

# bsp=0: per-step BSP barrier across the two workers (reference
# BarrierWorker, ParameterServerCommunicate.py:49-53); bsp=k: SSP with
# staleness bound k (reference ssp_init/ssp_sync)
ex = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid", bsp=BSP)
c = PSClient.get()
c.BarrierWorker("post_init")     # both executors finished param_set

rng = np.random.RandomState(100 + rank)
half = V // 2
losses = []
for _ in range(STEPS):
    # worker r touches only its half of the vocabulary
    idb = rng.randint(rank * half, (rank + 1) * half,
                      (B, 4)).astype(np.int32)
    yb = np.eye(2, dtype=np.float32)[rng.randint(0, 2, B)]
    out = ex.run("train", feed_dict={ids_node: idb, y: yb})
    losses.append(float(np.asarray(out[0])))
assert all(np.isfinite(l) for l in losses), losses
if os.environ.get("HETU_PS_VAN"):
    # the deployment-shaped proof: the server advertised its C++ van
    # and this worker's sparse traffic actually opened fast-tier
    # sockets (phase A/B may run in pool threads; the process-wide
    # registry sees every one)
    vport, vkeys = c.t.call("van_info")
    assert vport and "e2e_table_table" in vkeys, (vport, vkeys)
    assert len(c._van_clients) > 0
c.BarrierWorker("trained")

table = np.asarray(c.pull("e2e_table_table"))
init = np.asarray(ex.variables["e2e_table_table"].init_value(0))
delta = np.abs(table - init).sum(axis=1)
# MY half moved (I trained it)...
mine = slice(rank * half, (rank + 1) * half)
assert delta[mine].sum() > 1e-6, delta
# ...and the OTHER worker's half moved too: cross-process updates
# through the one shared PS table
other = slice((1 - rank) * half, (2 - rank) * half)
assert delta[other].sum() > 1e-6, delta
open(os.path.join(OUT, f"trained{rank}"), "w").write(
    repr(losses))
""" % (d, bsp))
        port = _free_port()
        env_old = os.environ.get("HETU_PS_PORT")
        van_old = os.environ.get("HETU_PS_VAN")
        os.environ["HETU_PS_PORT"] = str(port)
        if van:
            os.environ["HETU_PS_VAN"] = "1"
        else:
            # an ambient HETU_PS_VAN must not leak into the non-van
            # variants (the launcher copies os.environ into children)
            os.environ.pop("HETU_PS_VAN", None)
        try:
            code = main(["-c", yml, sys.executable, script])
        finally:
            if env_old is None:
                os.environ.pop("HETU_PS_PORT", None)
            else:
                os.environ["HETU_PS_PORT"] = env_old
            if van_old is None:
                os.environ.pop("HETU_PS_VAN", None)
            else:
                os.environ["HETU_PS_VAN"] = van_old
        assert code == 0
        assert os.path.exists(os.path.join(d, "trained0"))
        assert os.path.exists(os.path.join(d, "trained1"))


class TestSchedulerHeartbeat:
    """ps-lite Postoffice heartbeat-map parity (SURVEY §5.3): liveness
    DETECTION at the scheduler; recovery stays checkpoint/restart, as
    in the reference (no elastic replacement there either)."""

    def test_health_marks_silent_nodes_dead(self):
        import time as _t
        from hetu_tpu.ps.server import Scheduler
        sched = Scheduler()
        sched.heartbeat("worker", 0)
        sched.heartbeat("worker", 1)
        sched.heartbeat("server", 0)
        h = sched.health(stale_after=15.0)
        assert set(h) == {"worker:0", "worker:1", "server:0"}
        assert all(v["alive"] for v in h.values())
        # worker:1 goes silent; a tight staleness window flags it
        _t.sleep(0.25)
        sched.heartbeat("worker", 0)
        h = sched.health(stale_after=0.2)
        assert h["worker:0"]["alive"]
        assert not h["worker:1"]["alive"]

    def test_client_heartbeat_thread_over_tcp(self):
        import os
        import time as _t
        from hetu_tpu.ps.server import Scheduler
        from hetu_tpu.ps.client import PSClient, _LocalTransport
        import socket as _sock
        sched = Scheduler()
        srv = _sock.socket()
        srv.bind(("127.0.0.1", 0))
        port = srv.getsockname()[1]
        srv.close()
        sched.serve_tcp(port, block=False)
        old = os.environ.get("HETU_SCHEDULER_ADDR")
        os.environ["HETU_SCHEDULER_ADDR"] = f"127.0.0.1:{port}"
        try:
            c = PSClient(transport=_LocalTransport())
            assert c.start_heartbeat(interval=0.1, node_id=7)
            deadline = _t.time() + 10
            while _t.time() < deadline:
                if "worker:7" in sched.health():
                    break
                _t.sleep(0.05)
            h = sched.health(stale_after=5.0)
            assert h["worker:7"]["alive"]
            c.stop_heartbeat()
        finally:
            if old is None:
                os.environ.pop("HETU_SCHEDULER_ADDR", None)
            else:
                os.environ["HETU_SCHEDULER_ADDR"] = old
            sched.shutdown()
