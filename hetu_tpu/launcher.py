"""Process launcher (reference bin/heturun + python/runner.py:150-260 +
python/hetu/launcher.py).

The reference spawns PS scheduler/server processes locally, starts remote
processes over ssh/paramiko, and runs workers under mpirun with DMLC_*
env vars.  The TPU build has no MPI and no scheduler role (the TCP PS
server is self-contained): `heturun -c cluster.yml python train.py`

- starts `servers:` PS processes per host (local ones directly; remote
  ones via the system `ssh` when configured),
- starts `workers:` worker processes per host with HETU_PS_* and
  JAX_COORDINATOR_* env so workers reach the PS and, on TPU pods,
  `jax.distributed.initialize()` finds the coordinator,
- tears everything down on SIGINT like the reference runner
  (runner.py:16-22).

The python API `launch(target, args)` mirrors reference launcher.py:18:
run a callable under a local PS "cluster" (used by the cache tests the
same way hetu_cache_test.py:11-34 uses it).
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
import multiprocessing

from .context import DistConfig

from . import envvars

_procs: list = []
DEFAULT_PS_PORT = 23455

# the most recent run_cluster's structured failure/restart event log
# (worker_exit / worker_restart / ps_server_exit / ps_restart /
# ps_resynced ... records); also appended as JSONL to $HETU_FAILURE_LOG
last_failure_events: list = []


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _start_ps_process(port, extra_env=None):
    proc = multiprocessing.get_context("spawn").Process(
        target=_ps_main, args=(port, extra_env), daemon=True)
    proc.start()
    _procs.append(proc)
    return proc


def _ps_main(port, extra_env=None):
    # env set in the CHILD only — mutating the launcher's own environ
    # would leak role variables (e.g. HETU_SCHEDULER_ADDR) into later
    # in-process PSClient.get() resolution
    os.environ.update(extra_env or {})
    os.environ["HETU_PS_PORT"] = str(port)
    from .ps.server import PSServer
    PSServer.serve_from_env()


def _scheduler_main(port):
    os.environ["HETU_SCHEDULER_PORT"] = str(port)
    from .ps.server import Scheduler
    Scheduler.serve_from_env()


def _start_scheduler_process(port):
    proc = multiprocessing.get_context("spawn").Process(
        target=_scheduler_main, args=(port,), daemon=True)
    proc.start()
    _procs.append(proc)
    return proc


def _wait_ps(host, port, timeout=20.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            s = socket.create_connection((host, port), timeout=1.0)
            s.close()
            return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError(f"PS at {host}:{port} did not come up")


def _worker_env(config, host, rank, nrank, ps_host, ps_port,
                coordinator=None):
    env = dict(os.environ)
    # make hetu_tpu importable from any cwd (reference hetu.exp sets
    # PYTHONPATH the same way)
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    if ps_port is not None:
        env["HETU_PS_ADDR"] = f"{ps_host}:{ps_port}"
        env["HETU_PS_RANK"] = str(rank)
        env["HETU_PS_NRANK"] = str(nrank)
    if coordinator and nrank > 1:
        # JAX_COORDINATOR_ADDRESS is read by jax.distributed.initialize();
        # process counts are NOT read from env by jax, so workers call our
        # distributed_init() helper (below) which passes them explicitly
        env["JAX_COORDINATOR_ADDRESS"] = coordinator
        env["HETU_NUM_PROCESSES"] = str(nrank)
        env["HETU_PROCESS_ID"] = str(rank)
    return env


def distributed_init():
    """Worker-side bring-up for multi-host meshes (replaces the
    reference's wrapped_mpi_nccl_init, executor.py:60-71): call this at
    the top of a worker script launched by heturun.  No-op single-host."""
    import jax

    nrank = envvars.get_int("HETU_NUM_PROCESSES")
    if nrank <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=nrank,
        process_id=envvars.require_int("HETU_PROCESS_ID"))


def _sigint(sig, frame):
    for p in _procs:
        try:
            (p.kill if hasattr(p, "kill") else p.terminate)()
        except Exception:
            pass
    sys.exit(0)


def _proc_poll(p):
    """Exit code or None, across subprocess.Popen and mp.Process."""
    if hasattr(p, "poll"):
        return p.poll()
    return None if p.is_alive() else p.exitcode


def run_cluster(config: DistConfig, command, coordinator_port=6655,
                supervise=None):
    """heturun main path: PS process(es) + worker subprocesses running
    `command` (argv list), SUPERVISED.  Returns worker exit codes.

    Multiple servers get sequential ports (our PS server is one process
    per port, unlike ps-lite's key-sharded server group); workers see the
    first as HETU_PS_ADDR and the full list as HETU_PS_ADDRS.

    The supervisor (default on; ``supervise=False`` or HETU_SUPERVISE=0
    restores fire-and-wait) watches child exit codes and respawns:

    - a dead PS server is restarted on its port and, when the group is
      replicated (HETU_PS_REPLICATE=1, >1 server), re-seeded from its
      ring replica via ``ps.sharded.resync_primary`` before workers
      route traffic back to it;
    - a worker exiting nonzero is restarted (the worker script resumes
      from its latest checkpoint — Executor.save/load persists params,
      optimizer slots, step, rng and dataloader positions; the child
      sees HETU_RESTART_COUNT);
    - each slot has an exponential-backoff restart budget:
      HETU_RESTART_LIMIT (default 3) restarts, HETU_RESTART_BACKOFF
      (default 0.5) * 2^attempt seconds apart;
    - every failure/restart appends a structured record to
      ``launcher.last_failure_events`` and (JSONL) to
      $HETU_FAILURE_LOG.

    With HETU_LIVENESS_STALE=<seconds> > 0 the supervisor also polls the
    rendezvous scheduler's heartbeat map and kills a *wedged* server
    (process alive, heartbeats stale) so the restart path above takes
    over — the mid-run wedge class of failure, not just clean exits."""
    signal.signal(signal.SIGINT, _sigint)
    _procs.clear()
    global last_failure_events
    events = last_failure_events = []

    def _event(kind, **fields):
        # ONE emitter repo-wide (telemetry/events.py): the sink appends
        # to $HETU_FAILURE_LOG (legacy stream path) and the merged
        # $HETU_TELEMETRY_LOG in the same {t, event, ...} shape
        from .telemetry import emit
        rec = emit(kind, _stream="failure", **fields)
        events.append(rec)
        if kind in ("worker_failed", "ps_server_dead",
                    "ps_restart_failed"):
            # terminal supervisor outcomes (budget spent / respawn
            # impossible): dump the flight ring so the post-mortem has
            # the restart/backoff records that led here
            from .telemetry.flight import RECORDER
            RECORDER.dump("launcher_failure", trigger=kind)
        print(f"[heturun] {kind}: {fields}", flush=True)

    if supervise is None:
        supervise = envvars.get_bool("HETU_SUPERVISE")
    restart_limit = envvars.get_int("HETU_RESTART_LIMIT")
    backoff0 = envvars.get_float("HETU_RESTART_BACKOFF")
    liveness_stale = envvars.get_float("HETU_LIVENESS_STALE")

    ps_port = None
    local_names = ("localhost", "127.0.0.1", socket.gethostname())
    # PS lives on the first host that configures servers (NOT necessarily
    # the chief)
    ps_host = next(iter(config.servers), config.chief or "localhost")
    ps_addrs = []
    sched_addr = None
    sched_port = None
    server_slots = []
    if config.enable_PS:
        base_port = envvars.get_int("HETU_PS_PORT", DEFAULT_PS_PORT)
        # scheduler rendezvous (ps-lite Postoffice role): servers
        # register; workers can resolve the group dynamically.  Static
        # HETU_PS_ADDRS is still exported and takes precedence — the
        # scheduler is the contract for deployments where ports are not
        # known up front.
        sched_port = _free_port()
        _start_scheduler_process(sched_port)
        _wait_ps("localhost", sched_port)
        sched_addr = f"{config.chief or 'localhost'}:{sched_port}"
        idx = 0
        for host, n in config.servers.items():
            for _ in range(n):
                port = base_port + idx
                env_extra = {"HETU_SCHEDULER_ADDR":
                             f"localhost:{sched_port}"
                             if host in local_names else sched_addr,
                             "HETU_PS_INDEX": str(idx),
                             "HETU_PS_ADVERTISE": f"{host}:{port}",
                             "HETU_CHAOS_ROLE": f"server:{idx}"}
                if host in local_names:
                    def spawn(port=port, env_extra=env_extra, restarts=0):
                        return _start_ps_process(port, dict(
                            env_extra, HETU_RESTART_COUNT=str(restarts)))
                else:
                    def spawn(host=host, port=port, env_extra=env_extra,
                              restarts=0):
                        return _ssh_spawn(host, [
                            sys.executable, "-m", "hetu_tpu.launcher",
                            "--serve-ps", str(port)], env=dict(
                                env_extra,
                                HETU_RESTART_COUNT=str(restarts)))
                server_slots.append({
                    "index": idx, "host": host, "port": port,
                    "spawn": spawn, "proc": spawn(), "restarts": 0,
                    "next_at": None})
                idx += 1
                ps_addrs.append(f"{host}:{port}")
        ps_host, ps_port = ps_addrs[0].rsplit(":", 1)
        ps_port = int(ps_port)
        for slot in server_slots:
            _wait_ps("localhost" if slot["host"] in local_names
                     else slot["host"], slot["port"])
    replicated = len(ps_addrs) > 1 and \
        envvars.get_bool("HETU_PS_REPLICATE")

    nrank = config.num_workers
    chief = config.chief or "localhost"
    coordinator = f"{chief}:{coordinator_port}" if nrank > 1 else None
    worker_slots = []
    rank = 0
    for host, n in config.workers.items():
        for _ in range(n):
            env = _worker_env(config, host, rank, nrank, ps_host, ps_port,
                              coordinator)
            if ps_addrs:
                env["HETU_PS_ADDRS"] = ",".join(ps_addrs)
                env["HETU_PS_NSERVERS"] = str(len(ps_addrs))
            if sched_addr:
                env["HETU_SCHEDULER_ADDR"] = sched_addr
            env["HETU_CHAOS_ROLE"] = f"worker:{rank}"

            def spawn(host=host, env=env, restarts=0):
                env = dict(env, HETU_RESTART_COUNT=str(restarts))
                if host in local_names:
                    p = subprocess.Popen(command, env=env)
                    _procs.append(p)
                    return p
                return _ssh_spawn(host, command, env={
                    k: v for k, v in env.items()
                    if k.startswith(("HETU_", "JAX_"))})
            worker_slots.append({
                "rank": rank, "spawn": spawn, "proc": spawn(),
                "restarts": 0, "next_at": None, "code": None})
            rank += 1

    def _respawn_server(slot):
        slot["proc"] = slot["spawn"](restarts=slot["restarts"])
        try:
            _wait_ps("localhost" if slot["host"] in local_names
                     else slot["host"], slot["port"])
        except TimeoutError as e:
            _event("ps_restart_failed", index=slot["index"],
                   error=str(e))
            return
        _event("ps_restart", index=slot["index"], port=slot["port"],
               attempt=slot["restarts"])
        if replicated:
            try:
                from .ps.sharded import resync_primary
                keys = resync_primary(ps_addrs, slot["index"])
                _event("ps_resynced", index=slot["index"],
                       keys=len(keys))
            except Exception as e:  # noqa: BLE001 — degraded, not fatal
                _event("ps_resync_failed", index=slot["index"],
                       error=f"{type(e).__name__}: {e}"[:200])

    def _check_liveness(now, state={"last": 0.0}):
        """Kill wedged-but-running servers flagged dead by the
        scheduler's heartbeat map (HETU_LIVENESS_STALE seconds)."""
        if liveness_stale <= 0 or sched_port is None or \
                now - state["last"] < max(liveness_stale / 2, 1.0):
            return
        state["last"] = now
        try:
            from .ps.client import _TCPTransport
            t = _TCPTransport("localhost", sched_port, timeout=2.0,
                              connect_timeout=2.0, retries=1)
            health = t.call("health", liveness_stale)
            t.close()
        except Exception:
            return
        for slot in server_slots:
            node = f"server:{slot['index']}"
            if health.get(node, {}).get("alive", True):
                continue
            if _proc_poll(slot["proc"]) is None:
                _event("ps_wedged_kill", index=slot["index"],
                       age_s=health[node]["age_s"])
                try:
                    (slot["proc"].kill if hasattr(slot["proc"], "kill")
                     else slot["proc"].terminate)()
                except Exception:
                    pass

    if not supervise:
        codes = [w["proc"].wait() for w in worker_slots]
    else:
        while any(w["code"] is None for w in worker_slots):
            now = time.monotonic()
            for w in worker_slots:
                if w["code"] is not None:
                    continue
                if w["proc"] is None:          # backoff window
                    if now >= w["next_at"]:
                        w["proc"] = w["spawn"](restarts=w["restarts"])
                        _event("worker_restart", rank=w["rank"],
                               attempt=w["restarts"])
                    continue
                rc = _proc_poll(w["proc"])
                if rc is None:
                    continue
                if rc == 0:
                    w["code"] = 0
                    continue
                _event("worker_exit", rank=w["rank"], rc=rc,
                       restarts=w["restarts"])
                if w["restarts"] < restart_limit:
                    w["restarts"] += 1
                    backoff = backoff0 * 2 ** (w["restarts"] - 1)
                    w["proc"], w["next_at"] = None, now + backoff
                    _event("worker_restart_scheduled", rank=w["rank"],
                           attempt=w["restarts"],
                           backoff_s=round(backoff, 3))
                else:
                    w["code"] = rc
                    _event("worker_failed", rank=w["rank"], rc=rc,
                           restarts=w["restarts"])
            for slot in server_slots:
                if slot["proc"] is None:       # backoff window
                    if now >= slot["next_at"]:
                        slot["next_at"] = None
                        _respawn_server(slot)
                    continue
                rc = _proc_poll(slot["proc"])
                if rc is None:
                    continue
                _event("ps_server_exit", index=slot["index"], rc=rc,
                       restarts=slot["restarts"])
                if slot["restarts"] < restart_limit:
                    slot["restarts"] += 1
                    backoff = backoff0 * 2 ** (slot["restarts"] - 1)
                    slot["proc"], slot["next_at"] = None, now + backoff
                else:
                    # terminal: budget spent — workers keep running on
                    # the replica (or fail with PSConnectionError)
                    _event("ps_server_dead", index=slot["index"], rc=rc)
                    slot["proc"], slot["next_at"] = None, float("inf")
            _check_liveness(now)
            time.sleep(0.2)
        codes = [w["code"] for w in worker_slots]
    for p in _procs:
        if hasattr(p, "poll") and p.poll() is None:
            p.terminate()
        elif hasattr(p, "is_alive") and p.is_alive():
            p.terminate()
    return codes


def _ssh_spawn(host, command, env=None):
    """Remote start over the system ssh (reference uses paramiko,
    runner.py:36-148).  Untested without a cluster; kept narrow."""
    import shlex

    parts = [f"{k}={shlex.quote(str(v))}" for k, v in (env or {}).items()]
    parts += [shlex.quote(str(c)) for c in command]
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host,
           " ".join(parts)]
    p = subprocess.Popen(cmd)
    _procs.append(p)
    return p


def launch(target, args=(), num_servers=1):
    """Python-API launcher (reference launcher.py:18): run `target(args)`
    with a freshly started local PS; tears the PS down after."""
    port = _free_port()
    proc = _start_ps_process(port)
    _wait_ps("localhost", port)
    old = envvars.get_str("HETU_PS_ADDR")
    os.environ["HETU_PS_ADDR"] = f"localhost:{port}"
    try:
        from .ps.client import PSClient
        PSClient._instance = None  # re-resolve transport from env
        return target(*args) if args else target()
    finally:
        if old is None:
            os.environ.pop("HETU_PS_ADDR", None)
        else:
            os.environ["HETU_PS_ADDR"] = old
        from .ps.client import PSClient
        PSClient._instance = None
        proc.terminate()
        proc.join(timeout=5)
        _procs.remove(proc) if proc in _procs else None


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        prog="heturun",
        description="hetu_tpu cluster launcher (reference bin/heturun)")
    parser.add_argument("-c", "--config", default=None,
                        help="cluster yaml (DistConfig format)")
    parser.add_argument("-s", "--servers", type=int, default=0,
                        help="local PS server count (no yaml)")
    parser.add_argument("-w", "--workers", type=int, default=1,
                        help="local worker count (no yaml)")
    parser.add_argument("--serve-ps", type=int, default=None,
                        help=argparse.SUPPRESS)  # internal: PS role
    parser.add_argument("--serve-scheduler", type=int, default=None,
                        help=argparse.SUPPRESS)  # internal: rendezvous
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="worker command, e.g. python train.py")
    args = parser.parse_args(argv)

    if args.serve_ps is not None:
        _ps_main(args.serve_ps)
        return 0
    if args.serve_scheduler is not None:
        _scheduler_main(args.serve_scheduler)
        return 0
    if not args.command:
        parser.error("no worker command given")
    if args.config:
        config = DistConfig(file=args.config)
    else:
        config = DistConfig(num_servers=args.servers,
                            num_workers=args.workers)
    codes = run_cluster(config, args.command)
    return max(codes) if codes else 0


if __name__ == "__main__":
    sys.exit(main())
