"""PS client: the ps-lite Worker API surface over in-process or TCP.

Reference: ps-lite Worker (worker/worker.h:19-65: pull/push/dd_pushpull/
sparse_pull/sparse_push/sd_pushpull/ss_pushpull/parameter_init/save/load/
wait) and the flat C exports consumed via ctypes (python_binding.cc:8-140:
Init/Pull/Push/..., ssp_init/ssp_sync/preduce_get_partner/getLoads).

Async semantics parity: push/pull return a ticket; ``wait(ticket)`` blocks
(reference Worker::wait) — implemented with a small thread pool so PS
traffic overlaps the jitted device step exactly like the reference overlaps
PS RPCs with CUDA compute via the d2h stream + PSEvent
(ParameterServerCommunicate.py:29-36, stream.py:73-87).
"""

from __future__ import annotations

import os
import socket
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor, Future

from .. import envvars, locks, quant

import numpy as np

from . import faults, wire

from .server import PSServer, _send_msg, _recv_msg
from .van import VanClient, VanTransportError


class PSConnectionError(ConnectionError):
    """A PS request could not be completed after retries.  Raised instead
    of hanging — the failure mode VERDICT r2 flagged (a dropped packet or
    dead server mid-training surfaced as a hang or pickle error)."""


# ---------------- wire quantization (HETU_PS_QUANT=int8) ---------------- #
#
# Gradients quantize CLIENT-side into a quant.QuantArray right before
# wire.dumps and dequantize SERVER-side before the optimizer step; pulls
# run the same pair in reverse (the client passes quant=... and decodes
# the response).  The ~3.7x wire reduction shows up directly in the
# per-shard ps.rpc.bytes_sent/recv counters; ps.rpc.bytes_saved records
# the delta.  Everything below is a no-op with the knob unset — the
# default wire stays byte-identical.

def _q_encode(arr):
    """QuantArray when int8 wire quantization is on and ``arr``
    qualifies (float, >= quant.WIRE_MIN_SIZE elements); else ``arr``
    unchanged.  Counts the saved bytes."""
    if quant.ps_quant() != "int8" or not quant.should_quantize(arr):
        return arr
    qa = quant.QuantArray.encode(arr)
    from .. import telemetry
    if telemetry.enabled():
        telemetry.inc("ps.rpc.bytes_saved", quant.wire_savings(qa))
    return qa


def _q_decode(value):
    """Decode a quantized response payload (pull half of the pair);
    plain arrays pass through.  Counts the saved bytes."""
    if isinstance(value, quant.QuantArray):
        from .. import telemetry
        if telemetry.enabled():
            telemetry.inc("ps.rpc.bytes_saved",
                          quant.wire_savings(value))
        return value.decode()
    return value


def _q_mode():
    """The quant argument verbs forward to the server (None = exact)."""
    return quant.ps_quant()


class _TCPTransport:
    """Reliable request/response over TCP.

    ps-lite robustness parity (resender.h + Van timeouts): every request
    carries a (client_id, seq) pair; on timeout or connection loss the
    client reconnects and resends, and the SERVER suppresses duplicate
    application by replaying the cached response for a seq it has already
    served (requests are serial per client thread, so a one-slot replay
    cache per client suffices).  After ``retries`` failed attempts a
    ``PSConnectionError`` surfaces — never a hang.

    Tunables (env): HETU_PS_TIMEOUT (per-call seconds, default 60),
    HETU_PS_CONNECT_TIMEOUT (default 10), HETU_PS_RETRIES (default 3)."""

    def __init__(self, host, port, timeout=None, connect_timeout=None,
                 retries=None):
        self._local = threading.local()
        self.host, self.port = host, port
        self.timeout = float(
            timeout if timeout is not None
            else envvars.get_float("HETU_PS_TIMEOUT"))
        self.connect_timeout = float(
            connect_timeout if connect_timeout is not None
            else envvars.get_float("HETU_PS_CONNECT_TIMEOUT"))
        self.retries = int(
            retries if retries is not None
            else envvars.get_int("HETU_PS_RETRIES"))

    def _state(self):
        st = self._local
        if getattr(st, "client_id", None) is None:
            st.client_id = uuid.uuid4().hex
            st.seq = 0
            st.sock = None
        return st

    def _connect(self):
        s = socket.create_connection((self.host, self.port),
                                     timeout=self.connect_timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(self.timeout)
        return s

    def call(self, method, *args, **kwargs):
        from .. import telemetry
        # lockdep held-across seam: an RPC (connect + send + recv, up
        # to retries x timeout seconds) under any traced lock turns
        # that lock's critical section into an unbounded wait
        locks.note_blocking("ps_rpc", method=method)
        st = self._state()
        st.seq += 1
        payload = wire.dumps(
            ("__req2__", st.client_id, st.seq, method, args, kwargs))
        chaos = faults.plan_from_env()
        last_err = None
        tel = telemetry.enabled()
        shard = f"{self.host}:{self.port}"
        t_call = time.perf_counter() if tel else 0.0
        for attempt in range(self.retries):
            # chaos seam (HETU_CHAOS): one decision per ATTEMPT, so an
            # injected loss exercises exactly the reconnect/resend path
            # a real one would (the seq is fixed per call — a post-apply
            # loss makes the server see a true duplicate)
            fault = chaos.draw(method) if chaos is not None else None
            try:
                if fault is not None:
                    if fault.kind == "delay":
                        time.sleep(fault.seconds)
                    elif fault.kind == "drop":
                        raise faults.InjectedFault(
                            "chaos: request dropped before send")
                    elif fault.kind == "reset":
                        raise faults.InjectedFault(
                            "chaos: connection reset")
                if st.sock is None:
                    st.sock = self._connect()
                _send_msg(st.sock, payload)
                raw = _recv_msg(st.sock)
                if raw is None:
                    raise ConnectionResetError("PS closed the connection")
                ok, result = wire.loads(raw)
                if not ok:
                    raise RuntimeError(
                        f"PS server error in {method}: {result}")
                if fault is not None and fault.kind == "dup":
                    # the server applied and answered, but the response
                    # is "lost": the retry resends the SAME seq and the
                    # server's replay cache must answer without
                    # re-applying (resender.h parity under test)
                    raise faults.InjectedFault(
                        "chaos: response dropped after apply")
                if fault is not None and fault.kind == "slow":
                    time.sleep(fault.seconds)
                if tel:
                    # per-shard RPC accounting (PS client half of the
                    # reference NCCLProfiler's comm visibility)
                    telemetry.observe(
                        "ps.rpc_ms." + method,
                        (time.perf_counter() - t_call) * 1e3)
                    telemetry.inc(f"ps.rpc.calls[{shard}]")
                    telemetry.inc("ps.rpc.bytes_sent", len(payload))
                    telemetry.inc("ps.rpc.bytes_recv", len(raw))
                    if attempt:
                        telemetry.inc("ps.rpc.recovered")
                return result
            except (OSError, ConnectionError, socket.timeout, EOFError,
                    wire.WireError) as e:
                last_err = e
                if tel:
                    telemetry.inc(f"ps.rpc.retries[{shard}]")
                    if isinstance(e, socket.timeout):
                        telemetry.inc(f"ps.rpc.timeouts[{shard}]")
                if st.sock is not None:
                    try:
                        st.sock.close()
                    except OSError:
                        pass
                    st.sock = None
                if attempt < self.retries - 1 and \
                        not isinstance(e, faults.InjectedFault):
                    # no backoff for synthetic losses: chaos runs model
                    # packet loss, not congestion
                    time.sleep(min(2.0, 0.2 * (attempt + 1)))
        if tel:
            telemetry.inc(f"ps.rpc.failures[{shard}]")
        telemetry.flight.RECORDER.dump(
            "ps_connection_error", method=method, shard=shard,
            retries=self.retries)
        raise PSConnectionError(
            f"PS request {method!r} to {self.host}:{self.port} failed "
            f"after {self.retries} attempts (last: "
            f"{type(last_err).__name__}: {last_err}); the server is down "
            f"or unreachable") from last_err

    def close(self):
        if getattr(self._local, "sock", None) is not None:
            self._local.sock.close()
            self._local.sock = None


def _local_chaos_call(server, method, args, kwargs):
    """In-process chaos seam shared by every local transport (here and
    sharded._LocalServerTransport).  There is no socket to resend over,
    so losses retry immediately; ``dup`` cannot double-apply in-process
    (a returned response cannot be lost) and degrades to a no-op
    decision; ``kill`` and the latency kinds behave as on the wire."""
    from .. import telemetry
    tel = telemetry.enabled()
    t_call = time.perf_counter() if tel else 0.0

    def _done(result):
        if tel:
            telemetry.observe("ps.rpc_ms." + method,
                              (time.perf_counter() - t_call) * 1e3)
            telemetry.inc("ps.rpc.calls[local]")
        return result

    chaos = faults.plan_from_env()
    if chaos is None:
        return _done(getattr(server, method)(*args, **kwargs))
    last = None
    for _ in range(3):
        fault = chaos.draw(method)
        if fault.kind == "delay":
            time.sleep(fault.seconds)
        elif fault.kind in ("drop", "reset"):
            last = faults.InjectedFault(f"chaos: {fault.kind} (local)")
            if tel:
                telemetry.inc("ps.rpc.retries[local]")
            continue
        result = getattr(server, method)(*args, **kwargs)
        if fault.kind == "slow":
            time.sleep(fault.seconds)
        return _done(result)
    if tel:
        telemetry.inc("ps.rpc.failures[local]")
    telemetry.flight.RECORDER.dump(
        "ps_connection_error", method=method, shard="local", retries=3)
    raise PSConnectionError(
        f"local PS call {method!r} dropped by chaos 3 times") from last


class _LocalTransport:
    def __init__(self):
        self.server = PSServer.get()

    def call(self, method, *args, **kwargs):
        return _local_chaos_call(self.server, method, args, kwargs)

    def close(self):
        pass


class PSClient:
    _instance = None

    def __init__(self, transport=None, rank=0, nrank=1):
        if transport is None:
            addr = envvars.get_str("HETU_PS_ADDR")
            if addr:
                host, port = addr.rsplit(":", 1)
                transport = _TCPTransport(host, int(port))
            else:
                transport = _LocalTransport()
        self.t = transport
        self.rank = rank
        self.nrank = nrank
        self._pool = ThreadPoolExecutor(max_workers=4,
                                        thread_name_prefix="ps-client")
        self._hb_stop = None
        # native-van fast tier: per-thread discovery + socket (the van
        # protocol is one blocking socket, not thread-safe).  All
        # sockets ever opened are also tracked process-wide so
        # finalize() can close the ones pool threads created.
        self._van_local = threading.local()
        self._van_clients = []
        self._van_clients_mu = locks.TracedLock("ps.van_clients")

    def start_heartbeat(self, interval=5.0, role="worker", node_id=None):
        """Beat the scheduler's liveness map (HETU_SCHEDULER_ADDR) every
        ``interval`` seconds from a daemon thread — the ps-lite
        Postoffice heartbeat role.  No-op without a scheduler."""
        sched = envvars.get_str("HETU_SCHEDULER_ADDR")
        if not sched or self._hb_stop is not None:
            return False
        host, port = sched.rsplit(":", 1)
        node = str(self.rank if node_id is None else node_id)
        stop = threading.Event()
        self._hb_stop = stop

        def beat():
            # short timeout, one retry: a stalled RPC must cost one
            # beat, not wedge the loop past the staleness window
            t = _TCPTransport(host, int(port),
                              timeout=max(1.0, interval / 2),
                              connect_timeout=max(1.0, interval / 2),
                              retries=1)
            first = True
            while True:
                if not first and stop.wait(interval):
                    break
                first = False
                try:
                    # immediate first beat: an early-crashing node must
                    # still APPEAR in the health map before dying
                    t.call("heartbeat", role, node)
                except Exception:
                    pass          # scheduler gone: detection is ITS job
            t.close()

        threading.Thread(target=beat, daemon=True,
                         name=f"ps-heartbeat-{role}-{node}").start()
        return True

    def stop_heartbeat(self):
        if self._hb_stop is not None:
            self._hb_stop.set()
            self._hb_stop = None

    @classmethod
    def get(cls):
        if cls._instance is None:
            rank = envvars.get_int("HETU_PS_RANK")
            nrank = envvars.get_int("HETU_PS_NRANK")
            addrs = envvars.get_list("HETU_PS_ADDRS")
            sched = envvars.get_str("HETU_SCHEDULER_ADDR")
            if not addrs and not envvars.is_set("HETU_PS_ADDR") and sched:
                # rendezvous: block until the expected server group has
                # registered, then connect directly (ps-lite Postoffice
                # bootstrap role).  The expected count is REQUIRED:
                # defaulting it would let early workers see a partial
                # group and shard keys inconsistently.
                nserv = envvars.get_int("HETU_PS_NSERVERS")
                if nserv is None:
                    raise ValueError(
                        "HETU_SCHEDULER_ADDR is set but HETU_PS_NSERVERS "
                        "is not: workers must agree on the server-group "
                        "size or they would shard keys inconsistently")
                host, port = sched.rsplit(":", 1)
                t = _TCPTransport(host, int(port))
                addrs = t.call(
                    "get_servers", int(nserv),
                    envvars.get_float("HETU_PS_TIMEOUT"))
                t.close()
                if len(addrs) == 1:
                    h2, p2 = addrs[0].rsplit(":", 1)
                    cls._instance = PSClient(
                        transport=_TCPTransport(h2, int(p2)),
                        rank=rank, nrank=nrank)
                    return cls._instance
            if len(addrs) > 1:
                # launcher exposed a server group: shard keys across it
                from .sharded import ShardedPSClient
                cls._instance = ShardedPSClient(addrs=addrs, rank=rank,
                                                nrank=nrank)
            else:
                cls._instance = PSClient(rank=rank, nrank=nrank)
        return cls._instance

    def finalize(self):
        self._pool.shutdown(wait=True)
        # close EVERY van socket ever opened, including the ones pool
        # threads created in their own thread-local state (each holds a
        # serve_conn thread on the server until closed)
        with self._van_clients_mu:
            clients, self._van_clients = self._van_clients, []
        for cli in clients:
            cli.close()
        st = getattr(self._van_local, "state", None)
        if st is not None:
            st["cli"] = None
        self.t.close()
        PSClient._instance = None

    # ---------------- Worker API (worker.h:19-65) ---------------- #

    def parameter_init(self, key, shape, init_type="constant", arg1=0.0,
                       arg2=1.0, seed=0, opt=None, opt_args=None,
                       param_type=0):
        return self.t.call("param_init", key, tuple(shape), init_type, arg1,
                           arg2, seed, opt, opt_args, param_type)

    def param_set(self, key, value, opt=None, opt_args=None):
        """Create-or-overwrite with an explicit value (executor bridge).
        Rides the quantized wire when HETU_PS_QUANT is set (the resync/
        replication paths move big tables through here), so replica
        rebuilds pay int8 bytes too; small control-plane arrays stay
        exact (quant.WIRE_MIN_SIZE floor)."""
        return self.t.call("param_set", key,
                           _q_encode(np.asarray(value, np.float32)),
                           opt, opt_args)

    def pull(self, key, async_=False):
        if async_:
            return self._pool.submit(self._pull_sync, key)
        return self._pull_sync(key)

    def _pull_sync(self, key):
        q = _q_mode()
        if q:
            return _q_decode(self.t.call("pull", key, quant=q))
        return self.t.call("pull", key)

    def push(self, key, grad, async_=False):
        grad = _q_encode(np.asarray(grad, np.float32))
        if async_:
            return self._pool.submit(self.t.call, "push", key, grad)
        return self.t.call("push", key, grad)

    def dd_pushpull(self, key, grad, async_=False):
        grad = np.asarray(grad, np.float32)
        if async_:
            return self._pool.submit(self._dd_pushpull_sync, key, grad)
        return self._dd_pushpull_sync(key, grad)

    def _dd_pushpull_sync(self, key, grad):
        q = _q_mode()
        if q:
            return _q_decode(self.t.call(
                "dd_pushpull", key, _q_encode(grad), quant=q))
        return self.t.call("dd_pushpull", key, grad)

    # The three sparse verbs route through the server's native C++ van
    # when it serves the key (reference: workers speak to the zmq_van
    # tier directly; the Executor's hybrid phases A/B inherit this).
    # Discovery is one van_info RPC; connection-level van failures fall
    # back to the python tier permanently for this thread.

    _VAN_REFRESH_S = 5.0      # re-ask van_info for missing keys at most
    _VAN_MAX_CONNECT_TRIES = 3   # this often; give up connecting after

    def _van_route(self, key):
        """(VanClient, van_key_id) when the server's native van serves
        ``key``; None otherwise.  Discovery failures and unseen keys
        are re-checked at most every ``_VAN_REFRESH_S`` seconds, so a
        serve_van() issued after traffic started still gets picked up;
        repeated connect failures retire the fast tier per-thread."""
        if not envvars.get_bool("HETU_PS_USE_VAN"):
            return None
        st = getattr(self._van_local, "state", None)
        if st is None:
            st = {"port": None, "keys": {}, "cli": None,
                  "checked_at": 0.0, "connect_fails": 0, "dead": False}
            self._van_local.state = st
        if st["dead"]:
            return None
        if key not in st["keys"]:
            now = time.monotonic()
            if now - st["checked_at"] < self._VAN_REFRESH_S:
                return None
            st["checked_at"] = now
            try:
                port, keys = self.t.call("van_info")
            except Exception:
                return None       # transient: retry after the window
            st["port"], st["keys"] = port, dict(keys)
            if key not in st["keys"]:
                return None
        if st["port"] is None:
            return None
        if st["cli"] is None:
            host = getattr(self.t, "host", "127.0.0.1")
            try:
                st["cli"] = VanClient(
                    host, st["port"],
                    timeout=envvars.get_float("HETU_PS_TIMEOUT"))
            except OSError:
                st["connect_fails"] += 1
                if st["connect_fails"] >= self._VAN_MAX_CONNECT_TRIES:
                    st["dead"] = True
                return None
            with self._van_clients_mu:
                self._van_clients.append(st["cli"])
        return st["cli"], st["keys"][key]

    def _van_drop(self):
        st = self._van_local.state
        if st["cli"] is not None:
            st["cli"].close()
        st["cli"] = None
        st["dead"] = True

    def _van_push_failed(self, key, err):
        """A van push failed at the socket level.  The van applies a
        request only after reading its complete frame, so a SEND-side
        failure is safe to retry through the python tier; a failure
        awaiting the response means the update may already be in the
        shared buffers — re-applying it there would double the step, so
        that surfaces as PSConnectionError instead (the resender-style
        dedup the python wire has does not exist on the van protocol)."""
        self._van_drop()
        if err.maybe_applied:
            raise PSConnectionError(
                f"van push for {key!r} failed awaiting the response; "
                f"the update may already be applied, so it is NOT "
                f"retried through the python tier (double-apply). "
                f"Last error: {err}") from err

    def sparse_pull(self, key, ids, async_=False):
        ids = np.asarray(ids, np.int64)
        if async_:
            return self._pool.submit(self._sparse_pull_sync, key, ids)
        return self._sparse_pull_sync(key, ids)

    def _sparse_pull_sync(self, key, ids):
        route = self._van_route(key) if ids.size else None
        if route is not None:
            cli, kid = route
            try:
                return cli.pull(kid, ids)
            except (OSError, ConnectionError):
                self._van_drop()    # reads are idempotent: fall back
            except RuntimeError:
                # van rejected (e.g. a pull too large for its 1 GiB
                # frame): nothing was applied and the connection is
                # healthy — the python tier is the authority
                pass
        q = _q_mode()
        if q:
            return _q_decode(self.t.call("sparse_pull", key, ids,
                                         quant=q))
        return self.t.call("sparse_pull", key, ids)

    def sparse_push(self, key, ids, rows, async_=False):
        ids = np.asarray(ids, np.int64)
        rows = np.asarray(rows, np.float32)
        if async_:
            return self._pool.submit(self._sparse_push_sync, key, ids,
                                     rows)
        return self._sparse_push_sync(key, ids, rows)

    def _sparse_push_sync(self, key, ids, rows):
        route = self._van_route(key) if ids.size else None
        if route is not None:
            cli, kid = route
            try:
                return cli.push(kid, ids, rows)
            except VanTransportError as e:
                self._van_push_failed(key, e)   # raises if maybe-applied
            except RuntimeError:
                pass   # van rejected the frame: NOT applied, safe retry
        return self.t.call("sparse_push", key, ids, _q_encode(rows))

    def sd_pushpull(self, key, ids, rows, pull_ids=None, async_=False):
        ids = np.asarray(ids, np.int64)
        rows = np.asarray(rows, np.float32)
        if async_:
            return self._pool.submit(self._sd_pushpull_sync, key, ids,
                                     rows, pull_ids)
        return self._sd_pushpull_sync(key, ids, rows, pull_ids)

    def _sd_pushpull_sync(self, key, ids, rows, pull_ids):
        # pull-only shards (sharded CTR hot path) still route: the van
        # accepts a zero-id push, and the python tier's sd_pushpull
        # always pushes — a shared Adam table's step counter must
        # advance the same way on both tiers
        want = bool(ids.size) or pull_ids is not None
        route = self._van_route(key) if want else None
        if route is not None:
            cli, kid = route
            try:
                if pull_ids is None:
                    return cli.sd_pushpull(kid, ids, rows)
                cli.push(kid, ids, rows)
            except VanTransportError as e:
                self._van_push_failed(key, e)   # raises if maybe-applied
            except RuntimeError:
                pass   # van rejected the frame: NOT applied, safe retry
            else:
                # the push landed; the (idempotent) pull half completes
                # through the pull route, which has its own fallback
                return self._sparse_pull_sync(
                    key, np.asarray(pull_ids, np.int64))
        q = _q_mode()
        if q:
            return _q_decode(self.t.call(
                "sd_pushpull", key, ids, _q_encode(rows), pull_ids,
                quant=q))
        return self.t.call("sd_pushpull", key, ids, rows, pull_ids)

    def ss_pushpull(self, key, ids, rows, pull_ids, async_=False):
        return self.sd_pushpull(key, ids, rows, pull_ids, async_=async_)

    def wait(self, ticket):
        if isinstance(ticket, Future):
            return ticket.result()
        return ticket

    def save(self, key, path):
        os.makedirs(path, exist_ok=True)
        return self.t.call("param_save", key, path)

    def load(self, key, path):
        return self.t.call("param_load", key, path)

    def clear(self, key):
        return self.t.call("param_clear", key)

    # ---------------- serving KV cold store (ISSUE 17) ------------- #
    # thin wrappers over the PSServer kv_* surface: the tiered-KV
    # ladder (serving/kv_tiers.py) parks spilled prefix payloads here

    def kv_put(self, key, payload, version=0):
        return self.t.call("kv_put", key, payload, version)

    def kv_get(self, key):
        return self.t.call("kv_get", key)

    def kv_del(self, key):
        return self.t.call("kv_del", key)

    def kv_keys(self):
        return self.t.call("kv_keys")

    # ---------------- SSP / BSP / preduce ---------------- #

    def ssp_init(self, group=0, bound=0):
        return self.t.call("ssp_init", group, self.rank, bound)

    def ssp_sync(self, group=0):
        return self.t.call("ssp_sync", group, self.rank)

    def BarrierWorker(self, group=0):
        return self.t.call("barrier", group, self.rank, self.nrank)

    def preduce_get_partner(self, key, max_worker, wait_time):
        return self.t.call("preduce_get_partner", key, self.rank,
                           max_worker, wait_time)

    # ---------------- cache sync ---------------- #
    # The HET verbs ride the van too (r5): sync_embedding is op 4 on
    # the C++ tier; push_embedding is a push on an accumulate-mode
    # table.  push_sync_embedding decomposes into the two frames — the
    # python server also takes the param lock once per half, so the
    # interleaving semantics are identical.

    def sync_embedding(self, key, ids, stored_versions, bound):
        route = self._van_route(key)
        if route is not None:
            cli, kid = route
            try:
                return cli.sync_embedding(kid, ids, stored_versions,
                                          bound)
            except (OSError, ConnectionError):
                self._van_drop()    # pure read: safe fallback
            except RuntimeError:
                pass                # rejected (e.g. no versions)
        q = _q_mode()
        if q:
            # int8 pull pair on the HET sync verb: the serving cache's
            # miss path pulls through here, so HETU_PS_QUANT shrinks
            # cold-start / post-outage refill bytes the same ~3.7x the
            # dense pulls get
            s_ids, s_rows, s_vers = self.t.call(
                "sync_embedding", key, ids, stored_versions, bound,
                quant=q)
            return s_ids, _q_decode(s_rows), s_vers
        return self.t.call("sync_embedding", key, ids, stored_versions,
                           bound)

    def push_embedding(self, key, ids, rows):
        # server-side push_embedding IS sparse_push (accumulate on an
        # optimizer-less table); reuse its van route + fallback contract
        return self.sparse_push(key, ids, rows)

    def push_sync_embedding(self, key, ids, rows, sync_ids, stored_versions,
                            bound):
        if self._van_route(key) is not None:
            self.push_embedding(key, ids, rows)
            return self.sync_embedding(key, sync_ids, stored_versions,
                                       bound)
        return self.t.call("push_sync_embedding", key, ids, rows, sync_ids,
                           stored_versions, bound)

    def getLoads(self):
        return self.t.call("get_loads")
