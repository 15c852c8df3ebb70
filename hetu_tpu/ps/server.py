"""Parameter server: keyed tensor store with server-side optimizers.

Reference: ps-lite PSHandler<kParameterServer> (PSFHandle.h:17) with
server-side optimizers (server/optimizer.h:36-275: SGD/Momentum/Nesterov/
AdaGrad/Adam), Param/Param2D/CacheTable storage (server/param.h), SSP
clocks (ssp_handler.h), preduce partner matching (preduce_handler.cc), and
the PSFunc RPC surface (psf/PSFunc.h:33-57: DensePush/Pull, DDPushPull,
SparsePush/Pull, SDPushPull, SSPushPull, ParamInit/Clear/Save/Load,
SyncEmbedding/PushEmbedding, SSPInit/SSPSync, PReduceGetPartner).

TPU-native: the server lives host-side on the TPU-VM (embeddings exceed
HBM; SURVEY.md §2.2 'TPU equivalent').  Two transports: in-process (zero
copy, default for single-host) and length-prefixed TCP carrying the
TYPED wire codec (ps/wire.py — plain-data envelope only, no pickle on
network bytes; ps-lite frames typed protobuf + raw buffers the same
way) for multi-process / multi-host.  Numpy is the compute engine
server-side — the hot sparse rows path is vectorized gather/scatter,
the same work the reference does in C++ loops.
"""

from __future__ import annotations

import ctypes
import os
import socket
import socketserver
import struct
import threading
import time

import numpy as np

from . import faults, wire
from .. import envvars, locks
from ..quant import QuantArray, maybe_decode, should_quantize


# ----------------------------------------------------------------- #
# native core: fused C++ update loops (hetu_tpu/native/ps_core.cpp),
# mirroring the reference's C++ server optimizers (server/optimizer.h).
# The numpy paths below serve what the native loops do not cover
# (broadcastable grads) and are the tests' reference implementation.
# ----------------------------------------------------------------- #

_NATIVE = None


def _native():
    """The fused C++ loops, built and loaded on the first PS update or
    gather — not at import, so a host without g++ fails a PS run only
    (``import hetu_tpu`` imports this module)."""
    global _NATIVE
    if _NATIVE is not None:
        return _NATIVE
    from ..native import build_and_load

    lib = build_and_load("ps_core.cpp", "libps_core.so",
                         deps=("ps_kernels.h",))
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i64 = ctypes.c_int64
    f32 = ctypes.c_float
    lib.ps_dense_sgd.argtypes = [f32p, f32p, i64, f32]
    lib.ps_dense_momentum.argtypes = [f32p, f32p, f32p, i64, f32, f32,
                                      ctypes.c_int]
    lib.ps_dense_adagrad.argtypes = [f32p, f32p, f32p, i64, f32, f32]
    lib.ps_dense_adam.argtypes = [f32p, f32p, f32p, f32p, i64, f32, f32,
                                  f32, f32, i64]
    lib.ps_sparse_sgd.argtypes = [f32p, i64p, f32p, i64, i64, f32]
    lib.ps_sparse_momentum.argtypes = [f32p, f32p, i64p, f32p, i64, i64,
                                       f32, f32, ctypes.c_int]
    lib.ps_sparse_adagrad.argtypes = [f32p, f32p, i64p, f32p, i64, i64,
                                      f32, f32]
    lib.ps_sparse_adam.argtypes = [f32p, f32p, f32p, i64p, f32p, i64,
                                   i64, f32, f32, f32, f32, i64]
    lib.ps_sparse_accum.argtypes = [f32p, i64p, f32p, i64, i64]
    lib.ps_sparse_gather.argtypes = [f32p, i64p, f32p, i64, i64]
    lib.ps_bump_versions.argtypes = [i64p, i64p, i64]
    _NATIVE = lib
    return lib


def _fp(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _ip(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f32_ready(*arrays):
    """Arrays safe to hand to the float32 C loops (dtype + layout)."""
    return all(a.dtype == np.float32 and a.flags["C_CONTIGUOUS"]
               for a in arrays)


def _dense_ready(value, grad, *state):
    """Dense fast path: exact shape match (the numpy fallback also
    supports broadcastable grads; those take the fallback)."""
    return value.shape == grad.shape and _f32_ready(value, grad, *state)


def _check_ids(ids, nrows):
    """Bounds-check before raw pointer arithmetic — preserves the
    IndexError the numpy paths raised for bad ids (the C loops would
    corrupt server memory instead)."""
    if len(ids) and (int(ids.min()) < 0 or int(ids.max()) >= nrows):
        raise IndexError(
            f"sparse ids out of range for table with {nrows} rows")


def _sparse_ready(value, ids, rows, *state):
    """Sparse fast path: 2D table, float32 everywhere, int64 contiguous
    ids within bounds, rows shaped (k, cols)."""
    if value.ndim != 2 or not _f32_ready(value, rows, *state):
        return False
    if ids.dtype != np.int64 or not ids.flags["C_CONTIGUOUS"]:
        return False
    if rows.shape != (len(ids), value.shape[1]):
        return False
    _check_ids(ids, value.shape[0])
    return True


# --------------------------------------------------------------------- #
# server-side optimizers (reference server/optimizer.h)
# --------------------------------------------------------------------- #

class ServerOptimizer:
    def __init__(self, learning_rate=0.1, **kwargs):
        self.lr = learning_rate

    def init_state(self, shape):
        return {}

    def apply_dense(self, value, grad, state):
        raise NotImplementedError

    def apply_sparse(self, value, ids, rows, state):
        """ids unique-merged client-side or here; default: dense emulation
        over touched rows."""
        uniq, inv = np.unique(ids, return_inverse=True)
        merged = np.zeros((len(uniq), rows.shape[-1]), rows.dtype)
        np.add.at(merged, inv, rows)
        self._sparse_rows(value, uniq, merged, state)

    def _sparse_rows(self, value, uniq, merged, state):
        value[uniq] -= self.lr * merged


class ServerSGD(ServerOptimizer):
    def apply_dense(self, value, grad, state):
        if _dense_ready(value, grad):
            _native().ps_dense_sgd(_fp(value), _fp(grad), value.size,
                                   self.lr)
            return
        value -= self.lr * grad

    def apply_sparse(self, value, ids, rows, state):
        if _sparse_ready(value, ids, rows):
            _native().ps_sparse_sgd(_fp(value), _ip(ids), _fp(rows),
                                    len(ids), value.shape[-1], self.lr)
            return
        super().apply_sparse(value, ids, rows, state)


class ServerMomentum(ServerOptimizer):
    def __init__(self, learning_rate=0.1, momentum=0.9, nesterov=False):
        super().__init__(learning_rate)
        self.momentum = momentum
        self.nesterov = nesterov

    def init_state(self, shape):
        return {"v": np.zeros(shape, np.float32)}

    def apply_dense(self, value, grad, state):
        if _dense_ready(value, grad, state["v"]):
            _native().ps_dense_momentum(_fp(value), _fp(state["v"]),
                                        _fp(grad), value.size, self.lr,
                                        self.momentum, int(self.nesterov))
            return
        v = state["v"]
        v *= self.momentum
        v -= self.lr * grad
        if self.nesterov:
            value += self.momentum * v - self.lr * grad
        else:
            value += v

    def apply_sparse(self, value, ids, rows, state):
        if _sparse_ready(value, ids, rows, state["v"]):
            _native().ps_sparse_momentum(
                _fp(value), _fp(state["v"]), _ip(ids), _fp(rows),
                len(ids), value.shape[-1], self.lr, self.momentum,
                int(self.nesterov))
            return
        super().apply_sparse(value, ids, rows, state)

    def _sparse_rows(self, value, uniq, merged, state):
        v = state["v"]
        v[uniq] = self.momentum * v[uniq] - self.lr * merged
        if self.nesterov:
            value[uniq] += self.momentum * v[uniq] - self.lr * merged
        else:
            value[uniq] += v[uniq]


class ServerNesterov(ServerMomentum):
    def __init__(self, learning_rate=0.1, momentum=0.9):
        super().__init__(learning_rate, momentum, nesterov=True)


class ServerAdaGrad(ServerOptimizer):
    def __init__(self, learning_rate=0.1, initial_accumulator_value=0.0,
                 eps=1e-7):
        super().__init__(learning_rate)
        self.init_acc = initial_accumulator_value
        self.eps = eps

    def init_state(self, shape):
        return {"acc": np.full(shape, self.init_acc, np.float32)}

    def apply_dense(self, value, grad, state):
        if _dense_ready(value, grad, state["acc"]):
            _native().ps_dense_adagrad(_fp(value), _fp(state["acc"]),
                                       _fp(grad), value.size, self.lr,
                                       self.eps)
            return
        state["acc"] += grad * grad
        value -= self.lr * grad / (np.sqrt(state["acc"]) + self.eps)

    def apply_sparse(self, value, ids, rows, state):
        if _sparse_ready(value, ids, rows, state["acc"]):
            _native().ps_sparse_adagrad(
                _fp(value), _fp(state["acc"]), _ip(ids), _fp(rows),
                len(ids), value.shape[-1], self.lr, self.eps)
            return
        super().apply_sparse(value, ids, rows, state)

    def _sparse_rows(self, value, uniq, merged, state):
        acc = state["acc"]
        acc[uniq] += merged * merged
        value[uniq] -= self.lr * merged / (np.sqrt(acc[uniq]) + self.eps)


class ServerAdam(ServerOptimizer):
    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7):
        super().__init__(learning_rate)
        self.beta1, self.beta2, self.eps = beta1, beta2, epsilon

    def init_state(self, shape):
        return {"m": np.zeros(shape, np.float32),
                "v": np.zeros(shape, np.float32), "t": np.zeros((), np.int64)}

    def apply_dense(self, value, grad, state):
        state["t"] += 1
        t = int(state["t"])
        m, v = state["m"], state["v"]
        if _dense_ready(value, grad, m, v):
            _native().ps_dense_adam(_fp(value), _fp(m), _fp(v), _fp(grad),
                                    value.size, self.lr, self.beta1,
                                    self.beta2, self.eps, t)
            return
        m *= self.beta1
        m += (1 - self.beta1) * grad
        v *= self.beta2
        v += (1 - self.beta2) * grad * grad
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        value -= self.lr * mhat / (np.sqrt(vhat) + self.eps)

    def apply_sparse(self, value, ids, rows, state):
        if _sparse_ready(value, ids, rows, state["m"], state["v"]):
            state["t"] += 1
            _native().ps_sparse_adam(
                _fp(value), _fp(state["m"]), _fp(state["v"]), _ip(ids),
                _fp(rows), len(ids), value.shape[-1], self.lr,
                self.beta1, self.beta2, self.eps, int(state["t"]))
            return
        super().apply_sparse(value, ids, rows, state)

    def _sparse_rows(self, value, uniq, merged, state):
        state["t"] += 1
        t = float(state["t"])
        m, v = state["m"], state["v"]
        m[uniq] = self.beta1 * m[uniq] + (1 - self.beta1) * merged
        v[uniq] = self.beta2 * v[uniq] + (1 - self.beta2) * merged * merged
        mhat = m[uniq] / (1 - self.beta1 ** t)
        vhat = v[uniq] / (1 - self.beta2 ** t)
        value[uniq] -= self.lr * mhat / (np.sqrt(vhat) + self.eps)


SERVER_OPTIMIZERS = {
    "sgd": ServerSGD, "SGD": ServerSGD,
    "momentum": ServerMomentum, "Momentum": ServerMomentum,
    "nesterov": ServerNesterov, "Nesterov": ServerNesterov,
    "adagrad": ServerAdaGrad, "AdaGrad": ServerAdaGrad,
    "adam": ServerAdam, "Adam": ServerAdam,
}


class _Param:
    """One stored tensor + optimizer slot state + per-row versions for the
    cache-sync protocol (reference server/param.h Param2D/CacheTable)."""

    def __init__(self, value, optimizer, opt_spec=(None, None)):
        self.value = value
        self.optimizer = optimizer
        self.state = optimizer.init_state(value.shape) if optimizer else {}
        # the (opt_name, opt_args) this param was created with — the
        # replica-resync path re-creates the table on a restarted
        # primary from this spec (ps/sharded.py resync_shard)
        self.opt_spec = opt_spec
        # per-row version counters (only meaningful for 2D tables)
        self.versions = np.zeros(value.shape[0], np.int64) \
            if value.ndim == 2 else None
        self.lock = locks.TracedLock("ps.param")


_AUTOSERVE = object()     # sentinel: serve_van registers future tables too


class PSServer:
    """The parameter server.  All public methods are the PSFunc surface."""

    _instance = None

    def __init__(self):
        self.params = {}
        # serving KV cold store (ISSUE 17): spilled prefix payloads,
        # key -> (payload, version) — a namespace of its own, never
        # cast through the f32 param path
        self.kv_cold = {}
        self.lock = locks.TracedLock("ps.server")
        # SSP: per-key worker clocks (reference ssp_handler.h)
        self.ssp_clocks = {}
        self.ssp_bound = {}
        self.ssp_cv = locks.TracedCondition(name="ps.ssp")
        # preduce matchmaking (reference preduce_handler.cc)
        self._preduce_groups = {}
        self._preduce_seq = 0
        self._preduce_last = {}   # (key, rank) -> last match seq
        self._preduce_cv = locks.TracedCondition(name="ps.preduce")
        # barrier for BSP (reference PSFHandle BarrierWorker)
        self._barrier_count = {}
        self._barrier_cv = locks.TracedCondition(name="ps.barrier")

    # ---------------- lifecycle ---------------- #

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = PSServer()
        return cls._instance

    @classmethod
    def serve_from_env(cls):
        port = envvars.get_int("HETU_PS_PORT")
        server = cls.get()
        tcp = server.serve_tcp(port, block=False)
        if envvars.get_bool("HETU_PS_VAN"):
            # fast tier: qualifying tables auto-register as clients
            # create them; workers discover it via the van_info RPC
            vport = server.enable_van_autoserve(
                envvars.get_int("HETU_PS_VAN_PORT"))
            print(f"[ps] native van listening on :{vport}", flush=True)
        # announce to the rendezvous scheduler, if one is configured
        _register_with_scheduler(port)
        tcp.serve_forever()

    def serve_tcp(self, port, block=True):
        self._tcp = _serve_object_tcp(self, port, block)
        return self._tcp

    def serve_van(self, keys=None, port=0):
        """Attach the native C++ van (ps/van.py, reference ps-lite
        zmq_van tier): the selected tables' sparse push/pull/push-pull
        are served zero-copy by C++ threads ON THE SAME BUFFERS the
        python PSFunc surface uses.  2-D float32 tables with any
        server-side optimizer from the SGD family qualify (the van
        applies SGD/Momentum/Nesterov/AdaGrad/Adam in-kernel, sharing
        the python tier's slot state — reference server/optimizer.h);
        their python lock becomes a composite lock shared with the
        van's per-table mutex, so both tiers serialize.

        Registration is race-free when it happens before workers start
        pushing to the table (the ``enable_van_autoserve`` path
        registers at creation).  A table already receiving traffic is
        swapped under its param lock, so in-flight python ops drain
        first; an op that read the OLD lock object but had not yet
        acquired it can still overlap the van's first requests for one
        op — prefer autoserve for live tables.

        Returns (port, {key: van_key_id}) — VanClient speaks van ids.
        """
        with self.lock:
            return self._serve_van_locked(keys, port)

    @staticmethod
    def _van_qualifies(p):
        """The van serves 2-D float32 buffers whose server optimizer it
        can apply in-kernel: the whole SERVER_OPTIMIZERS family, plus
        optimizer-less tables (accumulate mode — the HET cache
        write-back path, which also gets the sync_embedding verb)."""
        return ((p.optimizer is None
                 or isinstance(p.optimizer, (ServerSGD, ServerMomentum,
                                             ServerAdaGrad, ServerAdam)))
                and p.value.ndim == 2 and p.value.dtype == np.float32)

    def _serve_van_locked(self, keys=None, port=0):
        """serve_van body; caller holds self.lock (param_init's
        autoserve hook runs inside its own locked region)."""
        from .van import NativeVan, VanSharedLock
        if getattr(self, "_van", None) is None:
            self._van = NativeVan()
            # HETU_PS_VAN_BIND_ALL=1 exposes the (authentication-free)
            # fast tier beyond loopback for true multi-host heturun
            # deployments; "", "0" and "false" all mean loopback-only
            self._van_port = self._van.listen(
                port,
                bind_all=envvars.get_bool("HETU_PS_VAN_BIND_ALL"))
            self._van_keys = {}
        if keys is _AUTOSERVE:
            # every FUTURE qualifying table registers on creation
            # (heturun deployments init tables over RPC after the
            # server is up — see enable_van_autoserve)
            self._van_auto = True
            keys = None
        if keys is None:
            keys = [k for k, p in self.params.items()
                    if self._van_qualifies(p)]
        for k in keys:
            if k in self._van_keys:
                continue
            p = self.params[k]
            if not self._van_qualifies(p):
                raise ValueError(
                    f"van can only serve 2-D float32 tables (optimizer "
                    f"from the SGD family, or none = accumulate); "
                    f"{k!r} is {p.value.dtype}/{p.value.ndim}-D with "
                    f"{type(p.optimizer).__name__}")
            kid = len(self._van_keys)
            # the registered (contiguous) arrays ARE the served
            # buffers; the param points at exactly them and shares the
            # van's per-table mutex.  Register + lock swap run under
            # the param's EXISTING lock so any python op already inside
            # the table drains before the van can serve it (lock order
            # self.lock -> p.lock matches every PSFunc site).
            with p.lock:
                p.value = self._van.register_table(
                    kid, p.value, p.optimizer, p.state,
                    versions=p.versions)
                p.lock = VanSharedLock(p.lock, self._van, kid)
            self._van_keys[k] = kid
        return self._van_port, dict(self._van_keys)

    def enable_van_autoserve(self, port=0):
        """heturun deployment hook (HETU_PS_VAN=1): start the van now
        and auto-register every qualifying table as clients create it;
        workers discover the port/key map via ``van_info`` RPC."""
        return self.serve_van(keys=_AUTOSERVE, port=port)[0]

    def van_info(self):
        """(van port | None, {key: van key id}) — the RPC workers call
        to discover the fast tier."""
        with self.lock:      # the TCP server is threaded; shutdown()
            if getattr(self, "_van", None) is None:   # mutates under
                return None, {}                        # this lock
            return self._van_port, dict(self._van_keys)

    def _van_autoserve_locked(self, key):
        """Called at table creation (self.lock held) when autoserve is
        on; non-qualifying tables stay python-tier, but a registration
        FAILURE on a qualifying table stays loud."""
        if getattr(self, "_van_auto", False) and \
                self._van_qualifies(self.params[key]):
            self._serve_van_locked([key])

    def shutdown(self):
        hb = getattr(self, "_server_hb_stop", None)
        if hb is not None:
            hb.set()             # a dead server must stop reading alive
            self._server_hb_stop = None
        if getattr(self, "_tcp", None) is not None:
            self._tcp.shutdown()
            self._tcp = None
        if getattr(self, "_van", None) is not None:
            from .van import VanSharedLock
            with self.lock:
                # restore plain python locks BEFORE stopping the van: a
                # VanSharedLock over a destroyed handle would crash any
                # later PSFunc op on the key
                for k in getattr(self, "_van_keys", {}):
                    p = self.params.get(k)
                    if p is not None and isinstance(p.lock,
                                                    VanSharedLock):
                        p.lock = p.lock.pylock
                self._van_keys = {}
                self._van_auto = False
            self._van.stop()
            self._van = None

    # ---------------- PSFunc surface ---------------- #

    def param_init(self, key, shape, init_type="constant", arg1=0.0,
                   arg2=1.0, seed=0, opt=None, opt_args=None,
                   param_type=0):
        """ParamInit (PSFunc.h kParamInit; initializers.py init_on_ps)."""
        with self.lock:
            if key in self.params:
                return False
            rng = np.random.RandomState(seed)
            shape = tuple(shape)
            if init_type in ("constant", 0):
                value = np.full(shape, arg1, np.float32)
            elif init_type in ("uniform", 1):
                value = rng.uniform(arg1, arg2, shape).astype(np.float32)
            elif init_type in ("normal", "gaussian", 2):
                value = (arg1 + arg2 * rng.randn(*shape)).astype(np.float32)
            elif init_type in ("truncated_normal", 3):
                value = np.clip(rng.randn(*shape), -2, 2)
                value = (arg1 + arg2 * value).astype(np.float32)
            else:
                raise ValueError(f"unknown init type {init_type}")
            optimizer = None
            if opt is not None:
                optimizer = SERVER_OPTIMIZERS[opt](**(opt_args or {}))
            self.params[key] = _Param(value, optimizer, (opt, opt_args))
            self._van_autoserve_locked(key)
            return True

    def param_set(self, key, value, opt=None, opt_args=None):
        """Create-or-overwrite a param with an explicit value array.

        The executor's Hybrid/PS bridge: exact-value parity with the
        device-side initializer (param_init's distribution types can't
        reproduce a jax-PRNG init bit-for-bit).  Overwriting resets
        optimizer slot state and row versions.

        Always copies: np.asarray over a jax CPU array is zero-copy, and a
        donated step buffer would silently corrupt the stored table."""
        value = np.array(maybe_decode(value), np.float32, order="C",
                         copy=True)
        optimizer = None
        if opt is not None:
            optimizer = SERVER_OPTIMIZERS[opt](**(opt_args or {}))
        with self.lock:
            vkeys = getattr(self, "_van_keys", {})
            if key in vkeys:
                # a van-served key is RE-REGISTERED in place (the C++
                # tier swaps its pointers under the table mutex) rather
                # than refused — the executor bridge re-sets tables on
                # load_dict.  A respec the van cannot serve would
                # silently detach the fast tier, so that stays loud.
                from .van import VanSharedLock
                new_p = _Param(value, optimizer, (opt, opt_args))
                if not self._van_qualifies(new_p):
                    raise ValueError(
                        f"{key!r} is served by the native van and the "
                        f"new spec ({value.dtype}/{value.ndim}-D, "
                        f"{type(optimizer).__name__}) does not qualify "
                        f"— the van cannot be detached from a key")
                kid = vkeys[key]
                pylock = self.params[key].lock.pylock
                with pylock:       # drain python ops; the register
                    new_p.value = self._van.register_table(   # itself
                        kid, new_p.value, new_p.optimizer,    # fences
                        new_p.state, versions=new_p.versions)  # van
                    new_p.lock = VanSharedLock(pylock, self._van, kid)
                    self.params[key] = new_p
                return True
            self.params[key] = _Param(value, optimizer, (opt, opt_args))
            self._van_autoserve_locked(key)
            return True

    def param_spec(self, key):
        """(shape, opt_name, opt_args) a param was created with — lets a
        failover client or the supervisor rebuild the table elsewhere
        (replica resync) with identical server-side update semantics."""
        p = self.params[key]
        return tuple(p.value.shape), p.opt_spec[0], p.opt_spec[1]

    def param_assign(self, key, value):
        """In-place value overwrite that PRESERVES the server-side
        optimizer and its slot state (param_set would reset them) — the
        checkpoint-restore path."""
        value = np.asarray(maybe_decode(value), np.float32)
        with self.lock:
            p = self.params.get(key)
            if p is None:
                self.params[key] = _Param(value.copy(), None)
                return True
        with p.lock:
            p.value[...] = value
        return True

    def param_clear(self, key):
        with self.lock:
            if key in getattr(self, "_van_keys", {}):
                raise ValueError(
                    f"{key!r} is served by the native van; clearing it "
                    f"would leave the C++ tier serving freed memory")
            self.params.pop(key, None)

    # ---------------- serving KV cold store (ISSUE 17) ---------------- #
    # The tiered-KV ladder's coldest rung (serving/kv_tiers.py): spilled
    # prefix payloads — the export_blocks wire dict, int8 or exact —
    # live in their OWN namespace dict, versioned per put, and never
    # pass through the f32 param path (a cast would corrupt the int8
    # planes).  Public methods = PSFunc surface: callable through every
    # transport, chaos/telemetry included, like any other op.

    def kv_put(self, key, payload, version=0):
        """Park one cold payload under ``key`` (the tier store keys by
        prefix hash).  Last write wins; the version stamp lets a fetch
        refuse an entry someone overwrote behind its index."""
        with self.lock:
            self.kv_cold[key] = (payload, int(version))
        return True

    def kv_get(self, key):
        """``(payload, version)`` or None — a miss is an answer, not an
        error (the tier ladder degrades to cold prefill)."""
        with self.lock:
            return self.kv_cold.get(key)

    def kv_del(self, key):
        """Drop a cold payload (a fetch ends the residency); True when
        something was actually removed."""
        with self.lock:
            return self.kv_cold.pop(key, None) is not None

    def kv_keys(self):
        """Resident cold-store keys (introspection/tests)."""
        with self.lock:
            return sorted(self.kv_cold)

    def param_save(self, key, path):
        p = self.params[key]
        with p.lock:
            np.save(os.path.join(path, f"ps_param_{key}.npy"), p.value)

    def param_load(self, key, path):
        p = self.params[key]
        with p.lock:
            p.value[...] = np.load(os.path.join(path, f"ps_param_{key}.npy"))

    @staticmethod
    def _q_out(value, quant):
        """Quantize a pull response when the client asked for it (the
        pull half of the HETU_PS_QUANT pair); qualifying values only —
        tiny/integer payloads stay exact."""
        if quant == "int8" and should_quantize(value):
            return QuantArray.encode(value)
        return value

    def pull(self, key, quant=None):
        p = self.params[key]
        with p.lock:
            return self._q_out(p.value.copy(), quant)

    def push(self, key, grad):
        """DensePush: apply grad through the server optimizer (or raw add
        when no optimizer, matching reference kDensePush accumulate).
        Quantized payloads (QuantArray) are dequantized HERE, before the
        optimizer step — the server optimizes over the dequantized grad,
        so primary and replica (which replays the same quantized frame)
        walk identical trajectories."""
        grad = maybe_decode(grad)
        p = self.params[key]
        with p.lock:
            if p.optimizer is not None:
                p.optimizer.apply_dense(p.value, np.asarray(grad), p.state)
            else:
                p.value += np.asarray(grad)

    def dd_pushpull(self, key, grad, quant=None):
        grad = maybe_decode(grad)
        p = self.params[key]
        with p.lock:
            if p.optimizer is not None:
                p.optimizer.apply_dense(p.value, np.asarray(grad), p.state)
            else:
                p.value += np.asarray(grad)
            return self._q_out(p.value.copy(), quant)

    def sparse_pull(self, key, ids, quant=None):
        p = self.params[key]
        ids = np.ascontiguousarray(np.asarray(ids, np.int64).reshape(-1))
        with p.lock:
            if p.value.ndim == 2 and _f32_ready(p.value):
                _check_ids(ids, p.value.shape[0])
                out = np.empty((len(ids), p.value.shape[1]), np.float32)
                _native().ps_sparse_gather(_fp(p.value), _ip(ids), _fp(out),
                                           len(ids), p.value.shape[1])
                return self._q_out(out, quant)
            return self._q_out(p.value[ids], quant)

    def sparse_push(self, key, ids, rows):
        rows = maybe_decode(rows)
        p = self.params[key]
        ids = np.ascontiguousarray(np.asarray(ids, np.int64).reshape(-1))
        rows = np.ascontiguousarray(
            np.asarray(rows, np.float32).reshape(len(ids), -1))
        with p.lock:
            if p.optimizer is not None:
                p.optimizer.apply_sparse(p.value, ids, rows, p.state)
            elif _sparse_ready(p.value, ids, rows):
                _native().ps_sparse_accum(_fp(p.value), _ip(ids), _fp(rows),
                                          len(ids), p.value.shape[1])
            else:
                np.add.at(p.value, ids, rows)
            if p.versions is not None:
                if p.versions.flags["C_CONTIGUOUS"]:
                    _check_ids(ids, len(p.versions))
                    _native().ps_bump_versions(_ip(p.versions), _ip(ids),
                                               len(ids))
                else:
                    p.versions[np.unique(ids)] += 1

    def sd_pushpull(self, key, ids, rows, pull_ids=None, quant=None):
        self.sparse_push(key, ids, rows)
        return self.sparse_pull(
            key, pull_ids if pull_ids is not None else ids, quant=quant)

    def ss_pushpull(self, key, ids, rows, pull_ids, quant=None):
        return self.sd_pushpull(key, ids, rows, pull_ids, quant=quant)

    # ---------------- cache sync (HET protocol) ---------------- #

    def sync_embedding(self, key, ids, stored_versions, bound,
                       quant=None):
        """kSyncEmbedding (hetu_client.cc): return rows whose server version
        exceeds the client's stored version by more than ``bound``.
        ``quant="int8"`` ships the row payload as a QuantArray (the
        HETU_PS_QUANT pull pair — serving cache misses ride this)."""
        p = self.params[key]
        ids = np.asarray(ids, np.int64).reshape(-1)
        stored_versions = np.asarray(stored_versions, np.int64).reshape(-1)
        with p.lock:
            server_v = p.versions[ids]
            stale = (server_v - stored_versions) > bound
            return (ids[stale], self._q_out(p.value[ids[stale]], quant),
                    server_v[stale])

    def push_embedding(self, key, ids, rows, versions=None):
        """kPushEmbedding: apply client-accumulated embedding grads."""
        self.sparse_push(key, ids, rows)

    def push_sync_embedding(self, key, ids, rows, sync_ids,
                            stored_versions, bound):
        self.sparse_push(key, ids, rows)
        return self.sync_embedding(key, sync_ids, stored_versions, bound)

    # ---------------- SSP / BSP ---------------- #

    def ssp_init(self, group, worker, bound):
        with self.ssp_cv:
            self.ssp_clocks.setdefault(group, {})[worker] = 0
            self.ssp_bound[group] = bound

    def ssp_sync(self, group, worker, timeout=60.0):
        """Advance worker clock; block while ahead of slowest by > bound."""
        with self.ssp_cv:
            self.ssp_clocks[group][worker] += 1
            self.ssp_cv.notify_all()
            bound = self.ssp_bound[group]
            deadline = time.time() + timeout
            while True:
                clocks = self.ssp_clocks[group]
                if clocks[worker] - min(clocks.values()) <= bound:
                    return clocks[worker]
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError("ssp_sync timed out")
                self.ssp_cv.wait(remaining)

    def barrier(self, group, worker, nworkers, timeout=60.0):
        """BSP barrier (reference BarrierWorker)."""
        with self._barrier_cv:
            gen, count = self._barrier_count.get(group, (0, 0))
            count += 1
            if count >= nworkers:
                self._barrier_count[group] = (gen + 1, 0)
                self._barrier_cv.notify_all()
                return
            self._barrier_count[group] = (gen, count)
            deadline = time.time() + timeout
            while self._barrier_count.get(group, (0, 0))[0] == gen:
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError("barrier timed out")
                self._barrier_cv.wait(remaining)

    # ---------------- preduce matchmaking ---------------- #

    def preduce_get_partner(self, key, rank, max_worker, wait_time):
        """kPReduceGetPartner (preduce_handler.cc): batch arriving workers
        into a group; return (member ranks, match seq) once the group
        fills or ``wait_time`` (seconds) elapses.  The server-assigned
        sequence number gives all members a shared scratch-key namespace
        (local counters diverge when group membership varies)."""
        with self._preduce_cv:
            group = self._preduce_groups.setdefault(key, [])
            group.append(rank)
            self._preduce_cv.notify_all()
            deadline = time.time() + wait_time
            while len(group) < max_worker:
                remaining = deadline - time.time()
                if remaining <= 0:
                    break
                self._preduce_cv.wait(remaining)
            members = sorted(group)
            # first member to wake stamps the match and clears the batch
            if self._preduce_groups.get(key) is group:
                self._preduce_seq += 1
                self._preduce_groups[key] = []
                seq = self._preduce_seq
                for m in members:
                    self._preduce_last[(key, m)] = seq
            else:
                seq = self._preduce_last.get((key, rank), 0)
            return members, seq

    # ---------------- introspection ---------------- #

    def get_loads(self):
        return {k: int(np.prod(p.value.shape)) for k, p in self.params.items()}


# --------------------------------------------------------------------- #
# TCP framing
# --------------------------------------------------------------------- #

def _send_msg(sock, payload: bytes):
    # gather write: one syscall/segment, no header+payload concat copy
    # (payloads are multi-MB embedding batches)
    header = struct.pack("!Q", len(payload))
    total = len(header) + len(payload)
    try:
        sent = sock.sendmsg([header, payload])
    except (AttributeError, OSError):
        sock.sendall(header)
        sock.sendall(payload)
        return
    if sent < total:        # rare partial send: finish with a copy
        rest = memoryview(bytes(header) + bytes(payload))[sent:]
        sock.sendall(rest)


def _recv_msg(sock):
    header = _recv_exact(sock, 8)
    if header is None:
        return None
    (n,) = struct.unpack("!Q", header)
    return _recv_exact(sock, n)


def _recv_exact(sock, n):
    # recv_into a preallocated buffer: O(n), vs the O(n^2) bytes+=chunk
    # pattern that dominated large-message latency
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            return None
        got += r
    return buf      # wire.loads decodes arrays zero-copy from this buffer


def _serve_object_tcp(obj, port, block=True):
    """Serve ``obj``'s public methods over the length-prefixed TCP
    framing.  Requests come in two shapes:

    * legacy ``(method, args, kwargs)``;
    * ``('__req2__', client_id, seq, method, args, kwargs)`` — the
      reliable framing the hardened client sends.  The server keeps a
      one-slot replay cache per client: a request whose seq was already
      served gets the CACHED response replayed instead of re-applying the
      method (ps-lite resender.h parity — without this, a client retry
      after a lost response would double-apply a push)."""
    import collections as _collections
    replay = _collections.OrderedDict()   # client_id -> (seq, payload)
    replay_cv = locks.TracedCondition(name="ps.replay")
    _MAX_CLIENTS = 1024                   # LRU bound: one slot per client

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            try:
                while True:
                    raw = _recv_msg(self.request)
                    if raw is None:
                        return
                    msg = wire.loads(raw)
                    cid = seq = None
                    if isinstance(msg, tuple) and msg \
                            and msg[0] == "__req2__":
                        _, cid, seq, method, args, kwargs = msg
                        with replay_cv:
                            cached = replay.get(cid)
                            if cached is not None and cached[0] == seq:
                                # retransmit of an IN-FLIGHT request
                                # (payload None): wait for the original
                                # to finish, then replay its response —
                                # never execute twice
                                while cached is not None and \
                                        cached[0] == seq and \
                                        cached[1] is None:
                                    replay_cv.wait(1.0)
                                    cached = replay.get(cid)
                                if cached is not None and \
                                        cached[0] == seq:
                                    _send_msg(self.request, cached[1])
                                    continue
                            replay[cid] = (seq, None)   # mark in flight
                            replay.move_to_end(cid)
                            while len(replay) > _MAX_CLIENTS:
                                replay.popitem(last=False)
                    else:
                        method, args, kwargs = msg
                    # server-side chaos seam: a HETU_CHAOS plan with a
                    # role matching this process can SIGKILL it mid-run
                    # (the one-shot shard-loss fault) or slow its
                    # responses; loss kinds stay client-side where the
                    # resend machinery lives
                    plan = faults.plan_from_env()
                    if plan is not None:
                        f = plan.draw(method,
                                      kinds=("kill", "slow", "delay"))
                        if f.kind in ("slow", "delay"):
                            time.sleep(f.seconds)
                    from .. import telemetry
                    tel = telemetry.enabled()
                    t_handle = time.perf_counter() if tel else 0.0
                    try:
                        if method.startswith("_"):
                            raise AttributeError(
                                f"non-public method {method!r}")
                        result = getattr(obj, method)(*args, **kwargs)
                        payload = wire.dumps((True, result))
                    except Exception as e:  # noqa: BLE001
                        payload = wire.dumps((False, repr(e)))
                        if tel:
                            telemetry.inc("ps.server.errors")
                    if tel:
                        # server half of the RPC accounting: apply time
                        # + request/response bytes per verb
                        telemetry.observe(
                            "ps.server.handle_ms." + str(method),
                            (time.perf_counter() - t_handle) * 1e3)
                        telemetry.inc("ps.server.requests")
                        telemetry.inc("ps.server.bytes_in", len(raw))
                        telemetry.inc("ps.server.bytes_out",
                                      len(payload))
                    if cid is not None:
                        with replay_cv:
                            replay[cid] = (seq, payload)
                            replay_cv.notify_all()
                    _send_msg(self.request, payload)
            except (ConnectionResetError, BrokenPipeError, OSError):
                return

    class Threaded(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    srv = Threaded(("0.0.0.0", port), Handler)
    if block:
        srv.serve_forever()
    else:
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
    return srv


class Scheduler:
    """Rendezvous role (ps-lite Postoffice/scheduler parity): servers
    REGISTER themselves; workers BLOCK until the expected server group is
    complete and receive the address list.  With the TCP transport,
    workers then connect directly to servers — the scheduler is only the
    bootstrap, exactly the reference scheduler's role.

    Env contract: servers set HETU_SCHEDULER_ADDR (+ optional
    HETU_PS_INDEX / HETU_PS_ADVERTISE) and register on startup; workers
    with HETU_SCHEDULER_ADDR and no static HETU_PS_ADDR(S) resolve the
    group via ``get_servers`` (expected count HETU_PS_NSERVERS)."""

    def __init__(self):
        self._servers = {}           # index -> addr
        self._cv = locks.TracedCondition(name="scheduler")
        self._beats = {}             # "role:id" -> last monotonic beat

    def register_server(self, index, addr):
        with self._cv:
            self._servers[int(index)] = str(addr)
            self._beats[f"server:{int(index)}"] = time.monotonic()
            self._cv.notify_all()
        return True

    # ---- liveness (ps-lite postoffice heartbeat-map parity) ---- #

    def heartbeat(self, role, node_id):
        """Record a node's liveness beat (ps-lite Postoffice keeps the
        same heartbeat map; there is no elastic replacement in the
        reference either — SURVEY §5.3 — detection feeds the operator /
        launcher, recovery is checkpoint/restart)."""
        with self._cv:
            self._beats[f"{role}:{node_id}"] = time.monotonic()
        return True

    def health(self, stale_after=15.0):
        """{node: {age_s, alive}} for every node that ever beat; a node
        silent for > stale_after seconds reports alive=False."""
        now = time.monotonic()
        with self._cv:
            return {node: {"age_s": round(now - t, 3),
                           "alive": (now - t) <= float(stale_after)}
                    for node, t in self._beats.items()}

    def get_servers(self, expected, timeout=60.0):
        """Block until ``expected`` servers registered; return addresses
        ordered by server index.  TimeoutError (surfaced client-side as a
        server error) when the group never completes."""
        deadline = time.time() + float(timeout)
        with self._cv:
            while len(self._servers) < int(expected):
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError(
                        f"scheduler rendezvous: {len(self._servers)}/"
                        f"{expected} servers registered within {timeout}s")
                self._cv.wait(remaining)
            return [a for _, a in sorted(self._servers.items())]

    def num_servers(self):
        with self._cv:
            return len(self._servers)

    def serve_tcp(self, port, block=True):
        self._tcp = _serve_object_tcp(self, port, block)
        return self._tcp

    def shutdown(self):
        if getattr(self, "_tcp", None) is not None:
            self._tcp.shutdown()
            self._tcp = None

    @classmethod
    def serve_from_env(cls):
        port = envvars.get_int("HETU_SCHEDULER_PORT")
        cls().serve_tcp(port)


def _register_with_scheduler(port):
    """Server-side registration (called by serve_from_env when a
    scheduler is configured).  Also starts the server's ongoing
    liveness beats: register_server only SEEDS the health map — without
    beats every healthy server would read dead after the staleness
    window."""
    sched = envvars.get_str("HETU_SCHEDULER_ADDR")
    if not sched:
        return
    from .client import _TCPTransport
    host, sport = sched.rsplit(":", 1)
    t = _TCPTransport(host, int(sport))
    index = envvars.get_int("HETU_PS_INDEX")
    adv = envvars.get_str("HETU_PS_ADVERTISE") \
        or f"{socket.gethostname()}:{port}"
    t.call("register_server", index, adv)
    t.close()
    interval = envvars.get_float("HETU_HEARTBEAT_INTERVAL")
    srv = PSServer.get()
    # stoppable + restart-safe: shutdown() must silence the beats (a
    # dead server that keeps beating defeats the liveness map), and a
    # re-register must not stack threads for a stale index
    old = getattr(srv, "_server_hb_stop", None)
    if old is not None:
        old.set()
    stop = threading.Event()
    srv._server_hb_stop = stop

    def beat():
        bt = _TCPTransport(host, int(sport),
                           timeout=max(1.0, interval / 2),
                           connect_timeout=max(1.0, interval / 2),
                           retries=1)
        while not stop.is_set():
            try:
                bt.call("heartbeat", "server", index)
            except Exception:
                pass
            stop.wait(interval)
        bt.close()

    threading.Thread(target=beat, daemon=True,
                     name=f"ps-heartbeat-server-{index}").start()


