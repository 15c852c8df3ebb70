"""Native PS van: the C++ throughput tier for the sparse hot path
(reference ps-lite/src/zmq_van.h role; VERDICT r3 missing #5).

The Python ``PSServer`` remains the full-feature surface (PSFunc API,
optimizers, SSP/BSP, HET sync); ``NativeVan`` serves ONE pattern —
sparse push / pull / push-pull with a server-side optimizer on a
registered embedding table — entirely from C++ threads over a binary
protocol, so no Python executes per request.  The whole server
optimizer family is applied in-kernel (SGD/Momentum/Nesterov/AdaGrad/
Adam — reference ps-lite/include/ps/server/optimizer.h:36-275).  The
registered table IS the server's numpy buffer, and the optimizer slot
state (velocity / accumulator / m,v / Adam step) aliases the Python
tier's state arrays (zero copy between the tiers); Python paths
touching a registered table coordinate through the van's per-table
mutex (``table_lock``/``table_unlock``).

    van = NativeVan()
    port = van.listen()
    van.register_sgd_table(0, server_value_array, lr=0.01)
    cli = VanClient("127.0.0.1", port, dim=value.shape[1])
    rows = cli.sd_pushpull(0, ids, grads)
"""

from __future__ import annotations

import ctypes
import socket
import struct

import numpy as np

from ..native import build_and_load

_OP_PUSH, _OP_PULL, _OP_PUSHPULL, _OP_SYNCEMB = 1, 2, 3, 4
_HDR = struct.Struct("<BII")          # op, key, n  (little-endian)
_LEN = struct.Struct("<I")

_LIB = None


def _load():
    global _LIB
    if _LIB is None:
        lib = build_and_load("ps_van.cpp", "libps_van.so",
                             extra_flags=("-pthread",),
                             deps=("ps_kernels.h",))
        lib.van_create.restype = ctypes.c_void_p
        lib.van_listen.restype = ctypes.c_int
        lib.van_listen.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                   ctypes.c_int]
        f32p = ctypes.POINTER(ctypes.c_float)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.van_register_sgd_table.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, f32p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_float, i64p]
        lib.van_register_table.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, f32p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int, ctypes.c_float,
            ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_int, f32p, f32p, i64p, i64p]
        for name in ("van_table_lock", "van_table_unlock",
                     "van_stop", "van_destroy"):
            getattr(lib, name).argtypes = [ctypes.c_void_p] \
                if name in ("van_stop", "van_destroy") else \
                [ctypes.c_void_p, ctypes.c_uint32]
        _LIB = lib
    return _LIB


class NativeVan:
    """Owns one C++ serving loop; tables are registered numpy buffers."""

    def __init__(self):
        lib = _load()
        self._l = lib
        self._h = lib.van_create()
        self._tables = {}            # key -> value array (keepalive)
        self.port = None

    def listen(self, port=0, bind_all=False):
        got = self._l.van_listen(self._h, int(port), int(bool(bind_all)))
        if not got:
            raise OSError(f"van failed to bind port {port}")
        self.port = got
        return got

    def register_sgd_table(self, key, value, lr, versions=None):
        """``value``: C-contiguous float32 [nrows, dim] — the SERVER's
        buffer; updates land in place.  ``versions``: optional int64
        [nrows] HET version counters, bumped per pushed row."""
        value = np.ascontiguousarray(value, np.float32)
        assert value.ndim == 2
        vp = None
        if versions is not None:
            versions = np.ascontiguousarray(versions, np.int64)
            assert len(versions) == value.shape[0]
            vp = versions.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        self._l.van_register_sgd_table(
            self._h, int(key),
            value.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            value.shape[0], value.shape[1], float(lr), vp)
        # keep BOTH buffers alive for the van's lifetime
        self._tables[int(key)] = (value, versions)
        return value

    def register_table(self, key, value, optimizer, state,
                       versions=None):
        """Register a table with its full server optimizer (reference
        zmq_van + server/optimizer.h: the C++ tier applies the SAME
        optimizer family the python tier does).

        ``optimizer``: a ``Server{SGD,Momentum,Nesterov,AdaGrad,Adam}``
        from ps/server.py.  ``state``: that param's slot-state dict —
        its arrays are (re)made contiguous, REPLACED IN PLACE in the
        dict, and registered, so both tiers advance ONE set of slots.
        Returns the (possibly re-allocated contiguous) value array the
        param must now point at.
        """
        from .server import (ServerAdaGrad, ServerAdam, ServerMomentum,
                             ServerSGD)
        value = np.ascontiguousarray(value, np.float32)
        assert value.ndim == 2
        f32p = ctypes.POINTER(ctypes.c_float)
        i64p = ctypes.POINTER(ctypes.c_int64)

        def _slot(name):
            arr = np.ascontiguousarray(state[name], np.float32)
            assert arr.shape == value.shape
            state[name] = arr          # the python tier must see the
            return arr                 # SAME memory the van mutates

        kind, hp1, hp2, eps, nesterov = 0, 0.0, 0.0, 0.0, 0
        s1 = s2 = step = None
        if optimizer is None:
            kind = 4        # accumulate (the HET cache write-back mode)
        elif type(optimizer) is ServerSGD:
            kind = 0
        elif isinstance(optimizer, ServerMomentum):   # incl. Nesterov
            kind, hp1 = 1, optimizer.momentum
            nesterov = int(optimizer.nesterov)
            s1 = _slot("v")
        elif isinstance(optimizer, ServerAdaGrad):
            kind, eps = 2, optimizer.eps
            s1 = _slot("acc")
        elif isinstance(optimizer, ServerAdam):
            kind = 3
            hp1, hp2, eps = optimizer.beta1, optimizer.beta2, optimizer.eps
            s1, s2 = _slot("m"), _slot("v")
            # the 0-d step counter is shared as-is (ascontiguousarray
            # would promote it to 1-d and break the python tier's
            # ``int(state["t"])``)
            if state["t"].dtype != np.int64:
                state["t"] = state["t"].astype(np.int64)
            step = state["t"]
        else:
            raise ValueError(
                f"van cannot serve {type(optimizer).__name__}")
        vp = None
        if versions is not None:
            versions = np.ascontiguousarray(versions, np.int64)
            assert len(versions) == value.shape[0]
            vp = versions.ctypes.data_as(i64p)
        self._l.van_register_table(
            self._h, int(key), value.ctypes.data_as(f32p),
            value.shape[0], value.shape[1], kind,
            float(optimizer.lr) if optimizer is not None else 0.0,
            float(hp1), float(hp2), float(eps), nesterov,
            s1.ctypes.data_as(f32p) if s1 is not None else None,
            s2.ctypes.data_as(f32p) if s2 is not None else None,
            step.ctypes.data_as(i64p) if step is not None else None,
            vp)
        # keep every registered buffer alive for the van's lifetime
        self._tables[int(key)] = (value, versions, s1, s2, step)
        return value

    def table_lock(self, key):
        self._l.van_table_lock(self._h, int(key))

    def table_unlock(self, key):
        self._l.van_table_unlock(self._h, int(key))

    def table_array(self, key):
        return self._tables[int(key)][0]

    def stop(self):
        if self._h:
            self._l.van_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class VanSharedLock:
    """Composite lock for a table served by BOTH tiers: acquires the
    python _Param lock AND the van's per-table C++ mutex, so python
    PSFunc paths and C++ van threads serialize on the same buffer.
    Drop-in for the ``with p.lock:`` sites in ps/server.py."""

    def __init__(self, pylock, van, key_id):
        self.pylock = pylock
        self.van = van
        self.key_id = int(key_id)

    def __enter__(self):
        self.pylock.acquire()
        self.van.table_lock(self.key_id)
        return self

    def __exit__(self, *exc):
        self.van.table_unlock(self.key_id)
        self.pylock.release()
        return False


class VanTransportError(ConnectionError):
    """A van round-trip failed at the socket level.  ``maybe_applied``
    says whether the server may already have APPLIED the request: the
    van applies only after reading a complete frame, so a failure while
    SENDING means not-applied (safe to retry elsewhere), while a
    failure while awaiting the response means the push may have landed
    — callers must not re-apply it through another tier."""

    def __init__(self, msg, maybe_applied):
        super().__init__(msg)
        self.maybe_applied = maybe_applied


class VanClient:
    """Blocking binary-protocol client for one van.

    ``dim`` is optional: pushes carry it in the row payload and pull
    responses reveal it in the frame length, so a dim-less client can
    serve tables of any width (the PSClient fast-tier route uses this).
    """

    def __init__(self, host, port, dim=None, timeout=30.0):
        self.dim = None if dim is None else int(dim)
        self._sock = socket.create_connection((host, port),
                                              timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _send_frame(self, parts):
        """sendmsg + drain: sendmsg may queue only part of a multi-MB
        payload (python docs: the caller must finish delivery)."""
        total = sum(len(p) for p in parts)
        sent = self._sock.sendmsg(parts)
        if sent < total:
            rest = b"".join(bytes(p) for p in parts)   # rare path
            self._sock.sendall(rest[sent:])

    def _exchange(self, parts, maybe_applied_on_recv, reject_msg):
        """One frame out, one frame back.  Socket failures surface as
        VanTransportError; ``maybe_applied_on_recv`` says whether a
        failure while awaiting the response can mean the server already
        applied the request (pushes) or not (pure reads).  Returns the
        response payload past the ok byte."""
        total = sum(len(p) for p in parts)
        sent_all = False
        try:
            # scatter-gather send: no join copy of the multi-MB payload
            self._send_frame([_LEN.pack(total)] + parts)
            sent_all = True
            (m,) = _LEN.unpack(self._recv_exact(4))
            payload = self._recv_exact(m)
        except (OSError, ConnectionError) as e:
            raise VanTransportError(
                f"van round-trip failed while "
                f"{'awaiting the response' if sent_all else 'sending'}"
                f": {type(e).__name__}: {e}",
                maybe_applied=sent_all and maybe_applied_on_recv) from e
        if payload[0] != 1:
            raise RuntimeError(reject_msg)
        return payload

    def _roundtrip(self, op, key, ids, rows, want_rows):
        ids = np.ascontiguousarray(ids, np.int64).reshape(-1)
        n = len(ids)
        parts = [_HDR.pack(op, key, n), memoryview(ids).cast("B")]
        # a zero-id push carries no row payload (and reshape(0, -1) is
        # a numpy error) — the server accepts the 0-byte row section
        if rows is not None and n > 0:
            rows = np.ascontiguousarray(rows, np.float32)
            rows = rows.reshape(n, -1 if self.dim is None else self.dim)
            parts.append(memoryview(rows).cast("B"))
        payload = self._exchange(
            parts, maybe_applied_on_recv=op != _OP_PULL,
            reject_msg="van rejected the request (unknown key, id out "
                       "of range, or malformed frame)")
        if want_rows:
            if n == 0:       # reshape(0, -1) is a numpy error; width
                return np.zeros((0, self.dim or 0), np.float32)
            arr = np.frombuffer(payload, np.float32, offset=1)
            return arr.reshape(n, -1).copy()
        return None

    def _recv_exact(self, n):
        buf = bytearray(n)
        view = memoryview(buf)
        got = 0
        while got < n:
            r = self._sock.recv_into(view[got:])
            if r == 0:
                raise ConnectionError("van closed the connection")
            got += r
        return bytes(buf)

    def sync_embedding(self, key, ids, stored_versions, bound):
        """HET cache sync (server sync_embedding semantics): returns
        (stale_ids, rows, server_versions) for rows whose server
        version exceeds the stored one by more than ``bound``."""
        ids = np.ascontiguousarray(ids, np.int64).reshape(-1)
        stored = np.ascontiguousarray(stored_versions,
                                      np.int64).reshape(-1)
        assert len(stored) == len(ids)
        n = len(ids)
        parts = [_HDR.pack(_OP_SYNCEMB, key, n),
                 memoryview(ids).cast("B"), memoryview(stored).cast("B"),
                 struct.pack("<q", int(bound))]
        payload = self._exchange(
            parts, maybe_applied_on_recv=False,   # sync is a pure read
            reject_msg="van rejected sync_embedding (unknown key, no "
                       "version counters, id out of range, or "
                       "oversize response)")
        (m,) = _LEN.unpack(payload[1:5])
        off = 5
        stale_ids = np.frombuffer(payload, np.int64, count=m,
                                  offset=off).copy()
        off += m * 8
        row_bytes = len(payload) - off - m * 8
        dim = row_bytes // (4 * m) if m else (self.dim or 0)
        rows = np.frombuffer(payload, np.float32, count=m * dim,
                             offset=off).reshape(m, dim).copy()
        off += m * dim * 4
        versions = np.frombuffer(payload, np.int64, count=m,
                                 offset=off).copy()
        return stale_ids, rows, versions

    def push(self, key, ids, grads):
        self._roundtrip(_OP_PUSH, key, ids, grads, want_rows=False)

    def pull(self, key, ids):
        return self._roundtrip(_OP_PULL, key, ids, None, want_rows=True)

    def sd_pushpull(self, key, ids, grads):
        return self._roundtrip(_OP_PUSHPULL, key, ids, grads,
                               want_rows=True)

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass
