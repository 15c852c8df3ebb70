"""Embedding cache core: ctypes binding over the C++ library, with a
pure-Python mirror (``prefer_native=False``) that the tests hold the
native cache to.

Both expose the same interface; `EmbeddingCache(...)` is native unless
told otherwise, and a native build that fails raises.  Policies: 'LRU', 'LFU', 'LFUOpt' (reference lru_cache.h:17,
lfu_cache.h:17, lfuopt_cache.h:18).
"""

from __future__ import annotations

import ctypes
from collections import OrderedDict

import numpy as np

_POLICIES = {"LRU": 0, "LFU": 1, "LFUOPT": 2}


def _policy_code(name):
    code = _POLICIES.get(str(name).upper())
    if code is None:
        raise ValueError(f"unknown cache policy {name!r}; "
                         f"choose from {sorted(_POLICIES)}")
    return code


class NativeCache:
    """ctypes wrapper over native/cache.cpp (flat C ABI)."""

    _lib = None

    @classmethod
    def load_lib(cls):
        if cls._lib is None:
            from ..native import build_and_load
            lib = build_and_load("cache.cpp", "libhetu_cache.so")
            i64p = ctypes.POINTER(ctypes.c_int64)
            f32p = ctypes.POINTER(ctypes.c_float)
            u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.cache_create.restype = ctypes.c_void_p
            lib.cache_create.argtypes = [ctypes.c_int, ctypes.c_int64,
                                         ctypes.c_int64]
            lib.cache_destroy.argtypes = [ctypes.c_void_p]
            lib.cache_size.restype = ctypes.c_int64
            lib.cache_size.argtypes = [ctypes.c_void_p]
            lib.cache_counters.argtypes = [ctypes.c_void_p, i64p, i64p,
                                           i64p]
            lib.cache_lookup.argtypes = [ctypes.c_void_p, i64p,
                                         ctypes.c_int64, f32p, u8p]
            lib.cache_versions.argtypes = [ctypes.c_void_p, i64p,
                                           ctypes.c_int64, i64p]
            lib.cache_insert.restype = ctypes.c_int64
            lib.cache_insert.argtypes = [ctypes.c_void_p, i64p,
                                         ctypes.c_int64, f32p, i64p,
                                         i64p, f32p, ctypes.c_int64]
            lib.cache_update.restype = ctypes.c_int64
            lib.cache_update.argtypes = [ctypes.c_void_p, i64p,
                                         ctypes.c_int64, f32p]
            lib.cache_max_updates.restype = ctypes.c_int64
            lib.cache_max_updates.argtypes = [ctypes.c_void_p]
            lib.cache_dirty.argtypes = [ctypes.c_void_p, i64p,
                                        ctypes.c_int64, u8p]
            lib.cache_collect_dirty.restype = ctypes.c_int64
            lib.cache_collect_dirty.argtypes = [ctypes.c_void_p, i64p,
                                                f32p, ctypes.c_int64]
            lib.cache_refresh.argtypes = [ctypes.c_void_p, i64p,
                                          ctypes.c_int64, f32p, i64p]
            cls._lib = lib
        return cls._lib

    def __init__(self, limit, width, policy="LRU"):
        lib = self.load_lib()
        self._l = lib
        self.limit = int(limit)
        self.width = int(width)
        self._h = lib.cache_create(_policy_code(policy), self.limit,
                                   self.width)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._l.cache_destroy(self._h)
        except Exception:
            pass

    @staticmethod
    def _i64(a):
        return np.ascontiguousarray(a, np.int64)

    @staticmethod
    def _f32(a):
        return np.ascontiguousarray(a, np.float32)

    def _ptr(self, a, typ):
        return a.ctypes.data_as(ctypes.POINTER(typ))

    def lookup(self, ids):
        ids = self._i64(ids)
        n = len(ids)
        out = np.zeros((n, self.width), np.float32)
        hit = np.zeros(n, np.uint8)
        self._l.cache_lookup(self._h, self._ptr(ids, ctypes.c_int64), n,
                             self._ptr(out, ctypes.c_float),
                             self._ptr(hit, ctypes.c_uint8))
        return out, hit.astype(bool)

    def versions(self, ids):
        ids = self._i64(ids)
        n = len(ids)
        out = np.zeros(n, np.int64)
        self._l.cache_versions(self._h, self._ptr(ids, ctypes.c_int64), n,
                               self._ptr(out, ctypes.c_int64))
        return out

    def insert(self, ids, rows, versions=None):
        ids = self._i64(ids)
        rows = self._f32(rows)
        n = len(ids)
        if versions is None:
            versions = np.zeros(n, np.int64)
        versions = self._i64(versions)
        ev_ids = np.zeros(n + 1, np.int64)
        ev_grads = np.zeros((n + 1, self.width), np.float32)
        n_ev = self._l.cache_insert(
            self._h, self._ptr(ids, ctypes.c_int64), n,
            self._ptr(rows, ctypes.c_float),
            self._ptr(versions, ctypes.c_int64),
            self._ptr(ev_ids, ctypes.c_int64),
            self._ptr(ev_grads, ctypes.c_float), n + 1)
        return ev_ids[:n_ev], ev_grads[:n_ev]

    def update(self, ids, deltas):
        ids = self._i64(ids)
        deltas = self._f32(deltas)
        return int(self._l.cache_update(
            self._h, self._ptr(ids, ctypes.c_int64), len(ids),
            self._ptr(deltas, ctypes.c_float)))

    def max_updates(self):
        return int(self._l.cache_max_updates(self._h))

    def dirty(self, ids):
        ids = self._i64(ids)
        out = np.zeros(len(ids), np.uint8)
        self._l.cache_dirty(self._h, self._ptr(ids, ctypes.c_int64),
                            len(ids), self._ptr(out, ctypes.c_uint8))
        return out.astype(bool)

    def collect_dirty(self):
        cap = max(1, self.size())
        ids = np.zeros(cap, np.int64)
        grads = np.zeros((cap, self.width), np.float32)
        k = self._l.cache_collect_dirty(
            self._h, self._ptr(ids, ctypes.c_int64),
            self._ptr(grads, ctypes.c_float), cap)
        return ids[:k], grads[:k]

    def refresh(self, ids, rows, versions):
        ids = self._i64(ids)
        rows = self._f32(rows)
        versions = self._i64(versions)
        self._l.cache_refresh(self._h, self._ptr(ids, ctypes.c_int64),
                              len(ids), self._ptr(rows, ctypes.c_float),
                              self._ptr(versions, ctypes.c_int64))

    def size(self):
        return int(self._l.cache_size(self._h))

    def counters(self):
        h = ctypes.c_int64()
        m = ctypes.c_int64()
        e = ctypes.c_int64()
        self._l.cache_counters(self._h, ctypes.byref(h), ctypes.byref(m),
                               ctypes.byref(e))
        return {"hits": h.value, "misses": m.value, "evictions": e.value}


class PythonCache:
    """Pure-Python mirror of the native cache (same interface/semantics)."""

    def __init__(self, limit, width, policy="LRU"):
        self.limit = int(limit)
        self.width = int(width)
        self.policy = _policy_code(policy)
        self.store = OrderedDict()  # id -> [row, grad, version, updates, dirty, freq]
        self.hits = self.misses = self.evictions = 0
        self._max_upd = 0

    def _touch(self, id_):
        e = self.store[id_]
        if self.policy == 0:
            self.store.move_to_end(id_)
        else:
            e[5] += 1

    def _evict_one(self):
        if self.policy == 0:
            vid = next(iter(self.store))
        else:
            minf = min(e[5] for e in self.store.values())
            vid = next(i for i, e in self.store.items() if e[5] == minf)
            if self.policy == 2 and \
                    sum(1 for e in self.store.values() if e[5] == minf) == 1:
                for e in self.store.values():
                    e[5] //= 2
        e = self.store.pop(vid)
        self.evictions += 1
        if e[4]:
            return vid, e[1]
        return None

    def lookup(self, ids):
        ids = np.asarray(ids, np.int64)
        out = np.zeros((len(ids), self.width), np.float32)
        hit = np.zeros(len(ids), bool)
        for i, id_ in enumerate(ids):
            e = self.store.get(int(id_))
            if e is None:
                self.misses += 1
                continue
            hit[i] = True
            self.hits += 1
            out[i] = e[0]
            self._touch(int(id_))
        return out, hit

    def versions(self, ids):
        return np.array([self.store[int(i)][2] if int(i) in self.store
                         else -1 for i in np.asarray(ids)], np.int64)

    def insert(self, ids, rows, versions=None):
        ids = np.asarray(ids, np.int64)
        rows = np.asarray(rows, np.float32)
        if versions is None:
            versions = np.zeros(len(ids), np.int64)
        ev_ids, ev_grads = [], []
        for i, id_ in enumerate(ids):
            id_ = int(id_)
            if id_ in self.store:
                e = self.store[id_]
                e[0] = rows[i].copy()
                e[2] = int(versions[i])
                self._touch(id_)
                continue
            if len(self.store) >= self.limit:
                ev = self._evict_one()
                if ev is not None:
                    ev_ids.append(ev[0])
                    ev_grads.append(ev[1])
            self.store[id_] = [rows[i].copy(),
                               np.zeros(self.width, np.float32),
                               int(versions[i]), 0, False, 1]
        if ev_ids:
            return np.asarray(ev_ids, np.int64), np.stack(ev_grads)
        return (np.zeros(0, np.int64),
                np.zeros((0, self.width), np.float32))

    def update(self, ids, deltas):
        ids = np.asarray(ids, np.int64)
        deltas = np.asarray(deltas, np.float32)
        missed = 0
        for i, id_ in enumerate(ids):
            e = self.store.get(int(id_))
            if e is None:
                missed += 1
                continue
            e[1] += deltas[i]
            e[0] += deltas[i]
            e[3] += 1
            e[4] = True
            self._max_upd = max(self._max_upd, e[3])
            self._touch(int(id_))
        return missed

    def max_updates(self):
        return self._max_upd

    def dirty(self, ids):
        return np.array([int(i) in self.store and self.store[int(i)][4]
                         for i in np.asarray(ids)], bool)

    def collect_dirty(self):
        ids, grads = [], []
        for id_, e in self.store.items():
            if e[4]:
                ids.append(id_)
                grads.append(e[1].copy())
                e[1][:] = 0
                e[3] = 0
                e[4] = False
        self._max_upd = 0
        if ids:
            return np.asarray(ids, np.int64), np.stack(grads)
        return np.zeros(0, np.int64), np.zeros((0, self.width), np.float32)

    def refresh(self, ids, rows, versions):
        for i, id_ in enumerate(np.asarray(ids, np.int64)):
            e = self.store.get(int(id_))
            if e is None:
                continue
            e[0] = np.asarray(rows[i], np.float32).copy()
            e[2] = int(np.asarray(versions)[i])

    def size(self):
        return len(self.store)

    def counters(self):
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}


def merge_sparse(ids_a, rows_a, ids_b, rows_b):
    """Merge two (ids, rows) sparse delta sets, summing duplicate ids
    (scatter-add semantics — write-back deltas commute, so an outage
    replay buffer can keep merging new pushes into itself without
    growing per step).  Returns sorted unique ids + merged float32 rows.
    Used by CacheSparseTable's PS-outage push backlog."""
    ids = np.concatenate([np.asarray(ids_a, np.int64).reshape(-1),
                          np.asarray(ids_b, np.int64).reshape(-1)])
    rows = np.concatenate([np.asarray(rows_a, np.float32),
                           np.asarray(rows_b, np.float32)])
    uniq, inv = np.unique(ids, return_inverse=True)
    merged = np.zeros((len(uniq), rows.shape[1]), np.float32)
    np.add.at(merged, inv, rows)
    return uniq, merged


def EmbeddingCache(limit, width, policy="LRU", prefer_native=True):
    """Factory: the native C++ cache, or its Python mirror on request."""
    if prefer_native:
        return NativeCache(limit, width, policy)
    return PythonCache(limit, width, policy)
