"""HET embedding cache tests: policies, native/python equivalence, and the
CacheSparseTable sync protocol against an in-process PS (reference pattern:
tests/hetu_cache/hetu_cache_test.py drives CacheSparseTable against a local
PS)."""

import numpy as np
import pytest

from hetu_tpu.cache.cache import PythonCache, NativeCache, EmbeddingCache
from hetu_tpu.cache.cstable import CacheSparseTable
from hetu_tpu.ps.server import PSServer

# smoke tier: this module is part of the <3-min verification
# battery (`pytest -m smoke`; ROADMAP tier-1 note)
pytestmark = pytest.mark.smoke

W = 4


def impls():
    return [PythonCache, NativeCache]


@pytest.mark.parametrize("Cache", impls())
def test_lru_eviction_order(Cache):
    c = Cache(limit=2, width=W, policy="LRU")
    c.insert([1], np.ones((1, W)))
    c.insert([2], np.full((1, W), 2.0))
    c.lookup([1])                      # 1 now most recent
    c.insert([3], np.full((1, W), 3.0))  # evicts 2
    _, hit = c.lookup([1, 2, 3])
    assert list(hit) == [True, False, True]


@pytest.mark.parametrize("Cache", impls())
def test_lfu_eviction_order(Cache):
    c = Cache(limit=2, width=W, policy="LFU")
    c.insert([1], np.ones((1, W)))
    c.insert([2], np.full((1, W), 2.0))
    for _ in range(3):
        c.lookup([1])                  # freq(1) >> freq(2)
    c.insert([3], np.full((1, W), 3.0))  # evicts 2 (lowest freq)
    _, hit = c.lookup([1, 2, 3])
    assert list(hit) == [True, False, True]


@pytest.mark.parametrize("Cache", impls())
def test_dirty_eviction_reports_grads(Cache):
    c = Cache(limit=1, width=W, policy="LRU")
    c.insert([1], np.ones((1, W)))
    c.update([1], np.full((1, W), 0.5))
    ev_ids, ev_grads = c.insert([2], np.zeros((1, W)))
    assert list(ev_ids) == [1]
    np.testing.assert_allclose(ev_grads[0], np.full(W, 0.5))


@pytest.mark.parametrize("Cache", impls())
def test_update_writeback_and_collect(Cache):
    c = Cache(limit=4, width=W, policy="LRU")
    c.insert([1, 2], np.ones((2, W)))
    c.update([1], np.full((1, W), 0.25))
    rows, hit = c.lookup([1])
    np.testing.assert_allclose(rows[0], np.full(W, 1.25))
    assert c.max_updates() == 1
    ids, grads = c.collect_dirty()
    assert list(ids) == [1]
    np.testing.assert_allclose(grads[0], np.full(W, 0.25))
    assert c.max_updates() == 0
    ids2, _ = c.collect_dirty()
    assert len(ids2) == 0


def test_native_python_equivalence_random_workload():
    rng = np.random.RandomState(0)
    nc = NativeCache(limit=8, width=W, policy="LRU")
    pc = PythonCache(limit=8, width=W, policy="LRU")
    for step in range(200):
        op = rng.randint(3)
        ids = rng.randint(0, 32, size=rng.randint(1, 5))
        ids = np.unique(ids)
        if op == 0:
            rows = rng.randn(len(ids), W).astype(np.float32)
            nc.insert(ids, rows)
            pc.insert(ids, rows)
        elif op == 1:
            r1, h1 = nc.lookup(ids)
            r2, h2 = pc.lookup(ids)
            np.testing.assert_array_equal(h1, h2)
            np.testing.assert_allclose(r1[h1], r2[h2], rtol=1e-6)
        else:
            d = rng.randn(len(ids), W).astype(np.float32)
            assert nc.update(ids, d) == pc.update(ids, d)
    assert nc.size() == pc.size()


def _server_with_table(key="emb", vocab=64):
    server = PSServer()
    server.param_init(key, (vocab, W), "normal", 0.0, 1.0, seed=3)
    return server


def test_cstable_lookup_update_flush():
    server = _server_with_table()
    t = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb",
                         comm=server, policy="LRU", push_bound=10)
    ids = np.array([3, 5, 3, 9])
    rows = t.embedding_lookup(ids)
    want = server.sparse_pull("emb", ids)
    np.testing.assert_allclose(rows, want, rtol=1e-6)
    # local update visible immediately (write-back)
    t.embedding_update([3], np.full((1, W), -0.5))
    rows2 = t.embedding_lookup([3])
    np.testing.assert_allclose(rows2[0], want[0] - 0.5, rtol=1e-6)
    # server not yet updated (push_bound=10)
    np.testing.assert_allclose(server.sparse_pull("emb", [3])[0], want[0],
                               rtol=1e-6)
    t.flush()
    np.testing.assert_allclose(server.sparse_pull("emb", [3])[0],
                               want[0] - 0.5, rtol=1e-6)


def test_cstable_push_bound_zero_pushes_immediately():
    server = _server_with_table(key="emb2")
    t = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb2",
                         comm=server, push_bound=0)
    base = server.sparse_pull("emb2", [7]).copy()
    t.embedding_lookup([7])
    t.embedding_update([7], np.full((1, W), 1.0))
    np.testing.assert_allclose(server.sparse_pull("emb2", [7]),
                               base + 1.0, rtol=1e-6)


def test_cstable_staleness_sync_two_clients():
    """Worker B's push bumps server versions; worker A's next lookup
    re-syncs rows beyond its pull bound (the HET bounded-staleness loop)."""
    server = _server_with_table(key="emb3")
    a = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb3",
                         comm=server, pull_bound=0, push_bound=0)
    b = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb3",
                         comm=server, pull_bound=0, push_bound=0)
    a.embedding_lookup([11])            # A caches row 11
    b.embedding_lookup([11])
    b.embedding_update([11], np.full((1, W), 2.0))   # bumps server version
    rows = a.embedding_lookup([11])     # A must see B's update
    np.testing.assert_allclose(rows[0], server.sparse_pull("emb3", [11])[0],
                               rtol=1e-6)
    assert a.num_synced_rows >= 1


def test_cstable_perf_counters():
    server = _server_with_table(key="emb4")
    t = CacheSparseTable(limit=4, vocab_size=64, width=W, key="emb4",
                         comm=server)
    t.embedding_lookup([1, 2, 3])
    t.embedding_lookup([1, 2, 3])
    s = t.perf_summary()
    assert s["pulled_rows"] == 3
    assert s["hit_rate"] > 0
    assert s["cache_size"] == 3


def test_cstable_eviction_flushes_to_ps():
    server = _server_with_table(key="emb5")
    t = CacheSparseTable(limit=2, vocab_size=64, width=W, key="emb5",
                         comm=server, policy="LRU", push_bound=100)
    base = server.sparse_pull("emb5", [1]).copy()
    t.embedding_lookup([1, 2])
    t.embedding_update([1], np.full((1, W), 3.0))
    # cache full: pulling two new ids evicts id 1 (dirty) -> push to PS
    t.embedding_lookup([4, 5])
    np.testing.assert_allclose(server.sparse_pull("emb5", [1]),
                               base + 3.0, rtol=1e-6)


def test_cstable_read_your_writes_under_sync():
    """A's unpushed local update must survive another worker's push (dirty
    lines are excluded from staleness refresh)."""
    server = _server_with_table(key="emb6")
    a = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb6",
                         comm=server, pull_bound=0, push_bound=5)
    b = CacheSparseTable(limit=16, vocab_size=64, width=W, key="emb6",
                         comm=server, pull_bound=0, push_bound=0)
    base = server.sparse_pull("emb6", [7])[0].copy()
    a.embedding_lookup([7])
    a.embedding_update([7], np.full((1, W), -0.5))   # unpushed (bound=5)
    b.embedding_lookup([7])
    b.embedding_update([7], np.full((1, W), 2.0))    # pushed immediately
    rows = a.embedding_lookup([7])                   # must keep A's -0.5
    np.testing.assert_allclose(rows[0], base - 0.5, rtol=1e-6)
    # after A flushes, everyone converges to base + 2.0 - 0.5
    a.flush()
    rows_a = a.embedding_lookup([7])
    np.testing.assert_allclose(server.sparse_pull("emb6", [7])[0],
                               base + 1.5, rtol=1e-6)
    np.testing.assert_allclose(rows_a[0], base + 1.5, rtol=1e-6)


def test_cstable_flush_without_comm_preserves_state():
    t = CacheSparseTable(limit=4, vocab_size=8, width=W, key="x", comm=None)
    t.cache.insert([1], np.ones((1, W)))
    t.cache.update([1], np.full((1, W), 0.5))
    t.flush()   # no comm: must NOT drain the accumulators
    ids, grads = t.cache.collect_dirty()
    assert list(ids) == [1]
    np.testing.assert_allclose(grads[0], np.full(W, 0.5))


def test_cstable_async_overlap_consistency():
    """Async lookups interleaved with sync updates serialize on the lock
    and end in a consistent state."""
    server = _server_with_table(key="emb7")
    t = CacheSparseTable(limit=32, vocab_size=64, width=W, key="emb7",
                         comm=server, push_bound=1)
    rng = np.random.RandomState(0)
    futs = []
    for step in range(50):
        ids = rng.randint(0, 64, size=8)
        futs.append(t.embedding_lookup_async(ids))
        t.embedding_update(ids, rng.randn(8, W).astype(np.float32) * 0.01)
    for f in futs:
        assert f.result().shape == (8, W)
    t.flush()
    s = t.perf_summary()
    assert s["lookups"] == 50


def test_update_assume_unique_matches_default():
    """The executor's phase B passes device-deduped unique rows with
    assume_unique=True; result must equal the default dedup path."""
    from hetu_tpu.cache.cstable import CacheSparseTable
    from hetu_tpu.ps.server import PSServer
    W = 4
    PSServer._instance = None
    srv = PSServer.get()
    for key, flag in (("au_a", False), ("au_b", True)):
        srv.param_init(key, (32, W), "constant", 1.0)
        t = CacheSparseTable(16, 32, W, key, comm=srv)
        ids = np.array([3, 7, 11])
        t.embedding_lookup(ids)
        t.embedding_update(ids, np.full((3, W), 0.25, np.float32),
                           assume_unique=flag)
        t.flush()
    a = srv.sparse_pull("au_a", np.array([3, 7, 11]))
    b = srv.sparse_pull("au_b", np.array([3, 7, 11]))
    np.testing.assert_allclose(a, b)
    PSServer._instance = None


def test_fetch_rows_alignment_with_shuffled_server_order():
    """_fetch_rows must realign rows when the server returns ids in a
    different order than requested (the vectorized argsort/searchsorted
    path)."""
    from hetu_tpu.cache.cstable import CacheSparseTable

    class ShufflingComm:
        """sync_embedding answering in REVERSED id order."""
        def __init__(self, table):
            self.table = table
        def sync_embedding(self, key, ids, stored, bound):
            ids = np.asarray(ids, np.int64)[::-1]
            return ids, self.table[ids], np.ones(len(ids), np.int64)
        def push_embedding(self, key, ids, rows, versions=None):
            pass

    table = np.arange(64, dtype=np.float32).reshape(16, 4)
    t = CacheSparseTable(8, 16, 4, "shuf", comm=ShufflingComm(table))
    ids = np.array([2, 9, 5])
    rows = t.embedding_lookup(ids)
    np.testing.assert_allclose(rows, table[ids])
