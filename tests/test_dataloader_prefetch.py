"""Async dataloader prefetch ring (reference: 3-deep pinned ring with
background workers, python/hetu/dataloader.py:30-100).

The ring must (1) preserve the exact batch sequence incl. epoch-seeded
shuffles, (2) overlap host-side batch assembly with the consumer, (3)
hand the executor device-resident (sharded) batches, (4) surface producer
errors, and (5) leave PS-embedding-feeding loaders host-side."""

import itertools
import threading

import numpy as np
import pytest

import jax
import hetu_tpu as ht
from hetu_tpu.dataloader import Dataloader


def _data(n=64, d=4, seed=0):
    return np.random.RandomState(seed).randn(n, d).astype(np.float32)


class TestRing:
    def test_order_identical_to_serial(self):
        X = _data()
        serial = Dataloader(X, 8, "train", shuffle=True, seed=7)
        ringed = Dataloader(X, 8, "train", shuffle=True, seed=7)
        ringed.start_prefetch()
        want = [serial.get_arr() for _ in range(20)]   # 2.5 epochs
        got = [ringed.get_arr() for _ in range(20)]
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        ringed.stop_prefetch()

    def test_peek_then_get_consistent(self):
        dl = Dataloader(_data(), 8, "train")
        dl.start_prefetch()
        p = dl.peek_arr()
        g = dl.get_arr()
        np.testing.assert_array_equal(p, g)
        # next batch differs (no shuffle, sequential slices)
        assert not np.array_equal(g, dl.get_arr())
        dl.stop_prefetch()

    def test_overlaps_producer_work(self):
        """The producer's transform (stand-in for host slicing +
        device_put) for batch n + 1 has been called before the consumer
        asks for it: it ran beside the consumer's compute on batch n."""
        events, count = [], itertools.count()
        made = [threading.Event() for _ in range(16)]

        def slow(batch):
            n = next(count)
            events.append(("made", n))
            made[n].set()
            return batch

        ringed = Dataloader(_data(256), 8, "train")
        ringed.start_prefetch(transform=slow)
        for n in range(10):
            events.append(("asked", n))
            ringed.get_arr()
            # consumer "compute", for as long as the producer takes: a
            # loader that made a batch only when asked would never end it
            assert made[n + 1].wait(60), f"batch {n + 1} waits to be asked"
        ringed.stop_prefetch()
        for n in range(1, 10):
            assert events.index(("made", n)) < events.index(("asked", n))

    def test_producer_error_surfaces(self):
        dl = Dataloader(_data(16), 8, "train")

        def boom(batch):
            raise RuntimeError("producer exploded")

        dl.start_prefetch(transform=boom)
        with pytest.raises(RuntimeError, match="exploded"):
            dl.get_arr()


class TestExecutorIntegration:
    def _build(self):
        X = _data(64, 4, seed=1)
        Y = np.eye(2, dtype=np.float32)[(X[:, 0] > 0).astype(int)]
        dlx = ht.dataloader_op([ht.Dataloader(X, 8, "train")])
        dly = ht.dataloader_op([ht.Dataloader(Y, 8, "train")])
        w = ht.init.xavier_uniform((4, 2), name="pf_w")
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(dlx, w), dly), axes=0)
        train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        return loss, train

    def test_prefetch_matches_no_prefetch(self):
        loss, train = self._build()
        ex1 = ht.Executor({"train": [loss, train]}, prefetch=False)
        w0 = ex1.return_tensor_values()
        base = [float(np.asarray(ex1.run("train")[0])) for _ in range(12)]

        loss, train = self._build()
        ex2 = ht.Executor({"train": [loss, train]}, prefetch=True)
        ex2.load_dict(w0)
        tr = [float(np.asarray(ex2.run("train")[0])) for _ in range(12)]
        np.testing.assert_allclose(tr, base, atol=1e-6)

    def test_batches_arrive_device_resident(self, monkeypatch):
        """Above the size threshold the ring's transform device_puts with
        the feed sharding, so the loop pops jax.Arrays (H2D off the
        critical path).  (Below it, assembly stays host-only — cheaper
        than the thread contention.)"""
        import hetu_tpu.executor as exe
        monkeypatch.setattr(exe, "_RING_DEVICE_PUT_MIN_BYTES", 0)
        from hetu_tpu.parallel.mesh import make_mesh
        loss, train = self._build()
        mesh = make_mesh({"dp": 8})
        ex = ht.Executor({"train": [loss, train]}, mesh=mesh)
        ex.run("train")
        sub = ex.subexecutor["train"]
        dl_op = sub.dataloader_ops[0]
        loader = dl_op.dataloaders["train"]
        assert loader._ring is not None
        batch = loader.peek_arr()
        assert isinstance(batch, jax.Array)
        assert len(batch.sharding.device_set) == 8

    def test_ps_feeding_loader_stays_host_side(self):
        """Ids consumed by a PS embedding lookup must remain numpy (phase
        A gathers rows host-side from the ids)."""
        from tests.test_hybrid import fresh_ps
        fresh_ps()
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 32, (64, 4)).astype(np.int32)
        Y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 64)]
        dl_ids = ht.dataloader_op([ht.Dataloader(ids, 8, "train")])
        dl_y = ht.dataloader_op([ht.Dataloader(Y, 8, "train")])
        emb = ht.layers.Embedding(32, 8, name="pf_emb")
        h = ht.embedding_lookup_op(emb.embedding_table, dl_ids)
        h = ht.reduce_mean_op(h, [1])
        logits = ht.matmul_op(h, ht.init.xavier_uniform((8, 2),
                                                        name="pf_head"))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, dl_y), axes=0)
        train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        ex = ht.Executor({"train": [loss, train]}, comm_mode="Hybrid")
        for _ in range(3):
            out = ex.run("train")
            assert np.isfinite(float(np.asarray(out[0])))
        sub = ex.subexecutor["train"]
        ids_loader = sub.dataloader_ops[0].dataloaders["train"]
        # the ids loader ring has no device_put transform
        if ids_loader._ring is not None:
            assert isinstance(ids_loader.peek_arr(), np.ndarray)


class TestMidEpochResume:
    """Checkpoint captures the dataloader position: a resumed run pops
    the EXACT batch stream the uninterrupted run would have (incl. the
    epoch-seeded reshuffles) — the reference restarts its iterator."""

    def _build(self, tag):
        X = _data(40, 4, seed=21)
        Y = np.eye(2, dtype=np.float32)[(X[:, 0] > 0).astype(int)]
        dlx = ht.dataloader_op([ht.Dataloader(X, 8, "train",
                                              shuffle=True, seed=4)])
        dly = ht.dataloader_op([ht.Dataloader(Y, 8, "train",
                                              shuffle=True, seed=4)])
        w = ht.init.xavier_uniform((4, 2), name=f"mr_w_{tag}")
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(dlx, w), dly), axes=0)
        train = ht.optim.AdamOptimizer(learning_rate=0.05).minimize(loss)
        return loss, train

    def test_resume_continues_batch_stream(self, tmp_path):
        # uninterrupted: 12 steps (2+ epochs of 5 batches, reshuffles)
        loss, train = self._build("a")
        ex = ht.Executor({"train": [loss, train]}, prefetch=False)
        w0 = ex.return_tensor_values()
        full = [float(np.asarray(ex.run("train")[0])) for _ in range(12)]

        # interrupted at step 7, checkpoint, fresh process resumes
        loss, train = self._build("a")
        ex1 = ht.Executor({"train": [loss, train]}, prefetch=False)
        ex1.load_dict(w0)
        part1 = [float(np.asarray(ex1.run("train")[0])) for _ in range(7)]
        ex1.save(str(tmp_path))

        loss, train = self._build("a")
        ex2 = ht.Executor({"train": [loss, train]}, prefetch=False)
        ex2.load(str(tmp_path))
        part2 = [float(np.asarray(ex2.run("train")[0])) for _ in range(5)]
        np.testing.assert_allclose(part1 + part2, full, atol=1e-6)

    def test_resume_with_prefetch_ring(self, tmp_path):
        """The ring prefetches ahead, but _consumed tracks the trainer's
        position, so resume is exact with prefetch on too."""
        loss, train = self._build("b")
        ex = ht.Executor({"train": [loss, train]}, prefetch=False)
        w0 = ex.return_tensor_values()
        full = [float(np.asarray(ex.run("train")[0])) for _ in range(10)]

        loss, train = self._build("b")
        ex1 = ht.Executor({"train": [loss, train]}, prefetch=True)
        ex1.load_dict(w0)
        part1 = [float(np.asarray(ex1.run("train")[0])) for _ in range(6)]
        ex1.save(str(tmp_path))

        loss, train = self._build("b")
        ex2 = ht.Executor({"train": [loss, train]}, prefetch=True)
        ex2.load(str(tmp_path))
        part2 = [float(np.asarray(ex2.run("train")[0])) for _ in range(4)]
        np.testing.assert_allclose(part1 + part2, full, atol=1e-6)


class TestResumeRobustness:
    def test_load_midsession_with_ring_running(self, tmp_path):
        """Executor.load() after training started (prefetch ring live)
        must drain + restart the ring at the restored position, not
        crash."""
        mk = TestMidEpochResume()
        loss, train = mk._build("rb")
        ex = ht.Executor({"train": [loss, train]}, prefetch=True)
        w0 = ex.return_tensor_values()
        full = [float(np.asarray(ex.run("train")[0])) for _ in range(9)]

        loss, train = mk._build("rb")
        ex1 = ht.Executor({"train": [loss, train]}, prefetch=True)
        ex1.load_dict(w0)
        part1 = [float(np.asarray(ex1.run("train")[0])) for _ in range(5)]
        ex1.save(str(tmp_path))
        # keep training past the save, then roll BACK mid-session — the
        # ring is running and ahead of the restored position
        for _ in range(3):
            ex1.run("train")
        ex1.load(str(tmp_path))
        part2 = [float(np.asarray(ex1.run("train")[0])) for _ in range(4)]
        np.testing.assert_allclose(part1 + part2, full, atol=1e-6)

    def test_seed_mismatch_rejected(self):
        dl = Dataloader(_data(32), 8, "t", shuffle=True, seed=4)
        dl.get_arr()
        st = dl.state_dict()
        other = Dataloader(_data(32), 8, "t", shuffle=True, seed=5)
        with pytest.raises(ValueError, match="seed"):
            other.load_state_dict(st)
