"""Expert parallelism actually running over an expert mesh.

Reference behavior being matched: MoE dispatch runs all-to-all across
devices (python/hetu/layers/moe_layer.py:45-93, gpu_ops/AllToAll.py:8-50);
hierarchical A2A composes intra- then inter-node exchanges
(src/communication/mpi_nccl_communication.cu:152-243).

TPU-native: expert weights stacked [E, D, F] and sharded over 'ep'
(StackedExperts); alltoall_op pins expert-major sharding so GSPMD emits
the exchange inside the one jitted step.  Tests assert (a) numerical
equivalence with the single-device run, (b) the compiled HLO actually
partitions the expert compute and contains a cross-device exchange, and
(c) the shard_map execution path runs real lax.all_to_all, flat and
hierarchical."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu.parallel.mesh import make_mesh


E, D, F, B = 4, 8, 16, 32


def build_moe(num_tokens):
    x = ht.placeholder_op("x")
    y = ht.placeholder_op("y")
    gate = ht.layers.TopKGate(D, num_tokens, E, k=1, capacity_factor=1.0)
    experts = ht.layers.StackedExperts(E, D, F, activation="relu")
    moe = ht.layers.MoELayer(gate=gate, experts=experts, num_tokens=num_tokens,
                             embed_dim=D)
    out, l_aux = moe(x)
    head = ht.init.xavier_uniform((D, 2), name="moe_head")
    logits = ht.matmul_op(out, head)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(logits, y), axes=0) \
        + ht.mul_byconst_op(l_aux, 0.01)
    train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return x, y, loss, train


def batches(n=6, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        xb = rng.randn(B, D).astype(np.float32)
        yb = np.eye(2, dtype=np.float32)[(xb[:, 0] > 0).astype(int)]
        out.append((xb, yb))
    return out


class TestExpertParallelExecutor:
    def test_ep_trajectory_matches_single_device(self):
        x, y, loss, train = build_moe(B)
        ex = ht.Executor({"train": [loss, train]})
        w0 = ex.return_tensor_values()
        bs = batches()
        base = [float(np.asarray(ex.run("train", feed_dict={x: a, y: b})[0]))
                for a, b in bs]

        x, y, loss, train = build_moe(B)
        ex2 = ht.Executor({"train": [loss, train]},
                          dist_strategy=ht.dist.ExpertParallel(ep=4, dp=1))
        ex2.load_dict(w0)
        tr = [float(np.asarray(ex2.run("train", feed_dict={x: a, y: b})[0]))
              for a, b in bs]
        np.testing.assert_allclose(tr, base, atol=1e-5)

    def test_ep_times_dp_trajectory(self):
        x, y, loss, train = build_moe(B)
        ex = ht.Executor({"train": [loss, train]})
        w0 = ex.return_tensor_values()
        bs = batches()
        base = [float(np.asarray(ex.run("train", feed_dict={x: a, y: b})[0]))
                for a, b in bs]

        x, y, loss, train = build_moe(B)
        ex2 = ht.Executor({"train": [loss, train]},
                          dist_strategy=ht.dist.ExpertParallel(ep=2, dp=4))
        ex2.load_dict(w0)
        tr = [float(np.asarray(ex2.run("train", feed_dict={x: a, y: b})[0]))
              for a, b in bs]
        np.testing.assert_allclose(tr, base, atol=1e-5)

    def test_expert_weights_actually_sharded(self):
        x, y, loss, train = build_moe(B)
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=ht.dist.ExpertParallel(ep=4, dp=1))
        w1 = None
        for name, v in ex.var_values.items():
            if "expert_stack_w1" in name:
                w1 = v
        assert w1 is not None
        # leading expert dim split 4 ways: each shard holds E/4 experts
        shard_shapes = {s.data.shape for s in w1.addressable_shards}
        assert shard_shapes == {(E // 4, D, F)}

    def test_compiled_hlo_partitions_expert_compute(self):
        """The proof the EP path is real: compiled HLO of the executor step
        must (a) run expert matmuls at per-shard size E/ep and (b) contain
        a cross-partition exchange feeding them (all-to-all, or
        collective-permute when XLA lowers the reshard that way)."""
        x, y, loss, train = build_moe(B)
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=ht.dist.ExpertParallel(ep=4, dp=1))
        bs = batches(1)
        a, b = bs[0]
        ex.run("train", feed_dict={x: a, y: b})   # compile
        sub = ex.subexecutor["train"]
        fn = next(iter(sub._compiled.values()))
        feeds = {"x": a, "y": b}
        txt = fn.lower(ex.var_values, ex.opt_states, ex.step, ex.rng,
                       {k: np.asarray(v) for k, v in feeds.items()}
                       ).compile().as_text()
        assert "all-to-all" in txt or "collective-permute" in txt or \
            "all-gather" in txt, "no cross-device exchange in HLO"
        # expert batched matmul appears at per-shard expert count (dim E/4)
        per_shard = f"f32[{E // 4},{B // E},{F}]"
        assert per_shard in txt.replace(" ", ""), (
            f"expected per-shard expert activation {per_shard} in HLO")


class TestShardMapA2A:
    def test_flat_alltoall_executes(self):
        mesh = make_mesh({"ep": 4})
        from hetu_tpu.graph.ops_moe import alltoall_op
        from hetu_tpu.graph.node import TraceContext
        from jax import shard_map

        node = ht.placeholder_op("t")
        a2a = alltoall_op(node, axis="ep")
        xs = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3)

        def body(x):
            tc = TraceContext(axis_env=("ep",))
            return a2a.compute([x], tc)

        out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("ep"),
                                out_specs=P("ep")))(xs)
        # all_to_all over blocks: involution — applying twice restores
        out2 = jax.jit(shard_map(body, mesh=mesh, in_specs=P("ep"),
                                 out_specs=P("ep")))(out)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(xs))
        # and it is NOT the identity (devices exchanged rows)
        assert not np.array_equal(np.asarray(out), np.asarray(xs))

    def test_hierarchical_alltoall_over_ici_dcn(self):
        """('dcn','ici') mesh: halltoall composes per-axis exchanges; the
        composition must be an involution and must move data across both
        axes (reference mpi_nccl_communication.cu:152-243 semantics)."""
        mesh = make_mesh({"dcn": 2, "ici": 2})
        assert mesh.axis_names == ("dcn", "ici")
        from hetu_tpu.graph.ops_moe import halltoall_op
        from hetu_tpu.graph.node import TraceContext
        from jax import shard_map

        node = ht.placeholder_op("t")
        h = halltoall_op(node, axes=("ici", "dcn"))
        xs = jnp.arange(16 * 2, dtype=jnp.float32).reshape(16, 2)

        def body(x):
            tc = TraceContext(axis_env=("ici", "dcn"))
            return h.compute([x], tc)

        run = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=P(("dcn", "ici")),
                                out_specs=P(("dcn", "ici"))))
        out = run(xs)
        out2 = run(out)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(xs))
        assert not np.array_equal(np.asarray(out), np.asarray(xs))

        # the hierarchical two-stage exchange must equal ONE flat
        # all-to-all over the combined ('dcn','ici') superaxis
        def flat(x):
            n = 4
            parts = x.reshape(n, x.shape[0] // n, *x.shape[1:])
            return jax.lax.all_to_all(
                parts, ("dcn", "ici"), split_axis=0,
                concat_axis=0).reshape(x.shape)

        flat_out = jax.jit(shard_map(flat, mesh=mesh,
                                     in_specs=P(("dcn", "ici")),
                                     out_specs=P(("dcn", "ici"))))(xs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(flat_out))

    def test_hierarchical_moe_trains_on_ici_dcn_mesh(self):
        """MoE with hierarchical=True through the Executor on a
        ('dcn','ici') mesh (pjit mode: constraint spans both axes)."""
        x = ht.placeholder_op("x")
        y = ht.placeholder_op("y")
        gate = ht.layers.TopKGate(D, B, E, k=1, capacity_factor=1.0)
        experts = ht.layers.StackedExperts(E, D, F, activation="relu",
                                           name="hier")
        moe = ht.layers.MoELayer(gate=gate, experts=experts, num_tokens=B,
                                 embed_dim=D, hierarchical=True)
        out, l_aux = moe(x)
        head = ht.init.xavier_uniform((D, 2), name="hier_head")
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(out, head), y), axes=0) \
            + ht.mul_byconst_op(l_aux, 0.01)
        train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)

        mesh = make_mesh({"dcn": 2, "ici": 2})
        ex = ht.Executor({"train": [loss, train]}, mesh=mesh)
        for name, node in ex.variables.items():
            if "expert_stack" in name:
                node.sharding_spec = P(("dcn", "ici"), None, None)
        ex.var_values = {k: jax.device_put(v, ex.param_sharding(k))
                         for k, v in ex.var_values.items()}
        for a, b in batches(3):
            out_v = ex.run("train", feed_dict={x: a, y: b})
            assert np.isfinite(float(np.asarray(out_v[0])))


def test_dispatch_formulations_agree(monkeypatch):
    """The one-hot-matmul and row-scatter dispatch forms must produce
    identical expert buffers and identical combine-data gradients."""
    from hetu_tpu.graph import ops_moe
    from hetu_tpu.graph.ops_moe import _scatter_rows

    rng = np.random.RandomState(5)
    N, D, slots = 64, 16, 24
    src = jnp.asarray(rng.randn(N, D).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, slots + 4, N).astype(np.int32))
    valid = pos < slots            # some dropped
    gates = jnp.asarray(rng.rand(N).astype(np.float32))

    for terms in ([(pos, valid, None)],
                  [(pos, valid, gates)],
                  [(pos, valid, None), ((pos + 3) % slots,
                                        jnp.ones_like(valid), gates)]):
        a = _scatter_rows(terms, slots, src, jnp.float32)
        with monkeypatch.context() as m:
            m.setattr(ops_moe, "_ONEHOT_DISPATCH_MAX_ELEMS", 0)
            b = _scatter_rows(terms, slots, src, jnp.float32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_dispatch_form_follows_the_mask_size(monkeypatch):
    """``_scatter_rows`` chooses its form from ``N * n_slots`` alone:
    the one-hot matmul up to ``_ONEHOT_DISPATCH_MAX_ELEMS`` mask
    elements, the row scatter-add over it, with equal results at the
    boundary."""
    import jax
    from hetu_tpu.graph import ops_moe

    rng = np.random.RandomState(7)
    N, D, slots = 32, 8, 12
    src = jnp.asarray(rng.randn(N, D).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, slots, N).astype(np.int32))
    terms = [(pos, pos < slots, None)]

    def run(limit):
        monkeypatch.setattr(ops_moe, "_ONEHOT_DISPATCH_MAX_ELEMS", limit)
        fn = lambda s: ops_moe._scatter_rows(terms, slots, s, jnp.float32)
        prims = {e.primitive.name for e in jax.make_jaxpr(fn)(src).eqns}
        return prims, np.asarray(fn(src))

    at, out_at = run(N * slots)          # the mask fits: one-hot matmul
    over, out_over = run(N * slots - 1)  # one element over: scatter-add
    assert "dot_general" in at and "scatter-add" not in at
    assert "scatter-add" in over and "dot_general" not in over
    np.testing.assert_allclose(out_at, out_over, rtol=1e-5, atol=1e-5)


class TestBertMoEFlagship:
    """MoE composed into the flagship LM (reference
    examples/nlp/bert/hetu_bert_moe.py + train_hetu_bert_dp_moe.py):
    alternating MoE FFN blocks, aux balance loss in the total, trained
    through a dp x ep mesh with single-device-equivalent trajectories."""

    CFG = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=2, intermediate_size=64,
               max_position_embeddings=16, batch_size=4, seq_len=8,
               num_experts=4, top_k=1, moe_every=2,
               hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)

    def _build(self):
        from hetu_tpu.models import BertMoEConfig, BertMoEForPreTraining
        cfg = BertMoEConfig(**self.CFG)
        m = BertMoEForPreTraining(cfg)
        ids = ht.placeholder_op("bm_ids")
        tt = ht.placeholder_op("bm_tt")
        mlm = ht.placeholder_op("bm_mlm")
        nsp = ht.placeholder_op("bm_nsp")
        loss, _logits, _nspl = m(ids, tt, masked_lm_labels=mlm,
                                 next_sentence_label=nsp)
        train = ht.optim.AdamOptimizer(learning_rate=1e-3).minimize(loss)
        return cfg, (ids, tt, mlm, nsp), loss, train

    def _batches(self, n=5, seed=0):
        rng = np.random.RandomState(seed)
        out = []
        for _ in range(n):
            iv = rng.randint(0, 97, (4, 8)).astype(np.int32)
            tv = np.zeros((4, 8), np.int32)
            mv = np.where(rng.rand(4, 8) < 0.3, iv, -1).astype(np.int32)
            nv = rng.randint(0, 2, (4,)).astype(np.int32)
            out.append((iv, tv, mv, nv))
        return out

    def test_moe_blocks_alternate_and_aux_loss_present(self):
        from hetu_tpu.models import BertMoEConfig, BertMoEModel
        from hetu_tpu.models.bert_moe import BertMoELayer
        cfg = BertMoEConfig(**{**self.CFG, "num_hidden_layers": 4})
        model = BertMoEModel(cfg)
        kinds = [isinstance(l, BertMoELayer) for l in model.encoder_layers]
        assert kinds == [False, True, False, True]
        _cfg, nodes, loss, train = self._build()
        ids, tt, mlm, nsp = nodes
        ex = ht.Executor({"train": [loss, train]})
        iv, tv, mv, nv = self._batches(1)[0]
        out = ex.run("train", feed_dict={ids: iv, tt: tv, mlm: mv,
                                         nsp: nv})
        assert np.isfinite(float(np.asarray(out[0])))

    def test_ep_times_dp_trajectory_matches_single_device(self):
        _cfg, nodes, loss, train = self._build()
        ids, tt, mlm, nsp = nodes
        ex = ht.Executor({"train": [loss, train]})
        w0 = ex.return_tensor_values()
        bs = self._batches()
        base = [float(np.asarray(ex.run("train", feed_dict={
            ids: a, tt: b, mlm: c, nsp: d})[0])) for a, b, c, d in bs]

        _cfg, nodes, loss, train = self._build()
        ids, tt, mlm, nsp = nodes
        ex2 = ht.Executor({"train": [loss, train]},
                          dist_strategy=ht.dist.ExpertParallel(ep=4, dp=2))
        ex2.load_dict(w0)
        tr = [float(np.asarray(ex2.run("train", feed_dict={
            ids: a, tt: b, mlm: c, nsp: d})[0])) for a, b, c, d in bs]
        np.testing.assert_allclose(tr, base, atol=2e-5)

    def test_expert_stacks_sharded_dense_ffn_replicated(self):
        _cfg, nodes, loss, train = self._build()
        ids, tt, mlm, nsp = nodes
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=ht.dist.ExpertParallel(ep=4, dp=2))
        stack = dense = None
        for name, v in ex.var_values.items():
            if "_moe_expert_stack_w1" in name:
                stack = v
            if "_intermediate_weight" in name:
                dense = v
        assert stack is not None and dense is not None
        # 4 experts split over ep=4: each shard holds exactly 1 expert
        assert {s.data.shape for s in stack.addressable_shards} == \
            {(1, 32, 64)}
        # the dense block's FFN replicates across the expert axis
        assert {s.data.shape for s in dense.addressable_shards} == \
            {(32, 64)}


def test_bert_moe_under_pipeline_trains():
    """Composition row: the MoE flagship through Executor(pipeline=
    'gpipe').  EXACT trajectory equality with the full-batch run is
    deliberately NOT the contract here: TopKGate's static capacity is
    k*ceil(tokens/E) of the COMPILED batch, so each microbatch routes
    against its own (smaller) capacity pool and token-drop patterns
    differ from full-batch routing — the same per-chunk semantics every
    capacity-based MoE has under gradient accumulation (and the same
    caveat bert.py documents for the masked mean).  The contract: the
    composition runs and trains."""
    from hetu_tpu.models import BertMoEConfig, BertMoEForPreTraining

    # the graph bakes the MICROBATCH size (global batch 8 / M=2); the
    # pipeline splits each fed global batch across microbatches
    cfg = BertMoEConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=2, intermediate_size=64,
        batch_size=4, seq_len=8, num_experts=4, top_k=1,
        moe_every=2, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = BertMoEForPreTraining(cfg, name="plb")
    nodes = tuple(ht.placeholder_op(f"plb_{nm}")
                  for nm in ("ids", "tt", "mlm", "nsp"))
    loss, _, _ = m(nodes[0], nodes[1], masked_lm_labels=nodes[2],
                   next_sentence_label=nodes[3])
    train = ht.optim.SGDOptimizer(learning_rate=0.05).minimize(loss)

    def batches(n=4):
        rng = np.random.RandomState(3)
        out = []
        for _ in range(n):
            iv = rng.randint(0, 64, (8, 8)).astype(np.int32)
            mv = np.where(rng.rand(8, 8) < 0.3, iv, -1).astype(np.int32)
            out.append((iv, np.zeros((8, 8), np.int32), mv,
                        np.zeros((8,), np.int32)))
        return out

    ex2 = ht.Executor({"train": [loss, train]}, pipeline="gpipe",
                      num_microbatches=2)
    tr = []
    for iv, tv, mv, nv in batches(8):
        out = ex2.run("train", feed_dict=dict(zip(nodes,
                                                  (iv, tv, mv, nv))))
        tr.append(float(np.asarray(out[0]).reshape(-1)[0]))
    assert all(np.isfinite(v) for v in tr)
    assert np.mean(tr[-3:]) < np.mean(tr[:3]), tr
