"""Auto-parallel planner tests (Galvatron-equivalent, SURVEY.md §2.6).

Covers: strategy enumeration, memory/time cost model monotonicity, the
knapsack DP (optimality on a hand-checkable instance + memory-pressure
behavior), end-to-end search on a transformer stack, and applying a plan
to an Executor on the 8-device CPU mesh.
"""

import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.planner import (AutoParallel, ClusterSpec, DPAlg, LayerSpec,
                              MemoryCostModel, ParallelStrategy,
                              PlannerSearch, TimeCostModel,
                              candidate_strategies, pipeline_division_even,
                              plan_to_json)


def _cluster(**kw):
    kw.setdefault("n_devices", 8)
    return ClusterSpec(**kw)


class TestStrategyEnumeration:
    def test_covers_dp_tp_pp_corners(self):
        cands = {str(s) for s in candidate_strategies(8)}
        # the reference's 8-GPU baselines (dp_utils.py:41-46)
        assert "1-1-8" in cands       # pure DP
        assert "1-8-1" in cands       # pure TP
        assert "8-1-1" in cands       # pure PP
        assert "1-1-8f" in cands      # DP + fsdp

    def test_device_count_conserved(self):
        for n in (1, 2, 4, 8, 16):
            for s in candidate_strategies(n):
                assert s.n_devices == n

    def test_flags_restrict_space(self):
        no_fsdp = candidate_strategies(8, allow_fsdp=False)
        assert all(not s.fsdp for s in no_fsdp)
        no_cp = candidate_strategies(8, allow_cp=False)
        assert all(s.cp == 1 for s in no_cp)
        tp_capped = candidate_strategies(8, max_tp=2)
        assert all(s.tp <= 2 for s in tp_capped)


class TestCostModels:
    LAYER = LayerSpec.transformer_encoder(1024, 512)

    def test_tp_divides_params_and_fsdp_divides_states(self):
        c = _cluster()
        base = MemoryCostModel(ParallelStrategy(), self.LAYER, 8, c)
        tp = MemoryCostModel(ParallelStrategy(tp=8), self.LAYER, 8, c)
        fsdp = MemoryCostModel(ParallelStrategy(dp=8, fsdp=True),
                               self.LAYER, 8, c)
        assert tp.model_states == pytest.approx(base.model_states / 8)
        assert fsdp.model_states < base.model_states / 4  # 1/8 + bias
        assert fsdp.model_states > base.model_states / 8

    def test_dp_divides_activations(self):
        c = _cluster()
        base = MemoryCostModel(ParallelStrategy(), self.LAYER, 64, c)
        dp = MemoryCostModel(ParallelStrategy(dp=8), self.LAYER, 64, c)
        assert dp.activation == pytest.approx(base.activation / 8)

    def test_time_dp_speedup_with_comm_cost(self):
        c = _cluster()
        t1 = TimeCostModel(ParallelStrategy(), self.LAYER, 64, c).total
        t8 = TimeCostModel(ParallelStrategy(dp=8), self.LAYER, 64,
                           c).total
        assert t8 < t1                 # dp-8 is faster end-to-end
        assert t8 > t1 / 8             # but not ideal: grad allreduce

    def test_fsdp_costs_more_time_than_dp(self):
        c = _cluster()
        dp = TimeCostModel(ParallelStrategy(dp=8), self.LAYER, 64, c)
        fs = TimeCostModel(ParallelStrategy(dp=8, fsdp=True), self.LAYER,
                           64, c)
        assert fs.comm > dp.comm

    def test_slow_interconnect_penalizes_tp(self):
        fast = _cluster(ici_bandwidth=45e9)
        slow = _cluster(ici_bandwidth=1e9)
        s = ParallelStrategy(tp=8)
        t_fast = TimeCostModel(s, self.LAYER, 64, fast).total
        t_slow = TimeCostModel(s, self.LAYER, 64, slow).total
        assert t_slow > t_fast


class TestDPAlg:
    def test_picks_cheapest_when_memory_free(self):
        alg = DPAlg(max_mem=100, layer_num=3, strategy_num=2)
        v = np.ones((3, 2), dtype=np.int64)
        intra = np.array([[1.0, 5.0]] * 3)
        inter = np.zeros((3, 2, 2))
        alg.set_v_and_cost(v, intra, inter)
        cost, idx, left = alg.fit()
        assert idx == [0, 0, 0]
        assert cost == pytest.approx(3.0)

    def test_memory_pressure_forces_expensive_strategy(self):
        # strategy 0: fast but huge; strategy 1: slow but small
        alg = DPAlg(max_mem=6, layer_num=3, strategy_num=2)
        v = np.array([[4, 1]] * 3, dtype=np.int64)
        intra = np.array([[1.0, 2.0]] * 3)
        inter = np.zeros((3, 2, 2))
        alg.set_v_and_cost(v, intra, inter)
        cost, idx, _ = alg.fit()
        # only one layer can afford strategy 0 (4 + 1 + 1 = 6 fits)
        assert sorted(idx) == [0, 1, 1]
        assert cost == pytest.approx(1.0 + 2.0 + 2.0)

    def test_infeasible_returns_inf(self):
        alg = DPAlg(max_mem=2, layer_num=2, strategy_num=1)
        alg.set_v_and_cost(np.full((2, 1), 5, dtype=np.int64),
                           np.ones((2, 1)), np.zeros((2, 1, 1)))
        cost, idx, _ = alg.fit()
        assert cost == np.inf and idx is None

    def test_switch_cost_discourages_mixing(self):
        # equal intra costs; any mixing pays the switch penalty
        alg = DPAlg(max_mem=100, layer_num=4, strategy_num=2)
        v = np.ones((4, 2), dtype=np.int64)
        intra = np.ones((4, 2))
        inter = np.full((4, 2, 2), 0.5)
        for i in range(4):
            np.fill_diagonal(inter[i], 0.0)
        alg.set_v_and_cost(v, intra, inter)
        cost, idx, _ = alg.fit()
        assert len(set(idx)) == 1
        assert cost == pytest.approx(4.0)


class TestPipelineDivision:
    def test_even(self):
        assert pipeline_division_even(8, 4) == [[0, 1], [2, 3], [4, 5],
                                                [6, 7]]

    def test_uneven_front_loaded(self):
        stages = pipeline_division_even(10, 4)
        assert [len(s) for s in stages] == [3, 3, 2, 2]
        assert sum(stages, []) == list(range(10))


class TestEndToEndSearch:
    def test_small_model_prefers_data_parallel(self):
        layers = [LayerSpec.transformer_encoder(256, 128, name=f"l{i}")
                  for i in range(4)]
        plan = PlannerSearch(layers, global_batch_size=64,
                             cluster=_cluster()).search()
        assert plan is not None
        assert all(s.dp >= 4 for s in plan.strategies)

    def test_memory_pressure_moves_off_pure_dp(self):
        # params so large that replicated model states exceed HBM
        big = LayerSpec(name="big", param_bytes=3e9,
                        flops_per_sample=1e9,
                        act_bytes_per_sample=1e6, seq_len=512, hidden=4096)
        layers = [big] * 4
        plan = PlannerSearch(layers, global_batch_size=8,
                             cluster=_cluster(hbm_bytes=16e9)).search()
        assert plan is not None
        # 4 layers x 3GB x4 states = 48GB replicated: must shard states
        assert all(s.tp > 1 or s.fsdp or s.pp > 1
                   for s in plan.strategies), plan.describe()

    def test_plan_json_roundtrippable(self):
        layers = [LayerSpec.transformer_encoder(256, 128, name=f"l{i}")
                  for i in range(2)]
        plan = PlannerSearch(layers, global_batch_size=16,
                             cluster=_cluster()).search()
        d = plan_to_json(plan)
        assert len(d["layers"]) == 2
        assert set(d["mesh"]) == {"pp", "tp", "dp", "cp"}


class TestAutoParallelStrategy:
    def test_plan_shards_executor_variables(self):
        layers = [LayerSpec.transformer_encoder(64, 16, name=f"l{i}")
                  for i in range(2)]
        # force a TP-ish plan by making DP look terrible
        cluster = _cluster(hbm_bytes=1e18)
        plan = PlannerSearch(layers, global_batch_size=16,
                             cluster=cluster, allow_cp=False,
                             max_pp=1).search()
        # override to a known uniform tp=2 dp=4 plan for the apply test
        from hetu_tpu.planner import ParallelPlan
        strategies = [ParallelStrategy(tp=2, dp=4)] * 2
        plan = ParallelPlan(strategies, layers, 0.0, cluster)

        x = ht.placeholder_op("x")
        w0 = ht.init.xavier_uniform((64, 128), name="l0_ffn_wi")
        w1 = ht.init.xavier_uniform((128, 64), name="l0_ffn_wo")
        h = ht.matmul_op(ht.matmul_op(x, w0), w1)
        loss = ht.reduce_mean_op(ht.reduce_sum_op(ht.mul_op(h, h), [1]),
                                 [0])
        train = ht.optim.SGDOptimizer(learning_rate=0.01).minimize(loss)
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=AutoParallel(plan))
        out = ex.run("train", feed_dict={
            x: np.random.RandomState(0).randn(8, 64).astype(np.float32)})
        assert np.isfinite(float(np.asarray(out[0])))
        specs = {n: v.sharding_spec for n, v in ex.variables.items()}
        assert specs["l0_ffn_wi"] == __import__(
            "jax").sharding.PartitionSpec(None, "tp")
        assert specs["l0_ffn_wo"] == __import__(
            "jax").sharding.PartitionSpec("tp", None)


class TestClosedLoop:
    """The full Galvatron loop in one test: profile a REAL graph-built
    layer -> calibrate the cost models -> search -> apply -> execute on
    the 8-device CPU mesh (reference: test_env scripts ->
    cost-model configs -> search_layerwise_hp -> Galvatron runtime)."""

    H, S, L, V, GBS = 32, 16, 4, 100, 16

    def _specs(self):
        return [LayerSpec.transformer_encoder(self.H, self.S,
                                              name=f"l{i}")
                for i in range(self.L)]

    def test_profile_calibrate_search_apply_run(self):
        from hetu_tpu.models.bert import BertConfig, BertLayer, \
            BertForSequenceClassification
        from hetu_tpu.planner import calibrate_layers, graph_layer_fn, \
            measure_cluster

        # 1. profile a real encoder block built from the graph API
        cfg = BertConfig(vocab_size=self.V, hidden_size=self.H,
                         num_hidden_layers=1, num_attention_heads=2,
                         intermediate_size=4 * self.H, seq_len=self.S,
                         batch_size=4, hidden_dropout_prob=0.0,
                         attention_probs_dropout_prob=0.0)
        xin = ht.placeholder_op("cl_profile_x")
        fn = graph_layer_fn(BertLayer(cfg, name="cl_profile")(xin), xin)

        # 2. calibrate cluster + layer specs from measurements
        cluster = measure_cluster(n_devices=8, probe_dim=128)
        assert cluster.flops_per_sec > 0
        layers = self._specs()
        calibrate_layers(layers, [lambda x: fn(
            x.reshape(-1, self.H))], batch=4)
        assert all(l.fwd_time_per_sample and l.fwd_time_per_sample > 0
                   for l in layers)

        # 3. memory pressure: pure DP must NOT fit, so the search is
        # forced off the naive strategy ("beats naive DP" concretely:
        # naive DP is infeasible, the plan is feasible and executes)
        pure_dp = ParallelStrategy(dp=8)
        dp_mem = MemoryCostModel(pure_dp, layers[0], self.GBS,
                                 cluster).total
        cluster.hbm_bytes = dp_mem * 0.8 / 0.9   # cap below pure-DP need
        search = PlannerSearch(layers, global_batch_size=self.GBS,
                               cluster=cluster, mem_unit=4 * 1024,
                               allow_cp=False)
        plan = search.search()
        assert plan is not None, "no feasible plan found"
        assert all(str(s) != str(pure_dp) for s in plan.strategies)
        assert np.isfinite(plan.cost)

        # 4-5. apply + run: build the real model, train under the plan
        pp = plan.mesh_axes().get("pp", 1)
        num_mb = 2 * pp if pp > 1 else 1
        mcfg = BertConfig(vocab_size=self.V, hidden_size=self.H,
                          num_hidden_layers=self.L,
                          num_attention_heads=2,
                          intermediate_size=4 * self.H, seq_len=self.S,
                          batch_size=self.GBS // num_mb,
                          hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
        ids = ht.placeholder_op("cl_ids")
        labels = ht.placeholder_op("cl_labels")
        model = BertForSequenceClassification(mcfg, num_labels=2)
        loss, _ = model(ids, labels=labels)
        train = ht.optim.AdamOptimizer(learning_rate=1e-3).minimize(loss)
        from hetu_tpu.planner import AutoParallel
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=AutoParallel(plan))
        rng = np.random.RandomState(0)
        for _ in range(2):
            xb = rng.randint(0, self.V,
                             (self.GBS, self.S)).astype(np.int32)
            yb = rng.randint(0, 2, (self.GBS,)).astype(np.int32)
            out = ex.run("train", feed_dict={ids: xb, labels: yb})
            assert np.isfinite(float(np.asarray(out[0])))

    def test_pp_plan_drives_pipeline_mode(self):
        """A plan with pp>1 turns on Executor(pipeline='gpipe')."""
        from hetu_tpu.planner import ParallelPlan
        layers = self._specs()
        strat = ParallelStrategy(pp=2, dp=4)
        plan = ParallelPlan([strat] * self.L, layers, 1e-3, _cluster())
        from test_pipeline_executor import build_model
        x, y, loss, train = build_model()
        ex = ht.Executor({"train": [loss, train]},
                         dist_strategy=AutoParallel(plan))
        assert ex.config.pipeline == "gpipe"
        sub = ex.subexecutor["train"]
        assert sub.spmd    # uniform residual-MLP body on the pp mesh
        xb = np.random.RandomState(1).randn(16, 8).astype(np.float32)
        yb = np.eye(4, dtype=np.float32)[np.random.RandomState(2)
                                         .randint(0, 4, 16)]
        out = ex.run("train", feed_dict={x: xb, y: yb})
        assert np.isfinite(float(np.asarray(out[0])))


class TestChipCalibration:
    """VERDICT r2 item 4 machinery: single-chip calibration artifact +
    measured plan-vs-naive delta + ClusterSpec loader (run on the real
    chip by `python -m hetu_tpu.planner.chip_calibration`, artifact
    CALIBRATION_TPU.json)."""

    def test_calibrate_structure_and_loader(self, tmp_path):
        import json
        from hetu_tpu.planner.chip_calibration import (
            calibrate_chip, load_calibration)
        art = calibrate_chip(small=True)
        for key in ("matmul_tflops_bf16", "matmul_tflops_bf16_raw",
                    "matmul_clamped_to_spec", "host_link", "overlap",
                    "flash_blocks", "plan_vs_naive", "cluster_spec",
                    "unmeasurable_on_one_chip"):
            assert key in art, key
        # clamp bookkeeping: a clamped dim must have raw > recorded
        for d, clamped in art["matmul_clamped_to_spec"].items():
            if clamped:
                assert art["matmul_tflops_bf16_raw"][d] > \
                    art["matmul_tflops_bf16"][d]
        assert 0.0 <= art["overlap"]["overlap_h2d"] <= 1.0
        assert art["flash_blocks"]["chosen"] in \
            art["flash_blocks"]["step_ms"]
        p = tmp_path / "cal.json"
        p.write_text(json.dumps(art))
        spec = load_calibration(str(p), n_devices=4)
        assert spec.n_devices == 4
        assert spec.overlap == art["overlap"]["overlap_h2d"]
        assert spec.flops_per_sec == art["cluster_spec"]["flops_per_sec"]

    def test_search_consumes_calibration(self, tmp_path):
        """The DP search runs against a loaded calibration spec."""
        import json
        from hetu_tpu.planner.chip_calibration import (
            calibrate_chip, load_calibration)
        from hetu_tpu.planner.search import PlannerSearch
        from hetu_tpu.planner.cost_model import LayerSpec
        art = calibrate_chip(small=True)
        p = tmp_path / "cal.json"
        p.write_text(json.dumps(art))
        spec = load_calibration(str(p), n_devices=8)
        layers = [LayerSpec.transformer_encoder(64, 32)
                  for _ in range(4)]
        plan = PlannerSearch(layers, global_batch_size=32,
                             cluster=spec).search()
        assert plan is not None

    def test_search_consumes_checked_in_tpu_artifact(self):
        """The REAL CALIBRATION_TPU.json measured on the v5e drives a
        search end-to-end: the artifact's curve must be physical (no
        reading above the device's spec-sheet peak) and its ClusterSpec
        must produce a plan."""
        import os
        from hetu_tpu.planner.chip_calibration import (
            CALIBRATION_FILE, load_calibration, spec_peak_tflops)
        from hetu_tpu.planner.search import PlannerSearch
        from hetu_tpu.planner.cost_model import LayerSpec
        if not os.path.exists(CALIBRATION_FILE):
            import pytest
            pytest.skip("no checked-in calibration artifact")
        import json
        with open(CALIBRATION_FILE) as f:
            art = json.load(f)
        if art.get("platform") == "cpu":
            import pytest
            pytest.skip("artifact is a CPU small-mode placeholder")
        spec_peak = spec_peak_tflops(art["device_kind"])
        for d, v in art["matmul_tflops_bf16"].items():
            assert v is None or v <= spec_peak, (d, v)
        spec = load_calibration(n_devices=8)
        assert spec.flops_per_sec > 1e12   # a real chip, not a CPU
        layers = [LayerSpec.transformer_encoder(768, 512)
                  for _ in range(12)]
        plan = PlannerSearch(layers, global_batch_size=256,
                             cluster=spec).search()
        assert plan is not None


class TestExecConfigPlanner:
    """Single-chip execution-config ranking closed over the measured
    ablation sweep (VERDICT r3 item 6; reference Galvatron profiles
    components then ranks full configs, utils/cost_model.py:38-60)."""

    @staticmethod
    def _synthetic_sweep(noise=0.0, seed=0):
        """Rows from a known generative model: per-sample base 2ms,
        flash +0.5ms/sample, fused head +0.3ms/sample, fixed 5ms."""
        import numpy as np
        rng = np.random.RandomState(seed)
        rows = []
        for b in (8, 16, 32, 64):
            for a in ("xla", "flash"):
                for h in ("materialized", "fused"):
                    t = b * (2.0 + 0.5 * (a == "flash")
                             + 0.3 * (h == "fused")) + 5.0
                    rows.append({"batch": b, "attention": a, "head": h,
                                 "step_time_ms":
                                     t * (1 + noise * rng.randn())})
        return rows

    def test_model_recovers_generative_components(self):
        from hetu_tpu.planner.exec_plan import ExecConfigModel
        import numpy as np
        m = ExecConfigModel().fit(self._synthetic_sweep())
        # generative model has no quadratic term: c2 must fit ~0
        np.testing.assert_allclose(
            m.coef, [2.0, 0.0, 0.5, 0.3, 5.0], atol=1e-7)

    def test_argmax_match_with_heldout_winner(self):
        """The strict split: the measured-best config is EXCLUDED from
        the fit and the model must still predict it on top."""
        from hetu_tpu.planner.exec_plan import validate_against_sweep
        rep = validate_against_sweep(self._synthetic_sweep(noise=0.02))
        assert rep["ok"], rep
        assert rep["regret"] <= rep["regret_tol"]
        assert rep["spearman_rho"] > 0.9
        assert rep["n_fit"] == rep["n_configs"] - 1

    def test_a_winner_the_model_cannot_crown_is_not_ok(self):
        """A sweep whose measured best is an outlier no component model
        explains (one small-batch row three times too fast): held out of
        the fit, it is not the model's argmax, trusting the model's pick
        loses far more than ``regret_tol``, and the report says so."""
        from hetu_tpu.planner.exec_plan import validate_against_sweep
        rows = self._synthetic_sweep()
        odd = next(r for r in rows if (r["batch"], r["attention"],
                                       r["head"]) == (8, "flash", "fused"))
        odd["step_time_ms"] /= 3.0
        rep = validate_against_sweep(rows)
        assert rep["measured_best"] == [8, "flash", "fused"]
        assert rep["predicted_best"] != rep["measured_best"]
        assert not rep["argmax_match"]
        assert rep["regret"] > rep["regret_tol"]
        assert rep["ok"] is False

    def test_negative_extrapolation_ranks_last(self):
        from hetu_tpu.planner.exec_plan import ExecConfigModel
        m = ExecConfigModel()
        m.coef = [0.1, 0.0, 0.0, 0.0, -100.0]  # negative times, small b
        import numpy as np
        m.coef = np.asarray(m.coef)
        cfg = {"batch": 4, "attention": "xla", "head": "materialized"}
        assert m.predict_throughput(cfg) == 0.0


class TestPlanAssumedConstants:
    """ICI/DCN constants the one-chip calibration cannot measure are
    flagged in plan output (VERDICT r3 item 6 tail)."""

    def test_load_calibration_marks_provenance(self, tmp_path):
        import json
        from hetu_tpu.planner.chip_calibration import (calibrate_chip,
                                                       load_calibration)
        art = calibrate_chip(small=True)
        p = tmp_path / "cal.json"
        p.write_text(json.dumps(art))
        spec = load_calibration(str(p), n_devices=8)
        assert spec.provenance["flops_per_sec"] == "measured"
        assert spec.provenance["ici_bandwidth"] == "spec-assumed"
        assert spec.provenance["dcn_bandwidth"] == "spec-assumed"
        assumed = spec.assumed_constants()
        assert "ici_bandwidth" in assumed
        assert "flops_per_sec" not in assumed

    def test_plan_json_and_describe_surface_assumptions(self, tmp_path):
        import json
        from hetu_tpu.planner import (LayerSpec, PlannerSearch,
                                      plan_to_json)
        from hetu_tpu.planner.chip_calibration import (calibrate_chip,
                                                       load_calibration)
        art = calibrate_chip(small=True)
        p = tmp_path / "cal.json"
        p.write_text(json.dumps(art))
        spec = load_calibration(str(p), n_devices=8)
        layers = [LayerSpec.transformer_encoder(64, 32) for _ in range(4)]
        plan = PlannerSearch(layers, global_batch_size=32,
                             cluster=spec).search()
        j = plan_to_json(plan)
        assert "ici_bandwidth" in j["assumed_constants"]
        assert j["assumed_constants"]["ici_bandwidth"]["provenance"] == \
            "spec-assumed"
        assert "NOT from measurement" in plan.describe()
        # planner honesty (VERDICT next #6): the banner is PROMINENT —
        # a top-level WARNING key in the json, the FIRST line of
        # describe() — not a footnote
        assert "unvalidated on hardware" in j["WARNING"]
        assert "ici_bandwidth" in j["WARNING"]
        desc = plan.describe()
        assert desc.splitlines()[0].startswith("*** WARNING")
        assert "unvalidated on hardware" in desc.splitlines()[0]


class TestEnvProfiler:
    """Environment profiler CLI (reference tools/Galvatron/test_env
    bandwidth/overlap scripts): per-axis collective bandwidths + overlap
    coefficient measured on the current mesh."""

    def test_profile_env_structure(self, tmp_path):
        from hetu_tpu.planner.env_profile import profile_env
        art = profile_env({"dp": 2, "tp": 2}, size_mb=1, compute_dim=128)
        assert set(art["axes"]) == {"dp", "tp"}
        for ax in ("dp", "tp"):
            c = art["axes"][ax]["collectives"]
            for key in ("allreduce_bytes_per_s", "allgather_bytes_per_s",
                        "alltoall_bytes_per_s", "ppermute_bytes_per_s"):
                assert c[key] > 0, (ax, key)
            ov = art["axes"][ax]["overlap"]
            assert 0.0 <= ov["overlap"] <= 1.0
        assert art["matmul_tflops_bf16"] > 0

    def test_cpu_profile_refuses_chip_label(self):
        """Planner honesty (VERDICT next #6): a CPU-platform profile is
        host-characterizing — labeled so in the artifact with a WARNING
        banner, and a 'chip' claim is refused outright."""
        from hetu_tpu.planner.env_profile import profile_env
        art = profile_env({"dp": 1}, size_mb=1, compute_dim=64)
        assert art["platform"] == "cpu"
        assert art["characterizes"] == "host"
        assert "characterize the HOST" in art["WARNING"]
        with pytest.raises(ValueError, match="refusing to label"):
            profile_env({"dp": 1}, size_mb=1, compute_dim=64,
                        claim="chip")

    def test_cli_writes_artifact(self, tmp_path):
        import json
        import subprocess
        import sys
        out = tmp_path / "env.json"
        r = subprocess.run(
            [sys.executable, "-m", "hetu_tpu.planner.env_profile",
             "--axes", "dp=2", "--size-mb", "1", "--compute-dim", "128",
             "--out", str(out)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ,
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                 "JAX_PLATFORMS": "cpu"},
            cwd=os.path.join(os.path.dirname(__file__), ".."))
        assert r.returncode == 0, r.stderr[-500:]
        art = json.loads(out.read_text())
        assert "dp" in art["axes"]


class TestDecoderLayerSpec:
    def test_decoder_vs_encoder(self):
        enc = LayerSpec.transformer_encoder(64, 32)
        dec = LayerSpec.transformer_decoder(64, 32)
        assert dec.param_bytes == enc.param_bytes
        # causal halves the 2*2*S^2*H attention flops
        assert dec.flops_per_sample == \
            enc.flops_per_sample - 2 * 32 * 32 * 64
        assert dec.tp_comm_factor == 6 and enc.tp_comm_factor == 4
