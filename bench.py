"""Benchmark matrix: per-config JSON artifacts + ONE headline JSON line.

VERDICT r2 item 1: the flagship number must be the TRUE config, not a
proxy, and every BASELINE.md config must persist a per-config artifact.
Configs (BASELINE.md table):

  bert_base     BERT-base TRUE: 12 layers, seq 512, hidden 768, flash
                attention, bf16 — samples/s/chip + MFU   (headline line)
  bert4l        the round-1/2 4-layer seq-128 proxy (round-over-round
                continuity with BENCH_r01/r02)
  resnet18      ResNet-18 / CIFAR-10 shapes                (config 1)
  ctr_hybrid    Wide&Deep Criteo-shape, PS+HET-cache Hybrid: samples/s,
                embedding rows/s, cache hit rate           (config 3)
  moe           MoE MLP top-2 gate: tokens/s               (config 4)
  long_context  32k-token causal flash attention: tokens/s (new-capability
                axis; the reference caps at seq 512)

Every config's full stats land in BENCH_MATRIX.json (written incrementally
— a crash mid-matrix keeps earlier configs).  stdout still carries exactly
ONE JSON line (the driver contract): the bert_base headline with
`"matrix"` carrying each other config's key number.

One process per chip: every config, probe and sweep cell runs in THIS
process (a child could not take the chip its parent holds).  A run that
finds no TPU fails; ``JAX_PLATFORMS=cpu`` is the one, explicit way to a
CPU run at verification scale, and nothing from an earlier run is ever
re-emitted in its place.

Select a subset with HETU_BENCH_CONFIGS=bert_base,moe; force small scale
with HETU_BENCH_SMALL=1.
"""

from __future__ import annotations

import json
import os
import time

from hetu_tpu import envvars

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_MATRIX_FILE = os.path.join(_HERE, "BENCH_MATRIX.json")


def _peak_tflops(device_kind: str):
    """bf16 spec peak for the MFU denominator — single source of truth
    lives next to the calibration's physics ceiling."""
    from hetu_tpu.planner.chip_calibration import spec_peak_tflops
    return spec_peak_tflops(device_kind)


def _require_backend():
    """The platform this run measures on.  No chip, no run: a backend
    other than TPU is accepted only when ``JAX_PLATFORMS=cpu`` asked for
    it (verification scale, labelled ``cpu``)."""
    import jax

    platform = jax.default_backend()
    if platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            f"bench: JAX found no TPU (default backend {platform!r}); "
            f"set JAX_PLATFORMS=cpu for an explicit CPU run at "
            f"verification scale")
    return platform


# --------------------------------------------------------------------- #
# shared timing harness
# --------------------------------------------------------------------- #

def _time_steps(run_step, iters, materialize):
    """Time ``iters`` calls of run_step; host-side dispatch time is
    measured separately (the per-step host work on the critical path —
    outputs only materialize after the loop, forcing the full donated
    chain)."""
    out = run_step()                      # warmup/compile
    materialize(out)
    t_host = 0.0
    t0 = time.perf_counter()
    for _ in range(iters):
        tf0 = time.perf_counter()
        out = run_step()
        t_host += time.perf_counter() - tf0
    materialize(out)
    dt = (time.perf_counter() - t0) / iters
    return dt, t_host / (dt * iters)


def _mfu(flops_per_step, dt, n_chips, platform):
    import jax
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind) if platform != "cpu" else None
    tflops_chip = flops_per_step / dt / n_chips / 1e12
    return kind, round(tflops_chip, 2), \
        (round(tflops_chip / peak, 4) if peak else None)


# --------------------------------------------------------------------- #
# config: transformer LM (bert_base / bert4l share the builder)
# --------------------------------------------------------------------- #

def _build_lm(batch, seq, hidden, heads, layers_n, vocab, use_flash, mesh,
              n_batches):
    """Model + input pipeline.  Inputs come through the Dataloader (with
    its background prefetch ring device_putting ahead of need), like the
    reference benches pull from their dataloader — a fixed fed array
    would understate host work and overstate throughput."""
    import hetu_tpu as ht

    rng = np.random.RandomState(0)
    id_data = rng.randint(0, vocab, (batch * n_batches, seq)).astype(
        np.int32)
    label_data = rng.randint(0, vocab, (batch * n_batches, seq)).astype(
        np.int32)
    ids = ht.dataloader_op([ht.Dataloader(id_data, batch, "train")])
    labels = ht.dataloader_op([ht.Dataloader(label_data, batch, "train")])
    emb = ht.layers.Embedding(vocab, hidden, name="tok_emb")
    pos = ht.init.random_normal((seq, hidden), stddev=0.02, name="pos_emb")
    h = ht.embedding_lookup_op(emb.embedding_table, ids)
    h = h + ht.broadcast_shape_op(pos, (batch, seq, hidden), add_axes=[0])
    h = ht.array_reshape_op(h, [batch * seq, hidden])
    for i in range(layers_n):
        attn = ht.layers.MultiHeadAttention(hidden, heads, seq, batch,
                                            use_flash=use_flash,
                                            name=f"l{i}_attn")
        h = ht.layers.LayerNorm(hidden, name=f"l{i}_ln1")(h + attn(h))
        wi = ht.layers.Linear(hidden, hidden * 4, name=f"l{i}_ffn_wi")
        wo = ht.layers.Linear(hidden * 4, hidden, name=f"l{i}_ffn_wo")
        h = ht.layers.LayerNorm(hidden, name=f"l{i}_ln2")(
            h + wo(ht.gelu_op(wi(h))))
    # LM head TIED to the token embedding, as the reference BERT ties its
    # decoder (examples/nlp/bert/hetu_bert.py:421) — and as honest MFU
    # accounting requires: an untied gather-only table would otherwise
    # inflate the 6*P*T numerator with params that never hit the MXU.
    # Default is the materialized head: the chunked fused head
    # (tied_lm_head_xent_op) measured 14% SLOWER at BERT-base scale on
    # the v5e (its fp32 dW scan carry outweighs the saved logits
    # traffic) — it is a MEMORY tool for vocab/batch scales where the
    # [B*S, vocab] chain doesn't fit.  HETU_BENCH_FUSED_HEAD=1 A/Bs it.
    head_bias = ht.init.zeros((vocab,), name="lm_head_bias")
    flat_labels = ht.array_reshape_op(labels, [batch * seq])
    if envvars.get_bool("HETU_BENCH_FUSED_HEAD"):
        loss = ht.reduce_mean_op(
            ht.tied_lm_head_xent_op(h, emb.embedding_table, head_bias,
                                    flat_labels), axes=0)
    else:
        logits = ht.linear_op(h, emb.embedding_table, head_bias,
                              trans_B=True)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_sparse_op(logits, flat_labels), axes=0)
    train = ht.optim.AdamOptimizer(learning_rate=1e-4).minimize(loss)
    # bf16 compute / fp32 masters: the MXU path
    ex = ht.Executor({"train": [loss, train]}, mixed_precision="bf16",
                     mesh=mesh)
    return ex


def _bench_lm(platform, reduced, *, layers_n, seq, per_chip_batch,
              hidden=768, heads=12, vocab=30522, iters=20,
              keep_batch=False):
    import jax
    from hetu_tpu.parallel.mesh import make_mesh

    n_chips = max(1, jax.device_count())
    if reduced:
        # keep_batch: the sweep varies per_chip_batch as a REAL axis even
        # at reduced scale — overriding it here would make every sweep
        # cell measure the identical workload and the batch ranking
        # fictitious
        if not keep_batch:
            per_chip_batch = 4
        seq, hidden, heads, layers_n, vocab = 64, 128, 4, 2, 1000
        iters = 3
    batch = per_chip_batch * n_chips
    mesh = make_mesh({"dp": n_chips}) if n_chips > 1 else None
    # flash attention wins on long sequences (the 32k config NEEDS it);
    # at seq 512 the fused kernel measured ~8% SLOWER than XLA's batched
    # attention on the v5e (its per-block matmuls contract over only
    # head_dim=64 while the saved probs traffic is ~1 ms/layer), so the
    # crossover is taken at 1024.  Reduced (CPU) scale keeps flash on so
    # the kernel path stays exercised in verification runs.
    use_flash = (platform == "tpu" and seq >= 1024) or reduced
    # sweep/ablation override: pin the attention impl regardless of the
    # crossover default (HETU_BENCH_SWEEP drives both impls per batch)
    forced = envvars.get_str("HETU_BENCH_FORCE_FLASH")
    if forced is not None:
        use_flash = forced == "1"
    flash_forced = forced is not None
    # a flash build that fails raises: re-running unfused would record
    # an XLA-attention time under a flash row
    ex = _build_lm(batch, seq, hidden, heads, layers_n, vocab,
                   use_flash, mesh, n_batches=iters + 2)
    dt, host_frac = _time_steps(
        lambda: ex.run("train"),
        iters, lambda out: float(np.asarray(out[0])))

    # Analytic FLOPs (XLA cost_analysis would require re-lowering and
    # RE-COMPILING the whole step just to read a number — minutes on TPU).
    # Honest MFU accounting: count ONLY matmul-participating weights —
    # 12*H^2 per layer (4 attention projections + 8 FFN) plus the H*V
    # head matmul (whose weight is the tied embedding table, counted
    # once).  Embedding gathers, position adds, LayerNorms, biases and
    # the softmax-xent are real work the numerator deliberately ignores.
    # The attention score/context matmuls add 12*B*S^2*H per layer.
    matmul_params = 12.0 * hidden * hidden * layers_n + hidden * vocab
    flops = 6.0 * matmul_params * (batch * seq) \
        + layers_n * 12.0 * batch * seq * seq * hidden
    kind, tflops_chip, mfu = _mfu(flops, dt, n_chips, platform)
    out = {
        "value": round(batch / dt / n_chips, 2),
        "unit": "samples/sec/chip",
        "step_time_ms": round(dt * 1e3, 3),
        "tflops_per_sec_chip": tflops_chip,
        "mfu": mfu,
        "host_fraction": round(host_frac, 4),
        "device_kind": kind,
        "n_chips": n_chips,
        "flash_attention": use_flash,
        "reduced_scale": reduced,
        "config": {"per_chip_batch": per_chip_batch, "seq": seq,
                   "hidden": hidden, "layers": layers_n, "vocab": vocab},
    }
    if flash_forced:
        # provenance in the artifact itself: this row's attention impl
        # was pinned by HETU_BENCH_FORCE_FLASH, not chosen by the
        # seq-crossover heuristic (ADVICE: a forced bert4l row is
        # otherwise indistinguishable from a default-path measurement)
        out["flash_forced"] = True
    # physics ceiling: a row claiming more than the silicon can do is a
    # measurement defect, not a result (telemetry/health.py)
    from hetu_tpu.telemetry import health as _health
    ceiling = _health.check_physics_ceiling(
        mfu=mfu, tflops_chip=tflops_chip, platform=platform)
    if not ceiling["ok"]:
        out["health_violation"] = ceiling["violations"]
    return out


def _probe(fn):
    """One probe, in this process: its result, or the error as a string
    so that one failed batch size / table rung / sweep cell (an OOM,
    say) costs that probe and not the matrix."""
    try:
        return fn()
    except Exception as e:
        return f"{type(e).__name__}: {e}"[:200]


def _probe_health(numeric):
    """Telemetry health gate over the batch-probe readings (VERDICT
    next-#1's banking rule): a probe >2x below the median of its
    siblings is a disturbed reading, not a slow batch size.  The
    wedged entries are REMOVED from ``numeric`` (they can neither win
    nor veto), and the verdict dict lands in the artifact so a
    degraded window is visible in the record, never silently banked."""
    if len(numeric) < 2:
        return None
    from hetu_tpu.telemetry import health
    verdict = health.check_sibling_consistency(numeric)
    for b in list(verdict["wedged"]):
        numeric.pop(int(b), None)
    return verdict


def _record_retry_probe(probes, numeric, b, first, retry):
    """Outlier re-probe bookkeeping: keep the better of the two
    readings under ``probes[b]`` and record THE DISCARDED ONE in the
    artifact — ``<b>_first_reading`` when the retry won,
    ``<b>_retry_reading`` when the original stood (ADVICE: the old code
    wrote the kept value twice, making the retry unverifiable)."""
    if not isinstance(retry, (int, float)):
        return          # skipped/failed retry records nothing
    retry = float(retry)
    if retry > first:
        probes[b] = numeric[b] = retry
        probes[f"{b}_first_reading"] = first
    else:
        probes[f"{b}_retry_reading"] = retry


def bench_bert_base(platform, reduced):
    """BERT-base TRUE: 12 layers, seq 512 (BASELINE config 2 for real).

    Auto-tunes the per-chip batch over {32, 48, 64} with a short
    in-process probe each; a failed probe (an OOM, say) is skipped and
    recorded.  Override with HETU_BENCH_BERT_BATCH to pin a batch."""
    fixed = envvars.get_int("HETU_BENCH_BERT_BATCH")
    if fixed is not None or reduced:
        return _bench_lm(platform, reduced, layers_n=12, seq=512,
                         per_chip_batch=int(fixed or 32), iters=10)
    def probe(b):
        return _probe(lambda: _bench_lm(
            platform, False, layers_n=12, seq=512, per_chip_batch=b,
            iters=3)["value"])

    probes = {}
    for b in (32, 48, 64):
        got = probe(b)
        probes[b] = float(got) if isinstance(got, (int, float)) else got
    numeric = {b: v for b, v in probes.items()
               if isinstance(v, (int, float))}
    # re-probe implausible outliers once: a hiccup inside a 3-iter
    # probe yields a reading several-fold low, which would silently
    # veto that batch.
    if len(numeric) >= 2:
        top = max(numeric.values())
        for b, v in sorted(numeric.items()):
            if v < 0.5 * top:
                got = probe(b)
                # the presence of a <b>_first_reading / <b>_retry_reading
                # key means "a second probe ran" (its value is whichever
                # reading was discarded); a skipped retry records nothing
                _record_retry_probe(probes, numeric, b, v, got)
    if platform == "tpu" and not numeric:
        raise RuntimeError(f"all batch probes failed: {probes}")
    # health gate: a probe still >2x off its siblings AFTER the retry
    # is a degraded window — exclude it from winner selection and say
    # so in the artifact
    health = _probe_health(numeric)
    best = max(numeric, key=numeric.get) if numeric else 32
    out = _bench_lm(platform, reduced, layers_n=12, seq=512,
                    per_chip_batch=best, iters=10)
    out["batch_probe_samples_per_sec"] = probes
    if health is not None:
        out["probe_health"] = health
        if not health["ok"]:
            out["health_warning"] = (
                "degraded measurement window: probe(s) "
                f"{sorted(health['wedged'])} wedged (>2x off siblings) "
                "even after re-probe; row measured from the surviving "
                "batches — treat with suspicion")
    return out


def bench_bert4l(platform, reduced):
    """Round-1/2 proxy (4L, seq 128) for round-over-round continuity."""
    return _bench_lm(platform, reduced, layers_n=4, seq=128,
                     per_chip_batch=64, iters=20)


def bench_gpt_small(platform, reduced):
    """GPT-2-small-shaped decoder-only LM at seq 1024 — the model-zoo
    axis the reference lacks, and the config where flash attention is
    past its measured crossover (>= 1024).  Trains through
    models.GPTForCausalLM (fused QKV, flash causal attention, fused
    chunked tied head + masked mean)."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models import GPTConfig, GPTForCausalLM

    B, S, H, L, V, iters = 8, 1024, 768, 12, 50257, 10
    if reduced:
        B, S, H, L, V, iters = 2, 128, 64, 2, 500, 2
    clip = 1.0

    def build(use_flash):
        cfg = GPTConfig(vocab_size=V, hidden_size=H,
                        num_hidden_layers=L,
                        num_attention_heads=max(2, H // 64),
                        max_position_embeddings=S, batch_size=B,
                        seq_len=S, dropout_rate=0.0, use_flash=use_flash)
        m = GPTForCausalLM(cfg)
        ids = ht.placeholder_op("gb_ids")
        labels = ht.placeholder_op("gb_labels")
        loss, _ = m(ids, labels=labels)
        opt = ht.optim.AdamWOptimizer(learning_rate=3e-4,
                                      weight_decay=0.01)
        opt.clip_grad_norm = clip
        train = opt.minimize(loss)
        ex = ht.Executor({"train": [loss, train]},
                         mixed_precision="bf16")
        return ids, labels, ex

    rng = np.random.RandomState(0)
    pool_np = [(rng.randint(0, V, (B, S)).astype(np.int32),
                rng.randint(0, V, (B, S)).astype(np.int32))
               for _ in range(4)]

    def measure(use_flash):
        ids, labels, ex = build(use_flash)
        # device-resident feed ring, consistent with the other
        # device-capability configs
        pool = [(jax.device_put(a), jax.device_put(b))
                for a, b in pool_np]
        it = {"i": 0}

        def step():
            a, b = pool[it["i"] % len(pool)]
            it["i"] += 1
            return ex.run("train", feed_dict={ids: a, labels: b})
        return _time_steps(step, iters,
                           lambda out: float(np.asarray(out[0])))

    # flash stays ON at reduced scale so verification runs exercise the
    # causal kernel path (same policy as _bench_lm); full scale follows
    # the measured crossover (flash at seq >= 1024); a kernel that
    # fails raises
    use_flash = True if reduced else S >= 1024
    dt, host_frac = measure(use_flash)
    # honest matmul accounting: 12H^2 per block + tied H*V head; causal
    # attention matmuls add 12*B*S^2*H/2 per layer
    matmul_params = 12.0 * H * H * L + H * V
    flops = 6.0 * matmul_params * (B * S) + L * 12.0 * B * S * S * H / 2
    kind, tflops_chip, mfu = _mfu(flops, dt, 1, platform)
    out = {
        "value": round(B * S / dt, 1),
        "unit": "tokens/sec/chip",
        "step_time_ms": round(dt * 1e3, 3),
        "tflops_per_sec_chip": tflops_chip,
        "mfu": mfu,
        "host_fraction": round(host_frac, 4),
        "device_kind": kind,
        "n_chips": 1,
        "flash_attention": use_flash,
        "reduced_scale": reduced,
        "config": {"per_chip_batch": B, "seq": S, "hidden": H,
                   "layers": L, "vocab": V, "clip_grad_norm": clip},
    }
    return out


# --------------------------------------------------------------------- #
# config: ResNet-18 / CIFAR-10
# --------------------------------------------------------------------- #

def bench_resnet18(platform, reduced):
    """ResNet-18 / CIFAR-10 (BASELINE config 1).

    Reports TWO input paths: the Dataloader path (whatever the host link
    delivers for a 3 MB/step feed) and a device-resident path (inputs
    pre-staged on the chip) that measures what the CHIP does.  The
    headline value is the device-resident one, labeled as such."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models.cnn import resnet18

    n_chips = max(1, jax.device_count())
    per_chip_batch, iters = 256, 20
    if reduced:
        per_chip_batch, iters = 8, 2
    batch = per_chip_batch * n_chips
    rng = np.random.RandomState(0)
    n_batches = iters + 2
    xs = rng.randn(batch * n_batches, 3, 32, 32).astype(np.float32)
    ys = np.eye(10, dtype=np.float32)[
        rng.randint(0, 10, batch * n_batches)]
    from hetu_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"dp": n_chips}) if n_chips > 1 else None

    # path 1: Dataloader + prefetch ring (host link on the feed path)
    x = ht.dataloader_op([ht.Dataloader(xs, batch, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(ys, batch, "train")])
    loss, pred = resnet18(x, y_)
    train = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
    ex = ht.Executor({"train": [loss, train]}, mixed_precision="bf16",
                     mesh=mesh)
    dt_loader, host_frac = _time_steps(lambda: ex.run("train"), iters,
                                       lambda out: float(np.asarray(out[0])))
    del ex

    # path 2: inputs pre-staged on device (gather_feeds passes
    # jax.Arrays through untouched), cycled through placeholder feeds
    xp = ht.placeholder_op("rn_x")
    yp = ht.placeholder_op("rn_y")
    loss2, _ = resnet18(xp, yp)
    train2 = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss2)
    ex2 = ht.Executor({"train": [loss2, train2]}, mixed_precision="bf16",
                      mesh=mesh)
    dev_batches = [(jax.device_put(xs[i * batch:(i + 1) * batch]),
                    jax.device_put(ys[i * batch:(i + 1) * batch]))
                   for i in range(n_batches)]
    it = {"i": 0}

    def step_dev():
        xb, yb = dev_batches[it["i"] % n_batches]
        it["i"] += 1
        return ex2.run("train", feed_dict={xp: xb, yp: yb})
    dt_dev, _ = _time_steps(step_dev, iters,
                            lambda out: float(np.asarray(out[0])))
    return {
        "value": round(batch / dt_dev / n_chips, 2),
        # the unit names the path: a bare "samples/sec/chip" would
        # not say that the input is already on the chip (the fed-path
        # number is loader_value)
        "unit": "samples/sec/chip (device-resident input)",
        "input_path": "device-resident (chip capability; see loader_*)",
        "step_time_ms": round(dt_dev * 1e3, 3),
        "loader_value": round(batch / dt_loader / n_chips, 2),
        "loader_step_time_ms": round(dt_loader * 1e3, 3),
        "loader_host_fraction": round(host_frac, 4),
        "feed_bytes_per_step": int(batch * (3 * 32 * 32 + 10) * 4),
        "device_kind": jax.devices()[0].device_kind,
        "n_chips": n_chips,
        "reduced_scale": reduced,
        "config": {"per_chip_batch": per_chip_batch, "dataset": "cifar10",
                   "depth": 18},
    }


# --------------------------------------------------------------------- #
# config: Wide&Deep CTR through the PS + HET-cache hybrid path
# --------------------------------------------------------------------- #

def _ctr_hybrid_once(platform, reduced, *, batch=1024, iters=20,
                     feature_dim=1_000_000, subgraph="train",
                     tier="cache"):
    """One measured hybrid CTR config; shared by the matrix entry and
    the rows-per-chip ladder.

    ``tier`` selects the host path: "cache" = HET cache + python sync
    protocol (the staleness-bounded tier); "van" = no cache, phases A/B
    ride the native C++ van through PSClient's fast-tier route (the
    zmq_van role — r5 wiring)."""
    import hetu_tpu as ht
    from hetu_tpu.models import ctr as ctr_models

    if reduced:
        batch, iters, feature_dim = 128, 3, min(feature_dim, 10_000)
    cache_bound = max(feature_dim // 10, 1024)
    rng = np.random.RandomState(0)
    n_pool = iters + 2
    # zipf-skewed ids: the regime the HET cache exists for
    raw = rng.zipf(1.05, size=(n_pool * batch, 26))
    sparse = ((raw - 1) % feature_dim).astype(np.int32)
    dense = rng.randn(n_pool * batch, 13).astype(np.float32)
    label = np.eye(2, dtype=np.float32)[
        rng.randint(0, 2, n_pool * batch)]
    d = ht.dataloader_op([ht.Dataloader(dense, batch, subgraph)])
    s = ht.dataloader_op([ht.Dataloader(sparse, batch, subgraph)])
    y_ = ht.dataloader_op([ht.Dataloader(label, batch, subgraph)])
    loss, pred, _lab, train = ctr_models.wdl_criteo(
        d, s, y_, feature_dimension=feature_dim, embedding_size=16)
    # bf16 wire: phase A casts the gathered rows host-side and the step
    # emits bf16 grads, halving BOTH directions of the host link — the
    # link IS the hybrid path's bottleneck (the PS accumulates fp32
    # regardless).  HETU_BENCH_CTR_FP32=1 pins the old full-width wire.
    mp = None if envvars.get_bool("HETU_BENCH_CTR_FP32") else "bf16"
    from hetu_tpu.ps.server import PSServer
    import hetu_tpu.ps.client as psc
    PSServer._instance = None      # each tier gets a fresh server so
    psc.PSClient._instance = None  # neither inherits the other's state
    if not envvars.is_set("HETU_PS_ADDR"):
        # BOTH tiers get the C++ van (the cache tier's sync_embedding/
        # push_embedding verbs are van ops too — r5); enable BEFORE the
        # init window so a cold g++ build of the .so is not charged to
        # table_init_s.  With HETU_PS_ADDR the executor talks to a
        # REMOTE server a local van can't serve — the row then honestly
        # records van_served=False.
        PSServer.get().enable_van_autoserve()
    t_init = time.monotonic()
    if tier == "van":
        ex = ht.Executor({subgraph: [loss, train]}, comm_mode="Hybrid",
                         mixed_precision=mp)
    else:
        ex = ht.Executor({subgraph: [loss, train]}, comm_mode="Hybrid",
                         cstable_policy="lfu", cache_bound=cache_bound,
                         mixed_precision=mp)
    init_s = time.monotonic() - t_init
    dt, host_frac = _time_steps(
        lambda: ex.run(subgraph), iters,
        lambda out: float(np.asarray(out[0]).reshape(-1)[0]))
    hit_rate = None
    if ex.cstables:
        perf = ex.ps_perf_summary()
        hit_rate = round(float(np.mean(
            [p["hit_rate"] for p in perf.values()])), 4)
    srv = PSServer._instance
    van_served = bool(srv is not None
                      and getattr(srv, "_van_keys", {}))
    # real teardown, not just singleton clearing: finalize() closes the
    # client pool + van sockets, shutdown() stops the C++ serve thread
    # and restores the python locks — later bench configs must not
    # inherit live threads or a bound van port
    cli = psc.PSClient._instance
    if cli is not None:
        cli.finalize()
    srv = PSServer._instance
    if srv is not None:
        srv.shutdown()
    PSServer._instance = None
    psc.PSClient._instance = None
    return {
        "value": round(batch / dt, 2),
        "unit": "samples/sec",
        "embedding_rows_per_sec": round(batch * 26 / dt, 1),
        "step_time_ms": round(dt * 1e3, 3),
        "host_fraction": round(host_frac, 4),
        "cache_hit_rate": hit_rate,
        "table_init_s": round(init_s, 2),
        "reduced_scale": reduced,
        "config": {"batch": batch, "feature_dim": feature_dim,
                   "fields": 26, "embedding_size": 16,
                   "tier": tier, "van_served": van_served,
                   "cache_bound": cache_bound if tier == "cache"
                   else None,
                   "policy": "lfu" if tier == "cache" else None,
                   "wire_dtype": mp or "fp32"},
    }


def bench_ctr_hybrid(platform, reduced):
    """Measure BOTH host tiers and headline the faster one: the HET
    cache path and the native-van direct path (r5 — the VERDICT r4
    criterion is host_fraction, and the C++ tier is the fix)."""
    r_cache = _ctr_hybrid_once(platform, reduced)
    r_van = _ctr_hybrid_once(platform, reduced, subgraph="train_van",
                             tier="van")
    best = r_van if r_van["value"] >= r_cache["value"] else r_cache
    out = dict(best)
    out["tiers"] = {
        t: {k: r[k] for k in ("value", "step_time_ms", "host_fraction",
                              "cache_hit_rate")}
        for t, r in (("cache", r_cache), ("van", r_van))}
    for t, r in (("cache", r_cache), ("van", r_van)):
        out["tiers"][t]["van_served"] = r["config"]["van_served"]
    return out


_CTR_ROWS_FILE = os.path.join(_HERE, "BENCH_CTR_ROWS.json")

def _persist_artifact(path, art, reduced, has_data):
    """Shared artifact-persistence policy (hetu_tpu/artifact.py): a
    reduced/CPU run never overwrites a full-scale TPU record, and an
    all-error run never overwrites a record that has data."""
    from hetu_tpu.artifact import persist_artifact
    return persist_artifact(path, art, reduced, has_data=has_data)


def sweep_ctr_rows(platform, reduced):
    """BASELINE's third headline metric: max embedding rows trainable
    per chip.  Climb a table-size ladder (a rung that fails, an OOM
    say, ends the climb and is recorded); max_rows = the
    largest table that completes training steps.  Writes
    BENCH_CTR_ROWS.json with the full rows/s curve."""
    ladder = (1_000_000, 4_000_000, 16_000_000, 64_000_000, 256_000_000)
    if reduced:
        ladder = (10_000, 40_000)
    rungs = []
    for rows in ladder:
        # reduced=False bypasses _ctr_hybrid_once's shape clamp (the
        # ladder IS the variable); a reduced rung is tagged honestly
        kw = dict(iters=3, batch=128) if reduced else dict(iters=8)
        got = _probe(lambda: _ctr_hybrid_once(
            platform, False, feature_dim=rows,
            subgraph=f"rows{rows}", **kw))
        if not isinstance(got, dict):
            rungs.append({"rows": rows, "error": got})
            break
        if reduced:
            got["reduced_scale"] = True
        rungs.append({"rows": rows, **got})
    ok = [r for r in rungs if "error" not in r]
    art = {
        "platform": platform,
        "reduced_scale": reduced,
        "measured_at": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "metric": "max embedding rows trainable per chip "
                  "(host PS + HET cache, dim 16, fp32 server rows)",
        "max_rows": max((r["rows"] for r in ok), default=0),
        "rungs": rungs,
    }
    _persist_artifact(_CTR_ROWS_FILE, art, reduced, has_data=bool(ok))
    return art


# --------------------------------------------------------------------- #
# config: MoE (top-2 gate)
# --------------------------------------------------------------------- #

def bench_moe(platform, reduced):
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models import moe_mlp

    batch, tokens, model_dim, hidden, experts, iters = 8, 1024, 768, \
        3072, 8, 15
    top_k = 2
    if reduced:
        batch, tokens, model_dim, hidden, experts, iters = 2, 64, 64, \
            128, 4, 2
    # chip-fill tuning knobs for the on-chip re-measure (VERDICT r3
    # item 4: the recorded config underfilled the chip)
    if envvars.is_set("HETU_BENCH_MOE_BATCH"):
        batch = envvars.get_int("HETU_BENCH_MOE_BATCH")
    if envvars.is_set("HETU_BENCH_MOE_TOKENS"):
        tokens = envvars.get_int("HETU_BENCH_MOE_TOKENS")
    rng = np.random.RandomState(0)
    # device-resident feeds: a 25MB host feed per step would measure the
    # host link's H2D, not the MoE step (jax.Arrays pass through the feed
    # path untouched)
    xb = jax.device_put(rng.randn(batch, tokens, model_dim)
                        .astype(np.float32))
    yb = jax.device_put(rng.randint(0, model_dim, (batch * tokens,))
                        .astype(np.int32))

    def run_variant(expert_parallel):
        x = ht.placeholder_op("x")
        y_ = ht.placeholder_op("y_")
        loss, _y = moe_mlp(x, y_, batch, tokens, model_dim, hidden,
                           num_local_experts=experts, gate_type="top",
                           top_k=top_k, sparse_labels=True,
                           expert_parallel=expert_parallel)
        train = ht.optim.AdamOptimizer(
            learning_rate=1e-4).minimize(loss)
        ex = ht.Executor({"train": [loss, train]},
                         mixed_precision="bf16")
        return _time_steps(
            lambda: ex.run("train", feed_dict={x: xb, y_: yb}), iters,
            lambda out: float(np.asarray(out[0])))

    # A/B matrix: expert formulation (per-local-expert loop vs stacked
    # batched einsum) x dispatch formulation (GShard one-hot matmul vs
    # row scatter-add) — the right choice is hardware-generation
    # dependent, so measure rather than assume
    variants = {}
    saved_env = envvars.get_raw("HETU_MOE_SCATTER_DISPATCH")
    try:
        for name, ep in (("expert_loop", False), ("stacked", True)):
            for dname, denv in (("matmul_dispatch", None),
                                ("scatter_dispatch", "1")):
                key = f"{name}/{dname}"
                if denv is None:
                    os.environ.pop("HETU_MOE_SCATTER_DISPATCH", None)
                else:
                    os.environ["HETU_MOE_SCATTER_DISPATCH"] = denv
                try:
                    dt_v, hf_v = run_variant(ep)
                    variants[key] = {"step_ms": round(dt_v * 1e3, 3),
                                     "host_fraction": round(hf_v, 4)}
                except Exception as e:
                    variants[key] = {
                        "error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        if saved_env is None:
            os.environ.pop("HETU_MOE_SCATTER_DISPATCH", None)
        else:
            os.environ["HETU_MOE_SCATTER_DISPATCH"] = saved_env
    ok = {k: v for k, v in variants.items() if "step_ms" in v}
    best = min(ok, key=lambda k: ok[k]["step_ms"])
    dt = ok[best]["step_ms"] / 1e3
    # useful-work MFU: expert-FFN matmul flops for ROUTED tokens only
    # (capacity padding does extra real matmul work, so this is a
    # conservative utilization figure), fwd + bwd = 3x, 2 matmuls of
    # d x h each way per routed token
    useful_flops = 3.0 * 2 * (batch * tokens) * 4 * model_dim * hidden
    kind, tflops_chip, mfu = _mfu(useful_flops, dt, 1, platform)
    # A2A accounting (BASELINE config 4 asks for the A2A time fraction).
    # On ONE chip ep=1 and no all-to-all runs, so the single-chip row
    # reports the MODEL-LEVEL a2a volume and an estimated fraction for
    # an ep=experts deployment (one expert per device): the [E, cap, D]
    # dispatch buffer crosses the exchange on dispatch + combine, each
    # again in backward (4x), moving (ep-1)/ep of its bytes over ICI.
    # same static-capacity formula the gate uses (layers/moe.py:44
    # topkgating: k * ceil(num_tokens/num_experts * capacity_factor)),
    # at the bench's default capacity_factor = 1.0
    import math as _math
    cap = top_k * _math.ceil(batch * tokens / experts * 1.0)
    a2a_buffer_bytes = experts * cap * model_dim * 2      # bf16
    ep_deploy = experts
    a2a_bytes = 4.0 * a2a_buffer_bytes * (ep_deploy - 1) / ep_deploy
    from hetu_tpu.planner.cost_model import ClusterSpec
    ici = ClusterSpec().ici_bandwidth
    a2a_est_s = a2a_bytes / ici
    return {
        "value": round(batch * tokens / dt, 1),
        "unit": "tokens/sec/chip",
        "step_time_ms": ok[best]["step_ms"],
        "host_fraction": ok[best]["host_fraction"],
        "expert_tflops_per_sec_chip": tflops_chip,
        "mfu": mfu,
        "best_variant": best,
        "variants": variants,
        "a2a_bytes_per_step": int(a2a_bytes),
        "a2a_fraction_est": round(a2a_est_s / (a2a_est_s + dt), 4),
        "a2a_note": (f"single-chip run has ep=1 (no live all-to-all); "
                     f"estimate assumes ep={ep_deploy} over spec ICI "
                     f"{ici/1e9:.0f} GB/s (spec-assumed, unmeasurable "
                     f"on one chip) against the measured compute step"),
        "reduced_scale": reduced,
        "config": {"batch": batch, "tokens": tokens,
                   "model_dim": model_dim, "hidden": hidden,
                   "experts": experts, "top_k": top_k},
    }


# --------------------------------------------------------------------- #
# config: 32k-token long context (causal flash attention)
# --------------------------------------------------------------------- #

def bench_long_context(platform, reduced):
    import jax
    import jax.numpy as jnp
    from hetu_tpu.kernels.flash_attention import flash_attention

    B, S, H, D, layers_n, iters = 1, 32768, 8, 64, 2, 5
    if reduced:
        B, S, H, D, layers_n, iters = 1, 2048, 2, 32, 1, 2
    hidden = H * D
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, hidden), jnp.bfloat16)
    ws = [jax.random.normal(jax.random.fold_in(key, i),
                            (hidden, 3 * hidden), jnp.bfloat16) * 0.02
          for i in range(layers_n)]

    # block-size override for on-chip tuning sweeps: the 512x1024
    # default was tuned at seq 4-8k; S/cp-sized and 32k chunks may want
    # different tiles (VERDICT r3 item 2)
    blocks = envvars.get_str("HETU_BENCH_LC_BLOCKS")
    bq, bk = (int(t) for t in blocks.split(",")) if blocks else (512, 1024)
    # record what will actually RUN: the kernel shrinks non-divisor
    # tiles to the largest divisor, and a sweep must not label two
    # identical runs as different tiles
    from hetu_tpu.kernels.flash_attention import _fit_block
    bq, bk = _fit_block(bq, S), _fit_block(bk, S)

    def loss_fn(ws, x):
        h = x
        for w in ws:
            qkv = (h @ w).reshape(B, S, 3, H, D)
            o = flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                                causal=True, block_q=bq, block_k=bk)
            h = h + o.reshape(B, S, hidden)
        return (h.astype(jnp.float32) ** 2).mean()

    step = jax.jit(jax.grad(loss_fn))

    def run():
        return step(ws, x)

    dt, _ = _time_steps(run, iters,
                        lambda out: np.asarray(out[0][:1, :1]))
    # causal attention FLOPs: 2 matmuls * 2BS^2HD/2 (causal half) fwd,
    # x3 with backward; + qkv projection 6*B*S*hidden*3*hidden
    flops = layers_n * (3 * 2 * 2 * B * S * S * H * D / 2
                        + 6 * B * S * hidden * 3 * hidden)
    kind, tflops_chip, mfu = _mfu(flops, dt, 1, platform)
    return {
        "value": round(B * S / dt, 1),
        "unit": "tokens/sec/chip",
        "step_time_ms": round(dt * 1e3, 3),
        "attn_tflops_per_sec_chip": tflops_chip,
        "mfu": mfu,
        "reduced_scale": reduced,
        "config": {"batch": B, "seq": S, "heads": H, "head_dim": D,
                   "layers": layers_n, "kernel": "pallas_flash_causal",
                   "block_q": bq, "block_k": bk},
    }


# --------------------------------------------------------------------- #

_CONFIGS = {
    "bert_base": bench_bert_base,
    "bert4l": bench_bert4l,
    "gpt_small_1k": bench_gpt_small,
    "resnet18": bench_resnet18,
    "ctr_hybrid": bench_ctr_hybrid,
    "moe": bench_moe,
    "long_context": bench_long_context,
}


_DECODE_FILE = os.path.join(_HERE, "BENCH_DECODE.json")


def bench_decode(platform, reduced):
    """KV-cached serving throughput (models/gpt_decode.py): GPT-2-small
    shape, one compiled scan, batched prompts; tokens/s = generated
    tokens per wall second after the compile is warm."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models import GPTConfig, GPTForCausalLM
    from hetu_tpu.models.gpt_decode import generate_fast

    # gen = S_max - prompt: the scan always runs S_max-1 positions, so
    # counting fewer generated tokens than the paid compute would
    # understate tokens/s by the unused tail
    S_max, hidden, layers_n, heads, vocab, batch, gen = \
        1024, 768, 12, 12, 50257, 8, 1008
    if reduced:
        S_max, hidden, layers_n, heads, vocab, batch, gen = \
            64, 64, 2, 2, 256, 2, 48
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_hidden_layers=layers_n,
                    num_attention_heads=heads,
                    max_position_embeddings=S_max, batch_size=batch,
                    seq_len=S_max, dropout_rate=0.0)
    model = GPTForCausalLM(cfg, name="dec")
    ids = ht.placeholder_op("dec_ids")
    logits = model(ids)
    ex = ht.Executor({"gen": [logits]})     # materializes init params
    del logits
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, vocab, (batch, 16)).astype(np.int32)

    from hetu_tpu.models.gpt_decode import _prep_param
    import jax.numpy as jnp

    def run(dtype):
        # params are cast/placed ONCE outside the timed window (the
        # bf16 variant must not pay the ~500MB f32->bf16 cast inside
        # its measurement; per-call prep is then a no-op)
        dt_ = jnp.float32 if dtype is None else dtype
        prepped = {k: _prep_param(v, dt_)
                   for k, v in ex.var_values.items()}
        generate_fast(prepped, cfg, prompts, num_tokens=4,
                      dtype=dt_)                         # compile
        t0 = time.perf_counter()
        out = generate_fast(prepped, cfg, prompts,
                            num_tokens=gen, dtype=dt_)
        dt = time.perf_counter() - t0
        assert out.shape == (batch, 16 + gen)
        return round(batch * gen / dt, 1), round(dt, 3)

    tps_f32, dt_f32 = run(None)
    # bf16 variant: half the weights AND the KV cache, MXU fast path
    # (the serving configuration of record on TPU)
    tps_bf16, dt_bf16 = run(jnp.bfloat16)
    best = max(tps_f32, tps_bf16)
    art = {
        "platform": platform,
        "reduced_scale": reduced,
        "measured_at": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "tokens_per_sec": best,
        "variants": {
            "f32": {"tokens_per_sec": tps_f32, "seconds": dt_f32},
            "bf16": {"tokens_per_sec": tps_bf16, "seconds": dt_bf16},
        },
        "config": {"batch": batch, "s_max": S_max, "hidden": hidden,
                   "layers": layers_n, "heads": heads, "vocab": vocab,
                   "generated": gen, "kernel": "kv_cached_scan",
                   "headline": "best of f32/bf16"},
    }
    _persist_artifact(_DECODE_FILE, art, reduced, has_data=True)
    return art


_EMBED_SERVE_FILE = os.path.join(_HERE, "BENCH_EMBED_SERVE.json")


def bench_embed_serve(platform, reduced):
    """Embedding-cache recommendation serving (ISSUE 14 tentpole,
    hetu_tpu/serving/embed_engine): replay ONE seeded zipf(1.05) CTR
    scoring trace through the cache-fronted engine at a ladder of
    cache-limit points (p99 latency + QPS + hit rate per point), A/B
    the int8 PS pull wire against exact f32 on ACTUAL transport reply
    payload bytes (``cache.pull_bytes`` counts decoded f32 rows by
    design, so the wire win is metered at the transport seam — the
    byte floor is asserted here, not just recorded), and kill the PS
    for the middle third of a final run to prove the stale/zero
    degradation protocol retires every request anyway."""
    from hetu_tpu.cache.cstable import CacheSparseTable
    from hetu_tpu.ps.client import PSClient, PSConnectionError
    from hetu_tpu.ps.server import PSServer
    from hetu_tpu.ps.sharded import _LocalServerTransport
    from hetu_tpu.quant import QuantArray
    from hetu_tpu.serving import EmbedRequest, EmbedServingEngine

    vocab, e_dim, n_req, pairs, wave = 8192, 16, 256, 4, 8
    if reduced:
        vocab, e_dim, n_req, pairs, wave = 1024, 16, 96, 4, 8

    class _MeteredTransport:
        """_LocalServerTransport + wire accounting + a kill switch.
        Sums the ACTUAL pull-reply row payload (QuantArray int8+scales
        vs f32 rows) — the in-process path never crosses
        ``_TCPTransport``, so the ``ps.rpc.bytes_*`` counters don't
        tick and the A/B must meter here."""

        def __init__(self, server):
            self._inner = _LocalServerTransport(server)
            self.pull_payload_bytes = 0
            self.down = False

        @staticmethod
        def _nb(rows):
            if isinstance(rows, QuantArray):
                return rows.nbytes
            if isinstance(rows, np.ndarray):
                return rows.nbytes
            return 0

        def call(self, method, *a, **kw):
            if self.down:
                raise PSConnectionError("PS down (bench outage)")
            out = self._inner.call(method, *a, **kw)
            if method in ("sync_embedding", "push_sync_embedding"):
                self.pull_payload_bytes += self._nb(out[1])
            elif method == "sparse_pull":
                self.pull_payload_bytes += self._nb(out)
            return out

        def close(self):
            self._inner.close()

    rng = np.random.RandomState(777)
    h = 16
    flat = 26 * e_dim
    params = {"W1": rng.randn(13, h) * 0.3,
              "W2": rng.randn(h, h) * 0.3,
              "W3": rng.randn(h, h) * 0.3,
              "W4": rng.randn(flat + h, 1) * 0.3}
    trace = []
    for _ in range(n_req):
        raw = rng.zipf(1.05, size=(pairs, 26))
        trace.append(((raw - 1) % vocab,
                      rng.randn(pairs, 13).astype(np.float32)))

    def mk_reqs():
        # pinned ids: the A/B compares per-request scores across runs
        return [EmbedRequest(item_ids=ids, dense_features=d,
                             request_id=f"r{i:04d}")
                for i, (ids, d) in enumerate(trace)]

    def mk_engine(limit):
        server = PSServer()
        server.param_init("snd_order_embedding", (vocab, e_dim),
                          "normal", 0.0, 1.0, seed=3)
        meter = _MeteredTransport(server)
        comm = PSClient(transport=meter)
        table = CacheSparseTable(limit=limit, vocab_size=vocab,
                                 width=e_dim,
                                 key="snd_order_embedding", comm=comm,
                                 policy="LRU")
        eng = EmbedServingEngine(params,
                                 {"snd_order_embedding": table},
                                 model="wdl", wave=wave,
                                 queue_limit=n_req)
        return eng, table, meter, comm

    # ---- warm every row-bucket compile outside the measured windows
    # (wave composition is deterministic given the trace, so one full
    # warm pass covers every bucket the ladder runs will hit) ---- #
    warm, _, _, warm_comm = mk_engine(vocab)
    warm.run(mk_reqs())
    warm_comm.finalize()

    def run_point(limit):
        eng, table, meter, comm = mk_engine(limit)
        t0 = time.perf_counter()
        res = eng.run(mk_reqs())
        wall = time.perf_counter() - t0
        assert len(res) == n_req and all(
            r.finish_reason == "scored" for r in res.values()), \
            "embed serve ladder lost requests"
        snap = eng.metrics.snapshot()
        cs = table.perf_summary()
        comm.finalize()
        scores = np.concatenate(
            [res[k].scores for k in sorted(res)])
        return {
            "cache_limit": limit,
            "hit_rate": round(cs["hit_rate"], 4),
            "qps": snap["qps"],
            "pairs_per_sec": snap["pairs_per_sec"],
            "latency_p50_ms": round((snap["latency_p50_s"] or 0) * 1e3,
                                    3),
            "latency_p99_ms": round((snap["latency_p99_s"] or 0) * 1e3,
                                    3),
            "gather_ms_p50": snap["gather_ms_p50"],
            "wave_ms_p50": snap["wave_ms_p50"],
            "pulled_rows": cs["pulled_rows"],
            "pull_bytes_decoded": cs["pull_bytes"],
            "wire_pull_payload_bytes": meter.pull_payload_bytes,
            "wall_s": round(wall, 3),
        }, scores

    # ---- cache-limit ladder: the zipf head fits at every point; how
    # much of the tail fits is what the limit buys ---- #
    ladder = []
    for limit in (vocab // 32, vocab // 8, vocab // 2, vocab):
        row, _ = run_point(limit)
        ladder.append(row)

    # ---- int8 pull wire A/B at full cache (every pull is the cold
    # refill, the byte-bound phase int8 exists for).  Floor asserted:
    # quantized pulls must halve the wire, and scores must agree to
    # the chunked-int8 tolerance ---- #
    saved_q = os.environ.pop("HETU_PS_QUANT", None)
    try:
        exact_row, exact_scores = run_point(vocab)
        os.environ["HETU_PS_QUANT"] = "int8"
        int8_row, int8_scores = run_point(vocab)
    finally:
        os.environ.pop("HETU_PS_QUANT", None)
        if saved_q is not None:
            os.environ["HETU_PS_QUANT"] = saved_q
    byte_ratio = (exact_row["wire_pull_payload_bytes"]
                  / max(int8_row["wire_pull_payload_bytes"], 1))
    score_max_err = float(np.max(np.abs(exact_scores - int8_scores)))
    assert byte_ratio >= 2.0, \
        f"int8 pull wire saved only {byte_ratio:.2f}x (floor 2.0x)"
    assert score_max_err < 0.05, \
        f"int8 pull scores diverged: max |d| {score_max_err}"
    quant_ab = {
        "exact": exact_row,
        "int8": int8_row,
        "wire_byte_ratio": round(byte_ratio, 3),
        "score_max_abs_err": round(score_max_err, 6),
        "floor": "wire_byte_ratio >= 2.0 (asserted in-bench; small "
                 "tail pulls stay f32 below quant.WIRE_MIN_SIZE)",
    }

    # ---- PS-kill chaos: same trace, PS dark for the middle third;
    # stale rows for warm ids, zeros for cold ones, ZERO loss ---- #
    eng, table, meter, comm = mk_engine(vocab // 8)
    reqs = mk_reqs()
    third = n_req // 3
    res = dict(eng.run(reqs[:third]))
    meter.down = True
    res.update(eng.run(reqs[third:2 * third]))
    meter.down = False
    res.update(eng.run(reqs[2 * third:]))
    comm.finalize()
    assert len(res) == n_req and all(
        r.finish_reason == "scored" for r in res.values()), \
        "PS outage lost requests"
    cs = table.perf_summary()
    assert cs["ps_failures"] > 0, "the bench outage never fired"
    chaos = {
        "requests": n_req,
        "scored": sum(1 for r in res.values()
                      if r.finish_reason == "scored"),
        "zero_request_loss": True,
        "ps_failures": cs["ps_failures"],
        "stale_served_rows": cs["stale_served_rows"],
        "zero_served_rows": cs["zero_served_rows"],
        "replayed_rows": cs["replayed_rows"],
        "hit_rate": round(cs["hit_rate"], 4),
        "cache_limit": vocab // 8,
    }

    art = {
        "platform": platform,
        "reduced_scale": reduced,
        "measured_at": time.strftime("%Y-%m-%d %H:%M UTC",
                                     time.gmtime()),
        "workload": "embedding-cache CTR serving (wdl tower, zipf "
                    "sparse ids through CacheSparseTable -> one "
                    "jitted wave forward)",
        "cache_ladder": ladder,
        "quant_ab": quant_ab,
        "ps_kill_chaos": chaos,
        "trace": {"seed": 777, "zipf_a": 1.05, "n_requests": n_req,
                  "pairs_per_request": pairs, "sparse_fields": 26,
                  "dense_fields": 13, "wave": wave},
        "config": {"vocab": vocab, "embed_dim": e_dim, "model": "wdl",
                   "hidden": h, "policy": "LRU",
                   "comm": "PSClient over in-process transport "
                           "(wire bytes metered at the transport "
                           "seam)"},
    }
    _persist_artifact(_EMBED_SERVE_FILE, art, reduced, has_data=True)
    return art


_SWEEP_FILE = os.path.join(_HERE, "SWEEP_BERT_BASE.json")

def sweep_bert(platform, reduced, batches=(16, 32, 48, 64)):
    """On-chip ablation sweep over (per-chip batch x attention impl x
    LM-head variant) -> SWEEP_BERT_BASE.json, the measured strategy
    space the exec-config planner is validated against
    (planner/exec_plan.py; VERDICT r3 item 6).

    Every cell runs in this process; a cell that fails records its
    error.  Reduced mode measures the tiny-graph grid with the batch
    axis kept REAL (keep_batch) — the artifact then records a
    CPU-measured space, still a genuine measured ordering for the
    validation loop to close over."""
    import itertools as _it
    if reduced:
        batches = (2, 4, 8)
    grid = list(_it.product(batches, ("xla", "flash"),
                            ("materialized", "fused")))
    rows = []
    for b, attn, head in grid:
        cell = {"batch": b, "attention": attn, "head": head}
        old_flash = envvars.get_raw("HETU_BENCH_FORCE_FLASH")
        old_fused = envvars.get_raw("HETU_BENCH_FUSED_HEAD")
        os.environ["HETU_BENCH_FORCE_FLASH"] = \
            "1" if attn == "flash" else "0"
        if head == "fused":
            os.environ["HETU_BENCH_FUSED_HEAD"] = "1"
        else:
            os.environ.pop("HETU_BENCH_FUSED_HEAD", None)
        try:
            r = _bench_lm(platform, reduced, layers_n=12, seq=512,
                          per_chip_batch=b, iters=3 if reduced else 8,
                          keep_batch=True)
            cell["step_time_ms"] = r["step_time_ms"]
        except Exception as e:
            cell["error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            if old_flash is None:
                os.environ.pop("HETU_BENCH_FORCE_FLASH", None)
            else:
                os.environ["HETU_BENCH_FORCE_FLASH"] = old_flash
            if old_fused is None:
                os.environ.pop("HETU_BENCH_FUSED_HEAD", None)
            else:
                os.environ["HETU_BENCH_FUSED_HEAD"] = old_fused
        rows.append(cell)

    art = {
        "platform": platform,
        "reduced_scale": reduced,
        "measured_at": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "model": ("bert_base 12L seq 512" if not reduced
                  else "reduced LM 2L seq 64 (batch axis real)"),
        "objective": "samples/sec/chip (throughput = batch / step_time)",
        "configs": rows,
    }
    try:
        from hetu_tpu.planner.exec_plan import validate_against_sweep
        art["planner_validation"] = validate_against_sweep(art)
    except Exception as e:
        art["planner_validation"] = {
            "error": f"{type(e).__name__}: {e}"[:300]}
    _persist_artifact(_SWEEP_FILE, art, reduced,
                      has_data=any("step_time_ms" in r for r in rows))
    return art


def _provenance_fields(results, ran, head_name, run_platform,
                       prev_platform=None):
    """Live-vs-banked accounting for the ONE headline record (VERDICT
    weak #4): ``platform`` is the platform of the HEADLINE ROW actually
    measured, with this run's platform preserved separately as
    ``run_platform``, and every row is explicitly listed under
    ``rows_live`` or ``rows_banked`` (rows merged from the matrix file
    of an earlier run keep their own ``measured_at``)."""
    head = results.get(head_name, {})
    live = sorted(n for n in results if n in ran)
    banked = {n: {"measured_at": results[n].get("measured_at"),
                  "platform": results[n].get("platform")
                  or prev_platform or "unknown"}
              for n in sorted(results) if n not in ran}
    if head_name in ran:
        head_platform = head.get("platform") or run_platform
    else:
        head_platform = head.get("platform") or prev_platform or "unknown"
    return {
        "platform": head_platform,
        "run_platform": run_platform,
        "headline_provenance": "live" if head_name in ran else "banked",
        # quantization provenance: the headline row's quant modes (rows
        # predating the stamp read "off" — they were measured exact)
        "quant": head.get("quant", "off"),
        "rows_live": live,
        "rows_banked": banked,
    }


def main():
    platform = _require_backend()
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    reduced = envvars.get_bool("HETU_BENCH_SMALL") or platform == "cpu"

    if envvars.get_bool("HETU_BENCH_DECODE"):
        art = bench_decode(platform, reduced)
        print(json.dumps({
            "metric": "gpt_decode_tokens_per_sec",
            "value": art["tokens_per_sec"], "unit": "tokens/sec",
            "vs_baseline": None, "platform": platform,
            "batch": art["config"]["batch"],
            "s_max": art["config"]["s_max"],
            **({"not_written": art["not_written"]}
               if "not_written" in art else
               {"decode_file": os.path.basename(_DECODE_FILE)})}))
        return

    if envvars.get_bool("HETU_BENCH_EMBED_SERVE"):
        art = bench_embed_serve(platform, reduced)
        best = art["cache_ladder"][-1]
        print(json.dumps({
            "metric": "embed_serve_qps",
            "value": best["qps"], "unit": "requests/sec",
            # vs_baseline here = the int8 pull wire ratio on the same
            # trace (the ISSUE 14 byte-floor acceptance, asserted
            # in-bench)
            "vs_baseline": art["quant_ab"]["wire_byte_ratio"],
            "platform": platform,
            "hit_rate_ladder": [
                {"cache_limit": r["cache_limit"],
                 "hit_rate": r["hit_rate"],
                 "latency_p99_ms": r["latency_p99_ms"],
                 "qps": r["qps"]} for r in art["cache_ladder"]],
            "ps_kill_zero_loss":
                art["ps_kill_chaos"]["zero_request_loss"],
            **({"not_written": art["not_written"]}
               if "not_written" in art else
               {"embed_serve_file":
                    os.path.basename(_EMBED_SERVE_FILE)})}))
        return

    if envvars.get_bool("HETU_BENCH_CTR_ROWS"):
        art = sweep_ctr_rows(platform, reduced)
        best = max((r for r in art["rungs"] if "error" not in r),
                   key=lambda r: r["rows"], default=None)
        print(json.dumps({
            "metric": "ctr_max_embedding_rows_per_chip",
            "value": art["max_rows"], "unit": "rows",
            "vs_baseline": None, "platform": platform,
            "rows_per_sec_at_max": (best or {}).get(
                "embedding_rows_per_sec"),
            "rungs": [{"rows": r["rows"],
                       **({"error": r["error"]} if "error" in r else
                          {"rows_per_sec": r["embedding_rows_per_sec"]})}
                      for r in art["rungs"]],
            **({"not_written": art["not_written"]}
               if "not_written" in art else
               {"rows_file": os.path.basename(_CTR_ROWS_FILE)})}))
        return

    if envvars.get_bool("HETU_BENCH_SWEEP"):
        art = sweep_bert(platform, reduced)
        pv = art.get("planner_validation", {})
        print(json.dumps({
            "metric": "bert_sweep_planner_choice_ok",
            "value": (1.0 if pv.get("ok") else 0.0),
            "unit": "bool", "vs_baseline": None,
            "platform": platform,
            "argmax_match": pv.get("argmax_match"),
            "regret": pv.get("regret"),
            "spearman_rho": pv.get("spearman_rho"),
            "measured_best": pv.get("measured_best"),
            "predicted_best": pv.get("predicted_best"),
            **({"not_written": art["not_written"]}
               if "not_written" in art else
               {"sweep_file": os.path.basename(_SWEEP_FILE)})}))
        return

    sel = envvars.get_str("HETU_BENCH_CONFIGS")
    names = [n.strip() for n in sel.split(",")] if sel else list(_CONFIGS)
    # MERGE into the existing matrix: a HETU_BENCH_CONFIGS subset run (or
    # a reduced CPU run) must not wipe other configs' recorded numbers —
    # full-scale same-platform runs replace their own entries only
    matrix = {}
    try:
        with open(_MATRIX_FILE) as f:
            matrix = json.load(f)
    except (OSError, ValueError):
        pass
    # the previous capture's platform is the provenance fallback for
    # merged rows that predate per-row platform stamps
    prev_platform = matrix.get("platform")
    results = dict(matrix.get("configs", {}))
    if reduced and any(
            not r.get("reduced_scale") and "error" not in r
            for r in results.values()):
        # never overwrite full-scale records with reduced-scale ones
        results = dict(results)
        names = [n for n in names
                 if results.get(n, {}).get("reduced_scale", True)
                 or "error" in results.get(n, {})]
    matrix["platform"] = platform
    matrix["measured_at"] = time.strftime("%Y-%m-%d %H:%M UTC",
                                          time.gmtime())
    # this note DESCRIBES the current accounting; it must not be
    # merge-carried from an older file whose rows it was written about
    # (per-row measured_at is the provenance for any one entry)
    matrix["accounting_note"] = (
        "MFU = 6*P*T/peak over matmul-participating weights only "
        "(12*H^2/layer + the H*V tied head counted once) plus the "
        "attention score/context matmuls; embedding gathers, LayerNorm, "
        "biases and softmax-xent are excluded from the numerator. Rows "
        "carry their own measured_at: subset runs (HETU_BENCH_CONFIGS) "
        "merge-preserve other rows, so entries may predate the "
        "top-level measured_at.")
    ran = set()
    for name in names:
        try:
            results[name] = _CONFIGS[name](platform, reduced)
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        ran.add(name)
        # per-row stamp: merge keeps rows from older runs/platforms, so
        # the top-level measured_at says nothing about THIS row, and
        # the platform must travel WITH the row it describes
        results[name]["measured_at"] = time.strftime(
            "%Y-%m-%d %H:%M UTC", time.gmtime())
        results[name]["platform"] = platform
        from hetu_tpu import quant, telemetry
        # quant rides every bench row (and the headline provenance):
        # an int8-wire/int8-KV run can never be compared against an
        # exact run silently — hetu_trace --check rejects mixed rows
        results[name]["quant"] = quant.active_modes()
        telemetry.emit("bench_row", config=name, platform=platform,
                       value=results[name].get("value"),
                       mfu=results[name].get("mfu"),
                       quant=results[name]["quant"],
                       **({"error": results[name]["error"]}
                          if "error" in results[name] else {}))
        matrix["configs"] = results
        try:
            # atomic: a stage timeout mid-dump must not truncate the
            # matrix of record (later runs would discard + overwrite)
            from hetu_tpu.artifact import atomic_json_dump
            atomic_json_dump(_MATRIX_FILE, matrix)
        except OSError:
            pass
    matrix["configs"] = results

    # ---- the ONE headline line (driver contract) ---- #
    head_name = "bert_base" if "bert_base" in results else \
        (names[0] if names else next(iter(results), "bert_base"))
    head = results.get(head_name, {})
    target = 100.0      # driver-defined north star, samples/sec/chip
    value = head.get("value")
    head_reduced = head.get("reduced_scale", reduced)
    from hetu_tpu.telemetry.health import stamp_provenance
    out = {
        "metric": ("bert_base_seq512_train_throughput"
                   if not head_reduced and head_name == "bert_base"
                   else f"{head_name}_reduced_train_throughput"
                   if head_reduced else f"{head_name}_train_throughput"),
        "value": value,
        "unit": head.get("unit", "samples/sec/chip"),
        "vs_baseline": (round(value / target, 3)
                        if value and not head_reduced
                        and head_name == "bert_base" else None),
        # platform = the headline ROW's platform; rows_live/rows_banked
        # make every row's provenance explicit
        **_provenance_fields(results, ran, head_name, platform,
                             prev_platform),
        "mfu": head.get("mfu"),
        "device_kind": head.get("device_kind"),
        "matrix": {n: stamp_provenance(
            {"value": r.get("value"), "unit": r.get("unit"),
             "mfu": r.get("mfu"),
             **({"error": r["error"]} if "error" in r else {})},
            live=n in ran, measured_at=r.get("measured_at"))
            for n, r in results.items()},
        "matrix_file": os.path.basename(_MATRIX_FILE),
    }
    if "error" in head:
        out["headline_error"] = head["error"]
    if "health_warning" in head:
        # the probe gate's degraded-window flag must surface on the
        # headline, not just deep in the matrix row
        out["headline_health"] = head["health_warning"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
