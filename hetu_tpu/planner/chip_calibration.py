"""On-chip planner calibration (VERDICT r2 item 4).

Galvatron measures its cost-model constants with dedicated scripts on
the target cluster (tools/Galvatron/test_env bandwidth/overlap probes,
utils/cost_model.py:38-60 consumes the coefficients); until round 3 this
build's planner calibrated only against the virtual CPU mesh and assumed
``overlap=0.7``.  This module measures every SINGLE-CHIP-measurable
constant on the live backend and records which constants cannot be
measured without multi-chip hardware:

* achieved bf16 matmul TFLOP/s across sizes (the MXU utilization curve),
* H2D / D2H host-link bandwidth,
* HBM capacity,
* an MEASURED overlap coefficient: how much host->device transfer hides
  under compute when dispatched concurrently (the single-chip analogue
  of Galvatron's comm/compute overlap probe — ICI/DCN overlap still
  needs chips we don't have, and the artifact says so),
* a measured kernel-choice micro-search: flash-attention block sizes
  (Galvatron-style profiling IS search over measured configs).

``plan_vs_naive`` closes the loop the VERDICT asked for: the
calibration-driven choice (best-measured flash blocks) against the
naive default (square 128x128 blocks, what a GPU port would pick),
with the MEASURED step-time delta recorded next to the prediction.

Run ``python -m hetu_tpu.planner.chip_calibration`` on the target chip;
the artifact lands in CALIBRATION_TPU.json at the repo root and
``load_calibration`` feeds it back into a ClusterSpec for the search.
"""

from __future__ import annotations

import json
import os
import time


import numpy as np
import jax
import jax.numpy as jnp

from .cost_model import ClusterSpec

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
CALIBRATION_FILE = os.path.join(_REPO, "CALIBRATION_TPU.json")


def _timeit(fn, *args, warmup=2, iters=8):
    """Median-of-3 wall time per call; ``block_until_ready`` on every
    call's output is the barrier (transfers and computations are separate
    queues, so waiting for the last output alone would not do)."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(iters)])
        reps.append((time.perf_counter() - t0) / iters)
    return sorted(reps)[1]


def measure_matmul_curve(dims=(1024, 2048, 4096, 8192),
                         dtype=jnp.bfloat16, light=False):
    """Achieved TFLOP/s per matmul size — the utilization curve the
    cost model's flops_per_sec should reflect (small layers never reach
    the peak the spec sheet quotes).

    Returns ``(curve, raw)``: ``curve`` holds the physics-clamped values
    the cost model consumes; ``raw`` holds the unclamped slope readings,
    so a value calibrated FROM the spec peak (raw > spec, clamped to it)
    is distinguishable in the artifact from a genuine measurement.

    Methodology: one jitted program per (size, K) holding K UNROLLED
    chained matmuls, timed to ``block_until_ready``.  Per-matmul time is
    the (t_K2 - t_K1)/(K2 - K1) slope, which cancels the fixed
    per-program dispatch latency."""
    out = {}
    raw_out = {}
    for d in dims:
        a = jnp.full((d, d), 1.0 / d, dtype)
        b = jnp.eye(d, dtype=dtype)

        def make(K):
            def chain(x, y):
                for _ in range(K):
                    x = x @ y        # x @ eye: bounded numerics
                return x
            return jax.jit(chain)

        # K spans sized so the K2-K1 slope clears host-clock jitter at
        # EVERY dim (a ~0.01 ms d=1024 matmul needs hundreds of extra
        # copies in the K2 program); ``light`` (CPU test mode) keeps
        # compiles small
        if light:
            k1, k2 = (2, 10)
        else:
            k1, k2 = {8192: (2, 10), 4096: (4, 40), 2048: (8, 232)}.get(
                d, (8, 512) if d <= 1024 else (4, 40))
        t1 = _timeit(make(k1), a, b, warmup=1, iters=3)
        t2 = _timeit(make(k2), a, b, warmup=1, iters=3)
        # A slope that doesn't clear the dispatch-jitter floor is NOISE,
        # not a measurement — record it as unmeasurable rather than
        # dividing by epsilon and writing a fantasy TFLOP/s number into
        # the artifact (the failure mode this module exists to prevent).
        if t2 - t1 > max(3e-4, 0.05 * t1):
            t = (t2 - t1) / (k2 - k1)
            tflops = round(2.0 * d ** 3 / t / 1e12, 2)
            raw_out[str(d)] = tflops
            # physics check: a reading above the device's spec-sheet
            # peak is residual slope jitter, not throughput — >1.1x is
            # rejected outright, <=1.1x is clamped TO the spec peak so
            # the cost model never calibrates to an above-physical rate
            # (raw_out keeps the unclamped reading for the artifact)
            spec = (spec_peak_tflops()
                    if jax.default_backend() == "tpu" else None)
            if spec is not None and tflops > 1.1 * spec:
                out[str(d)] = None
            elif spec is not None:
                out[str(d)] = min(tflops, spec)
            else:
                out[str(d)] = tflops
        else:
            out[str(d)] = None   # dispatch-latency-dominated at this size
            raw_out[str(d)] = None
    return out, raw_out


# bf16 spec-sheet peak TFLOP/s keyed by the exact ``device_kind`` JAX
# reports (source: Google Cloud TPU documentation, the system-architecture
# page of each generation).  The single source of truth: chip_smoke.py's
# device check reads it too.  A kind that is
# not here is an error, not a default.
SPEC_PEAKS = {
    "TPU v2": 45.0,         # "TPU v2": 45 TFLOP/s per chip
    "TPU v3": 123.0,        # "TPU v3": 123 TFLOP/s per chip
    "TPU v4": 275.0,        # "TPU v4": 275 TFLOP/s per chip
    "TPU v5 lite": 197.0,   # "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5": 459.0,        # "TPU v5p": 459 TFLOP/s bf16 per chip
    "TPU v6 lite": 918.0,   # "TPU v6e": 918 TFLOP/s bf16 per chip
}


def spec_peak_tflops(device_kind=None):
    kind = (device_kind if device_kind is not None
            else jax.devices()[0].device_kind)
    if kind not in SPEC_PEAKS:
        raise KeyError(
            f"no spec peak recorded for device_kind {kind!r}; known: "
            f"{sorted(SPEC_PEAKS)} — add it to SPEC_PEAKS with its source")
    return SPEC_PEAKS[kind]


def measure_host_link(size_mb=256):
    """H2D and D2H bandwidth (GB/s) — phase A/B of the PS path and the
    dataloader ride this link."""
    n = int(size_mb) * (1 << 20)
    host = np.ones(n // 4, np.float32)
    jax.block_until_ready(jax.device_put(host))          # warmup
    reps = 4
    t0 = time.perf_counter()
    for _ in range(reps):
        dev = jax.block_until_ready(jax.device_put(host))
    t_h2d = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        # a fresh device array per fetch: np.asarray caches the host
        # copy on the jax.Array it was taken from
        np.asarray(dev + 0)
    t_d2h = (time.perf_counter() - t0) / reps
    return {"h2d_gbps": round(n / t_h2d / 1e9, 2),
            "d2h_gbps": round(n / t_d2h / 1e9, 2)}


def measure_overlap_coefficient(compute_dim=4096, transfer_mb=128):
    """Fraction of a host->device transfer hidden under concurrently
    dispatched device compute.

    overlap = (t_compute + t_transfer - t_both) / min(t_compute,
    t_transfer): 1 = fully hidden, 0 = fully serialized.  This is the
    single-chip analogue of Galvatron's overlap-slowdown probe
    (utils/cost_model.py:49-56 coefficients); ICI-collective overlap
    needs >1 chip and stays an assumption (recorded as such)."""
    a = jnp.ones((compute_dim, compute_dim), jnp.bfloat16)
    eye = jnp.eye(compute_dim, dtype=jnp.bfloat16)
    chain = jax.jit(lambda x, y: ((x @ y) @ y) @ y)
    host = np.ones(int(transfer_mb) * (1 << 20) // 4, np.float32)

    def compute_step():
        return chain(a, eye)

    def transfer_step():
        return jax.device_put(host)

    def both():
        # async dispatch of the compute, then the transfer: both are in
        # flight together and both are waited for
        return compute_step(), transfer_step()

    t_compute = _timeit(compute_step, warmup=1, iters=4)
    t_transfer = _timeit(transfer_step, warmup=1, iters=4)
    t_both = _timeit(both, warmup=1, iters=4)
    hidden = max(0.0, t_compute + t_transfer - t_both)
    denom = min(t_compute, t_transfer)
    return {
        "t_compute_ms": round(t_compute * 1e3, 3),
        "t_transfer_ms": round(t_transfer * 1e3, 3),
        "t_both_ms": round(t_both * 1e3, 3),
        "overlap_h2d": round(min(1.0, hidden / denom), 3)
        if denom > 0 else 0.0,
    }


def measure_flash_block_choice(seq=4096, heads=8, head_dim=64, batch=2,
                               candidates=((128, 128), (256, 512),
                                           (512, 1024), (1024, 1024))):
    """Measured fwd+bwd step time of the Pallas flash kernel per block
    config at a long-context shape.  The planner's kernel choice = the
    argmin; 'naive' = square 128x128 (the config a straight GPU port
    ships)."""
    from ..kernels.flash_attention import flash_attention
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, seq, heads, head_dim),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), q.shape,
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), q.shape,
                          jnp.bfloat16)
    out = {}
    for bq, bk in candidates:
        def loss(q, k, v, _bq=bq, _bk=bk):
            o = flash_attention(q, k, v, causal=True, block_q=_bq,
                                block_k=_bk)
            return (o.astype(jnp.float32) ** 2).sum()
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        t = _timeit(g, q, k, v, warmup=1, iters=4)
        out[f"{bq}x{bk}"] = round(t * 1e3, 3)
    best = min(out, key=out.get)
    return {"step_ms": out, "chosen": best,
            "config": {"seq": seq, "heads": heads, "head_dim": head_dim,
                       "batch": batch}}


def plan_vs_naive(flash_result):
    """The measured plan-vs-naive delta the VERDICT asked for: the
    calibration-driven flash block choice vs the naive 128x128 default,
    both MEASURED (flash_result comes from measure_flash_block_choice)."""
    times = flash_result["step_ms"]
    naive = times.get("128x128")
    chosen = times[flash_result["chosen"]]
    return {
        "decision": "flash_attention_block_sizes",
        "naive": {"config": "128x128", "step_ms": naive},
        "planned": {"config": flash_result["chosen"],
                    "step_ms": chosen},
        "measured_speedup_vs_naive": round(naive / chosen, 3)
        if naive and chosen else None,
    }


def calibrate_chip(small=False):
    """Measure everything; ``small`` shrinks probes for CPU test runs."""
    dev = jax.devices()[0]
    dims = (256, 512) if small else (1024, 2048, 4096, 8192)
    curve, curve_raw = measure_matmul_curve(dims=dims, light=small)
    art = {
        "platform": jax.default_backend(),
        "device_kind": dev.device_kind,
        "measured_at": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "matmul_tflops_bf16": curve,
        # unclamped slope readings: a dim where raw > spec peak was
        # clamped TO spec in matmul_tflops_bf16 — consumers can tell
        # calibrated-from-measurement from calibrated-from-spec
        "matmul_tflops_bf16_raw": curve_raw,
        "matmul_clamped_to_spec": {
            d: (curve_raw[d] is not None and curve[d] is not None
                and curve_raw[d] > curve[d])
            for d in curve},
        "host_link": measure_host_link(size_mb=8 if small else 64),
        "overlap": measure_overlap_coefficient(
            compute_dim=512 if small else 4096,
            transfer_mb=4 if small else 16),
        "flash_blocks": measure_flash_block_choice(
            seq=256 if small else 4096,
            candidates=((128, 128), (256, 256)) if small
            else ((128, 128), (256, 512), (512, 1024), (1024, 1024))),
        "unmeasurable_on_one_chip": [
            "ici_bandwidth (needs >1 chip; ClusterSpec keeps the 45GB/s "
            "v5e link spec)",
            "dcn_bandwidth (needs >1 host)",
            "collective/compute overlap over ICI (overlap_h2d above is "
            "the host-link analogue; ClusterSpec.overlap uses it as the "
            "measured stand-in)",
        ],
    }
    try:
        stats = dev.memory_stats()
        if stats and "bytes_limit" in stats:
            art["hbm_bytes"] = int(stats["bytes_limit"])
    except Exception:
        pass
    art["plan_vs_naive"] = plan_vs_naive(art["flash_blocks"])
    measured = [v for v in art["matmul_tflops_bf16"].values()
                if v is not None]
    if not measured:
        raise RuntimeError(
            "matmul curve entirely dispatch-noise-dominated; no peak "
            "to calibrate from — rerun with larger sizes")
    peak_tflops = max(measured)
    art["cluster_spec"] = {
        "flops_per_sec": peak_tflops * 1e12,
        "mfu": 1.0,
        "overlap": art["overlap"]["overlap_h2d"],
        **({"hbm_bytes": float(art["hbm_bytes"])}
           if "hbm_bytes" in art else {}),
    }
    return art


def load_calibration(path=CALIBRATION_FILE, n_devices=None):
    """ClusterSpec from a checked-in calibration artifact; measured
    fields override the analytic defaults.  Provenance is recorded per
    constant: what the artifact measured is 'measured'; ICI/DCN
    bandwidth stay 'spec-assumed' (unmeasurable on one chip — the
    artifact's unmeasurable_on_one_chip list says so) so plan output
    can flag rankings that rest on them."""
    with open(path) as f:
        art = json.load(f)
    spec = ClusterSpec()
    for k, v in art.get("cluster_spec", {}).items():
        setattr(spec, k, v)
        spec.provenance[k] = "measured"
    # flops_per_sec is max() over the matmul curve: if the peak dim's
    # reading was clamped TO the spec-sheet value, the constant is a
    # spec number, not a measurement — say so (matmul_clamped_to_spec
    # exists in post-r4 artifacts; older ones default to 'measured')
    curve = art.get("matmul_tflops_bf16", {})
    clamped = art.get("matmul_clamped_to_spec", {})
    peaks = [d for d, v in curve.items() if v is not None]
    if peaks and "flops_per_sec" in spec.provenance:
        peak_dim = max(peaks, key=lambda d: curve[d])
        if clamped.get(peak_dim):
            spec.provenance["flops_per_sec"] = "spec-clamped"
    for k in ("ici_bandwidth", "dcn_bandwidth"):
        spec.provenance.setdefault(k, "spec-assumed")
    if n_devices is not None:
        spec.n_devices = n_devices
    return spec


def main():
    from ..artifact import persist_artifact
    # cheap pre-check: a degraded run (not on real TPU) that would be
    # refused anyway must not burn minutes of matmul sweeps first
    reduced_now = jax.default_backend() != "tpu"
    try:
        with open(CALIBRATION_FILE) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = None
    if (isinstance(existing, dict) and reduced_now
            and not existing.get("reduced_scale")
            and existing.get("platform") == "tpu"):
        print(json.dumps({
            "platform": jax.default_backend(),
            "not_written": "full-scale TPU calibration record already "
                           "present; degraded run skipped"}))
        return
    art = calibrate_chip()
    # a non-TPU backend must never clobber a full-scale TPU
    # calibration record
    art["reduced_scale"] = art.get("platform") != "tpu"
    if not persist_artifact(CALIBRATION_FILE, art,
                            reduced=art["reduced_scale"]):
        print(json.dumps({"platform": art["platform"],
                          "not_written": art["not_written"]}))
        return
    print(json.dumps({"platform": art["platform"],
                      "device_kind": art["device_kind"],
                      "peak_tflops": round(
                          art["cluster_spec"]["flops_per_sec"] / 1e12, 2),
                      "overlap_h2d": art["overlap"]["overlap_h2d"],
                      "plan_vs_naive": art["plan_vs_naive"][
                          "measured_speedup_vs_naive"]}))


if __name__ == "__main__":
    main()
