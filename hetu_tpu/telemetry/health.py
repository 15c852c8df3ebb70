"""Measurement health gates: refuse to trust readings that cannot be real.

VERDICT r5's three top weaknesses were all measurement-trust failures,
not code failures: a wedged 64.6-samples/s batch probe banked next to
216/223 siblings, a 3.2x bert4l regression nobody reconciled, and a
headline record that wrapped a CPU run around on-chip values.  These
gates codify the banking rules so a degraded measurement window can no
longer silently become a headline row:

- **sibling consistency** — a probe >2x below the median of its
  batch-size neighbors is a wedged reading, not a slow config; it is
  excluded from winner selection and reported as degraded.
- **physics ceiling** — a throughput implying MFU above 1.0 (or an
  achieved TFLOP/s above the chip's CALIBRATION_TPU.json measured
  matmul peak) is impossible; the row is rejected, whatever it claims.
- **provenance stamping** — every banked-vs-live decision is explicit:
  records carry ``provenance: live|banked`` (+ the banked row's own
  ``measured_at``), so "which rows did THIS run measure" is a field,
  not archaeology.

All checks return JSON-able verdict dicts (never raise on a bad
reading — the bench must record the rejection, not crash) and emit a
``bench_probe_health`` event into the telemetry stream.
"""

from __future__ import annotations

import json
import os

from .events import emit

SIBLING_TOL = 2.0        # VERDICT's rule: >2x off neighbors = wedged
MFU_CEILING = 1.0        # honest-accounting MFU can approach, not pass
CEILING_MARGIN = 1.02    # 2% timer/accounting slack before "impossible"

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
CALIBRATION_FILE = os.path.join(_REPO, "CALIBRATION_TPU.json")


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return None
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def check_sibling_consistency(probes, tol=SIBLING_TOL):
    """Flag wedged probes in ``{key: samples_per_sec}``.

    A probe is *wedged* when the median of its siblings is more than
    ``tol``x its own reading (the Aug-2 case: batch 48 at 64.6 against
    216/223 — ratio 3.4).  Slow-but-real configs survive: a genuine 2x
    spread between batch sizes has never been observed on this
    hardware, a disturbed window produces 3-10x.  Returns a verdict dict;
    ``ok`` is False when any probe is wedged (the whole window is
    suspect, per VERDICT next-#1's banking rule)."""
    numeric = {k: float(v) for k, v in probes.items()
               if isinstance(v, (int, float))}
    wedged, clean = {}, {}
    for k, v in numeric.items():
        siblings = [x for kk, x in numeric.items() if kk != k]
        med = _median(siblings)
        if med is not None and v > 0 and med / v > tol:
            wedged[str(k)] = {"value": v,
                              "siblings_median": round(med, 3),
                              "ratio": round(med / v, 3)}
        else:
            clean[str(k)] = v
    verdict = {"check": "sibling-consistency", "tol": tol,
               "ok": not wedged, "wedged": wedged, "clean": clean}
    emit("bench_probe_health", ok=verdict["ok"],
         check="sibling-consistency",
         wedged=sorted(wedged), n_probes=len(numeric))
    return verdict


def _calibrated_peak_tflops():
    """The measured bf16 matmul peak from CALIBRATION_TPU.json (max
    over the dim ladder), or None when no calibration exists."""
    try:
        with open(CALIBRATION_FILE) as f:
            art = json.load(f)
        curve = art.get("matmul_tflops_bf16") or {}
        vals = [float(v) for v in curve.values()
                if isinstance(v, (int, float))]
        return max(vals) if vals else None
    except (OSError, ValueError):
        return None


def check_physics_ceiling(mfu=None, tflops_chip=None, platform=None,
                          margin=CEILING_MARGIN):
    """Reject readings that exceed what the silicon can do.

    ``mfu`` is checked against 1.0 (the honest-accounting numerator can
    approach but never pass peak); ``tflops_chip`` against the
    calibration artifact's measured matmul peak.  CPU platforms make no
    chip claim (their MFU field is None by construction), so they pass
    with a note rather than a fake ceiling."""
    if platform == "cpu":
        return {"check": "physics-ceiling", "ok": True,
                "note": "cpu platform: no chip ceiling claimed"}
    violations = []
    if mfu is not None and float(mfu) > MFU_CEILING * margin:
        violations.append(
            f"MFU {float(mfu):.3f} > {MFU_CEILING} — impossible under "
            f"honest accounting (timer or FLOP-count defect)")
    peak = _calibrated_peak_tflops()
    if tflops_chip is not None and peak is not None \
            and float(tflops_chip) > peak * margin:
        violations.append(
            f"achieved {float(tflops_chip):.1f} TFLOP/s/chip > "
            f"calibrated matmul peak {peak:.1f} "
            f"({os.path.basename(CALIBRATION_FILE)})")
    return {"check": "physics-ceiling", "ok": not violations,
            **({"violations": violations} if violations else {})}


def stamp_provenance(record, live, measured_at=None):
    """Mark a record live-vs-banked IN the record (satellite: headline
    BENCH rows must say which they are, explicitly).  Banked rows keep
    their own ``measured_at`` so the reader knows how stale they are."""
    record["provenance"] = "live" if live else "banked"
    if not live and measured_at and "measured_at" not in record:
        record["measured_at"] = measured_at
    return record
