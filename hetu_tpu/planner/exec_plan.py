"""Single-chip execution-config ranking — the planner closed over the
measured ablation space (VERDICT r3 item 6).

Galvatron's loop is: profile components on the target hardware, then let
the cost model rank FULL configurations it never ran (reference
tools/Galvatron/utils/cost_model.py:38-60 consumes per-component
profiled coefficients; bert/profile_forward.py produces them).  The
multi-device half of that loop lives in cost_model.py/search.py; this
module closes the SINGLE-CHIP half over the knobs an on-chip ablation
sweep measures: per-chip batch, attention
implementation (XLA batched vs fused flash), and LM-head variant
(materialized vs fused chunked).

``ExecConfigModel`` decomposes step time into component costs

    t(b, attn, head) = c1*b + c2*b^2 + d_attn*b + d_head*b + c_fixed

fit by least squares on a calibration SUBSET of measured configs, then
predicts every config — including held-out ones — and ranks them by
throughput.  The quadratic term matters: throughput b/t(b) then has an
INTERIOR optimum at b = sqrt(c_fixed/c2), which is what the v5e
measured (batch 32 beat 48 and 64 per chip) — a linear per-sample model
can only ever crown the largest batch.  ``validate_against_sweep`` is
the closed-loop check, fit with the winner held out: the model's argmax
over the full grid must be the measured-best config, or — when two
configs measure within noise of each other — a config whose MEASURED
throughput is within ``regret_tol`` of the best (the planner's job is
to pick a config that IS fast, not to break measurement-noise ties).
"""

from __future__ import annotations

import numpy as np


def _key(cfg):
    return (int(cfg["batch"]), str(cfg["attention"]), str(cfg["head"]))


class ExecConfigModel:
    """Least-squares component model over (batch, attention, head).

    Features per config: [b, b^2, b*is_flash, b*is_fused, 1] —
    per-sample base cost, super-linear efficiency-decay term (HBM
    pressure / utilization falloff past the sweet spot), per-sample
    attention-impl delta, per-sample head-variant delta, and fixed
    per-step overhead (dispatch, optimizer).
    """

    N_COEF = 5

    def __init__(self):
        self.coef = None

    @staticmethod
    def _features(cfg):
        b = float(cfg["batch"])
        return np.array([
            b,
            b * b,
            b * (cfg["attention"] == "flash"),
            b * (cfg["head"] == "fused"),
            1.0,
        ])

    def fit(self, rows):
        """rows: [{batch, attention, head, step_time_ms}]"""
        if len(rows) < self.N_COEF:
            raise ValueError(
                f"need >= {self.N_COEF} calibration configs to fit "
                f"{self.N_COEF} coefficients, got {len(rows)}")
        X = np.stack([self._features(r) for r in rows])
        y = np.array([float(r["step_time_ms"]) for r in rows])
        self.coef, *_ = np.linalg.lstsq(X, y, rcond=None)
        return self

    def predict_step_ms(self, cfg):
        assert self.coef is not None, "fit() first"
        return float(self._features(cfg) @ self.coef)

    def predict_throughput(self, cfg):
        """samples/sec — the ranking objective (matches the sweep's
        measured objective)."""
        t = self.predict_step_ms(cfg)
        if t <= 0:
            # an extrapolated negative time means the fit is outside its
            # valid region; rank it last rather than crowning it
            return 0.0
        return float(cfg["batch"]) / (t / 1e3)


def validate_against_sweep(sweep, fit_keys=None, regret_tol=0.02):
    """Fit on a subset, rank the FULL grid, compare against measured.

    ``sweep``: a dict ``{"configs": [...]}`` or the list of config rows
    directly.  Each row: {batch, attention, head,
    step_time_ms}.  ``fit_keys``: optional iterable of (batch, attn,
    head) keys to calibrate on; default = every row EXCEPT the measured
    best (the strictest honest split: the model must predict the winner
    without having seen it).

    Returns {measured_best, predicted_best, argmax_match, regret,
    ok, spearman_rho, per_config: [...]}.  ``regret`` = 1 -
    measured_thr(predicted_best)/measured_thr(best): how much throughput
    a user loses by trusting the model's pick.  ``ok`` = exact argmax
    match OR regret <= regret_tol.
    """
    rows = sweep["configs"] if isinstance(sweep, dict) else list(sweep)
    rows = [r for r in rows
            if isinstance(r.get("step_time_ms"), (int, float))]
    # +1: the default split holds the measured-best row OUT of the fit,
    # so the fit itself still needs N_COEF rows
    need = ExecConfigModel.N_COEF + 1
    if len(rows) < need:
        raise ValueError(
            f"sweep has {len(rows)} measured rows; need >= {need} "
            f"(fit {ExecConfigModel.N_COEF} coefficients with the "
            f"winner held out)")
    thr = {_key(r): float(r["batch"]) / (r["step_time_ms"] / 1e3)
           for r in rows}
    measured_best = max(thr, key=thr.get)
    if fit_keys is None:
        fit_rows = [r for r in rows if _key(r) != measured_best]
    else:
        fit_keys = {tuple(k) for k in fit_keys}
        fit_rows = [r for r in rows if _key(r) in fit_keys]
    model = ExecConfigModel().fit(fit_rows)
    pred = {_key(r): model.predict_throughput(r) for r in rows}
    predicted_best = max(pred, key=pred.get)

    meas_order = sorted(thr, key=thr.get)
    pred_order = sorted(pred, key=pred.get)
    n = len(meas_order)
    mrank = {k: i for i, k in enumerate(meas_order)}
    prank = {k: i for i, k in enumerate(pred_order)}
    d2 = sum((mrank[k] - prank[k]) ** 2 for k in thr)
    rho = 1.0 - 6.0 * d2 / (n * (n * n - 1)) if n > 2 else 1.0

    regret = 1.0 - thr[predicted_best] / thr[measured_best]
    return {
        "measured_best": list(measured_best),
        "predicted_best": list(predicted_best),
        "argmax_match": predicted_best == measured_best,
        "regret": round(regret, 4),
        "regret_tol": regret_tol,
        "ok": predicted_best == measured_best or regret <= regret_tol,
        "spearman_rho": round(rho, 4),
        "n_configs": n,
        "n_fit": len(fit_rows),
        "coef_ms": {
            "per_sample_base": round(float(model.coef[0]), 5),
            "per_sample_sq_decay": round(float(model.coef[1]), 6),
            "per_sample_flash_delta": round(float(model.coef[2]), 5),
            "per_sample_fused_head_delta": round(float(model.coef[3]), 5),
            "fixed": round(float(model.coef[4]), 5),
        },
        "per_config": [
            {"config": list(k),
             "measured_samples_per_sec": round(thr[k], 2),
             "predicted_samples_per_sec": round(pred[k], 2)}
            for k in sorted(thr, key=thr.get, reverse=True)
        ],
    }
