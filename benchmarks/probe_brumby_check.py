"""Not part of a run: the ``serve-brumby-14b-docs-closed`` cell's check
read on one served window against the reference as stated (float32,
attention form) and against each control.  On the reference's side
(``reference_brumby.CONTROLS``): float8 operands (the nearest precision
below the bfloat16 the configuration states), the gates left out, the
keys one position on.  On the program's side: a SECOND served window
with the states kept in bfloat16 (``state_dtype``: ``S`` and ``z``
rounded at every write, each chunk and each decoded token), the same
weights.  Every control has to come out as not correct.  PERF.md's
readings come from it.

    python3 benchmarks/probe_brumby_check.py --seed <n> [--seconds <s>]

One process, the cell's own runner (``serve_window`` then ``agree``), the
device required as ``run.py`` requires it; the last line of standard
output holds every record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import reference_brumby, run as bench_run  # noqa: E402

CELL = "serve-brumby-14b-docs-closed"
PROGRAM_CONTROLS = ("state_bf16",)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    ap.add_argument("--controls", default=",".join(
        reference_brumby.CONTROLS + PROGRAM_CONTROLS))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_retention")
    limits = dict(h.config["runner_args"])
    if args.check_requests:
        limits["check_requests"] = args.check_requests
    controls = [c for c in args.controls.split(",") if c]
    out = {}

    def check(w, key, control=None):
        ok, record = runner.agree(
            h, w["params"], w["ref_config"], w["out"]["done"], limits,
            args.seconds, read=w["read"], probes=w["probes"],
            long_done=w["long_done"], control=control)
        record.pop("rms", None)
        out[key] = dict(record, correct=ok,
                        tokens_per_s=w["out"]["tokens_per_s"])

    w = runner.serve_window(h)
    check(w, "float32")
    for control in controls:
        if control in reference_brumby.CONTROLS:
            check(w, control, control)
    if "state_bf16" in controls:
        check(runner.serve_window(h, params=w["params"],
                                  state_dtype="bfloat16"), "state_bf16")
    print(json.dumps({"seed": args.seed, "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
