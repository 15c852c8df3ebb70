"""Not part of a run: the ``serve-mellum2-12b-code-closed`` cell's check
read twice on one served window, against the reference as stated
(float32) and against the reference with float8 operands and a bfloat16
router and softmax (the nearest precision below the bfloat16 weights
and float32 router the configuration states), which has to come out as
not correct.  PERF.md's two readings come from it.

    python3 benchmarks/probe_mellum2_check.py --seed <n> [--seconds <s>]

One process, the cell's own runner (``serve_window`` then ``agree``), the
device required as ``run.py`` requires it; the last line of standard
output holds both records.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

CELL = "serve-mellum2-12b-code-closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_window_moe")
    w = runner.serve_window(h)
    limits = dict(h.config["runner_args"])
    if args.check_requests:
        limits["check_requests"] = args.check_requests
    out = {}
    for lower in (False, True):
        ok, record = runner.agree(h, w["params"], w["ref_config"],
                                  w["out"]["done"], limits,
                                  w["margin_steps"], lower=lower)
        out["float8" if lower else "float32"] = dict(record, correct=ok)
    print(json.dumps({"seed": args.seed, "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
