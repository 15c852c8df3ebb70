"""Not part of a run: the ``serve-command-a-plus-grounded-closed`` cell's
check read on one served window against the reference as stated
(float32) and against each of ``reference_command_a_plus.CONTROLS``
computed on the reference's side: float8 operands (the nearest precision
below the bfloat16 the configuration states), the block made sequential,
the full layers rotated, the sliding layers unrotated, a window of 2,048
and of 8,192, the shared experts summed in place of averaged, the
weights normalised over the held experts alone, RMSNorm in place of
LayerNorm.  Every control has to come out as not correct; the exit code
is 1 where one does not (or the sound reading is not correct).
PERF.md's readings come from it.

    python3 benchmarks/probe_command_a_plus_check.py --seed <n> [--seconds <s>]

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

from benchmarks import reference_command_a_plus, run as bench_run  # noqa: E402

CELL = "serve-command-a-plus-grounded-closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    ap.add_argument("--controls", default=",".join(
        reference_command_a_plus.CONTROLS))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_parallel_moe")
    w = runner.serve_window(h)
    limits = dict(h.config["runner_args"])
    if args.check_requests:
        limits["check_requests"] = args.check_requests
    out = {}
    for control in [None] + [c for c in args.controls.split(",") if c]:
        ok, record = runner.agree(h, w["params"], w["ref_config"],
                                  w["held"], w["out"]["done"], limits,
                                  control=control)
        if control is not None:
            record.pop("rms", None)
        out[control or "float32"] = dict(record, correct=ok)
    unresolved = [c for c, r in out.items()
                  if r["correct"] != (c == "float32")]
    print(json.dumps({"seed": args.seed, "device": device,
                      "tokens_per_s": w["out"]["tokens_per_s"],
                      "finished": len(w["out"]["done"]),
                      "memory_peak_bytes":
                          w["stats"].get("peak_bytes_in_use", 0),
                      "counters": {
                          part: {k: v for k, v in c.items()
                                 if k != "moe_load"}
                          for part, c in w["counters"].items()},
                      "unresolved": unresolved, **out}))
    return 1 if unresolved else 0


if __name__ == "__main__":
    sys.exit(main())
