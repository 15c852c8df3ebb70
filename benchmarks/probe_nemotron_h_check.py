"""Not part of a run: the ``serve-nemotron3-super-agent-closed`` cell's check
read on one served window against the reference as stated (float32) and
against each of ``reference_nemotron_h.CONTROLS`` computed on the
reference's side: float8 operands (the nearest precision below the
bfloat16 the configuration states), the matrix state rounded to bfloat16
every step, the carry zeroed at the first chunk boundary, the keys one
position on, the mixers left out, the latent projections left out, the
WRONG SHARE (the held leaves taken for experts [128, 256)) and the
weights normalised over the held experts alone.  Every control has to
come out as not correct.  PERF.md's readings come from it.

    python3 benchmarks/probe_nemotron_h_check.py --seed <n> [--seconds <s>]

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

from benchmarks import reference_nemotron_h, run as bench_run  # noqa: E402

CELL = "serve-nemotron3-super-agent-closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    ap.add_argument("--controls", default=",".join(
        reference_nemotron_h.CONTROLS))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_nemotron_h")
    w = runner.serve_window(h)
    limits = dict(h.config["runner_args"])
    if args.check_requests:
        limits["check_requests"] = args.check_requests
    out = {}
    for control in [None] + [c for c in args.controls.split(",") if c]:
        ok, record = runner.agree(h, w["params"], w["ref_config"],
                                  w["held"], w["out"]["done"], limits,
                                  args.seconds, states=w["states"],
                                  control=control)
        if control is not None:
            record.pop("rms", None)
        out[control or "float32"] = dict(record, correct=ok)
    print(json.dumps({"seed": args.seed, "device": device,
                      "tokens_per_s": w["out"]["tokens_per_s"],
                      "counters": {
                          part: {k: v for k, v in c.items()
                                 if k != "moe_load"}
                          for part, c in w["counters"].items()}, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
