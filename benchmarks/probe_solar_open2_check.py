"""Not part of a run: the ``serve-solar-open2-longdoc-closed`` cell's
check read on one served window against the reference as stated (float32,
the recurrence step by step) and against each control.  On the
reference's side (``reference_solar_open2.CONTROLS``): beta not doubled,
the safe gate in place of softplus, the KDA gate a head instead of a
channel, the GQA gate off, the GQA layer rotated, the GQA layer at place 3
instead of 0, conv tails cut at the chunk's boundaries, float8 operands
(the nearest precision below the bfloat16 the configuration states), the
decay dropped, the delta correction dropped.  On the program's side: a
SECOND served window with the state kept in bfloat16 (``state_dtype``:
``S`` rounded at every write, each chunk and each decoded token), the same
weights.  Every control has to come out as not correct.  PERF.md's
readings and the configuration file's ``*_why`` keys come from it.

    python3 benchmarks/probe_solar_open2_check.py --seed <n> [--seconds <s>]

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

from benchmarks import reference_solar_open2, run as bench_run  # noqa: E402

CELL = "serve-solar-open2-longdoc-closed"
PROGRAM_CONTROLS = ("state_bf16",)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    ap.add_argument("--controls", default=",".join(
        reference_solar_open2.CONTROLS + PROGRAM_CONTROLS))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_kda_gqa")
    limits = dict(h.config["runner_args"])
    if args.check_requests:
        limits["check_requests"] = args.check_requests
    controls = [c for c in args.controls.split(",") if c]
    out = {}

    def check(w, key, control=None):
        ok, record = runner.agree(
            h, w["params"], w["ref_config"], w["held"], w["out"]["done"],
            limits, args.seconds, read=w["read"], probes=w["probes"],
            control=control)
        if key != "float32":
            record.pop("rms", None)
        out[key] = dict(record, correct=ok,
                        tokens_per_s=w["out"]["tokens_per_s"])
        print(json.dumps({key: out[key]}), flush=True)

    w = runner.serve_window(h)
    check(w, "float32")
    for control in controls:
        if control in reference_solar_open2.CONTROLS:
            check(w, control, control)
    if "state_bf16" in controls:
        check(runner.serve_window(h, params=w["params"],
                                  state_dtype="bfloat16"), "state_bf16")
    print(json.dumps({"seed": args.seed, "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
