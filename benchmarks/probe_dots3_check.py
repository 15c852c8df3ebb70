"""Not part of a run: the ``serve-dots3-note-notes-closed`` cell's check
read on one served window against the reference as stated (float32) and
against each of ``CONTROLS`` computed on the reference's side: the
nearest 2,048 positions in place of the indexer's, no selection at all,
a window of 256 or 1,026, the gate left out, the rescale left out, the
indexer's rotation left out, float8 operands (the nearest precision
below the bfloat16 the configuration states) and the routing weights
normalised over the held experts alone.  Every control has to come out
as not correct, and the reference as stated as correct: the exit code is
1 where one does not.  PERF.md's readings come from it.

NOT among them: a window of 512 or 514 (the reference's "window_minus" /
"window_plus").  One position of 513 moves 0.40-1.01 % of a served
window's rows over the margin where sound runs read 0.14-0.37 % and the
limit is 3 %: the cell's check does not resolve it (PERF.md section 7
(ap)); ``tests/test_sparse_latent.py`` holds the band's edge in float32
at a small size.  ``--controls window_minus`` still reads one.

    python3 benchmarks/probe_dots3_check.py --seed <n> [--seconds <s>]

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

from benchmarks import reference_dots3_note, run as bench_run  # noqa: E402

CELL = "serve-dots3-note-notes-closed"
# what the cell's check has to refuse
CONTROLS = tuple(c for c in reference_dots3_note.CONTROLS
                 if c not in ("window_minus", "window_plus"))


def unsound(records):
    """The records the check got wrong: a control that came out correct,
    "float32" (the reference as stated) where it did not."""
    return [c for c, r in records.items()
            if bool(r["correct"]) != (c == "float32")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--check-requests", type=int, default=None)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, CELL)
    peaks = bench_run.load_json(os.path.join(HERE, "peaks.json"))
    device = bench_run.require_device(1, peaks)
    bench_run.enable_compile_cache()
    h = bench_run.Harness(resolved, args.seed, args.seconds, 0,
                          peaks[device["kind"]])
    h.count_compiles()
    runner = bench_run.load_module("runners", "serve_sparse_latent")
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
    print(json.dumps({"seed": args.seed, "device": device,
                      "tokens_per_s": w["out"]["tokens_per_s"],
                      "counters": {
                          part: {k: v for k, v in c.items()
                                 if k != "moe_load"}
                          for part, c in w["counters"].items()}, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
