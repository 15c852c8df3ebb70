"""Median device time, in ms, of one execution of the serving wave's
program, over the waves of ONE ``kind`` (``decode``, ``chunk``,
``verify``): ``module_time`` split by what ``wave_trace.waves`` says each
``_serve_mixed_paged`` module event is.  An earlier line
(``wave_module_time``) holds the count, the waves of each q bucket (the
dispatch span's ``q=``) and the bucket the median fell on: a cell's chunk
waves come in up to three buckets of very different lengths."""

import statistics

from benchmarks import wave_trace


def read(data, kind):
    modules = wave_trace.modules_of(data, kind)
    if modules is None:
        return None
    ordered = sorted(modules, key=lambda m: m["end"] - m["start"])
    median = statistics.median(m["end"] - m["start"] for m in ordered) / 1e6
    by_q = {}
    for m in ordered:
        by_q.setdefault(str(m["q"]), []).append((m["end"] - m["start"]) / 1e6)
    harness = data.get("harness")
    if harness is not None:
        harness.log(line="wave_module_time", kind=kind, waves=len(ordered),
                    median_ms=median,
                    median_fell_on_q=ordered[(len(ordered) - 1) // 2]["q"],
                    by_q={q: {"waves": len(v),
                              "median_ms": statistics.median(v)}
                          for q, v in sorted(by_q.items())})
    return median
