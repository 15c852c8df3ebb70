"""Median duration, in ms, of the program's spans named ``span``, less
their child spans named ``minus`` (a wave's host time is the wave less
its wait for the device).  With ``only_with_minus`` a span that holds no
such child is skipped (a step with nothing live is no wave); without it
the span counts whole."""

import statistics

from benchmarks import program_trace


def read(data, span, minus=None, only_with_minus=True):
    values = []
    for n in program_trace.span_forest(data["trace"]):
        if n["name"] != span:
            continue
        held = [c for c in n["children"] if c["name"] == minus]
        if minus is not None and only_with_minus and not held:
            continue
        values.append((n["end"] - n["start"]
                       - sum(c["end"] - c["start"] for c in held)) / 1e6)
    if not values:
        program_trace.missing(data, "span_median",
                              [span] + ([minus] if minus else []))
        return None
    return statistics.median(values)
