"""Share of the device's idle time (gaps between ``XLA Ops`` inside the
traced window) that lies under one of the program's spans ``spans``:
each gap is laid over the host's spans and every part of it goes to the
innermost span over that part.  The rest lies under another span (the
wait for the device), a ``bench.*`` span only, or none.

The device's timestamps lead the host's by a millisecond or two, half of
a decode wave's gap, so the gaps are first moved onto the host's clock
by the middle of the bounds causality gives in this trace
(``program_trace.device_clock_lead``; an earlier line of the run holds
them).  Where the trace gives none the gaps stay where they are and the
line says so."""

from benchmarks import program_trace, xplane


def read(data, spans):
    trace = data["trace"]
    window = xplane.window_of(trace)
    planes = xplane.device_planes(trace)
    nodes = program_trace.span_forest(trace, window=window)
    if not window or not planes or not nodes:
        program_trace.missing(data, "idle_under_spans", "hetu.* spans")
        return None
    busy = xplane.merged_intervals(
        xplane.line_events(planes[0], xplane.OPS_LINE), *window)
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = sum(b - a for a, b in gaps)
    if not idle:
        return None
    lead = program_trace.device_clock_lead(trace)
    harness = data.get("harness")
    if harness is not None:
        harness.log(line="device_clock_lead", reader="idle_under_spans",
                    bounds_ns=lead)
    shift = sum(lead) / 2 if lead else 0.0
    under = program_trace.time_under(
        nodes, [(a + shift, b + shift) for a, b in gaps], spans)
    return 100.0 * under / idle
