"""From a profiler trace to numbers: the benchmark's own reduction.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into a small
plain structure (planes -> lines -> events as [name, start_ns,
duration_ns]), which is also the format of the recorded fixture under
``benchmarks/fixtures/``.  Everything else here works on that structure,
so a reader never touches the profiler's own classes.

What the TPU's trace looks like (looked at by hand on the chip, PR 24):
one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA
Modules`` (one event per executed program, named ``jit_<function>(<id>)``),
``XLA Ops`` (one event per executed HLO operation, named by the
operation's WHOLE text, ``%fusion.12 = bf16[...] fusion(...), kind=...``)
and ``Async XLA Ops``.  An operation inside a ``while`` is an event of its
own within the loop's event, so durations nest and their sum exceeds the
busy time.  A Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"`` and NOT the kernel's name
(``%step_fn.24 = ... custom-call(...)``): which kernel it is cannot be read
from the trace until the program names its kernels.  The host's
``TraceAnnotation`` spans are events of the host plane's ``python3`` line,
on the same clock.
"""

from __future__ import annotations

import re
import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path):
    """The trace as plain data.  ``path``: an ``.xplane.pb`` or a
    ``.json.gz`` written by ``dump``."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def dump(trace, path):
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def trim(trace, start_ns, end_ns, keep_lines=(OPS_LINE, MODULES_LINE)):
    """The part of a trace inside [start_ns, end_ns): device lines named
    in ``keep_lines`` and the benchmark's own host spans."""
    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith(DEVICE_PREFIX)
        lines = []
        for line in plane["lines"]:
            if device and line["name"] not in keep_lines:
                continue
            ev = [e for e in line["events"]
                  if start_ns <= e[1] < end_ns
                  and (device or e[0].startswith(SPAN_PREFIX))]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(trace):
    """The benchmark's ``TraceAnnotation`` spans: [name, start, dur]
    sorted by start, from every host line."""
    spans = [e for p in trace["planes"]
             if not p["name"].startswith(DEVICE_PREFIX)
             for line in p["lines"] for e in line["events"]
             if e[0].startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e[1])


def window_of(trace):
    """(start_ns, end_ns) of the measured part of the trace: from the
    start of the first benchmark span to the end of the last; with no
    span, the extent of the device events."""
    spans = host_spans(trace)
    if not spans:
        spans = [e for p in device_planes(trace)
                 for e in line_events(p, OPS_LINE)]
    if not spans:
        return None
    return (min(e[1] for e in spans), max(e[1] + e[2] for e in spans))


def merged_intervals(events, start_ns, end_ns):
    """Union of the events' intervals, clipped to the window, as a sorted
    list of [start, end]."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, start_ns), min(s + d, end_ns)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(trace, window=None):
    """(busy_s averaged over the device planes, window_s)."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not window or not planes:
        return None, None
    busy = [sum(b - a for a, b in merged_intervals(
        line_events(p, OPS_LINE), *window)) for p in planes]
    return sum(busy) / len(busy) / 1e9, (window[1] - window[0]) / 1e9


def op_seconds(trace, window=None):
    """{operation name: device seconds} over the first device plane's
    ``XLA Ops`` inside the window."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not window or not planes:
        return {}
    total = {}
    for name, s, d in line_events(planes[0], OPS_LINE):
        if window[0] <= s < window[1]:
            total[name] = total.get(name, 0.0) + d / 1e9
    return total


def matching_events(trace, line_name, substrings, window=None):
    """Events of the first device plane's line whose name holds any of
    ``substrings``, inside the window."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not window or not planes:
        return []
    return [e for e in line_events(planes[0], line_name)
            if window[0] <= e[1] < window[1]
            and any(s in e[0] for s in substrings)]


def idle_gaps(trace, window=None, top=10):
    """The longest gaps in which no operation ran on the first device,
    each under the name of the benchmark span that covered its middle
    (``(none)`` where no span did): [[name, seconds], ...], gaps under
    one name summed, longest first."""
    window = window or window_of(trace)
    planes = device_planes(trace)
    if not window or not planes:
        return []
    busy = merged_intervals(line_events(planes[0], OPS_LINE), *window)
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    spans = host_spans(trace)
    total = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        # the innermost (latest-starting) span over the gap's middle
        name = "(none)"
        for sname, s, d in spans:
            if s <= mid < s + d:
                name = sname
            elif s > mid:
                break
        total[name] = total.get(name, 0.0) + (b - a) / 1e9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]


def short_name(text):
    """An operation's kind from its whole text: the name without ``%``
    and its number, with a custom call's target: ``fusion``,
    ``convolution_add_fusion``, ``step_fn[tpu_custom_call]``."""
    name = re.sub(r"\.\d+$", "", text.split(" = ")[0].lstrip("%"))
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name}[{target.group(1)}]" if target else name[:64]


def top_ops(trace, window=None, top=10):
    """The kinds of operation that took most device time: [[kind,
    seconds], ...].  A ``while`` counts its body's operations again."""
    total = {}
    for text, secs in op_seconds(trace, window).items():
        kind = short_name(text)
        total[kind] = total.get(kind, 0.0) + secs
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]
