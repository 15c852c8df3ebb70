"""The program's own spans and names in a profiler trace.

Since PR 25 the program under test writes two things into the profiler's
trace that the readers here turn into per-layer metrics (PROGRAM_SPANS.md
has the names and where each lands):

* **host spans** ``hetu.<name>``: every ``hetu_tpu.telemetry.span`` is a
  ``TraceAnnotation`` on the host plane, on the device trace's clock,
  beside the benchmark's ``bench.*`` spans.  They nest by containment on a
  host line (``hetu.serve.wave`` holds ``hetu.serve.wave.sync``).
* **names on the device**: a Pallas kernel's ``name=`` is its HLO
  instruction's name, so its ``XLA Ops`` event text starts with
  ``%flash_fwd.3 = ...`` (``%jvp_flash_fwd_.7`` where a JAX transform
  wraps the call); a ``jax.named_scope`` is NOT in the event's text but in
  the ``tf_op`` stat of the event's METADATA (the operation's name stack,
  ``jit(_serve_mixed_paged)/sample/while/body/...:``), which
  ``xplane.load`` drops and ``ProfileData`` does not hand out.
  ``scoped_trace`` reads the profiler's file again and keeps that stat as
  ``trace["op_scopes"]``, parallel to the first device plane's ``XLA Ops``
  events; the recorded fixture carries the same key.

The device's timestamps are NOT on the host's clock to the millisecond:
in every trace looked at they lie 0.4-1.7 ms BEFORE the host's, by
another amount in every session (a program
"starts" on the device before the host has launched it).  A reader that
lays device time against host spans asks ``device_clock_lead`` for the
bounds that causality puts on that lead in its own trace.

A program without these (the parent of PR 25) gives every reader here
nothing to read: it returns None, the metric is left out, nothing raises.
A reader that finds none of its names says so on an earlier line
(``missing``), so that a rename shows as a missing metric, not a zero.
"""

from __future__ import annotations

import bisect
import gzip
import re

from benchmarks import xplane

PREFIX = "hetu."
SCOPE_STAT = "tf_op"
# the runtime's own host events around a program's run (host tracer
# level 1): the call that hands it to the device, and the device's
# completion arriving on the host
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"


def missing(data, reader, what):
    """One earlier line of the run naming what a reader did not find."""
    harness = data.get("harness")
    if harness is not None:
        harness.log(line="metric_missing", reader=reader, missing=what)


# --------------------------------------------------------------------- #
# host spans
# --------------------------------------------------------------------- #

def span_forest(trace, prefix=PREFIX, window=None):
    """The program's spans inside the window as nodes ``{"name" (without
    the prefix), "start", "end", "parent" (a node or None), "children"}``,
    nested by containment on each host line, in start order."""
    window = window or xplane.window_of(trace)
    nodes = []
    for plane in trace["planes"]:
        if plane["name"].startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            spans = sorted((e for e in line["events"]
                            if e[0].startswith(prefix)
                            and (window is None
                                 or window[0] <= e[1] < window[1])),
                           key=lambda e: (e[1], -e[2]))
            open_ = []
            for name, start, dur in spans:
                node = {"name": name[len(prefix):], "start": start,
                        "end": start + dur, "parent": None, "children": []}
                while open_ and open_[-1]["end"] < node["end"]:
                    open_.pop()
                if open_:
                    node["parent"] = open_[-1]
                    open_[-1]["children"].append(node)
                open_.append(node)
                nodes.append(node)
    return sorted(nodes, key=lambda n: n["start"])


def innermost_each(nodes, times):
    """For ascending ``times``, the innermost span over each (or None);
    ``nodes`` in start order, as ``span_forest`` returns them."""
    open_, i, out = [], 0, []
    for t in times:
        while i < len(nodes) and nodes[i]["start"] <= t:
            open_.append(nodes[i])
            i += 1
        open_ = [n for n in open_ if n["end"] > t]
        # the latest start among the spans still open is the deepest
        out.append(open_[-1] if open_ else None)
    return out


def time_under(nodes, intervals, names):
    """Of the ascending, disjoint ``intervals`` [a, b), the time that
    lies under a span named in ``names``, the innermost span over each
    moment deciding: an interval is cut wherever a span opens or closes
    and every piece goes to the span over its middle."""
    cuts = sorted({t for n in nodes for t in (n["start"], n["end"])})
    pieces = []
    for a, b in intervals:
        edges = ([a] + cuts[bisect.bisect_right(cuts, a):
                            bisect.bisect_left(cuts, b)] + [b])
        pieces.extend(zip(edges, edges[1:]))
    over = innermost_each(nodes, [(a + b) / 2 for a, b in pieces])
    return sum(b - a for (a, b), n in zip(pieces, over)
               if n is not None and n["name"] in names)


def device_clock_lead(trace):
    """Bounds (lo, hi), in ns, on how far the first device plane's
    timestamps lie BEFORE the host's, from causality alone: the k-th
    program cannot have started on the device before the runtime's k-th
    ``LAUNCH`` event opened on the host (lo = the largest such
    violation), nor have ended after its ``DONE`` event opened (hi = the
    smallest slack).  None where the trace does not hold one ``LAUNCH``
    and one ``DONE`` for every ``XLA Modules`` event (another tracer
    level, several chips, a program in flight at either end) or the
    bounds cross."""
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    modules = sorted(xplane.line_events(planes[0], xplane.MODULES_LINE),
                     key=lambda e: e[1])
    host = {LAUNCH: [], DONE: []}
    for plane in trace["planes"]:
        if plane["name"].startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for e in line["events"]:
                if e[0] in host:
                    host[e[0]].append(e[1])
    if not modules or not (len(host[LAUNCH]) == len(host[DONE])
                           == len(modules)):
        return None
    lo = max(t - m[1] for t, m in zip(sorted(host[LAUNCH]), modules))
    hi = min(t - (m[1] + m[2]) for t, m in zip(sorted(host[DONE]), modules))
    return (lo, hi) if lo <= hi else None


# --------------------------------------------------------------------- #
# device operations
# --------------------------------------------------------------------- #

_TRANSFORMED = re.compile(
    r"^(?:(?:jvp|transpose|vmap|remat|checkpoint|custom_jvp|custom_vjp)_)+"
    r"(.+?)_+$")


def op_name(text):
    """An ``XLA Ops`` event's own instruction name, without ``%`` and its
    number: ``%flash_fwd.3 = bf16[...] custom-call(...)`` -> ``flash_fwd``.
    (Substring search in the whole text would also find the operations
    that take ``%flash_fwd.3`` as an operand.)  Traced under a JAX
    transform the name arrives wrapped, ``jvp(flash_fwd)`` spelt
    ``%jvp_flash_fwd_.7``: the wrapper is taken off, so the kernel a VJP
    node recomputes counts as the kernel it is."""
    name = re.sub(r"\.\d+$", "", text.split(" = ")[0].lstrip("%"))
    wrapped = _TRANSFORMED.match(name)
    return wrapped.group(1) if wrapped else name


def window_ops(trace, window=None):
    """(index, [text, start, dur]) of the first device plane's ``XLA Ops``
    events inside the window, in start order (outer before inner)."""
    window = window or xplane.window_of(trace)
    planes = xplane.device_planes(trace)
    if not window or not planes:
        return []
    events = xplane.line_events(planes[0], xplane.OPS_LINE)
    inside = [(i, e) for i, e in enumerate(events)
              if window[0] <= e[1] < window[1]]
    return sorted(inside, key=lambda ie: (ie[1][1], -ie[1][2]))


def top_level(trace, window=None):
    """([text, start, dur], name stack) of the operations inside the
    window that no other operation's event contains: a ``while`` is
    kept, its body's operations are not, so a sum over the result counts
    no time twice.  ``trace`` carries ``op_scopes``.  The profiler gives
    a ``while`` itself no name stack: it takes that of the first
    operation inside it that has one (``.../sample/while/body/...``)."""
    scopes = trace["op_scopes"]
    table, index = scopes["table"], scopes["index"]
    out, end = [], -1.0
    for i, e in window_ops(trace, window):
        stack = table[index[i]]
        if e[1] >= end:
            out.append([e, stack])
            end = e[1] + e[2]
        elif stack and not out[-1][1]:
            out[-1][1] = stack
    return out


def _interned(stacks):
    """``op_scopes`` of a sequence of name stacks: each distinct stack
    once in ``table``, an ``index`` into it for every operation."""
    table, index, seen = [], [], {}
    for stack in stacks:
        if stack not in seen:
            seen[stack] = len(table)
            table.append(stack)
        index.append(seen[stack])
    return {"table": table, "index": index}


def scoped_trace(data):
    """``data["trace"]`` with ``op_scopes`` (see the module's docstring),
    read once a run from the profiler's file; None where neither the
    trace nor a file has them."""
    trace = data["trace"]
    if "op_scopes" in trace:
        return trace
    if "scoped_trace" not in data:
        data["scoped_trace"] = None
        harness = data.get("harness")
        path = harness and xplane.find_xplane(harness.trace_dir)
        if path:
            scopes = read_scopes(path)
            if scopes is not None:
                data["scoped_trace"] = dict(trace, op_scopes=scopes)
    return data["scoped_trace"]


def record(path, start_ns, end_ns):
    """A slice [start_ns, end_ns) of a profiler's file as the plain data
    a fixture keeps: the first device plane's ``XLA Ops`` and ``XLA
    Modules`` events, the host's ``hetu.*`` and ``bench.*`` spans, and
    ``op_scopes`` for the kept operations (``xplane.dump`` writes it)."""
    trace, scopes = xplane.load(path), read_scopes(path)
    out = {"planes": []}
    first_device = True
    for plane in trace["planes"]:
        device = plane["name"].startswith(xplane.DEVICE_PREFIX)
        if device and not first_device:
            continue
        first_device = first_device and not device
        lines = []
        for line in plane["lines"]:
            if device and line["name"] not in (xplane.OPS_LINE,
                                               xplane.MODULES_LINE):
                continue
            keep = [i for i, e in enumerate(line["events"])
                    if start_ns <= e[1] < end_ns
                    and (device or e[0] in (LAUNCH, DONE)
                         or e[0].startswith((PREFIX, xplane.SPAN_PREFIX)))]
            if not keep:
                continue
            lines.append({"name": line["name"],
                          "events": [line["events"][i] for i in keep]})
            if device and line["name"] == xplane.OPS_LINE and scopes:
                out["op_scopes"] = _interned(
                    scopes["table"][scopes["index"][i]] for i in keep)
        if lines:
            out["planes"].append({"name": plane["name"], "lines": lines})
    return out


def read_scopes(path):
    """The name stack (``tf_op``) of every ``XLA Ops`` event of the first
    device plane of an ``.xplane.pb`` (or ``.xplane.pb.gz``), in the
    events' order, as ``{"table": [stack, ...], "index": [i, ...]}``;
    None when no operation has one.

    The stat sits on the event's METADATA (``XEventMetadata.stats``),
    which ``jax.profiler.ProfileData`` does not hand out, so the few
    messages involved are read from the protobuf's wire format here:
    ``XSpace.planes=1``; ``XPlane.name=2 lines=3 event_metadata=4
    stat_metadata=5``; ``XLine.name=2 events=4``; ``XEvent.metadata_id=1``;
    ``XEventMetadata.stats=5``; ``XStat.metadata_id=1 str_value=5
    ref_value=7``; ``XStatMetadata.name=2``; a map entry is key=1
    value=2."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = f.read()
    for number, plane in _fields(space):
        if number != 1:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for n, value in _fields(plane):
            if n in parts:
                parts[n].append(value)
        if not parts[2] or not bytes(parts[2][0]).decode().startswith(
                xplane.DEVICE_PREFIX):
            continue
        stat_names = {}
        for entry in parts[5]:
            pair = dict(_fields(entry))
            stat_names[pair[1]] = bytes(
                dict(_fields(pair[2])).get(2, b"")).decode()
        stack_of = {}             # event metadata id -> name stack
        for entry in parts[4]:
            pair = dict(_fields(entry))
            for n, stat in _fields(pair[2]):
                if n != 5:
                    continue
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) != SCOPE_STAT:
                    continue
                stack = (stat_names.get(stat[7], "") if 7 in stat
                         else bytes(stat.get(5, b"")).decode())
                stack_of[pair[1]] = stack
        for line in parts[3]:
            line_parts = list(_fields(line))
            name = next((bytes(v).decode() for n, v in line_parts
                         if n == 2), "")
            if name != xplane.OPS_LINE:
                continue
            scopes = _interned(
                stack_of.get(dict(_fields(event)).get(1), "")
                for n, event in line_parts if n == 4)
            return scopes if any(scopes["table"]) else None
    return None


def _fields(message):
    """(field number, value) over a protobuf message's bytes: an int for
    a varint field, a memoryview for a length-delimited one."""
    view = memoryview(message)
    i, n = 0, len(view)
    while i < n:
        key, i = _varint(view, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(view, i)
        elif wire == 2:
            size, i = _varint(view, i)
            value, i = view[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = view[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield key >> 3, value


def _varint(view, i):
    shift = out = 0
    while True:
        byte = view[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def under_scope(stack, scopes):
    """Whether a name stack (``jit(f)/jit(main)/sample/while/body``,
    ``.../transpose(jvp(FlashAttention))/...``) passes through any of
    ``scopes``: a whole path component, bare or inside transforms."""
    for part in stack.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            return True
    return False
