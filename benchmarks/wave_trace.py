"""A serving wave's identity in a profiler trace: kind, number, order.

Since PR 40 the program says what a wave is in the only sink a run of the
benchmark reads (PROGRAM_SPANS.waves.md has every field and scope):

* **on the host**: the fields a ``telemetry.span`` is OPENED with are the
  stats of its ``hetu.<name>`` host event.  ``serve.wave.dispatch`` carries
  ``wave=`` (the wave's number), ``kind=`` (``chunk`` / ``verify`` /
  ``decode``), ``q=`` (its q-block bucket) and ``ahead=``;
  ``serve.wave.sync`` ``wave=``, ``kind=``, ``ahead=``; the root
  ``serve.wave`` ``order=`` (``ahead`` / ``inorder`` / ``first``).
  ``xplane.load`` keeps an event's name and times only, so ``spans`` reads
  the profiler's file again, once a run, and keeps the stats as
  ``trace["span_fields"]`` (the recorded fixture carries the same key).
* **on the device**: ``_mixed_step`` traces its body under ONE outer
  ``jax.named_scope``, ``wave_chunk`` / ``wave_verify`` / ``wave_decode``,
  so an operation's name stack reads ``jit(_serve_mixed_paged)/wave_chunk/
  attention/...``.  The two wave programs are both
  ``jit__serve_mixed_paged(<id>)`` on the ``XLA Modules`` line; ``waves``
  labels each such event by the scope of any operation inside its
  interval (a ``while`` and the compiler's ``ragged-dot-none`` carry no
  stack: one operation that does is enough).

``waves`` then joins the two: the k-th ``_serve_mixed_paged`` module event
of the trace is the k-th ``serve.wave.dispatch`` span's wave (a module that
began before the first dispatch span of the trace was launched before the
trace began and is left out).  Where a scope label and its span's ``kind=``
disagree, or a module precedes its own dispatch by more than the device
clock's lead allows (``program_trace.device_clock_lead``), the join is
wrong and every reader built on it returns None with a ``metric_missing``
line: a wrong label must never become a number.

A program without these (the parent of PR 40: spans with no stats, no
``wave_*`` scope) gives every reader here nothing to read.
"""

from __future__ import annotations

import bisect

from benchmarks import program_trace, xplane

WAVE_MODULE = "_serve_mixed_paged"
KIND_SCOPES = {"wave_chunk": "chunk", "wave_verify": "verify",
               "wave_decode": "decode"}
ROOT, DISPATCH, SYNC = "serve.wave", "serve.wave.dispatch", "serve.wave.sync"
# where the trace cannot bound the device clock's lead, the most it was
# ever seen to be (PERF.md section 3: 0.13-1.71 ms), doubled
LEAD_MAX_NS = 3.5e6


def read_span_fields(path):
    """[[name, start_ns, duration_ns, {stat: value}], ...] for every
    ``hetu.*`` host event of a profiler's file, in start order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(program_trace.PREFIX):
                    out.append([e.name, float(e.start_ns),
                                float(e.duration_ns),
                                {k: v for k, v in e.stats}])
    return sorted(out, key=lambda e: e[1])


def spans(data):
    """The program's spans WITH the fields they were opened with, as
    nodes ``{"name" (without the prefix), "start", "end", "fields"}`` in
    start order, read once a run; None where neither the trace nor a
    profiler's file holds a single field (the parent's program)."""
    if "wave_spans" not in data:
        data["wave_spans"] = None
        trace = data["trace"]
        raw = trace.get("span_fields")
        if raw is None:
            harness = data.get("harness")
            path = harness and xplane.find_xplane(harness.trace_dir)
            raw = read_span_fields(path) if path else []
        if any(f for _, _, _, f in raw):
            data["wave_spans"] = [
                {"name": n[len(program_trace.PREFIX):], "start": s,
                 "end": s + d, "fields": f} for n, s, d, f in raw]
    return data["wave_spans"]


def _module_kinds(scoped, modules):
    """The kind of each module event, from the ``wave_*`` component of
    the name stack of any operation that starts inside it (None where no
    operation has one, "mixed" where two kinds meet in one module)."""
    table = scoped["op_scopes"]["table"]
    index = scoped["op_scopes"]["index"]
    kind_of = []
    for stack in table:
        found = {KIND_SCOPES[c] for c in stack.split("/")
                 if c in KIND_SCOPES}
        kind_of.append(found.pop() if len(found) == 1 else None)
    events = xplane.line_events(xplane.device_planes(scoped)[0],
                                xplane.OPS_LINE)
    ops = sorted((e[1], kind_of[j]) for e, j in zip(events, index)
                 if kind_of[j])
    starts = [o[0] for o in ops]
    out = []
    for _, s, d in modules:
        inside = {k for _, k in ops[bisect.bisect_left(starts, s):
                                    bisect.bisect_left(starts, s + d)]}
        out.append(inside.pop() if len(inside) == 1
                   else "mixed" if inside else None)
    return out


def waves(data):
    """The trace's serving waves, joined: ``{"modules": [...], "roots":
    [...], "window": (start, end)}``.  ``modules`` are the
    ``_serve_mixed_paged`` module events that START inside the measured
    window, each ``{"start", "end", "kind", "wave", "q", "ahead"}`` with
    the kind its scopes and its dispatch span agree on; ``roots`` the
    window's ``serve.wave`` spans that launched or landed a wave, each
    ``{"start", "end", "order", "launched", "landed"}``.  None, with a
    ``metric_missing`` line, where the program says none of it or the
    join does not hold (see the module's docstring).  Computed once a
    run."""
    if "waves" in data:
        return data["waves"]
    data["waves"] = None

    def none(what):
        program_trace.missing(data, "wave_trace", what)

    nodes = spans(data)
    scoped = program_trace.scoped_trace(data)
    planes = xplane.device_planes(data["trace"])
    window = xplane.window_of(data["trace"])
    if nodes is None or not any("kind" in n["fields"] for n in nodes):
        return none("span fields (wave=, kind=, order=)")
    if scoped is None or not planes or not window:
        return none("name stacks")
    modules = sorted((e for e in xplane.line_events(
        planes[0], xplane.MODULES_LINE) if WAVE_MODULE in e[0]),
        key=lambda e: e[1])
    dispatches = [n for n in nodes if n["name"] == DISPATCH]
    if not modules or not dispatches:
        return none([WAVE_MODULE, DISPATCH])
    kinds = _module_kinds(scoped, modules)
    if not any(kinds):
        return none(sorted(KIND_SCOPES))
    lead = program_trace.device_clock_lead(data["trace"])
    lead_hi = lead[1] if lead else LEAD_MAX_NS
    # a module launched before the trace began has no dispatch span
    first = dispatches[0]["start"]
    early = sum(1 for m in modules if m[1] + lead_hi < first)
    joined, wrong = [], []
    for (_, s, d), kind, span in zip(modules[early:], kinds[early:],
                                     dispatches):
        f = span["fields"]
        if kind != f.get("kind") or s + lead_hi < span["start"]:
            wrong.append({"wave": f.get("wave"), "scope": kind,
                          "span": f.get("kind"),
                          "module_before_dispatch_ns": span["start"] - s})
        joined.append({"start": s, "end": s + d, "kind": kind,
                       "wave": f.get("wave"), "q": f.get("q"),
                       "ahead": bool(f.get("ahead"))})
    harness = data.get("harness")
    inside = [m for m in joined if window[0] <= m["start"] < window[1]]
    counts = {}
    for m in inside:
        counts[m["kind"]] = counts.get(m["kind"], 0) + 1
    if harness is not None:
        harness.log(line="wave_kinds", modules_in_window=len(inside),
                    by_kind=counts, launched_before_the_trace=early,
                    device_clock_lead_ns=lead, disagreements=wrong[:8],
                    n_disagreements=len(wrong))
    if wrong:
        return none("a scope label that agrees with its span's kind= "
                    f"({len(wrong)} of {len(joined)} waves do not)")
    by_root = {}
    roots = [n for n in nodes if n["name"] == ROOT
             and window[0] <= n["start"] < window[1]]
    starts = [r["start"] for r in roots]
    for n in nodes:
        if n["name"] in (DISPATCH, SYNC):
            i = bisect.bisect_right(starts, n["start"]) - 1
            if i >= 0 and n["end"] <= roots[i]["end"]:
                by_root.setdefault(i, {})[n["name"]] = n["fields"].get("wave")
    data["waves"] = {
        "modules": inside, "window": window,
        "roots": [{"start": r["start"], "end": r["end"],
                   "order": r["fields"].get("order"),
                   "launched": by_root[i].get(DISPATCH),
                   "landed": by_root[i].get(SYNC)}
                  for i, r in enumerate(roots) if i in by_root]}
    return data["waves"]


def modules_of(data, kind):
    """The window's wave modules of one kind, or None with a
    ``metric_missing`` line."""
    w = waves(data)
    if w is None:
        return None
    found = [m for m in w["modules"] if m["kind"] == kind]
    if not found:
        program_trace.missing(data, "wave_trace", f"{kind} waves")
        return None
    return found


def top_level_in(data, kind):
    """([text, start, dur], name stack) of the window's TOP-LEVEL
    operations (``program_trace.top_level``) that start inside a module
    event of the waves of one kind, with the number of those waves:
    ``(operations, n_waves)``, once a run a kind; None where
    ``modules_of`` gives none."""
    cache = data.setdefault("wave_top_level", {})
    if kind not in cache:
        cache[kind] = None
        modules = modules_of(data, kind)
        scoped = program_trace.scoped_trace(data)
        if modules is not None and scoped is not None:
            starts = [m["start"] for m in modules]
            inside = []
            for e, stack in program_trace.top_level(scoped):
                i = bisect.bisect_right(starts, e[1]) - 1
                if i >= 0 and e[1] < modules[i]["end"]:
                    inside.append((e, stack))
            cache[kind] = (inside, len(modules))
    return cache[kind]


def clipped(intervals, holders):
    """The parts of the ascending, disjoint ``intervals`` [a, b) that
    lie inside any of the ascending, disjoint ``holders`` [a, b)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holders) and holders[j][1] <= a:
            j += 1
        k = j
        while k < len(holders) and holders[k][0] < b:
            lo, hi = max(a, holders[k][0]), min(b, holders[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def idle_gaps(data):
    """The device's idle gaps inside the window, moved onto the host's
    clock as ``readers/idle_under_spans.py`` moves them (by the middle of
    the bounds causality gives in this trace; unmoved where it gives
    none): ``[(a, b), ...]`` or None."""
    trace = data["trace"]
    window = xplane.window_of(trace)
    planes = xplane.device_planes(trace)
    if not window or not planes:
        return None
    busy = xplane.merged_intervals(
        xplane.line_events(planes[0], xplane.OPS_LINE), *window)
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    lead = program_trace.device_clock_lead(trace)
    shift = sum(lead) / 2 if lead else 0.0
    return [(a + shift, b + shift)
            for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def record(path, start_ns, end_ns):
    """``program_trace.record`` plus the stats of the kept ``hetu.*``
    spans (``span_fields``): the plain data a wave fixture keeps."""
    out = program_trace.record(path, start_ns, end_ns)
    out["span_fields"] = [e for e in read_span_fields(path)
                          if start_ns <= e[1] < end_ns]
    return out
