"""Merge/tail the JSONL telemetry streams; export a Perfetto trace.

``bin/hetu_trace.py`` is the CLI.  Input is any number of
contract-shaped JSONL files (``{"t", "event", ...}`` — the merged
``$HETU_TELEMETRY_LOG`` or the per-stream legacy logs); with no paths
given, every stream log currently configured in the environment is
read.  Output:

- default: the merged, time-sorted stream as JSONL on stdout (the
  one ``tail | jq`` pipeline, now across all streams at once);
- ``--export trace.json``: a Chrome/Perfetto-loadable trace —
  duration-carrying records (``span``/``serve_step``/``serve_prefill``/
  ``req_span``/``compile``) become ``"X"`` complete events laid out per
  pid/thread track (``compile`` records, what JAX traced, lowered,
  compiled or loaded, on a track of their own beside their thread's,
  ``compile:<thread>``: the phase is the event's name, ``fun`` and the
  span it was built under, ``parent``, its arguments, so a cold start
  shows which dispatch built what), ``gauge`` records become ``"C"`` counter tracks (occupancy,
  queue depth, blocks_free render as time series; ``serve_step``
  records contribute ``serve.queue_depth``/``serve.live`` counters
  too), everything else an ``"i"`` instant — plus a one-line summary
  on stdout.

Request-lifecycle tracks: ``req_span`` records (serving/metrics.py, one
per queue/kv_alloc/prefill/decode/requeue phase of each request) land
on a per-request track named ``req:<request_id>``, so one request's
whole lifecycle reads as a lane; flow arrows (``s``/``t``/``f`` events
keyed by the request id) connect its decode span into every engine
fused-step wave it participated in (``serve_step`` records carry the
per-wave request list).

Durations: a ``span``/``req_span`` record's ``t`` is its START epoch
and ``ms`` its length (events.py writes them that way); serving
step/prefill records timestamp the END of the phase, so the exporter
backdates their start by the duration field.

Nesting: a ``span`` record carries ``parent`` (the enclosing span's
name on its thread, None at a root), ``us`` (its start to the
microsecond; ``t`` has three decimals) and the identifier its root
shares with its children (``wave=``, ``step=``).  The exporter places a
span by ``us`` and clips a child that the rounding of ``ms`` let run
past its parent, so a wave draws as ``serve.wave`` holding
``serve.admit``, ``serve.wave.assemble``, ``serve.wave.dispatch``,
``serve.wave.sync`` and ``serve.wave.unpack``.  ``--check`` enforces the
span-nesting rule: a span that names a parent must find a span of that
name on its thread whose interval holds its start.

``--check`` validates every record against the event contract AND the
span-balance rule: every ``serve_admit`` must have a matching
``serve_finish`` (a request admitted but never retired is a leaked
slot or a crashed scheduler loop).  Fleet streams (``replica``-tagged
serve events from a ServingRouter) additionally pair admit/finish PER
REPLICA, with requests the router requeued off a dead replica
(``router_hop`` records) exempt — they must finish on *some* replica.
Balance is skipped when the input contains a ``flight_dump`` header —
a flight recording is by definition a mid-flight snapshot.

``--check`` also enforces the speculative-attribution rule: a
``req_retire`` record carrying spec fields must satisfy
``spec_accepted + spec_bonus + 1 == n_generated`` — every retired
token is the prefill sample, an accepted draft, or a bonus sample.
Rejected drafts (``spec_proposed - spec_accepted``) are exempt: they
cost compute, never sequence length.

``--check`` also enforces the KV-handoff pairing rule (ISSUE 12):
every ``kv_handoff_out`` must pair with a ``kv_handoff_in`` for the
same request (blocks that left a replica must land on one), and a
handed-off request must retire exactly once per router admission —
two ``serve_finish`` records (prefill clone + real request), with
``router_hop``-carrying requests exempt the same way span-balance
exempts them.

``--check`` also enforces the version-coherence rule (ISSUE 15): all
of one request's ``weight_version``-stamped records must agree on a
single version — a rolling weight swap only lands on a drained
replica, so a request that spans two versions without a ``router_hop``
requeue (or a handoff pair) means a swap landed under a live request.

``--check`` also enforces the scale-balance rule (ISSUE 16): every
``scale_up`` must pair with a ``replica_ready`` on the same replica
(the bring-up probe admitted it) and every ``scale_down`` with a
``replica_retired`` there, and each rid the retirement names as
drained must retire exactly once AFTER the drain, on a peer — never
on the draining replica itself, never twice, never zero times
(deadline-expired rids excepted).

``--check`` also enforces the lifecycle-residue rule: any
``serve_lifecycle_residue`` record fails the gate — a mixed-mode
request waited long between its claim and its first token outside
every wave (a paused host, or wave attribution that broke; the
assertion this replaced raised out of the scheduler).

``--check`` also enforces the window-ring rule (ISSUE 42): a
``serve_step`` record of an engine with window layers carries
``window_ring`` and ``window_held_max``, and no slot holds more
window-pool blocks than its ring.

``--check`` also enforces the wave-pairing rule (ISSUE 40): on every
thread, each ``serve.wave.dispatch`` span has exactly one
``serve.wave.sync`` span after it with the same ``wave=`` and
``kind=`` (the engine's invariant since it runs a wave ahead: what was
launched is landed once, as what it was launched as); the newest
dispatch of a thread may still be in flight where the stream ends.  The
exporter draws the same pair as a flow arrow (``wave_flow``) from the
dispatch slice to the sync slice, which sit in different ``serve.wave``
roots whenever the engine ran ahead.

``--check`` also enforces the lockdep rule (ISSUE 19): any
``lockdep_violation`` record fails the gate outright — the sanitizer
(``hetu_tpu/locks.py`` under ``HETU_LOCKDEP=1``) only emits one after
proving a lock-order inversion, a blocking call under a held lock, or
a hold past ``HETU_LOCKDEP_HOLD_MS``, so presence is the finding.
"""

from __future__ import annotations

import argparse
import json
import os

from .. import envvars
from .events import STREAMS, validate_record

# kind -> (duration field in ms, track name); t marks the end for the
# serving kinds (their emitter stamps after the phase completes)
_DUR_FIELDS = {
    "span": ("ms", None),              # name comes from the record
    "req_span": ("ms", None),          # name = the lifecycle phase
    "compile": ("ms", None),           # name = the phase (trace, lower, ..)
    "serve_prefill": ("prefill_ms", "serve.prefill"),
    "serve_step": ("decode_ms", "serve.decode"),
}
_T_IS_END = ("serve_prefill", "serve_step")

# serve_step fields worth a counter track alongside the wave span
_STEP_COUNTERS = (("queue_depth", "serve.queue_depth"),
                  ("live", "serve.live"))


def configured_logs():
    """Every stream log path currently set in the environment."""
    paths = []
    for env in list(STREAMS.values()) + ["HETU_TELEMETRY_LOG"]:
        if env:
            p = envvars.get_path(env)
            if p and p not in paths:
                paths.append(p)
    return paths


def read_events(paths, strict=False):
    """Parse + merge JSONL files, time-sorted.  Bad lines are counted,
    not fatal (a crashed writer may leave a torn tail) unless
    ``strict``."""
    events, bad = [], 0
    for path in paths:
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            continue
        for ln in lines:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                bad += 1
                if strict:
                    raise
                continue
            if isinstance(rec, dict) and "t" in rec and "event" in rec:
                rec["_src"] = os.path.basename(path)
                events.append(rec)
            else:
                bad += 1
    events.sort(key=lambda r: (r.get("t", 0.0)))
    return events, bad


def to_chrome_trace(events):
    """Chrome trace-event JSON (Perfetto-loadable): spans as complete
    ("X") events, gauges + serve_step depths as counter ("C") tracks,
    request lifecycles as per-request ``req:<id>`` tracks with flow
    arrows into the engine's fused-step wave spans, point events as
    instants ("i"), with thread-name metadata so tracks read as the
    emitting thread."""
    out = []
    tids = {}
    waves = []          # (start_us, end_us, pid, tid, request ids)
    decode_spans = {}   # request id -> (start_us, end_us, pid, tid)

    def tid_for(pid, name):
        key = (pid, name)
        if key not in tids:
            tids[key] = len(tids) + 1
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tids[key], "args": {"name": str(name)}})
        return tids[key]

    n_spans = 0
    for rec in events:
        kind = rec.get("event")
        pid = int(rec.get("pid", 0))
        if kind == "req_span":
            # lifecycle phases live on the request's own track
            track = f"req:{rec.get('request')}"
        elif kind == "compile":
            # beside the thread's spans, not among them: a program's
            # phases overlap the dispatch that built it
            track = f"compile:{rec.get('tid', 'events')}"
        else:
            track = rec.get("tid", rec.get("_src", "events"))
        tid = tid_for(pid, track)
        ts_us = float(rec.get("t", 0.0)) * 1e6
        if kind in ("span", "compile") \
                and isinstance(rec.get("us"), (int, float)):
            ts_us = float(rec["us"])
        args = {k: v for k, v in rec.items()
                if k not in ("t", "us", "event", "pid", "tid", "_src")
                and isinstance(v, (int, float, str, bool))}
        if kind == "compile" and isinstance(rec.get("parent"), dict):
            # serve.wave.dispatch(wave=3, kind=chunk, q=256)
            fields = dict(rec["parent"])
            args["parent"] = "{}({})".format(
                fields.pop("name", None),
                ", ".join(f"{k}={v}" for k, v in fields.items()))
        if kind == "gauge":
            out.append({"name": str(rec.get("name")), "cat": "gauge",
                        "ph": "C", "ts": ts_us, "pid": pid,
                        "tid": tid_for(pid, "counters"),
                        "args": {"value": rec.get("value")}})
            continue
        dur_spec = _DUR_FIELDS.get(kind)
        dur_ms = (rec.get(dur_spec[0])
                  if dur_spec is not None else None)
        if isinstance(dur_ms, (int, float)):
            dur_us = float(dur_ms) * 1e3
            if kind in _T_IS_END:
                ts_us -= dur_us
            name = (rec.get("name") or rec.get("phase")
                    or dur_spec[1] or kind)
            out.append({"name": str(name), "cat": kind, "ph": "X",
                        "ts": ts_us, "dur": dur_us, "pid": pid,
                        "tid": tid, "args": args})
            n_spans += 1
            if kind == "serve_step":
                for field, cname in _STEP_COUNTERS:
                    if isinstance(rec.get(field), (int, float)):
                        out.append({
                            "name": cname, "cat": "gauge", "ph": "C",
                            "ts": ts_us, "pid": pid,
                            "tid": tid_for(pid, "counters"),
                            "args": {"value": rec[field]}})
                reqs = rec.get("requests")
                if isinstance(reqs, (list, tuple)):
                    waves.append((ts_us, ts_us + dur_us, pid, tid,
                                  [str(r) for r in reqs],
                                  rec.get("step")))
            elif kind == "req_span" and rec.get("phase") == "decode":
                decode_spans[str(rec.get("request"))] = \
                    (ts_us, ts_us + dur_us, pid, tid)
        else:
            out.append({"name": str(kind), "cat": "event", "ph": "i",
                        "s": "t", "ts": ts_us, "pid": pid, "tid": tid,
                        "args": args})
    # flow arrows: each request's decode span -> the engine wave spans
    # it participated in (s on the request track, t bound inside each
    # wave slice, f back on the request track at retire)
    n_flows = 0
    for rid, (d0, d1, rpid, rtid) in sorted(decode_spans.items()):
        hits = [(w0, wpid, wtid, step)
                for w0, w1, wpid, wtid, reqs, step in waves if rid in reqs]
        if not hits:
            continue
        flow = {"name": "req_flow", "cat": "req", "id": rid}
        out.append({**flow, "ph": "s", "ts": d0, "pid": rpid,
                    "tid": rtid})
        for w0, wpid, wtid, step in sorted(
                hits, key=lambda hit: hit[:3]):
            # clamp into the decode span: the wave's backdated start
            # can drift past the request's retire stamp by scheduler-
            # loop overhead (the two are stamped at different points of
            # the same iteration), and flow steps must stay s <= t <= f.
            # The clamp can move a step out of its wave's slice, so the
            # step also NAMES its wave (the serve_step record's ``step``)
            out.append({**flow, "ph": "t",
                        "ts": min(max(w0, d0), d1),
                        "pid": wpid, "tid": wtid,
                        "args": {"wave": step}})
        out.append({**flow, "ph": "f", "bp": "e", "ts": d1,
                    "pid": rpid, "tid": rtid})
        n_flows += 1
    # a wave's launch and its landing, joined by their ``wave=``: the
    # two sit in different roots whenever the engine ran a wave ahead
    for i, pair in enumerate(wave_pairs(events)[0]):
        flow = {"name": "wave_flow", "cat": "wave", "id": f"wave:{i}",
                "args": {"wave": pair[0].get("wave"),
                         "kind": pair[0].get("kind")}}
        for rec, end in zip(pair, ({"ph": "s"}, {"ph": "f", "bp": "e"})):
            pid = int(rec.get("pid", 0))
            out.append({**flow, **end, "ts": _span_start_us(rec),
                        "pid": pid, "tid": tid_for(pid, rec.get(
                            "tid", rec.get("_src", "events")))})
    _clip_children(out)
    return {"traceEvents": out, "displayTimeUnit": "ms"}, n_spans


def _span_start_us(rec):
    """A ``span`` record's start to the microsecond (``t`` has three
    decimals)."""
    return float(rec.get("us", float(rec.get("t", 0.0)) * 1e6))


def wave_pairs(events):
    """A serving wave's ``serve.wave.dispatch`` span joined to its
    ``serve.wave.sync`` span by ``wave=``, thread by thread in time
    order (two engines that ran one after the other on a thread both
    count from 1).  Returns ``(pairs, problems)``: ``pairs`` the
    (dispatch, sync) records, ``problems`` what ``--check`` reports (a
    dispatch never landed or landed twice, a sync of nothing, a ``kind``
    that changed in flight).  Exempt: the newest dispatch of a thread
    (in flight where the stream ends) and a sync that precedes every
    dispatch of its thread (a stream cut at its head)."""
    by_thread = {}
    for e in events:
        if e.get("event") == "span" and e.get("name") in (
                "serve.wave.dispatch", "serve.wave.sync"):
            by_thread.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    pairs, problems = [], []
    for thread in by_thread.values():
        thread.sort(key=_span_start_us)
        flying = {}              # wave -> its dispatch record
        landed = set()
        seen_dispatch = False
        for e in thread:
            wave = e.get("wave")
            if e["name"] == "serve.wave.dispatch":
                if wave in flying:
                    problems.append(
                        f"wave-pairing: wave {wave!r} was dispatched "
                        f"twice with no sync between")
                flying[wave] = e
                landed.discard(wave)
                seen_dispatch = True
                continue
            d = flying.pop(wave, None)
            if d is None:
                if wave in landed:
                    problems.append(
                        f"wave-pairing: wave {wave!r} was synced twice")
                elif seen_dispatch:
                    problems.append(
                        f"wave-pairing: sync of wave {wave!r} has no "
                        f"dispatch")
                continue
            landed.add(wave)
            if d.get("kind") != e.get("kind"):
                problems.append(
                    f"wave-pairing: wave {wave!r} was dispatched as "
                    f"{d.get('kind')!r} and synced as {e.get('kind')!r}")
            pairs.append((d, e))
        newest = max(flying.values(), key=_span_start_us, default=None)
        for wave, d in flying.items():
            if d is not newest:
                problems.append(
                    f"wave-pairing: wave {wave!r} ({d.get('kind')!r}) was "
                    f"dispatched and never synced")
    return pairs, problems


def check_wave_pairing(events):
    """The wave-pairing rule (``wave_pairs``); a flight recording is a
    mid-flight snapshot and is exempt."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    return wave_pairs(events)[1]


def _clip_children(trace_events):
    """A viewer nests complete events of one track by containment; a
    child span's rounded length can overrun its parent's end by a
    microsecond, which would draw it beside the parent.  Clip it."""
    tracks = {}
    for ev in trace_events:
        if ev.get("ph") == "X" and ev.get("cat") == "span":
            tracks.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    for spans in tracks.values():
        open_ = []
        for ev in sorted(spans, key=lambda e: (e["ts"], -e["dur"])):
            while open_ and open_[-1]["ts"] + open_[-1]["dur"] <= ev["ts"]:
                open_.pop()
            if open_ and ev["args"].get("parent") == open_[-1]["name"]:
                end = open_[-1]["ts"] + open_[-1]["dur"]
                ev["dur"] = min(ev["dur"], end - ev["ts"])
            open_.append(ev)


def check_span_balance(events):
    """The request span-balance rule: every ``serve_admit`` must pair
    with a ``serve_finish`` for the same request id (and vice versa —
    a finish with no admit is a torn or miswired log).  Returns problem
    strings; empty on a balanced stream.  A stream containing a
    ``flight_dump`` header is a mid-flight snapshot and is exempt.

    Fleet streams (serve events tagged ``replica=<k>`` by the router's
    engines) are checked per replica too: an admit on replica k must
    finish ON replica k — a leaked slot on one replica is invisible to
    the set-based rule once a same-id request retires elsewhere —
    UNLESS a ``router_hop`` record shows the router requeued the
    request off a dead replica, in which case finishing on *some*
    replica is the contract (requeue hops are exempt from the
    per-replica pairing, like flight dumps are from the whole rule)."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    admits, finishes = {}, {}     # request id -> set of replica tags
    hopped = set()                # requests the router requeued
    for e in events:
        kind = e.get("event")
        if kind == "serve_admit":
            admits.setdefault(e.get("request"), set()).add(
                e.get("replica"))
        elif kind == "serve_finish":
            finishes.setdefault(e.get("request"), set()).add(
                e.get("replica"))
        elif kind == "router_hop":
            hopped.add(e.get("request"))
    problems = []
    for rid in sorted(str(r) for r in set(admits) - set(finishes)):
        problems.append(f"span-balance: request {rid!r} admitted but "
                        f"never finished/retired")
    for rid in sorted(str(r) for r in set(finishes) - set(admits)):
        problems.append(f"span-balance: request {rid!r} finished "
                        f"without a matching admit")
    for rid in sorted(admits, key=str):
        if rid not in finishes or rid in hopped:
            continue
        for rep in sorted(admits[rid] - finishes[rid],
                          key=lambda x: str(x)):
            if rep is None:
                continue   # untagged single-engine stream: set rule
            problems.append(
                f"span-balance: request {rid!r} admitted on replica "
                f"{rep} but finished elsewhere with no router_hop "
                f"(leaked slot?)")
    return problems


def check_gather_balance(events):
    """The gather-phase rule (embedding serving): every ``req_retire``
    carrying a ``gather_ms`` component must pair with a ``req_span``
    record of phase "gather" for the same request — a retirement that
    billed gather time without tracing the phase is a torn lifecycle
    (and the reverse, a gather span with no retirement, a leaked
    request).  GPT retirements (no ``gather_ms`` field) are skipped;
    flight-dump streams are exempt (mid-flight snapshot)."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    retired, spanned = set(), set()
    for e in events:
        kind = e.get("event")
        if kind == "req_retire" and e.get("gather_ms") is not None:
            retired.add(e.get("request"))
        elif kind == "req_span" and e.get("phase") == "gather":
            spanned.add(e.get("request"))
    problems = []
    for rid in sorted(str(r) for r in retired - spanned):
        problems.append(
            f"gather-balance: request {rid!r} retired with a "
            f"gather_ms component but no req_span phase=gather")
    for rid in sorted(str(r) for r in spanned - retired):
        problems.append(
            f"gather-balance: request {rid!r} traced a gather phase "
            f"but never retired with a gather_ms component")
    return problems


def check_handoff_balance(events):
    """The KV-handoff pairing rule (ISSUE 12): every ``kv_handoff_out``
    must pair with a ``kv_handoff_in`` for the same request — blocks
    that left a replica must land on one — and vice versa (an import
    with no export is a miswired log); the out/in counts must match
    (one landing per departure).  A handed-off request must also still
    retire exactly ONCE per router admission: the prefill clone and the
    real request each admit+finish on their engines, so its stream
    carries exactly two ``serve_finish`` records — more means a
    duplicate retirement leaked through, fewer a lost phase.  Requests
    with a ``router_hop`` are exempt from the finish count (a requeue
    legitimately re-runs a phase — the same exemption the per-replica
    span-balance rule grants), and flight-dump streams are exempt
    entirely (mid-flight snapshot)."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    outs, ins, finishes = {}, {}, {}
    hopped = set()
    for e in events:
        kind = e.get("event")
        rid = e.get("request")
        if kind == "kv_handoff_out":
            outs[rid] = outs.get(rid, 0) + 1
        elif kind == "kv_handoff_in":
            ins[rid] = ins.get(rid, 0) + 1
        elif kind == "serve_finish":
            finishes[rid] = finishes.get(rid, 0) + 1
        elif kind == "router_hop":
            hopped.add(rid)
    problems = []
    for rid in sorted(str(r) for r in set(outs) - set(ins)):
        problems.append(f"handoff: request {rid!r} exported KV "
                        f"(kv_handoff_out) that never landed "
                        f"(no kv_handoff_in)")
    for rid in sorted(str(r) for r in set(ins) - set(outs)):
        problems.append(f"handoff: request {rid!r} imported KV "
                        f"(kv_handoff_in) that was never exported")
    for rid in sorted(set(outs) & set(ins), key=str):
        if outs[rid] != ins[rid]:
            problems.append(
                f"handoff: request {rid!r} has {outs[rid]} exports "
                f"but {ins[rid]} imports")
    for rid in sorted(set(outs) & set(ins), key=str):
        n = finishes.get(rid, 0)
        if rid in hopped or n == 0:
            continue    # requeue re-runs a phase / engine log absent
        if n != 2:
            problems.append(
                f"handoff: request {rid!r} was handed off but "
                f"retired {n} time(s) — expected exactly 2 "
                f"(prefill clone + real request)")
    return problems


def check_scale_balance(events):
    """The elastic-fleet pairing rule (ISSUE 16): every ``scale_up``
    must pair with a ``replica_ready`` on the same replica (the
    bring-up probe passed and the replica was admitted) and every
    ``scale_down`` with a ``replica_retired`` there (the drain
    completed) — an unpaired scale event is a membership change that
    never finished.  Replica indexes are never reused (a retired slot's
    index stays burned), so one pairing per index is exact.  Each rid a
    ``replica_retired`` names as drained must retire exactly once on a
    PEER: never on the draining replica itself (a finish there after
    the drain means the corpse kept serving), never twice fleet-wide,
    and never zero times (a lost drain).  Rids that expired at their
    deadline (``router_deadline``) are exempt — expiry is an accounted
    outcome, not a loss — and streams without any ``serve_finish``
    records skip the rid-level audit (the engine log was not merged
    in).  The audit is ORDER-aware over the merged stream: a finish
    BEFORE the drain (a handed-off rid's prefill clone, say) is
    legitimate; what must hold is exactly one finish AFTER it, on a
    peer.  Flight-dump streams are mid-flight snapshots: exempt
    entirely."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    ups, downs, ready, retired = set(), set(), set(), set()
    drained = {}          # rid -> retiring replica index
    post = {}             # rid -> [replica finishing AFTER the drain]
    deadline = set()
    have_finish = False
    for e in events:
        kind = e.get("event")
        rep = e.get("replica")
        if kind == "scale_up":
            ups.add(rep)
        elif kind == "scale_down":
            downs.add(rep)
        elif kind == "replica_ready":
            ready.add(rep)
        elif kind == "replica_retired":
            retired.add(rep)
            for rid in e.get("rids") or ():
                drained[rid] = rep
                post.setdefault(rid, [])
        elif kind == "serve_finish":
            have_finish = True
            rid = e.get("request")
            if rid in drained:
                post[rid].append(rep)
        elif kind == "router_deadline":
            deadline.add(e.get("request"))
    problems = []
    for rep in sorted(ups - ready, key=str):
        problems.append(
            f"scale: scale_up of replica {rep} never reached "
            f"replica_ready — the bring-up probe failed or the scale "
            f"action was abandoned")
    for rep in sorted(downs - retired, key=str):
        problems.append(
            f"scale: scale_down of replica {rep} never reached "
            f"replica_retired — the drain was abandoned")
    if have_finish:
        for rid in sorted(drained, key=str):
            if rid in deadline:
                continue
            where = post[rid]
            if not where:
                problems.append(
                    f"scale: request {rid!r} was drained off retiring "
                    f"replica {drained[rid]} but never retired "
                    f"anywhere — a lost drain")
            elif drained[rid] in where:
                problems.append(
                    f"scale: request {rid!r} retired on replica "
                    f"{drained[rid]} AFTER it was drained off it — "
                    f"the draining replica kept serving")
            elif len(where) > 1:
                problems.append(
                    f"scale: drained request {rid!r} retired "
                    f"{len(where)} times after the drain (replicas "
                    f"{sorted(where)}) — expected exactly once on a "
                    f"peer")
    return problems


def check_tier_balance(events):
    """The tiered-KV pairing rule (ISSUE 17): a ``kv_spill`` opens a
    tier residency for its prefix; exactly ONE terminal event closes
    it — a ``kv_fetch`` (the payload was re-admitted into a pool) or a
    ``kv_tier_drop`` (ring overflow past a dead/absent PS, corruption,
    shutdown).  The audit is ORDER-aware per prefix hash over the
    merged stream: a second spill while the first residency is still
    open is a double-spill (a refresh must NOT re-emit); a fetch or
    drop with no open residency closes nothing (a fabricated fetch);
    and a residency still open at end-of-stream is a leak — completed
    runs call ``TieredKVStore.close()``, which drops every resident.
    Note a host->PS demotion inside the ladder is NOT an event (the
    residency merely moved rungs).  Flight-dump streams are mid-flight
    snapshots: exempt entirely."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    open_res = {}          # prefix hash -> count of open residencies
    problems = []
    for e in events:
        kind = e.get("event")
        if kind not in ("kv_spill", "kv_fetch", "kv_tier_drop"):
            continue
        h = e.get("prefix")
        n = open_res.get(h, 0)
        if kind == "kv_spill":
            if n > 0:
                problems.append(
                    f"tier-balance: prefix {h!r} spilled while already "
                    f"tier-resident — a refresh re-emitted kv_spill")
            open_res[h] = n + 1
        else:
            if n <= 0:
                problems.append(
                    f"tier-balance: prefix {h!r} saw {kind} with no "
                    f"open tier residency — nothing was spilled")
            else:
                open_res[h] = n - 1
    for h in sorted(k for k, n in open_res.items() if n > 0):
        problems.append(
            f"tier-balance: prefix {h!r} still tier-resident at end "
            f"of stream — no terminal kv_fetch/kv_tier_drop (close() "
            f"not called?)")
    return problems


def check_spec_attribution(events):
    """The speculative-attribution rule: per retired request, accepted
    draft tokens + bonus samples + the prefill token must equal the
    retired sequence length (``n_generated``) — a mismatch means the
    engine emitted tokens it never accounted for, or rolled back tokens
    it already reported.  Records WITHOUT spec fields (non-speculative
    engines) are skipped; rejected drafts are exempt by construction
    (they are not part of the sum).  Returns problem strings."""
    problems = []
    for e in events:
        if e.get("event") != "req_retire":
            continue
        acc = e.get("spec_accepted")
        if acc is None:
            continue
        bonus = e.get("spec_bonus", 0)
        n = e.get("n_generated")
        if not all(isinstance(v, int) for v in (acc, bonus, n)):
            problems.append(
                f"spec-attribution: request {e.get('request')!r} "
                f"carries non-integer spec fields")
            continue
        if acc + bonus + 1 != n:
            problems.append(
                f"spec-attribution: request {e.get('request')!r} "
                f"retired {n} tokens but accounts for "
                f"{acc} accepted + {bonus} bonus + 1 prefill "
                f"= {acc + bonus + 1}")
    return problems


def check_moe_attribution(events):
    """The MoE routing-attribution rule (ISSUE 20): per ``serve_step``
    record, routed + dropped expert assignments must equal the wave's
    token count × top_k × MoE layer count — capacity overflow re-routes
    a token to the residual path (``moe_dropped``), it NEVER vanishes
    from the ledger, so the two sides always balance.  Records without
    ``moe_routed`` (dense engines) are exempt; a MoE record missing any
    of its companion fields is itself a violation.  A record of an
    engine whose expert layers hold a SHARE of their experts (ISSUE 48)
    carries ``moe_held`` beside it, the routed assignments that landed
    on held experts: an integer within ``[0, moe_routed]``.  Returns
    problem strings."""
    problems = []
    for e in events:
        if e.get("event") != "serve_step":
            continue
        routed = e.get("moe_routed")
        if routed is None:
            continue
        fields = {k: e.get(f"moe_{k}")
                  for k in ("tokens", "dropped", "k", "layers")}
        if not all(isinstance(v, int) for v in fields.values()) \
                or not isinstance(routed, int):
            problems.append(
                f"moe-attribution: step {e.get('step')!r} carries "
                f"moe_routed without complete integer companions "
                f"{sorted(k for k, v in fields.items() if not isinstance(v, int))}")
            continue
        want = fields["tokens"] * fields["k"] * fields["layers"]
        if routed + fields["dropped"] != want:
            problems.append(
                f"moe-attribution: step {e.get('step')!r} routed "
                f"{routed} + dropped {fields['dropped']} = "
                f"{routed + fields['dropped']} expert assignments but "
                f"{fields['tokens']} tokens x top_k {fields['k']} x "
                f"{fields['layers']} MoE layer(s) = {want} — a token "
                f"left the routing ledger")
        held = e.get("moe_held")
        if held is not None and not (isinstance(held, int)
                                     and 0 <= held <= routed):
            problems.append(
                f"moe-attribution: step {e.get('step')!r} has "
                f"{held!r} assignments on held experts of {routed} "
                f"routed — a layer that holds a share of its experts "
                f"computes at most what was routed")
    return problems


def _check_state_scan(events, kind, what):
    """``slot_steps = live slots x layers`` on every ``serve_step`` that
    carries ``<kind>_slot_steps`` (``ServingMetrics.record_state_scan``'s
    payload): every live slot's state moves once a layer a wave, a dead
    slot's never.  Records without the field are exempt; one missing a
    companion field is itself a violation."""
    problems = []
    for e in events:
        if e.get("event") != "serve_step" or f"{kind}_slot_steps" not in e:
            continue
        steps, slots, layers = (e.get(f"{kind}_{k}") for k in (
            "slot_steps", "live_slots", "layers"))
        if not all(isinstance(v, int) for v in (steps, slots, layers)):
            problems.append(
                f"{kind}-attribution: step {e.get('step')!r} carries "
                f"{kind}_slot_steps without integer {kind}_live_slots and "
                f"{kind}_layers")
        elif steps != slots * layers:
            problems.append(
                f"{kind}-attribution: step {e.get('step')!r} counts {steps} "
                f"slot steps but {slots} live slot(s) x {layers} "
                f"{what} layer(s) = {slots * layers}")
    return problems


def check_ssm_attribution(events):
    """The state-traffic rule (ISSUE 37): a ``serve_step`` record of an
    engine with state-space layers carries ``ssm_slot_steps``, and it
    must equal the wave's live slots times the state-space layers
    (``ssm_live_slots`` x ``ssm_layers``).  Returns problem strings
    (``_check_state_scan``)."""
    return _check_state_scan(events, "ssm", "state-space")


def check_ret_attribution(events):
    """The same rule for an engine with power-retention layers (ISSUE
    44): ``ret_slot_steps = ret_live_slots x ret_layers`` on every
    ``serve_step`` that carries it.  Returns problem strings."""
    return _check_state_scan(events, "ret", "retention")


def check_window_ring(events):
    """The window-pool rule (ISSUE 42): a ``serve_step`` record of an
    engine with window layers carries ``window_ring`` (the blocks of a
    slot's ring) and ``window_held_max`` (the most window-pool blocks
    any slot holds): a slot never holds more than its ring, whatever
    its sequence's length.  Records without ``window_ring`` are exempt;
    one missing the companion field is itself a violation.  Returns
    problem strings."""
    problems = []
    for e in events:
        if e.get("event") != "serve_step" or "window_ring" not in e:
            continue
        ring, held = e.get("window_ring"), e.get("window_held_max")
        if not isinstance(ring, int) or not isinstance(held, int):
            problems.append(
                f"window-ring: step {e.get('step')!r} carries window_ring "
                f"without an integer window_held_max")
        elif held > ring:
            problems.append(
                f"window-ring: step {e.get('step')!r}: a slot holds {held} "
                f"window blocks, over its ring of {ring}")
    return problems


def check_span_nesting(events):
    """The span-nesting rule: a ``span`` record that names a ``parent``
    must find a span of that name on its own pid/thread whose interval
    holds its start (one millisecond of slack: ``ms`` is rounded).  A
    stream cut short (``--last``, a flight dump) may have lost the
    parent, which closes after its children: records newer than the
    last root are exempt."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    spans = [e for e in events if e.get("event") == "span"
             and isinstance(e.get("ms"), (int, float))]

    start_us = _span_start_us

    def end_us(e):
        return start_us(e) + e["ms"] * 1e3

    by_thread = {}
    for e in spans:
        by_thread.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    problems = []
    for thread in by_thread.values():
        roots_end = max((end_us(e) for e in thread
                         if e.get("parent") is None), default=None)
        open_ = []
        for e in sorted(thread, key=lambda e: (start_us(e), -e["ms"])):
            at = start_us(e)
            open_ = [p for p in open_ if end_us(p) + 1e3 >= at]
            parent = e.get("parent")
            if parent is not None and roots_end is not None \
                    and at <= roots_end \
                    and not any(p.get("name") == parent for p in open_):
                problems.append(
                    f"span-nesting: span {e.get('name')!r} names parent "
                    f"{parent!r} but no such span holds it")
            open_.append(e)
    return problems


def check_lifecycle_residue(events):
    """The lifecycle-residue rule: presence is the finding (the engine
    emits one only past the threshold the old assertion raised at)."""
    return [f"lifecycle-residue: request {e.get('request')!r} waited "
            f"{e.get('residue_ms')} ms of a {e.get('wall_ms')} ms "
            f"prefill wall outside every wave"
            for e in events if e.get("event") == "serve_lifecycle_residue"]


def check_lockdep(events):
    """The lockdep rule (ISSUE 19): a ``lockdep_violation`` record in
    the stream IS a finding — the sanitizer only emits after it proved
    a lock-order inversion (a cycle in the acquisition graph), a
    blocking call (PS RPC, multi-MB wire encode) under a held lock, or
    a hold longer than ``HETU_LOCKDEP_HOLD_MS``.  Presence fails the
    gate; the record's ``kind``/``lock``/``other``/``site`` fields and
    the in-process report (``analysis.concurrency.lockdep_report``)
    carry both acquisition stacks."""
    problems = []
    for e in events:
        if e.get("event") != "lockdep_violation":
            continue
        msg = (f"lockdep: {e.get('kind')} violation on lock "
               f"{e.get('lock')!r}")
        if e.get("other"):
            msg += f" vs {e.get('other')!r}"
        if e.get("site"):
            msg += f" at {e.get('site')}"
        problems.append(msg)
    return problems


def check_version_coherence(events):
    """The live-weight-sync rule (ISSUE 15): no retirement may mix
    tokens from two weight versions.  Every per-request record
    (``serve_submit``/``serve_admit``/``serve_finish``, ``req_span``,
    ``req_retire``) carries the ``weight_version`` tag of the engine
    that emitted it, and a rolling swap only lands on a DRAINED
    replica — so all of one request's records must agree on a single
    version.  The one legal exception is a router requeue
    (``router_hop`` names the request): a request admitted pre-swap
    that loses its replica legitimately re-admits — token-identically
    — on a peer that may already run the new version.  A prefill ->
    decode handoff pair is exempt the same way (each phase admits on
    its own replica; a rollout may pass between them).  Streams from a
    flight-recorder dump are mid-flight snapshots and are exempt, as
    are unversioned fleets (no ``weight_version`` tags anywhere)."""
    if any(e.get("event") == "flight_dump" for e in events):
        return []
    versions, exempt = {}, set()
    for e in events:
        kind = e.get("event")
        rid = e.get("request")
        if kind in ("router_hop", "kv_handoff_out", "kv_handoff_in"):
            exempt.add(rid)
            continue
        v = e.get("weight_version")
        if rid is None or v is None:
            continue
        versions.setdefault(rid, set()).add(v)
    problems = []
    for rid in sorted(versions, key=str):
        vs = versions[rid]
        if len(vs) > 1 and rid not in exempt:
            problems.append(
                f"version-coherence: request {rid!r} carries records "
                f"from weight versions {sorted(vs)} with no router "
                f"requeue — a swap landed under a live request")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="hetu_trace",
        description="Merge the telemetry JSONL streams; optionally "
                    "export a Chrome/Perfetto trace of the spans.")
    ap.add_argument("paths", nargs="*",
                    help="JSONL files (default: every HETU_*_LOG / "
                         "HETU_TELEMETRY_LOG set in the environment)")
    ap.add_argument("--export", metavar="TRACE_JSON",
                    help="write a Perfetto-loadable trace.json and "
                         "print a summary line instead of the stream")
    ap.add_argument("--last", type=int, default=None, metavar="N",
                    help="only the newest N records (tail semantics)")
    ap.add_argument("--events", default=None,
                    help="comma-separated kind filter "
                         "(e.g. span,serve_step)")
    ap.add_argument("--check", action="store_true",
                    help="validate every record against the event "
                         "contract AND the request span-balance rule "
                         "(every serve_admit has a serve_finish), the "
                         "speculative-attribution "
                         "rule (accepted + bonus + 1 == n_generated "
                         "per retired request), and the KV-handoff "
                         "pairing rule (every kv_handoff_out has a "
                         "kv_handoff_in, one retirement per "
                         "admission), the gather-balance rule "
                         "(every embed retirement billing gather_ms "
                         "traced a gather phase), and the "
                         "version-coherence rule (no retirement mixes "
                         "weight versions; a request only changes "
                         "version across a router requeue), and the "
                         "scale-balance rule (every scale_up pairs "
                         "with a replica_ready, every scale_down with "
                         "a replica_retired whose drained rids each "
                         "retire exactly once on a peer), and the "
                         "tier-balance rule (every kv_spill closes "
                         "with exactly one kv_fetch or kv_tier_drop "
                         "for its prefix), and the lockdep rule (any "
                         "lockdep_violation record — a proved lock-"
                         "order inversion, blocking-under-lock, or "
                         "long hold — fails the gate), and the MoE "
                         "routing-attribution rule (per serve_step, "
                         "routed + dropped == tokens x top_k x MoE "
                         "layers; dense steps exempt), and the "
                         "span-nesting rule (a span naming a parent "
                         "lies inside one) and the lifecycle-residue "
                         "rule (any serve_lifecycle_residue record "
                         "fails the gate), and the wave-pairing rule "
                         "(every serve.wave.dispatch span has exactly "
                         "one serve.wave.sync of its wave= and kind=); "
                         "exit 1 on violations")
    args = ap.parse_args(argv)

    paths = args.paths or configured_logs()
    if not paths:
        ap.error("no paths given and no HETU_*_LOG configured")
    events, bad = read_events(paths)
    if args.events:
        kinds = {k.strip() for k in args.events.split(",") if k.strip()}
        events = [e for e in events if e.get("event") in kinds]
    if args.last:
        events = events[-args.last:]

    if args.check:
        problems = []
        for rec in events:
            for p in validate_record(rec):
                problems.append(f"{rec.get('_src')}: {p}: "
                                f"{json.dumps(rec)[:160]}")
        balance = check_span_balance(events)
        problems.extend(balance)
        spec = check_spec_attribution(events)
        problems.extend(spec)
        handoff = check_handoff_balance(events)
        problems.extend(handoff)
        gather = check_gather_balance(events)
        problems.extend(gather)
        version = check_version_coherence(events)
        problems.extend(version)
        scale = check_scale_balance(events)
        problems.extend(scale)
        tier = check_tier_balance(events)
        problems.extend(tier)
        lockdep = check_lockdep(events)
        problems.extend(lockdep)
        moe = check_moe_attribution(events)
        problems.extend(moe)
        ssm = check_ssm_attribution(events)
        problems.extend(ssm)
        ret = check_ret_attribution(events)
        problems.extend(ret)
        ring = check_window_ring(events)
        problems.extend(ring)
        nesting = check_span_nesting(events)
        problems.extend(nesting)
        residue = check_lifecycle_residue(events)
        problems.extend(residue)
        pairing = check_wave_pairing(events)
        problems.extend(pairing)
        for p in problems:
            print(p)
        print(json.dumps({"records": len(events), "bad_lines": bad,
                          "contract_violations": len(problems),
                          "span_balance_violations": len(balance),
                          "spec_attribution_violations": len(spec),
                          "handoff_violations": len(handoff),
                          "gather_violations": len(gather),
                          "version_violations": len(version),
                          "scale_balance_violations": len(scale),
                          "tier_balance_violations": len(tier),
                          "lockdep_violations": len(lockdep),
                          "moe_attribution_violations": len(moe),
                          "ssm_attribution_violations": len(ssm),
                          "ret_attribution_violations": len(ret),
                          "window_ring_violations": len(ring),
                          "span_nesting_violations": len(nesting),
                          "lifecycle_residue_violations":
                              len(residue),
                          "wave_pairing_violations": len(pairing)}))
        return 1 if problems or bad else 0

    if args.export:
        trace, n_spans = to_chrome_trace(events)
        with open(args.export, "w") as f:
            json.dump(trace, f)
        print(json.dumps({
            "records": len(events), "bad_lines": bad,
            "spans": n_spans,
            "trace_events": len(trace["traceEvents"]),
            "out": args.export}))
        return 0

    for rec in events:
        print(json.dumps(rec))
    return 0
