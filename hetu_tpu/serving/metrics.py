"""Serving telemetry: per-request lifecycle tracing + latency
aggregates + engine gauges.

Structured events flow through the ONE telemetry sink
(telemetry/events.py): ``{"t": <epoch>, "event": <kind>, **fields}``
records kept in memory and appended as JSONL to the ``serve`` stream —
``$HETU_SERVE_LOG`` (legacy path, one tail/jq pipeline with the failure
log) plus the merged ``$HETU_TELEMETRY_LOG``.

Request lifecycle (ISSUE 7 tentpole): every request is tracked through
submit -> queue -> kv_alloc -> prefill (per chunk) -> decode -> retire.
At retirement the tracker emits one ``req_span`` record per phase
(``t`` = the phase's START epoch, ``ms`` its length — the exact shape
``span`` records use, so ``hetu_trace --export`` renders each request
as its own Perfetto track) plus a ``req_retire`` record carrying the
full component breakdown:

    queue_ms        submit -> first admission attempt
    requeue_ms      head-of-queue wait while blocked (paged pool
                    exhaustion / prefix deferral); 0 when never blocked
    router_hop_ms   wall time lost to a FAILED placement before this
                    engine saw the request (the fleet router requeued
                    it off a dead/wedged replica — serving/router.py
                    credits the hop at re-submission); 0 un-routed
    handoff_ms      prefill->decode disaggregation detour before this
                    engine saw the request: clone prefill on the
                    prefill-heavy replica + KV export/import (the
                    router credits it when the imported blocks land);
                    0 without a handoff
    kv_alloc_ms     slot + block-table claim
    prefill_ms      prompt compute actually dispatched for this request
    chunk_stall_ms  prefill-phase wall not spent computing: the waves a
                    prompt chunk waited out because the wave's rows
                    were taken (``lc_stall``); else ~0 on a mixed-mode
                    engine, where a residue over the threshold is a
                    host pause or an accounting fault and is also
                    counted, see ``_retire``
    decode_ms       first token -> retirement

``snapshot()`` aggregates each component at p50/p95/p99 (over the
engine's life, or with ``since=mark()`` over what came after the mark) and
``explain_tail()`` names the component that dominates the p99-TTFT
tail — the "why was this request 40x the median" answer.

Memory: ``events`` is the full history only when a log path is
configured (the run is being deliberately observed and the JSONL has
it anyway); otherwise it is a bounded ring (``HETU_TELEMETRY_BUFFER``)
— a long-running engine no longer leaks one dict per record.
"""

from __future__ import annotations

import collections
import time

from .. import envvars, telemetry
from ..telemetry.metrics import percentile

import numpy as np

COMPONENTS = ("queue_ms", "requeue_ms", "router_hop_ms", "handoff_ms",
              "kv_alloc_ms", "prefill_ms", "chunk_stall_ms", "decode_ms")


def _pct(xs, q):
    """Seconds-valued percentile via THE shared interpolating helper
    (telemetry.metrics.percentile) — serving and the metrics registry
    now agree on what a p99 is."""
    xs = list(xs)
    return percentile(xs, q) if xs else None


class _Lifecycle:
    """Perf-counter timeline of one request, engine-side."""

    __slots__ = ("t_submit", "t_blocked", "t_claim", "kv_alloc_ms",
                 "prefill_ms", "t_first", "n_prefills", "hop_ms",
                 "handoff_ms", "stall_ms")

    def __init__(self, t_submit):
        self.t_submit = t_submit
        self.t_blocked = None     # first blocked admission attempt
        self.t_claim = None       # slot + KV claimed
        self.kv_alloc_ms = 0.0
        self.prefill_ms = 0.0     # dispatched prompt compute
        self.n_prefills = 0       # dispatches (chunks) it rode in
        self.t_first = None       # first token landed
        self.hop_ms = 0.0         # router requeue hops before us
        self.handoff_ms = 0.0     # prefill->decode handoff detour
        self.stall_ms = 0.0       # waves its chunk waited out


class MetricsCore:
    """Model-agnostic serving-telemetry base: the event pipeline,
    clock plumbing, and the submit/reject lifecycle every engine kind
    shares.  :class:`ServingMetrics` (GPT decode) and
    :class:`EmbedServingMetrics` (recommendation scoring) both build
    on this, so the fleet router / hetu_top / span-balance tooling
    read one event vocabulary regardless of workload."""

    def __init__(self, log_path=None, tags=None):
        self.log_path = (log_path if log_path is not None
                         else envvars.get_path("HETU_SERVE_LOG"))
        # fields stamped onto EVERY event this engine emits (the fleet
        # router tags each replica's engine with replica=<k>, which is
        # what lets hetu_top --fleet and the per-replica span-balance
        # rule tell N same-process engines apart in one merged stream)
        self.tags = dict(tags or {})
        cap = max(1, envvars.get_int("HETU_TELEMETRY_BUFFER"))
        # full in-memory history only when the run keeps a JSONL log
        # (deliberate observation); ring-buffered otherwise so a
        # long-running engine's memory stays bounded
        self.events = ([] if self.log_path
                       else collections.deque(maxlen=cap))
        self.submitted = 0
        self.rejected = 0
        self.finished = 0
        self._lc = {}              # request_id -> lifecycle record
        self._t0 = None
        self._t_last = None

    # ------------------------------------------------------------- #

    def event(self, kind, **fields):
        # a "t" field overrides the record's timestamp (req_span records
        # are START-stamped like `span` records)
        rec = telemetry.emit(kind, _stream="serve", _path=self.log_path,
                             _t=fields.pop("t", None),
                             **{**self.tags, **fields})
        self.events.append(rec)
        return rec

    def _mark(self):
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
        self._t_last = now

    # ONE epoch<->perf_counter offset for the whole process: deriving
    # it per call (time.time() - perf_counter() read back to back) let
    # scheduler preemption between the two clock reads skew req_span
    # stamps against serve_step stamps by milliseconds, which pushed
    # flow-arrow bindings outside their wave spans on loaded boxes —
    # a shared offset makes every exported timestamp mutually
    # consistent by construction (long-run clock drift is irrelevant
    # at trace granularity)
    _PERF_TO_EPOCH = time.time() - time.perf_counter()

    @classmethod
    def _epoch(cls, perf_t):
        """Map a perf_counter stamp onto the epoch clock the telemetry
        stream uses (so req_span tracks align with span tracks)."""
        return cls._PERF_TO_EPOCH + perf_t

    def _make_lc(self, t_submit):
        """Workload-specific lifecycle record for one request."""
        raise NotImplementedError

    def record_submit(self, request_id, queue_depth):
        self.submitted += 1
        self._lc[request_id] = self._make_lc(time.perf_counter())
        self.event("serve_submit", request=request_id,
                   queue_depth=queue_depth)

    def record_reject(self, request_id, queue_depth):
        self.rejected += 1
        self.event("serve_queue_reject", request=request_id,
                   queue_depth=queue_depth)

    def lc_hop(self, request_id, hop_ms):
        """Credit wall time the fleet router lost placing this request
        on a replica that died/wedged before it could retire (called by
        the router right after the re-submission; accumulates across
        hops)."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.hop_ms += float(hop_ms)


class ServingMetrics(MetricsCore):
    def __init__(self, log_path=None, tags=None):
        super().__init__(log_path=log_path, tags=tags)
        self.tokens_generated = 0
        self.ttfts = []            # seconds, submit -> first token
        self.latencies = []        # seconds, submit -> finish
        self.tpots = []            # per-request decode s/token means
        self.step_live = []        # live slots per fused step
        self.step_queue = []       # queue depth per fused step
        self.step_dt = []          # seconds per fused decode step
        self.step_tokens = []      # tokens EMITTED per fused step (==
        # live without speculation; 1..(k+1)*live with it — the TPOT
        # percentiles are computed from these real per-step counts)
        self.step_prefill = []     # prefill seconds folded into a step
        self.prefill_dt = []       # seconds per prefill dispatch
        self.prefill_reqs = 0      # requests prefilled
        self.prefill_batched = 0   # batched (fast-path) dispatches
        self.components = {c: [] for c in COMPONENTS}
        # per-request breakdowns explain_tail() slices (ring: the tail
        # report is about RECENT behavior, same cap as the event ring)
        cap = max(1, envvars.get_int("HETU_TELEMETRY_BUFFER"))
        self.breakdowns = collections.deque(maxlen=cap)
        self._slots = None
        # a dropless routed engine's running counts (``record_routed``);
        # ``moe_load`` is None until the first routed wave
        self.moe_assignments = 0
        self.moe_assignments_routed = 0
        self.moe_experts_touched = 0
        self.moe_kernel_waves = 0
        self.moe_load = None
        self.attn_ctx_tokens = 0
        self.attn_score_pairs = 0
        self.attn_tiles_live = 0
        self.attn_tiles_short = 0
        self.attn_q_tiles_moved = 0
        # an engine with window layers (``record_attention``)
        self.attn_window_ctx_tokens = 0
        self.attn_window_score_pairs = 0
        self.attn_window_bound_rows = 0
        self.window_blocks_recycled = 0
        # an engine whose full layers read the rows an indexer chose
        # (``record_sparse``)
        for key in self._SPARSE_COUNTS:
            setattr(self, key, 0)
        self.ssm_slot_steps = 0
        self.ssm_rows = 0
        self.ssm_chunk_pairs = 0
        self.ssm_kernel_slot_steps = 0
        self.ret_slot_steps = 0
        self.ret_rows = 0
        self.ret_chunk_pairs = 0
        self.ret_kernel_slot_steps = 0
        # an engine with delta-rule layers (``record_kda``)
        self.kda_slot_steps = 0
        self.kda_chunk_rows = 0
        self.kda_kernel_chunk_rows = 0
        self.wave_rows_live = 0
        self.wave_rows_computed = 0
        self.chunks_deferred = 0
        self.waves_ahead = 0
        self.rows_dead_ahead = 0
        # the float pool's wide write (``record_kv_write``)
        self.kv_write_pages = 0
        self.kv_write_rows = 0

    def _make_lc(self, t_submit):
        return _Lifecycle(t_submit)

    def record_wave(self, rows_live, rows_computed, deferred,
                    ahead=False, rows_dead=0):
        """One landed wave of any engine: ``rows_live`` (the q-blocks'
        live rows), ``rows_computed`` (the rows the wave's row-wise
        operators ran over, ``gpt_decode.wave_rows``: a chunk wave's
        packed rows, else slots x the padded q-block; live over computed
        is the packing's hit share), ``deferred`` (prompt chunks that
        waited this wave out because the wave's rows were taken),
        ``ahead`` (the wave was launched while the one before it was
        still in flight; over ``steps`` that is how often the engine
        runs one wave ahead) and ``rows_dead`` (of ``rows_live``, the
        rows computed for a request that had ended by the time they
        landed: an ``eos_id`` the wave before this one emitted).
        Running sums here (``snapshot(since=mark)`` windows them:
        ``waves_ahead``, ``rows_dead_ahead``) and the counters
        ``serve.wave.rows_live``, ``serve.wave.rows_computed``,
        ``serve.wave.chunks_deferred``, ``serve.wave.ahead``,
        ``serve.wave.rows_dead_ahead`` in ``telemetry``."""
        self.wave_rows_live += int(rows_live)
        self.wave_rows_computed += int(rows_computed)
        self.chunks_deferred += int(deferred)
        self.waves_ahead += bool(ahead)
        self.rows_dead_ahead += int(rows_dead)
        telemetry.inc("serve.wave.rows_live", int(rows_live))
        telemetry.inc("serve.wave.rows_computed", int(rows_computed))
        if deferred:
            telemetry.inc("serve.wave.chunks_deferred", int(deferred))
        if ahead:
            telemetry.inc("serve.wave.ahead")
        if rows_dead:
            telemetry.inc("serve.wave.rows_dead_ahead", int(rows_dead))

    def record_kv_write(self, pages, rows):
        """One wave whose K/V rows went to the float pool as PAGES
        (``kernels/paged_kv_write``: a q-block a page or more wide):
        ``pages``, the pages the wave's live rows touch under one table
        (one call's steps: a layer's, of either pool), and ``rows``, the
        live rows they hold.  Rows over pages x the block is how full
        the pages written are; pages a wave how much the write has to
        do.  ``kv_write_pages`` / ``kv_write_rows`` here and the counters
        ``serve.kv.write_pages`` / ``serve.kv.write_rows``."""
        self.kv_write_pages += int(pages)
        self.kv_write_rows += int(rows)
        telemetry.inc("serve.kv.write_pages", int(pages))
        telemetry.inc("serve.kv.write_rows", int(rows))

    def record_attention(self, ctx_tokens, score_pairs, window=None,
                         tiles=None):
        """One wave of any engine: ``ctx_tokens`` (the live slots'
        filled lengths after the wave's writes, once a wave) and
        ``score_pairs`` (the positions every live row sees): what a
        layer that attends over everything reads.  Running
        sums here (``snapshot(since=mark)`` windows them) and the
        counters ``serve.attn.ctx_tokens`` and
        ``serve.attn.score_pairs`` in ``telemetry``.  An engine with
        window layers adds ``window`` = (the positions a WINDOW layer
        had in sight, the pairs it scored, the window-pool blocks the
        wave's writes recycled, the live rows at a position of the
        window or more: those the band binds on):
        ``attn_window_ctx_tokens``, ``attn_window_score_pairs``,
        ``window_blocks_recycled``, ``attn_window_bound_rows`` and the
        counters ``serve.attn.window_ctx_tokens``,
        ``serve.attn.window_score_pairs``,
        ``serve.attn.window_bound_rows`` (``serve.kv.
        window_blocks_recycled`` is the manager's own).  An engine whose
        waves run a hand-paged attention kernel adds ``tiles`` = (the
        wave's live (slot, q-tile) steps of one call of the kernel,
        those of them it scored at the short height, the q-tiles (and o
        tiles) the call moved, live or dead:
        ``ragged_attention.tile_heights`` of the wave's ``q_len``; of a
        packed wave a row tile's visits to the slots whose rows cross
        it, ``row_tile_visits``, and the packed rows' tiles):
        ``attn_tiles_live``, ``attn_tiles_short``,
        ``attn_q_tiles_moved`` and the counters
        ``serve.attn.tiles_live``, ``serve.attn.tiles_short``,
        ``serve.attn.q_tiles_moved``."""
        self.attn_ctx_tokens += int(ctx_tokens)
        self.attn_score_pairs += int(score_pairs)
        telemetry.inc("serve.attn.ctx_tokens", int(ctx_tokens))
        telemetry.inc("serve.attn.score_pairs", int(score_pairs))
        if tiles is not None:
            live, short, moved = (int(v) for v in tiles)
            self.attn_tiles_live += live
            self.attn_tiles_short += short
            self.attn_q_tiles_moved += moved
            telemetry.inc("serve.attn.tiles_live", live)
            telemetry.inc("serve.attn.tiles_short", short)
            telemetry.inc("serve.attn.q_tiles_moved", moved)
        if window is not None:
            ctx, pairs, recycled, bound = (int(v) for v in window)
            self.attn_window_ctx_tokens += ctx
            self.attn_window_score_pairs += pairs
            self.window_blocks_recycled += recycled
            self.attn_window_bound_rows += bound
            telemetry.inc("serve.attn.window_ctx_tokens", ctx)
            telemetry.inc("serve.attn.window_score_pairs", pairs)
            telemetry.inc("serve.attn.window_bound_rows", bound)

    # ``record_sparse``'s running sums, in its arguments' order
    _SPARSE_COUNTS = ("sparse_rows", "sparse_rows_selecting",
                      "sparse_keys_in_sight", "sparse_keys_read",
                      "sparse_keys_needed", "index_ctx_tokens")

    def record_sparse(self, rows, selecting, in_sight, read, needed,
                      index_ctx):
        """One wave of an engine with layers that read the rows a
        learned indexer chose, every count summed over those layers:
        the wave's live rows (``sparse_rows``), those of them with more
        positions in sight than the indexer keeps
        (``sparse_rows_selecting``), the positions the rows had in sight
        (``sparse_keys_in_sight``), the cached rows they read, ``min(in
        sight, topk)`` a row (``sparse_keys_read``), the cached rows the
        wave could not do without, a slot's rows read or, where fewer,
        its positions in sight once (``sparse_keys_needed``), and the
        index keys the live slots held after the wave's writes, once a
        slot (``index_ctx_tokens``).  Running sums here and the counters
        ``serve.sparse.*`` / ``serve.index.ctx_tokens``."""
        self.sparse_rows += int(rows)
        self.sparse_rows_selecting += int(selecting)
        self.sparse_keys_in_sight += int(in_sight)
        self.sparse_keys_read += int(read)
        self.sparse_keys_needed += int(needed)
        self.index_ctx_tokens += int(index_ctx)
        telemetry.inc("serve.sparse.rows", int(rows))
        telemetry.inc("serve.sparse.rows_selecting", int(selecting))
        telemetry.inc("serve.sparse.keys_in_sight", int(in_sight))
        telemetry.inc("serve.sparse.keys_read", int(read))
        telemetry.inc("serve.sparse.keys_needed", int(needed))
        telemetry.inc("serve.index.ctx_tokens", int(index_ctx))

    def record_state_scan(self, kind, live_slots, rows, chunk_pairs,
                          layers, kernel_slots=0):
        """One wave of an engine with ``layers`` layers that scan a slot
        state, ``kind`` "ssm" (state-space mixers) or "ret" (power
        retention): ``live_slots`` (slots with a row in the wave: each
        one's state is read and written once a layer), ``rows`` (the
        wave's live rows) and ``chunk_pairs`` (the row pairs ``j <= i``
        inside the chunks of the q-blocks wider than one row: what the
        chunked form multiplies out besides); ``kernel_slots`` (the
        slots the wave's program took through a Pallas kernel by its own
        shape rule: of a retention wave those with a q-block wider than
        one row, whose chunked form ran through
        ``kernels/retention_scan``'s ``retention_chunk_scan``,
        ``retention_decode.takes_kernel`` of its head and q-block, and
        those with ONE row, whose step ran through the same file's
        ``retention_step_scan``, ``takes_kernel`` of its head alone: of
        a head of whole lane tiles every live slot; of a state-space
        wave those with ONE row,
        whose step ran through ``kernels/ssm_step``,
        ``ssm_decode.takes_kernel`` of its mixer's sizes; x layers the
        sum ``<kind>_kernel_slot_steps`` and the counter
        ``serve.<kind>.kernel_slot_steps``).  Running sums
        ``<kind>_slot_steps`` (live slots x layers), ``<kind>_rows`` and
        ``<kind>_chunk_pairs`` (each x layers) here, the counters
        ``serve.<kind>.slot_steps``, ``serve.<kind>.rows`` and
        ``serve.<kind>.chunk_pairs`` in ``telemetry``; returns the
        ``record_step`` payload whose ``slot_steps == live_slots *
        layers`` ``hetu_trace --check`` holds a ``serve_step`` to."""
        steps, rows = int(live_slots) * int(layers), int(rows) * int(layers)
        pairs = int(chunk_pairs) * int(layers)
        for key, n in (("slot_steps", steps), ("rows", rows),
                       ("chunk_pairs", pairs)):
            setattr(self, f"{kind}_{key}",
                    getattr(self, f"{kind}_{key}") + n)
        # (literal names: tests/test_telemetry.py reads them from here)
        ret = kind == "ret"
        telemetry.inc("serve.ret.slot_steps" if ret
                      else "serve.ssm.slot_steps", steps)
        telemetry.inc("serve.ret.rows" if ret else "serve.ssm.rows", rows)
        telemetry.inc("serve.ret.chunk_pairs" if ret
                      else "serve.ssm.chunk_pairs", pairs)
        if kernel_slots:
            by_kernel = int(kernel_slots) * int(layers)
            setattr(self, f"{kind}_kernel_slot_steps",
                    getattr(self, f"{kind}_kernel_slot_steps") + by_kernel)
            telemetry.inc("serve.ret.kernel_slot_steps" if ret
                          else "serve.ssm.kernel_slot_steps", by_kernel)
        return {"slot_steps": steps, "rows": rows,
                "live_slots": int(live_slots), "layers": int(layers)}

    def record_kda(self, one_row_slots, chunk_rows, layers, kernel=False):
        """One wave of an engine with ``layers`` delta-rule layers
        (``kda_decode``): ``one_row_slots`` (slots with ONE live row: each
        one's state takes one step of the recurrence a layer, read and
        written once) and ``chunk_rows`` (the live rows of the q-blocks
        wider than one row: what the chunked form runs over); ``kernel``
        whether the wave's program runs that form through
        ``kernels/kda_scan`` (``kda_decode.takes_kernel`` of its head and
        q-block).  Running sums ``kda_slot_steps``, ``kda_chunk_rows``
        and ``kda_kernel_chunk_rows`` (each x layers) here, the counters
        ``serve.kda.slot_steps``, ``serve.kda.chunk_rows`` and
        ``serve.kda.kernel_chunk_rows`` in ``telemetry``."""
        steps = int(one_row_slots) * int(layers)
        rows = int(chunk_rows) * int(layers)
        self.kda_slot_steps += steps
        self.kda_chunk_rows += rows
        telemetry.inc("serve.kda.slot_steps", steps)
        telemetry.inc("serve.kda.chunk_rows", rows)
        if kernel and rows:
            self.kda_kernel_chunk_rows += rows
            telemetry.inc("serve.kda.kernel_chunk_rows", rows)

    def record_routed(self, load, touched, kernel=False, routed=None):
        """One wave of a dropless routed engine: ``load`` [held experts]
        (assignments an expert the layers hold, summed over the routed
        layers), ``touched`` (held experts with load > 0, summed over
        them), ``routed`` (ALL the assignments the router made, of which
        the load's landed on held experts; None: every expert is held
        and it is the load's sum: ``serve.moe.assignments_routed``) and
        ``kernel`` (the wave's program ran its experts' products through
        ``kernels/grouped_matmul``: ``moe_decode.takes_kernel`` of its
        row count).  Running sums here (``snapshot(since=mark)`` windows
        them) and the counters ``serve.moe.assignments``,
        ``serve.moe.experts_touched``, ``serve.moe.kernel_waves`` and
        the gauge ``serve.moe.load_max`` (this wave's largest load) in
        ``telemetry``."""
        load = np.asarray(load, np.int64)
        assignments = int(load.sum())
        routed = assignments if routed is None else int(routed)
        self.moe_assignments += assignments
        self.moe_assignments_routed += routed
        self.moe_experts_touched += int(touched)
        self.moe_load = (load.copy() if self.moe_load is None
                         else self.moe_load + load)
        telemetry.inc("serve.moe.assignments", assignments)
        telemetry.inc("serve.moe.assignments_routed", routed)
        telemetry.inc("serve.moe.experts_touched", int(touched))
        if kernel:
            self.moe_kernel_waves += 1
            telemetry.inc("serve.moe.kernel_waves")
        telemetry.set_gauge("serve.moe.load_max", int(load.max()))

    # ------------------------------------------------------------- #
    # lifecycle marks (the engine calls these at phase boundaries)
    # ------------------------------------------------------------- #

    def lc_blocked(self, request_id):
        """The head-of-queue request could not admit this attempt
        (pool/slot exhaustion or prefix deferral): starts its requeue
        clock.  Idempotent — only the FIRST block mark counts."""
        lc = self._lc.get(request_id)
        if lc is not None and lc.t_blocked is None:
            lc.t_blocked = time.perf_counter()

    def lc_claimed(self, request_id, kv_alloc_ms):
        """Slot + KV claimed (queue/requeue phases end here)."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.t_claim = time.perf_counter()
            lc.kv_alloc_ms = float(kv_alloc_ms)

    def lc_prefill(self, request_id, dt_s, count=True):
        """Attribute one prefill dispatch's wall time to this request
        (a chunked prompt accumulates across chunks).  ``count=False``
        adds wall without counting a dispatch — the mixed-mode engine
        uses it to top a rider up to the full wave elapsed after the
        wave's unpack completes."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.prefill_ms += dt_s * 1e3
            if count:
                lc.n_prefills += 1

    def lc_stall(self, request_id, dt_s):
        """A wave this request's prompt chunk waited out (the wave's
        rows were taken by older chunks): wall that is the scheduler's
        doing, reported as ``chunk_stall_ms`` and not as a residue."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.stall_ms += dt_s * 1e3

    def lc_handoff(self, request_id, handoff_ms):
        """Credit the prefill->decode disaggregation detour: wall time
        between the router flipping this request into its prefill
        phase and the exported KV blocks landing on THIS engine's pool
        (called by the router right after the import)."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.handoff_ms += float(handoff_ms)

    # ------------------------------------------------------------- #

    def record_admit(self, request_id, slot, queue_wait_s, ttft_s):
        self._mark()
        self.ttfts.append(ttft_s)
        self.tokens_generated += 1          # prefill emits token #1
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.t_first = time.perf_counter()
        self.event("serve_admit", request=request_id, slot=slot,
                   queue_wait_s=round(queue_wait_s, 6),
                   ttft_s=round(ttft_s, 6))

    def record_prefill(self, n, bucket, dt_s, batched=False):
        """One prefill dispatch: ``n`` requests admitted in one jitted
        call (n > 1 only on the batched fast path) at prompt bucket
        ``bucket``."""
        self._mark()
        self.prefill_dt.append(dt_s)
        self.prefill_reqs += n
        if batched:
            self.prefill_batched += 1
        self.event("serve_prefill", n=n, bucket=bucket,
                   prefill_ms=round(dt_s * 1e3, 3), batched=bool(batched))

    def record_step(self, live, slots, queue_depth, dt_s, new_tokens,
                    prefill_s=0.0, step=None, requests=None,
                    end_perf=None, spec=None, mix=None, moe=None,
                    ssm=None, window=None, ret=None):
        """One fused decode step; ``prefill_s`` is the prefill wall time
        this scheduler iteration paid before decoding, so the per-step
        JSONL event attributes the phases separately (the masked vs
        ragged A/B reads these).  ``step``/``requests`` identify the
        wave and its participants — the trace exporter draws flow
        arrows from each request's lifecycle track into the wave.
        ``end_perf`` is the decode's end perf-stamp: the event's ``t``
        then marks the true phase end (the exporter backdates the wave
        start by ``decode_ms``) instead of the emission time, which
        trails it by the retire loop.

        ``new_tokens`` is the step's REAL emitted-token count (a
        speculative wave emits up to k+1 per slot): it lands in the
        event, in ``step_tokens``, and in the ``serve.tokens_per_step``
        histogram — TPOT is computed from these, never from a
        one-token-per-step assumption.  ``spec`` (a
        {k, proposed, accepted} dict) stamps a speculative wave's
        draft accounting onto the event.

        ``mix`` (a {q_prefill, q_verify, q_decode} dict, mixed-mode
        engines only) stamps the wave's per-mode q-token split onto the
        event — how many of the ragged dispatch's query rows were
        prompt prefill, spec-verify, and plain decode (hetu_top's
        mixed-wave columns and the tail report read these).

        ``moe`` (a {tokens, routed, dropped, k, layers, imb,
        drop_rate} dict, MoE engines only) stamps the step's expert
        routing outcome — ``routed + dropped == tokens * k * layers``
        is the invariant hetu_trace --check enforces, ``imb`` and
        ``drop_rate`` feed hetu_top's expert columns.  Dense steps
        carry no moe_* fields and the checker exempts them.

        ``ssm`` (``record_state_scan``'s {slot_steps, rows, live_slots, layers}
        dict, engines with state-space layers only) stamps the wave's
        state traffic: ``slot_steps == live_slots * layers`` is the
        invariant hetu_trace --check enforces; ``ret`` is the same
        payload for an engine's retention layers, under ``ret_*``.

        ``window`` ({ring, held_max}, engines with window layers only)
        stamps the window pool's ring and the most window blocks any
        slot holds: ``held_max <= ring`` is the invariant hetu_trace
        --check enforces."""
        self._mark()
        self._slots = slots
        self.step_live.append(live)
        self.step_queue.append(queue_depth)
        self.step_dt.append(dt_s)
        self.step_prefill.append(prefill_s)
        self.step_tokens.append(int(new_tokens))
        self.tokens_generated += new_tokens
        telemetry.observe("serve.tokens_per_step", int(new_tokens))
        fields = {}
        if step is not None:
            fields["step"] = step
        if requests is not None:
            fields["requests"] = list(requests)
        if end_perf is not None:
            fields["t"] = self._epoch(end_perf)
        if spec is not None:
            fields["spec_k"] = int(spec.get("k", 0))
            fields["spec_proposed"] = int(spec.get("proposed", 0))
            fields["spec_accepted"] = int(spec.get("accepted", 0))
        if mix is not None:
            fields["q_prefill"] = int(mix.get("q_prefill", 0))
            fields["q_verify"] = int(mix.get("q_verify", 0))
            fields["q_decode"] = int(mix.get("q_decode", 0))
        if moe is not None:
            fields["moe_tokens"] = int(moe.get("tokens", 0))
            fields["moe_routed"] = int(moe.get("routed", 0))
            fields["moe_dropped"] = int(moe.get("dropped", 0))
            if "held" in moe:
                # of the routed, those that landed on experts held here
                fields["moe_held"] = int(moe["held"])
            fields["moe_k"] = int(moe.get("k", 0))
            fields["moe_layers"] = int(moe.get("layers", 0))
            fields["moe_imb"] = round(float(moe.get("imb", 0.0)), 4)
            fields["moe_drop_rate"] = round(
                float(moe.get("drop_rate", 0.0)), 6)
        for kind, scan in (("ssm", ssm), ("ret", ret)):
            if scan is not None:
                for k in ("slot_steps", "rows", "live_slots", "layers"):
                    fields[f"{kind}_{k}"] = int(scan.get(k, 0))
        if window is not None:
            fields["window_ring"] = int(window["ring"])
            fields["window_held_max"] = int(window["held_max"])
        self.event("serve_step", live=live, queue_depth=queue_depth,
                   slots=slots, new_tokens=int(new_tokens),
                   prefill_ms=round(prefill_s * 1e3, 3),
                   decode_ms=round(dt_s * 1e3, 3), **fields)

    def record_finish(self, request_id, reason, n_generated, latency_s,
                      spec=None):
        """``spec`` ({accepted, proposed, bonus}, speculative engines
        only) rides into the req_retire record so hetu_trace --check
        can assert accepted + bonus + 1 == n_generated per request."""
        self._mark()
        self.finished += 1
        self.latencies.append(latency_s)
        self.event("serve_finish", request=request_id, reason=reason,
                   n_generated=n_generated, latency_s=round(latency_s, 6))
        return self._retire(request_id, n_generated, spec=spec)

    # ------------------------------------------------------------- #
    # retirement: component breakdown + per-phase req_span records
    # ------------------------------------------------------------- #

    def _retire(self, request_id, n_generated, spec=None):
        lc = self._lc.pop(request_id, None)
        if lc is None or lc.t_claim is None or lc.t_first is None:
            return None
        now = time.perf_counter()
        claim_end = lc.t_claim
        claim_start = claim_end - lc.kv_alloc_ms / 1e3
        queue_end = lc.t_blocked if lc.t_blocked is not None \
            else claim_start
        queue_ms = max(queue_end - lc.t_submit, 0.0) * 1e3
        requeue_ms = (max(claim_start - lc.t_blocked, 0.0) * 1e3
                      if lc.t_blocked is not None else 0.0)
        prefill_wall_ms = max(lc.t_first - claim_end, 0.0) * 1e3
        prefill_ms = min(lc.prefill_ms, prefill_wall_ms)
        chunk_stall_ms = max(prefill_wall_ms - prefill_ms, 0.0)
        # every step is ONE unified wave, and the whole ragged dispatch
        # IS this request's prefill compute — what is left is the waves
        # its chunk waited out for the wave's rows (``lc_stall``) and a
        # residue: host bookkeeping between claim and dispatch,
        # noise-scale by construction, and folded to 0.  A residue over
        # the threshold is a host pause mid-prefill (a profiler
        # starting, a long GC) or an accounting regression: the
        # scheduler goes on, and the residue stays visible in
        # chunk_stall_ms, one event and a counter that hetu_trace
        # --check flags.
        waited_ms = min(lc.stall_ms, chunk_stall_ms)
        residue_ms = chunk_stall_ms - waited_ms
        if residue_ms > max(50.0, 0.5 * prefill_wall_ms):
            telemetry.inc("serve.lifecycle_residue")
            self.event("serve_lifecycle_residue", request=request_id,
                       residue_ms=round(residue_ms, 3),
                       wall_ms=round(prefill_wall_ms, 3))
        else:
            chunk_stall_ms = waited_ms
        decode_ms = max(now - lc.t_first, 0.0) * 1e3 \
            if n_generated > 1 else 0.0
        ttft_ms = max(lc.t_first - lc.t_submit, 0.0) * 1e3
        comp = {"queue_ms": queue_ms, "requeue_ms": requeue_ms,
                "router_hop_ms": lc.hop_ms, "handoff_ms": lc.handoff_ms,
                "kv_alloc_ms": lc.kv_alloc_ms, "prefill_ms": prefill_ms,
                "chunk_stall_ms": chunk_stall_ms, "decode_ms": decode_ms}
        for k, v in comp.items():
            self.components[k].append(v)
        if n_generated > 1 and decode_ms > 0:
            # per-request decode MEAN (wall over tokens) — a valid
            # average either way, but NOT the TPOT percentile source:
            # snapshot() builds that from real per-step token counts
            self.tpots.append(decode_ms / 1e3 / (n_generated - 1))
        breakdown = {"request": request_id, "ttft_ms": ttft_ms,
                     **{k: round(v, 3) for k, v in comp.items()}}
        self.breakdowns.append(breakdown)
        # one span per phase, start-stamped like `span` records so the
        # exporter lays the request out as its own track
        phases = [("queue", lc.t_submit, queue_ms, {}),
                  ("kv_alloc", claim_start, lc.kv_alloc_ms, {})]
        if lc.t_blocked is not None:
            phases.insert(1, ("requeue", lc.t_blocked, requeue_ms, {}))
        if lc.handoff_ms > 0:
            # like the hop: the detour ended at this engine's submit —
            # backdate so the track reads handoff -> queue -> ...
            phases.insert(0, ("handoff",
                              lc.t_submit - lc.handoff_ms / 1e3,
                              lc.handoff_ms, {}))
        if lc.hop_ms > 0:
            # the hop happened BEFORE this engine's submit: backdate
            # its span so the request's track reads hop -> queue -> ...
            phases.insert(0, ("router_hop",
                              lc.t_submit - lc.hop_ms / 1e3,
                              lc.hop_ms, {}))
        phases.append(("prefill", claim_end, prefill_wall_ms,
                       {"compute_ms": round(prefill_ms, 3),
                        "stall_ms": round(chunk_stall_ms, 3),
                        "dispatches": lc.n_prefills}))
        if decode_ms > 0:
            phases.append(("decode", lc.t_first, decode_ms,
                           {"n_tokens": n_generated - 1}))
        for phase, t_start, ms, extra in phases:
            self.event("req_span", request=request_id, phase=phase,
                       ms=round(ms, 3), t=self._epoch(t_start), **extra)
        spec_fields = {}
        if spec is not None:
            spec_fields = {"spec_accepted": int(spec.get("accepted", 0)),
                           "spec_proposed": int(spec.get("proposed", 0)),
                           "spec_bonus": int(spec.get("bonus", 0))}
        self.event("req_retire", request=request_id,
                   ttft_ms=round(ttft_ms, 3),
                   n_generated=n_generated, **spec_fields,
                   **breakdown_fields(comp))
        return breakdown

    # ------------------------------------------------------------- #

    # what mark() records: the lengths of the append-only sample lists
    # and the values of the running counters
    _MARK_LISTS = ("ttfts", "tpots", "step_live", "step_queue",
                   "step_dt", "step_tokens", "prefill_dt")
    _MARK_COUNTS = ("submitted", "rejected", "finished",
                    "tokens_generated", "prefill_batched",
                    "moe_assignments", "moe_assignments_routed",
                    "moe_experts_touched",
                    "moe_kernel_waves", "attn_ctx_tokens", "attn_score_pairs",
                    "attn_tiles_live", "attn_tiles_short",
                    "attn_q_tiles_moved",
                    "attn_window_ctx_tokens", "attn_window_score_pairs",
                    "attn_window_bound_rows",
                    "window_blocks_recycled", *_SPARSE_COUNTS,
                    "ssm_slot_steps", "ssm_rows", "ssm_chunk_pairs",
                    "ssm_kernel_slot_steps",
                    "ret_slot_steps", "ret_rows", "ret_chunk_pairs",
                    "ret_kernel_slot_steps",
                    "kda_slot_steps", "kda_chunk_rows",
                    "kda_kernel_chunk_rows",
                    "wave_rows_live", "wave_rows_computed",
                    "chunks_deferred", "waves_ahead", "rows_dead_ahead",
                    "kv_write_pages", "kv_write_rows")

    def mark(self):
        """A position in this engine's history for ``snapshot(since=)``:
        opaque to the caller, valid for this object only."""
        m = {k: len(getattr(self, k)) for k in self._MARK_LISTS}
        m.update({k: getattr(self, k) for k in self._MARK_COUNTS})
        m["components"] = {c: len(xs) for c, xs in self.components.items()}
        m["t"] = self._t_last
        m["moe_load"] = None if self.moe_load is None \
            else self.moe_load.copy()
        return m

    def snapshot(self, since=None):
        """Aggregate view (JSON-able): throughput, TTFT/TPOT
        percentiles, mean batch occupancy over fused steps, queue
        stats, and the per-component tail decomposition — over the
        engine's whole life, or with ``since`` (a ``mark()``) over the
        steps and requests recorded after that mark."""
        since = since or {}
        at = since.get

        def tail(name):
            return getattr(self, name)[at(name, 0):]

        def count(name):
            return getattr(self, name) - at(name, 0)

        ttfts, tpots = tail("ttfts"), tail("tpots")
        step_live, step_queue = tail("step_live"), tail("step_queue")
        step_dt, step_tokens = tail("step_dt"), tail("step_tokens")
        prefill_dt = tail("prefill_dt")
        tokens_generated = count("tokens_generated")
        t_start = at("t") if at("t") is not None else self._t0
        wall = ((self._t_last - t_start)
                if t_start is not None and self._t_last > t_start
                else None)
        occ = ([l / self._slots for l in step_live]
               if self._slots else [])
        # TPOT from REAL per-step emitted-token counts: a step emitting
        # n tokens contributes n samples of dt/n — correct with and
        # without speculation (the old per-request decode_ms/(n-1)
        # assumed one token per wave and skewed the percentiles the
        # moment waves emitted more)
        tpot = []
        for dt, n in zip(step_dt, step_tokens):
            if n > 0:
                tpot.extend([dt / n] * n)
        comps = {}
        for name, xs in self.components.items():
            xs = xs[since.get("components", {}).get(name, 0):]
            if xs:
                comps[name] = {
                    "p50_ms": round(_pct(xs, 50), 3),
                    "p95_ms": round(_pct(xs, 95), 3),
                    "p99_ms": round(_pct(xs, 99), 3),
                    "mean_ms": round(float(np.mean(xs)), 3),
                }
        routed = {}
        if self.moe_load is not None:
            load = self.moe_load if at("moe_load") is None \
                else self.moe_load - at("moe_load")
            mean = float(load.mean())
            routed = {
                "moe_assignments": count("moe_assignments"),
                "moe_assignments_routed": count("moe_assignments_routed"),
                "moe_experts_touched": count("moe_experts_touched"),
                "moe_kernel_waves": count("moe_kernel_waves"),
                "moe_load": [int(x) for x in load],
                "moe_load_max": int(load.max()),
                "moe_load_imbalance": (float(load.max()) / mean
                                       if mean > 0 else None),
            }
        return {
            **routed,
            "attn_ctx_tokens": count("attn_ctx_tokens"),
            "attn_score_pairs": count("attn_score_pairs"),
            "attn_tiles_live": count("attn_tiles_live"),
            "attn_tiles_short": count("attn_tiles_short"),
            "attn_q_tiles_moved": count("attn_q_tiles_moved"),
            "attn_window_ctx_tokens": count("attn_window_ctx_tokens"),
            "attn_window_score_pairs": count("attn_window_score_pairs"),
            "attn_window_bound_rows": count("attn_window_bound_rows"),
            "window_blocks_recycled": count("window_blocks_recycled"),
            **{key: count(key) for key in self._SPARSE_COUNTS},
            "ssm_slot_steps": count("ssm_slot_steps"),
            "ssm_rows": count("ssm_rows"),
            "ssm_chunk_pairs": count("ssm_chunk_pairs"),
            "ssm_kernel_slot_steps": count("ssm_kernel_slot_steps"),
            "ret_slot_steps": count("ret_slot_steps"),
            "ret_rows": count("ret_rows"),
            "ret_chunk_pairs": count("ret_chunk_pairs"),
            "ret_kernel_slot_steps": count("ret_kernel_slot_steps"),
            "kda_slot_steps": count("kda_slot_steps"),
            "kda_chunk_rows": count("kda_chunk_rows"),
            "kda_kernel_chunk_rows": count("kda_kernel_chunk_rows"),
            "wave_rows_live": count("wave_rows_live"),
            "wave_rows_computed": count("wave_rows_computed"),
            "chunks_deferred": count("chunks_deferred"),
            "waves_ahead": count("waves_ahead"),
            "rows_dead_ahead": count("rows_dead_ahead"),
            "kv_write_pages": count("kv_write_pages"),
            "kv_write_rows": count("kv_write_rows"),
            "requests_submitted": count("submitted"),
            "requests_rejected": count("rejected"),
            "requests_finished": count("finished"),
            "tokens_generated": tokens_generated,
            "wall_s": round(wall, 6) if wall else None,
            "tokens_per_sec": (round(tokens_generated / wall, 2)
                               if wall else None),
            "ttft_p50_s": _pct(ttfts, 50),
            "ttft_p95_s": _pct(ttfts, 95),
            "ttft_p99_s": _pct(ttfts, 99),
            "ttft_mean_s": (float(np.mean(ttfts)) if ttfts else None),
            "tpot_p50_s": _pct(tpot, 50),
            "tpot_p99_s": _pct(tpot, 99),
            "tpot_req_mean_p50_s": _pct(tpots, 50),
            "tokens_per_step_mean": (float(np.mean(step_tokens))
                                     if step_tokens else None),
            "step_p50_s": _pct(step_dt, 50),
            "step_p99_s": _pct(step_dt, 99),
            "decode_ms_p50": (round(_pct(step_dt, 50) * 1e3, 3)
                              if step_dt else None),
            "prefill_ms_p50": (round(_pct(prefill_dt, 50) * 1e3, 3)
                               if prefill_dt else None),
            "prefill_total_s": (round(float(np.sum(prefill_dt)), 6)
                                if prefill_dt else None),
            "decode_total_s": (round(float(np.sum(step_dt)), 6)
                               if step_dt else None),
            "prefill_dispatches": len(prefill_dt),
            "prefill_batched_dispatches": count("prefill_batched"),
            "steps": len(step_live),
            "mean_batch_occupancy": (float(np.mean(occ)) if occ else None),
            "mean_queue_depth": (float(np.mean(step_queue))
                                 if step_queue else None),
            "components": comps,
        }

    def explain_tail(self, q=99):
        """Name the component that dominates the TTFT tail: slice the
        requests at or above the q-th TTFT percentile and average their
        breakdowns.  The dominant component is the report's headline —
        "p99 TTFT is queue-bound" is an actionable statement (admission
        control) where "p99 TTFT is 40x p50" is not.  Returns None with
        no finished requests."""
        rows = [b for b in self.breakdowns if b.get("ttft_ms") is not None]
        if not rows:
            return None
        ttfts = [b["ttft_ms"] for b in rows]
        cut = _pct(ttfts, q)
        tail = [b for b in rows if b["ttft_ms"] >= cut]
        means = {c: float(np.mean([b[c] for b in tail]))
                 for c in COMPONENTS}
        # decode is not part of TTFT — the tail is decomposed over the
        # submit->first-token phases only
        ttft_parts = {c: v for c, v in means.items() if c != "decode_ms"}
        dominant = max(ttft_parts, key=ttft_parts.get)
        total = sum(ttft_parts.values()) or 1.0
        share = ttft_parts[dominant] / total
        report = {
            "q": q,
            "ttft_p_ms": round(cut, 3),
            "ttft_p50_ms": round(_pct(ttfts, 50), 3),
            "n_requests": len(rows),
            "n_tail": len(tail),
            "dominant_component": dominant,
            "dominant_ms": round(ttft_parts[dominant], 3),
            "dominant_share": round(share, 4),
            "components_mean_ms": {c: round(v, 3)
                                   for c, v in means.items()},
            "tail_requests": [b["request"] for b in tail[:8]],
            "mixed_mode": True,
        }
        # the unified wave carries all modes: prefill_ms here means
        # "ragged dispatches this prompt rode in" and chunk_stall the
        # waves a chunk waited out for the wave's rows (the rest is
        # folded to 0 at retirement)
        report["summary"] = (
            f"p{q} TTFT {cut:.1f}ms ({len(tail)}/{len(rows)} requests): "
            f"dominated by {dominant.replace('_ms', '')} "
            f"({ttft_parts[dominant]:.1f}ms, {share:.0%} of the "
            f"pre-token wall) [mixed-mode: prefill attributed to unified "
            f"ragged waves; chunk_stall = waves waited out for row capacity]")
        return report


EMBED_COMPONENTS = ("queue_ms", "router_hop_ms", "gather_ms",
                    "forward_ms")


class _EmbedLifecycle:
    """Perf-counter timeline of one scoring request: submit -> wave
    claim -> gather (embedding fetch) -> forward (tower) -> retire."""

    __slots__ = ("t_submit", "t_claim", "gather_ms", "t_first",
                 "hop_ms")

    def __init__(self, t_submit):
        self.t_submit = t_submit
        self.t_claim = None       # wave claimed the request
        self.gather_ms = 0.0      # embedding gather attributed to it
        self.t_first = None       # scores landed
        self.hop_ms = 0.0         # router requeue hops before us


class EmbedServingMetrics(MetricsCore):
    """Embedding-engine telemetry: the GPT lifecycle with the KV
    phases replaced by ``gather_ms`` (CacheSparseTable fetch) and
    ``forward_ms`` (the jitted tower).  Emits the SAME event kinds the
    GPT engine does — serve_submit/serve_admit/serve_step/serve_finish
    plus per-phase req_span and req_retire — so hetu_trace --check's
    span-balance rule, hetu_top, and the SLO monitor work unmodified;
    the one new kind is the per-wave ``serve_gather`` record.  Every
    event carries ``workload="embed"`` (hetu_top's workload column)."""

    def __init__(self, log_path=None, tags=None):
        super().__init__(log_path=log_path, tags=tags)
        self.tags.setdefault("workload", "embed")
        self.pairs_scored = 0
        self.ttfts = []            # seconds, submit -> scores landed
        self.latencies = []        # == ttfts shape-wise; kept separate
        # so snapshot() reads like the GPT one
        self.step_live = []        # requests per wave
        self.step_queue = []       # queue depth per wave
        self.step_dt = []          # seconds per wave (gather+forward)
        self.step_rows = []        # pairs scored per wave
        self.gather_dt = []        # seconds per wave gather
        self.hit_rates = []        # cache hit-rate per wave gather
        self.components = {c: [] for c in EMBED_COMPONENTS}
        cap = max(1, envvars.get_int("HETU_TELEMETRY_BUFFER"))
        self.breakdowns = collections.deque(maxlen=cap)
        self._slots = None

    def _make_lc(self, t_submit):
        return _EmbedLifecycle(t_submit)

    # ------------------------------------------------------------- #

    def lc_claimed(self, request_id):
        """The wave claimed this request off the queue (queue phase
        ends here; gather starts)."""
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.t_claim = time.perf_counter()

    def record_gather(self, n, rows, gather_s, hit_rate, requests=()):
        """One wave's embedding gather: ``n`` requests, ``rows`` total
        pairs fetched through the cache in ``gather_s`` seconds at
        ``hit_rate``.  Attributes the wall to every participant."""
        self._mark()
        self.gather_dt.append(gather_s)
        self.hit_rates.append(float(hit_rate))
        for rid in requests:
            lc = self._lc.get(rid)
            if lc is not None:
                lc.gather_ms += gather_s * 1e3
        self.event("serve_gather", n=n, rows=rows,
                   gather_ms=round(gather_s * 1e3, 3),
                   hit_rate=round(float(hit_rate), 4))

    def record_admit(self, request_id, slot, queue_wait_s, ttft_s):
        """Scores landed for this request (embed waves emit the whole
        result at once, so admit == first-result)."""
        self._mark()
        self.ttfts.append(ttft_s)
        lc = self._lc.get(request_id)
        if lc is not None:
            lc.t_first = time.perf_counter()
        self.event("serve_admit", request=request_id, slot=slot,
                   queue_wait_s=round(queue_wait_s, 6),
                   ttft_s=round(ttft_s, 6))

    def record_step(self, live, slots, queue_depth, dt_s, rows,
                    gather_s=0.0, step=None, requests=None):
        """One scoring wave: ``dt_s`` is the wave wall (gather +
        forward), ``rows`` the pairs it scored.  Shapes the serve_step
        event like a GPT decode wave (decode_ms = the forward wall) so
        hetu_top and the trace exporter render waves unmodified."""
        self._mark()
        self._slots = slots
        self.step_live.append(live)
        self.step_queue.append(queue_depth)
        self.step_dt.append(dt_s)
        self.step_rows.append(int(rows))
        self.pairs_scored += int(rows)
        fields = {}
        if step is not None:
            fields["step"] = step
        if requests is not None:
            fields["requests"] = list(requests)
        self.event("serve_step", live=live, queue_depth=queue_depth,
                   slots=slots, rows=int(rows),
                   gather_ms=round(gather_s * 1e3, 3),
                   decode_ms=round(max(dt_s - gather_s, 0.0) * 1e3, 3),
                   **fields)

    def record_finish(self, request_id, reason, n_pairs, latency_s):
        self._mark()
        self.finished += 1
        self.latencies.append(latency_s)
        self.event("serve_finish", request=request_id, reason=reason,
                   n_generated=n_pairs, latency_s=round(latency_s, 6))
        return self._retire(request_id)

    def _retire(self, request_id):
        lc = self._lc.pop(request_id, None)
        if lc is None or lc.t_claim is None or lc.t_first is None:
            return None
        queue_ms = max(lc.t_claim - lc.t_submit, 0.0) * 1e3
        wave_wall_ms = max(lc.t_first - lc.t_claim, 0.0) * 1e3
        gather_ms = min(lc.gather_ms, wave_wall_ms)
        forward_ms = max(wave_wall_ms - gather_ms, 0.0)
        ttft_ms = max(lc.t_first - lc.t_submit, 0.0) * 1e3
        comp = {"queue_ms": queue_ms, "router_hop_ms": lc.hop_ms,
                "gather_ms": gather_ms, "forward_ms": forward_ms}
        for k, v in comp.items():
            self.components[k].append(v)
        breakdown = {"request": request_id, "ttft_ms": ttft_ms,
                     **{k: round(v, 3) for k, v in comp.items()}}
        self.breakdowns.append(breakdown)
        phases = [("queue", lc.t_submit, queue_ms, {}),
                  ("gather", lc.t_claim, gather_ms, {}),
                  ("forward", lc.t_claim + gather_ms / 1e3,
                   forward_ms, {})]
        if lc.hop_ms > 0:
            # the hop happened BEFORE this engine's submit: backdate
            # its span so the request's track reads hop -> queue -> ...
            phases.insert(0, ("router_hop",
                              lc.t_submit - lc.hop_ms / 1e3,
                              lc.hop_ms, {}))
        for phase, t_start, ms, extra in phases:
            self.event("req_span", request=request_id, phase=phase,
                       ms=round(ms, 3), t=self._epoch(t_start), **extra)
        self.event("req_retire", request=request_id,
                   ttft_ms=round(ttft_ms, 3),
                   **breakdown_fields(comp))
        return breakdown

    # ------------------------------------------------------------- #

    def snapshot(self):
        wall = ((self._t_last - self._t0)
                if self._t0 is not None and self._t_last > self._t0
                else None)
        occ = ([l / self._slots for l in self.step_live]
               if self._slots else [])
        comps = {}
        for name, xs in self.components.items():
            if xs:
                comps[name] = {
                    "p50_ms": round(_pct(xs, 50), 3),
                    "p95_ms": round(_pct(xs, 95), 3),
                    "p99_ms": round(_pct(xs, 99), 3),
                    "mean_ms": round(float(np.mean(xs)), 3),
                }
        return {
            "requests_submitted": self.submitted,
            "requests_rejected": self.rejected,
            "requests_finished": self.finished,
            "pairs_scored": self.pairs_scored,
            "wall_s": round(wall, 6) if wall else None,
            "qps": (round(self.finished / wall, 2) if wall else None),
            "pairs_per_sec": (round(self.pairs_scored / wall, 2)
                              if wall else None),
            "latency_p50_s": _pct(self.latencies, 50),
            "latency_p95_s": _pct(self.latencies, 95),
            "latency_p99_s": _pct(self.latencies, 99),
            "latency_mean_s": (float(np.mean(self.latencies))
                               if self.latencies else None),
            "gather_ms_p50": (round(_pct(self.gather_dt, 50) * 1e3, 3)
                              if self.gather_dt else None),
            "wave_ms_p50": (round(_pct(self.step_dt, 50) * 1e3, 3)
                            if self.step_dt else None),
            "cache_hit_rate_mean": (float(np.mean(self.hit_rates))
                                    if self.hit_rates else None),
            "steps": len(self.step_live),
            "rows_per_wave_mean": (float(np.mean(self.step_rows))
                                   if self.step_rows else None),
            "mean_batch_occupancy": (float(np.mean(occ)) if occ else None),
            "mean_queue_depth": (float(np.mean(self.step_queue))
                                 if self.step_queue else None),
            "components": comps,
        }

    def explain_tail(self, q=99):
        """Name the component dominating the latency tail (the embed
        twin of ServingMetrics.explain_tail — same report shape, over
        queue/hop/gather/forward instead of the KV phases)."""
        rows = [b for b in self.breakdowns if b.get("ttft_ms") is not None]
        if not rows:
            return None
        ttfts = [b["ttft_ms"] for b in rows]
        cut = _pct(ttfts, q)
        tail = [b for b in rows if b["ttft_ms"] >= cut]
        means = {c: float(np.mean([b[c] for b in tail]))
                 for c in EMBED_COMPONENTS}
        dominant = max(means, key=means.get)
        total = sum(means.values()) or 1.0
        share = means[dominant] / total
        return {
            "q": q,
            "ttft_p_ms": round(cut, 3),
            "ttft_p50_ms": round(_pct(ttfts, 50), 3),
            "n_requests": len(rows),
            "n_tail": len(tail),
            "dominant_component": dominant,
            "dominant_ms": round(means[dominant], 3),
            "dominant_share": round(share, 4),
            "components_mean_ms": {c: round(v, 3)
                                   for c, v in means.items()},
            "tail_requests": [b["request"] for b in tail[:8]],
            "summary": (
                f"p{q} latency {cut:.1f}ms ({len(tail)}/{len(rows)} "
                f"requests): dominated by {dominant.replace('_ms', '')} "
                f"({means[dominant]:.1f}ms, {share:.0%} of the wall)"),
        }


def breakdown_fields(comp):
    """Flatten a component dict for the req_retire record (scalar
    fields survive the trace exporter's args filter; a nested dict
    would be dropped)."""
    return {k: round(v, 3) for k, v in comp.items()}
