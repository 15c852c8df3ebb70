"""hetu_tpu.serving: continuous-batching inference over the KV-cached
decode path.

The offline path (``models/gpt_decode.generate_fast``) compiles one
whole-generation scan per (batch, S_max) — every request in the batch
enters and leaves together, padded to the longest.  This package is the
online counterpart: an iteration-level scheduler (Orca-style continuous
batching) that admits and retires sequences BETWEEN fused waves, over a
slot-structured KV cache, with the offline path's arithmetic (greedy
tokens are ``generate_fast``'s).

    engine.py     ServingEngine: admission queue with backpressure and
                  the scheduler, one on every backend: each step admits,
                  packs every live slot's q-block (a prompt or its next
                  chunk, a spec-verify block, a decode token) into ONE
                  ragged wave, dispatches it once, retires
    embed_engine.py
                  EmbedServingEngine: the recommendation workload —
                  waves of (user_ids, item_ids, dense_features)
                  requests gather embeddings through CacheSparseTable
                  (int8 PS pull on miss under HETU_PS_QUANT) and score
                  in one jitted WDL/DCN/NCF tower forward; degrades
                  through a PS outage exactly like training
                  (stale-serving + replay), zero request loss
    router.py     ServingRouter: the FLEET tier — health-aware weighted
                  routing over N supervised replicas, session affinity
                  (session_id -> home replica, warm prefix blocks),
                  per-replica circuit breakers, dead/wedged-replica
                  drain + requeue with zero request loss, bounded
                  retry/deadlines, SLO-class load shedding
                  (throughput-class first), QueueFull backpressure
                  propagated up
    replica.py    Replica: one supervised engine slot — respawn under
                  the launcher's HETU_RESTART_LIMIT/BACKOFF budget,
                  chaos kill/wedge at the step seam (HETU_CHAOS
                  role=replica<k>), heartbeat for wedge detection
    kv_manager.py PagedKVManager: the block-table paged pool, the
                  engine's one KV layout (free-list block allocator,
                  refcounted copy-on-write prefix sharing, chunked
                  prefill support, pow2-bucketed shapes; block 16,
                  $HETU_KV_BLOCK)
    kv_tiers.py   TieredKVStore: fleet-global prefix capacity — the
                  eviction-to-tier ladder behind every paged pool
                  (HBM pool -> host-RAM LRU ring sized by
                  HETU_KV_HOST_BYTES -> sharded-PS cold store under
                  HETU_KV_PS_TIER, keyed by prefix hash, versioned);
                  refcount-zero evictions spill the int8 handoff wire
                  payload down, admission misses fetch it back up
                  token-identically via import_blocks; a dead/killed
                  PS degrades to drop-on-evict with zero request loss
    prefix_directory.py
                  PrefixDirectory: the fleet-wide prefix-cache map
                  (prefix hash -> which replica holds the KV span),
                  fed by each replica's PagedKVManager register/evict
                  callbacks; the router consults it BEFORE the
                  affinity hash, so any replica's warm cache attracts
                  matching traffic (hit/steal), with TTL staleness and
                  graceful degradation to plain affinity when killed
    weight_sync.py
                  WeightSyncCoordinator: zero-downtime rolling weight
                  swaps — quiesce one replica (breaker-style routing
                  exclusion), drain its in-flight work, swap the param
                  dict under the engine (no recompile; the spec draft
                  inherits it), probe-decode on the new version, then
                  readmit; version-stamped end to end (every serve
                  event/Result carries weight_version), chaos-gated
                  (HETU_CHAOS role=swap), auto-rollback to the last
                  committed version on any mid-swap failure
    autoscaler.py FleetAutoscaler: SLO-burn-driven elasticity — one
                  control tick per router step watching worst-replica
                  burn rate + queue pressure, scaling the fleet live
                  between HETU_FLEET_MIN/MAX with hysteresis and
                  cooldown via router.add_replica (committed-version
                  admission, prefix warming, half-open bring-up probe)
                  / router.retire_replica (quiesce, prefix export,
                  zero-loss drain onto peers); never shrinks
                  mid-rollout; chaos-gated (HETU_CHAOS role=autoscale);
                  disabled == byte-identical to the static fleet
    traffic.py    TrafficGenerator: seeded diurnal/zipf/flash traffic
                  shapes rendered to replayable TrafficSpec traces
                  (chat / long-context / CTR-shaped classes), plus
                  replay() — virtual-clock playback into a router
    request.py    Request / Result dataclasses
    metrics.py    ServingMetrics: TTFT/TPOT percentiles, tok/s,
                  occupancy; JSONL events (per-step prefill_ms/
                  decode_ms attribution); per-request LIFECYCLE tracing
                  (queue/kv_alloc/prefill/decode/requeue req_span
                  records -> per-request Perfetto tracks) with a
                  component breakdown per retirement and
                  explain_tail() naming what owns the p99 TTFT

Observability: the engine's ``health()`` reports the SLO monitor's
ok/degraded/breach state (telemetry/slo.py, ``HETU_SLO_*`` knobs or an
explicit ``slo=``), ``bin/hetu_top.py`` renders the live dashboard, and
the flight recorder (telemetry/flight.py) dumps the records leading
into an engine exception or QueueFull storm to ``$HETU_FLIGHT_LOG``.

Speculative decoding (``spec=``/``$HETU_SPEC_K``): a truncated-layer
draft — the target's own first blocks, no separate weights — proposes
up to k tokens per slot in one scanned dispatch, the target verifies
all k+1 positions as a q-block of the wave, longest-prefix acceptance +
a bonus token emit 1..k+1 tokens per wave, and rejected positions roll
back via ``kv.truncate`` — outputs stay token-identical to plain decoding
(greedy AND sampled), with an adaptive-k controller riding a sliding
acceptance-rate window (``spec_adapt=``, on by default).

What scores the wave follows the platform (``fast_path=``/
``$HETU_SERVE_FAST``): the Pallas ragged kernel
(kernels/ragged_attention.py) on a TPU, so each slot fetches only its
live KV pages instead of streaming all of S_max; the masked
``jax.numpy`` reference elsewhere — greedy outputs are token-identical
between the two.

Quickstart (greedy results are token-identical to ``generate_fast``):

    from hetu_tpu.serving import ServingEngine, Request
    eng = ServingEngine(ex.var_values, cfg, slots=8)
    eng.submit(Request(prompt=[7, 8, 9], max_new_tokens=32, eos_id=50256))
    results = eng.run()           # {request_id: Result}
"""

from ..telemetry.slo import SLO, SLOMonitor
from .autoscaler import FleetAutoscaler
from .request import EmbedRequest, EmbedResult, Request, RequestCore, Result
from .kv_manager import (
    PagedKVManager, resolve_handoff_quant, resolve_kv_block,
    resolve_kv_quant, round_up_pow2,
)
from .metrics import (
    COMPONENTS, EMBED_COMPONENTS, EmbedServingMetrics, ServingMetrics,
)
from .engine import ServingEngine, QueueFull
from .embed_engine import EmbedServingEngine
from .kv_tiers import TieredKVStore
from .prefix_directory import PrefixDirectory, prefix_hash
from .replica import Replica
from .router import RouterShed, ServingRouter
from .traffic import TrafficGenerator, TrafficSpec, replay
from .weight_sync import WeightSyncCoordinator

__all__ = [
    "ServingEngine", "EmbedServingEngine", "ServingRouter", "Replica",
    "WeightSyncCoordinator", "FleetAutoscaler",
    "TrafficGenerator", "TrafficSpec", "replay",
    "QueueFull", "RouterShed", "Request", "RequestCore", "Result",
    "EmbedRequest", "EmbedResult",
    "PagedKVManager", "ServingMetrics",
    "EmbedServingMetrics", "COMPONENTS", "EMBED_COMPONENTS",
    "SLO", "SLOMonitor", "PrefixDirectory", "TieredKVStore",
    "prefix_hash", "resolve_handoff_quant",
    "resolve_kv_block", "resolve_kv_quant", "round_up_pow2",
]
