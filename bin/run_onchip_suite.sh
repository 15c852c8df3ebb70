#!/bin/bash
# The on-chip measurement battery, in priority order (VERDICT r3 items
# 1/2/4/5/6 measurement halves; see round4 COMPONENTS.md closure table).
# Run when a TPU answers; every stage is guarded against clobbering
# full-scale records with degraded runs, so re-running is always safe.
#
#   bash bin/run_onchip_suite.sh [logdir]
set -u
cd "$(dirname "$0")/.."
# one suite at a time: two concurrent batteries would interleave matrix
# writes and contend for the single chip
exec 9>.onchip_suite.lock
if ! flock -n 9; then
  echo "another on-chip suite holds .onchip_suite.lock — refusing to" \
       "run concurrently" >&2
  # distinctive code (EX_TEMPFAIL): "lock held, not an attempt" is not
  # a genuine early failure (exit 1)
  exit 75
fi
LOG=${1:-/tmp/onchip_$(date -u +%H%M)}
mkdir -p "$LOG"
echo "logging to $LOG"

run() {  # name, timeout_s, cmd... — a stage that hangs must cost
  local name=$1; shift       # ONE stage, not the whole battery
  local budget=$1; shift     # (every stage is rerunnable)
  echo "=== $name (<=${budget}s): $* ==="
  (time timeout -k 60 "$budget" "$@") >"$LOG/$name.log" 2>&1
  local rc=$?
  tail -2 "$LOG/$name.log"
  echo "=== $name rc=$rc ==="
}

# 00. static gate: lint + a build-time verification pass, BEFORE any
#     chip time.  The lint is pure-AST (no jax init) and the verifier
#     builds/validates a representative graph on CPU in seconds; a
#     miswired tree must cost this stage, not a TPU allocation.
run lint 300 python bin/hetu_lint.py hetu_tpu/ bench.py bin/
if grep -q 'finding(s)' "$LOG/lint.log"; then
  echo "lint gate FAILED — fix findings before burning chip time" >&2
  exit 1
fi
run verify 600 env HETU_VALIDATE=1 JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np, hetu_tpu as ht
x = ht.placeholder_op("x")
w = ht.init.xavier_uniform((64, 64), name="vg_w")
h = ht.relu_op(ht.matmul_op(x, w))
loss = ht.reduce_mean_op(ht.reduce_mean_op(h, axes=1), axes=0)
train = ht.optim.AdamOptimizer(learning_rate=1e-3).minimize(loss)
ex = ht.Executor({"train": [loss, train]})
ex.run("train", feed_dict={x: np.ones((8, 64), np.float32)})
print("verify gate OK")
PYEOF
if ! grep -q 'verify gate OK' "$LOG/verify.log"; then
  echo "verification gate FAILED — see $LOG/verify.log" >&2
  exit 1
fi

# 00b. telemetry gate: one instrumented CPU train step + the event
#      pipeline end to end — spans land in the merged JSONL, the
#      contract checks clean, and bin/hetu_trace.py exports a loadable
#      Perfetto trace.  Measurement plumbing is proven BEFORE any chip
#      time; the exported trace is the window's first artifact.
run telemetry 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/telemetry.jsonl" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np, hetu_tpu as ht
x = ht.placeholder_op("x")
w = ht.init.xavier_uniform((64, 64), name="tg_w")
h = ht.relu_op(ht.matmul_op(x, w))
loss = ht.reduce_mean_op(ht.reduce_mean_op(h, axes=1), axes=0)
train = ht.optim.AdamOptimizer(learning_rate=1e-3).minimize(loss)
ex = ht.Executor({"train": [loss, train]})
for _ in range(3):
    ex.run("train", feed_dict={x: np.ones((8, 64), np.float32)})
from hetu_tpu import telemetry
snap = telemetry.snapshot()
assert snap["counters"].get("exec.steps") == 3, snap["counters"]
print("telemetry gate OK")
PYEOF
if ! grep -q 'telemetry gate OK' "$LOG/telemetry.log"; then
  echo "telemetry gate FAILED — see $LOG/telemetry.log" >&2
  exit 1
fi
run trace_export 300 python bin/hetu_trace.py "$LOG/telemetry.jsonl" \
    --export "$LOG/trace.json"
if ! python -c "
import json
t = json.load(open('$LOG/trace.json'))
spans = [e for e in t['traceEvents'] if e.get('ph') == 'X']
assert spans, 'exported trace has no duration events'
print('trace artifact OK:', len(t['traceEvents']), 'events,',
      len(spans), 'spans')
"; then
  echo "trace-artifact sanity check FAILED — see $LOG/trace.json" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/telemetry.jsonl" --check \
    > "$LOG/trace_contract.log" || {
  echo "event-contract check FAILED — see $LOG/trace_contract.log" >&2
  exit 1
}

# 00c. request-observability gate: a tiny CPU serving trace-replay must
#      produce a balanced request stream (every admit has its retire —
#      hetu_trace --check's span-balance rule), per-request lifecycle
#      spans, and an exportable trace with request tracks, BEFORE any
#      chip-time serving stage trusts those records.
run serve_trace 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/serve_trace.jsonl" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import Request, ServingEngine

rng, hd = np.random.RandomState(0), 16
p = {"og_wte_table": rng.randn(61, hd) * 0.05,
     "og_wpe": rng.randn(32, hd) * 0.05,
     "og_ln_f_scale": np.ones(hd), "og_ln_f_bias": np.zeros(hd)}
for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
               ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
               ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
    p[f"og_h0_{w}_weight"] = rng.randn(*shp) * 0.05
    p[f"og_h0_{w}_bias"] = np.zeros(shp[1])
for ln in ("ln1", "ln2"):
    p[f"og_h0_{ln}_scale"] = np.ones(hd)
    p[f"og_h0_{ln}_bias"] = np.zeros(hd)
cfg = GPTConfig(vocab_size=61, hidden_size=hd, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)
eng = ServingEngine(p, cfg, slots=2, fast_path=False)
res = eng.run([Request(prompt=[7, 8, 9], max_new_tokens=4, seed=s)
               for s in range(3)])
assert len(res) == 3
assert eng.metrics.explain_tail() is not None
print("serve trace gate OK")
PYEOF
if ! grep -q 'serve trace gate OK' "$LOG/serve_trace.log"; then
  echo "serving trace gate FAILED — see $LOG/serve_trace.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/serve_trace.jsonl" --check \
    > "$LOG/serve_trace_contract.log" || {
  echo "serving span-balance/contract check FAILED — see" \
       "$LOG/serve_trace_contract.log" >&2
  exit 1
}
run serve_trace_export 300 python bin/hetu_trace.py \
    "$LOG/serve_trace.jsonl" --export "$LOG/serve_trace_export.json"

# 00d. router trace-replay gate: an N=2 CPU fleet with a seeded chaos
#      kill of one replica mid-trace must retire EVERY request exactly
#      once (requeued to the peer, never lost), leave contract-valid
#      failure events + a flight dump on the killed replica, and a
#      serve stream that passes the fleet span-balance rule — the
#      router's robustness contract proven BEFORE chip-time serving.
run router_trace 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/router_trace.jsonl" \
    HETU_FAILURE_LOG="$LOG/router_failure.jsonl" \
    HETU_FLIGHT_LOG="$LOG/router_flight.jsonl" \
    HETU_CHAOS="seed=3,kill=4,role=replica1" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import Request, ServingEngine, ServingRouter

rng, hd = np.random.RandomState(0), 16
p = {"rg_wte_table": rng.randn(61, hd) * 0.05,
     "rg_wpe": rng.randn(32, hd) * 0.05,
     "rg_ln_f_scale": np.ones(hd), "rg_ln_f_bias": np.zeros(hd)}
for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
               ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
               ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
    p[f"rg_h0_{w}_weight"] = rng.randn(*shp) * 0.05
    p[f"rg_h0_{w}_bias"] = np.zeros(shp[1])
for ln in ("ln1", "ln2"):
    p[f"rg_h0_{ln}_scale"] = np.ones(hd)
    p[f"rg_h0_{ln}_bias"] = np.zeros(hd)
cfg = GPTConfig(vocab_size=61, hidden_size=hd, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)
router = ServingRouter(
    lambda i: ServingEngine(p, cfg, slots=2, fast_path=False),
    replicas=2, restart_backoff=0.01)
treq = np.random.RandomState(11)
reqs = [Request(prompt=[int(t) for t in treq.randint(0, 61, 3)],
                max_new_tokens=4, seed=s) for s in range(8)]
res = router.run(reqs)
snap = router.snapshot()
assert len(res) == 8, f"retired {len(res)}/8"
assert snap["lost"] == 0 and snap["duplicates"] == 0, snap
assert snap["requeued"] >= 1, "the kill never cost a requeue?"
print("router gate OK: finished", snap["finished"],
      "requeued", snap["requeued"])
PYEOF
if ! grep -q 'router gate OK' "$LOG/router_trace.log"; then
  echo "router fleet gate FAILED — see $LOG/router_trace.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/router_trace.jsonl" \
    "$LOG/router_failure.jsonl" --check \
    > "$LOG/router_trace_contract.log" || {
  echo "router span-balance/contract check FAILED — see" \
       "$LOG/router_trace_contract.log" >&2
  exit 1
}
python bin/hetu_trace.py "$LOG/router_flight.jsonl" --check \
    > "$LOG/router_flight_contract.log" || {
  echo "router flight-dump contract check FAILED — see" \
       "$LOG/router_flight_contract.log" >&2
  exit 1
}

# 00e. fleet-KV gate (ISSUE 12): a role-split N=2 CPU fleet with the
#      prefix directory on and a seeded chaos kill of the DIRECTORY
#      mid-trace must retire every request token-identical to offline
#      generate_fast (the handoff payloads in flight still land; the
#      fleet degrades to PR 8 affinity routing), record the kill
#      (failure event + flight dump), and leave a serve stream that
#      passes the KV-handoff pairing rule (hetu_trace --check: every
#      kv_handoff_out has its kv_handoff_in, one retirement per
#      admission) — the fleet-KV contract proven before chip time.
run fleet_kv_gate 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/fleet_kv_trace.jsonl" \
    HETU_FAILURE_LOG="$LOG/fleet_kv_failure.jsonl" \
    HETU_FLIGHT_LOG="$LOG/fleet_kv_flight.jsonl" \
    HETU_CHAOS="seed=5,kill=3,role=directory" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.serving import Request, ServingEngine, ServingRouter

rng, hd = np.random.RandomState(0), 16
p = {"fg_wte_table": rng.randn(61, hd) * 0.05,
     "fg_wpe": rng.randn(32, hd) * 0.05,
     "fg_ln_f_scale": np.ones(hd), "fg_ln_f_bias": np.zeros(hd)}
for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
               ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
               ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
    p[f"fg_h0_{w}_weight"] = rng.randn(*shp) * 0.05
    p[f"fg_h0_{w}_bias"] = np.zeros(shp[1])
for ln in ("ln1", "ln2"):
    p[f"fg_h0_{ln}_scale"] = np.ones(hd)
    p[f"fg_h0_{ln}_bias"] = np.zeros(hd)
cfg = GPTConfig(vocab_size=61, hidden_size=hd, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)
router = ServingRouter(
    lambda i: ServingEngine(p, cfg, slots=2, fast_path=False,
                            kv_block=8, prefix_share=True),
    replicas=2, roles="prefill,decode")
sys_p = list(range(1, 18))          # shared long prompt (> one block)
reqs = [Request(prompt=sys_p + [20 + i], max_new_tokens=4,
                session_id=f"t{i}") for i in range(10)]
res = {}
for i in range(0, 10, 5):           # two waves: warm, then consult
    res.update(router.run(reqs[i:i + 5]))
snap = router.snapshot()
assert len(res) == 10, f"retired {len(res)}/10"
assert snap["lost"] == 0 and snap["duplicates"] == 0, snap
assert snap["directory_killed"], "the chaos kill never fired"
assert snap["handoffs"] > 0, "role-split fleet moved zero KV spans"
for r in reqs:                      # zero token loss, bit-for-bit
    want = generate_fast(p, cfg, [r.prompt], num_tokens=4)[0].tolist()
    got = res[r.request_id].tokens.tolist()
    assert got == want, (r.request_id, got, want)
print("fleet kv gate OK: finished", snap["finished"],
      "handoffs", snap["handoffs"], "killed", snap["directory_killed"])
PYEOF
if ! grep -q 'fleet kv gate OK' "$LOG/fleet_kv_gate.log"; then
  echo "fleet KV gate FAILED — see $LOG/fleet_kv_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/fleet_kv_trace.jsonl" \
    "$LOG/fleet_kv_failure.jsonl" --check \
    > "$LOG/fleet_kv_contract.log" || {
  echo "fleet KV handoff/contract check FAILED — see" \
       "$LOG/fleet_kv_contract.log" >&2
  exit 1
}
python bin/hetu_trace.py "$LOG/fleet_kv_flight.jsonl" --check \
    > "$LOG/fleet_kv_flight_contract.log" || {
  echo "fleet KV flight-dump contract check FAILED — see" \
       "$LOG/fleet_kv_flight_contract.log" >&2
  exit 1
}

# 00f. embedding-serving gate (ISSUE 14): a zipf(1.05) CTR trace
#      replayed through the cache-fronted EmbedServingEngine on CPU,
#      with the PS killed for the middle third of the trace — every
#      request must still score (stale hits + zero-vector misses,
#      ZERO loss), the cache counters must show the outage engaged,
#      and the merged serve stream must pass hetu_trace --check
#      including the gather span-balance rule — the second workload's
#      contract proven before any chip time.
run embed_serve_gate 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/embed_trace.jsonl" \
    JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.cache.cstable import CacheSparseTable
from hetu_tpu.ps.client import PSConnectionError
from hetu_tpu.ps.server import PSServer
from hetu_tpu.serving import EmbedRequest, EmbedServingEngine


class KillablePS:
    def __init__(self, server):
        self._server, self.down = server, False

    def __getattr__(self, name):
        fn = getattr(self._server, name)

        def w(*a, **kw):
            if self.down:
                raise PSConnectionError("PS down (chaos)")
            return fn(*a, **kw)
        return w


server = PSServer()
server.param_init("snd_order_embedding", (512, 8), "normal", 0.0, 1.0,
                  seed=3)
comm = KillablePS(server)
table = CacheSparseTable(limit=128, vocab_size=512, width=8,
                         key="snd_order_embedding", comm=comm,
                         policy="LRU")
rng = np.random.RandomState(0)
params = {"W1": rng.randn(13, 16) * .3, "W2": rng.randn(16, 16) * .3,
          "W3": rng.randn(16, 16) * .3,
          "W4": rng.randn(26 * 8 + 16, 1) * .3}
eng = EmbedServingEngine(params, {"snd_order_embedding": table},
                         model="wdl", wave=4, queue_limit=64)
treq = np.random.RandomState(42)
reqs = [EmbedRequest(item_ids=(treq.zipf(1.05, (2, 26)) - 1) % 512,
                     dense_features=treq.randn(2, 13).astype(np.float32))
        for _ in range(30)]
res = {}
res.update(eng.run(reqs[:10]))        # warm
comm.down = True                      # mid-trace PS kill
res.update(eng.run(reqs[10:20]))      # dark: stale/zero, zero loss
comm.down = False                     # recovery
res.update(eng.run(reqs[20:]))
s = table.perf_summary()
assert len(res) == 30, f"retired {len(res)}/30"
assert all(r.finish_reason == "scored" for r in res.values())
assert s["ps_failures"] > 0, "the kill never fired"
assert s["stale_served_rows"] + s["zero_served_rows"] > 0, s
assert s["hit_rate"] > 0.2, s
snap = eng.metrics.snapshot()
assert snap["requests_finished"] == 30, snap
print("embed serve gate OK: scored", snap["requests_finished"],
      "hit_rate", round(s["hit_rate"], 3),
      "ps_failures", s["ps_failures"])
PYEOF
if ! grep -q 'embed serve gate OK' "$LOG/embed_serve_gate.log"; then
  echo "embed serving gate FAILED — see $LOG/embed_serve_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/embed_trace.jsonl" --check \
    > "$LOG/embed_serve_contract.log" || {
  echo "embed serve span/gather contract check FAILED — see" \
       "$LOG/embed_serve_contract.log" >&2
  exit 1
}

# 00g. rolling-swap gate (ISSUE 15): an N=2 CPU fleet runs TWO v1 -> v2
#      rollouts mid-trace in one process.  The first is chaos-killed
#      mid-drain (HETU_CHAOS role=swap) and must fail CLEANLY — zero
#      request loss, fleet back on v1 (the corpse respawns on the
#      committed version), a flight dump holding the swap timeline.
#      The chaos kill is one-shot, so the second rollout must LAND:
#      fleet on v2, every Result version-stamped, and a trace stream
#      that passes the span-balance AND version-coherence rules.
run swap_gate 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/swap_trace.jsonl" \
    HETU_FAILURE_LOG="$LOG/swap_failure.jsonl" \
    HETU_FLIGHT_LOG="$LOG/swap_flight.jsonl" \
    HETU_CHAOS="seed=5,kill=2,role=swap" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import time
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import (Request, ServingEngine, ServingRouter,
                              WeightSyncCoordinator)

def mk_params(seed):
    rng, hd = np.random.RandomState(seed), 16
    p = {"sw_wte_table": rng.randn(61, hd) * 0.05,
         "sw_wpe": rng.randn(32, hd) * 0.05,
         "sw_ln_f_scale": np.ones(hd), "sw_ln_f_bias": np.zeros(hd)}
    for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                   ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                   ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
        p[f"sw_h0_{w}_weight"] = rng.randn(*shp) * 0.05
        p[f"sw_h0_{w}_bias"] = np.zeros(shp[1])
    for ln in ("ln1", "ln2"):
        p[f"sw_h0_{ln}_scale"] = np.ones(hd)
        p[f"sw_h0_{ln}_bias"] = np.zeros(hd)
    return p

p1, p2 = mk_params(0), mk_params(1)
cfg = GPTConfig(vocab_size=61, hidden_size=16, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)
router = ServingRouter(
    lambda i: ServingEngine(p1, cfg, slots=2, fast_path=False),
    replicas=2, restart_backoff=0.01)
coord = WeightSyncCoordinator(router, p1, version=1)

def trace(n, seed):
    rng = np.random.RandomState(seed)
    return [Request(prompt=[int(t) for t in rng.randint(0, 61, 3)],
                    max_new_tokens=4) for _ in range(n)]

# rollout 1: the seeded kill fires at replica 0's drain seam
assert coord.begin(p2, 2)
res1 = router.run(trace(8, 11))
coord.drain()
assert len(res1) == 8, f"retired {len(res1)}/8 under the chaos kill"
assert coord.state == "rolled_back", coord.last
deadline = time.time() + 10.0
while len(coord.fleet_versions()) < 2 and time.time() < deadline:
    router.step(); time.sleep(0.005)
assert coord.fleet_versions() == {0: 1, 1: 1}, coord.fleet_versions()

# rollout 2: the one-shot kill is spent — this one must land
assert coord.begin(p2, 2)
res2 = router.run(trace(8, 12))
coord.drain()
assert len(res2) == 8, f"retired {len(res2)}/8 in the clean rollout"
assert coord.state == "done", coord.last
assert coord.fleet_versions() == {0: 2, 1: 2}, coord.fleet_versions()
assert all(r.weight_version in (1, 2) for r in res2.values())
snap = router.snapshot()
assert snap["lost"] == 0 and snap["duplicates"] == 0, snap
print("rolling swap gate OK: failed+rolled_back then done,"
      " fleet v2, finished", snap["finished"])
PYEOF
if ! grep -q 'rolling swap gate OK' "$LOG/swap_gate.log"; then
  echo "rolling-swap gate FAILED — see $LOG/swap_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/swap_trace.jsonl" \
    "$LOG/swap_failure.jsonl" --check \
    > "$LOG/swap_trace_contract.log" || {
  echo "swap span-balance/version-coherence check FAILED — see" \
       "$LOG/swap_trace_contract.log" >&2
  exit 1
}
python bin/hetu_trace.py "$LOG/swap_flight.jsonl" --check \
    > "$LOG/swap_flight_contract.log" || {
  echo "swap flight-dump contract check FAILED — see" \
       "$LOG/swap_flight_contract.log" >&2
  exit 1
}

# 00h. elastic-fleet gate (ISSUE 16): one CPU process runs the three
#      autoscale chaos phases back to back.  Phase A: a burn-driven
#      scale-up whose bring-up is chaos-killed (role=autoscale takes
#      out the BUSIEST PEER mid-warm) — zero request loss, and every
#      finished request token-identical to an offline decode of the
#      same specs.  Phase B: a diurnal trough walks the fleet down,
#      then a flash crowd lands on the shrunken fleet — it must grow
#      back, still zero loss.  Phase C: a drain whose SUBJECT is
#      chaos-killed mid-drain (fresh one-shot plan) — the requeue reads
#      the router's records, never the corpse.  The combined stream
#      must pass the hetu_trace scale-balance rule (every scale_up
#      paired with replica_ready, every scale_down with
#      replica_retired, drained rids retiring exactly once on a peer).
run autoscale_gate 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/autoscale_trace.jsonl" \
    HETU_FAILURE_LOG="$LOG/autoscale_failure.jsonl" \
    HETU_FLIGHT_LOG="$LOG/autoscale_flight.jsonl" \
    HETU_CHAOS="seed=11,kill=1,role=autoscale" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import os
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.ps import faults
from hetu_tpu.serving import (SLO, FleetAutoscaler, Request,
                              ServingEngine, ServingRouter,
                              TrafficGenerator, replay)

def mk_params(seed=0):
    rng, hd = np.random.RandomState(seed), 16
    p = {"el_wte_table": rng.randn(61, hd) * 0.05,
         "el_wpe": rng.randn(32, hd) * 0.05,
         "el_ln_f_scale": np.ones(hd), "el_ln_f_bias": np.zeros(hd)}
    for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                   ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                   ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
        p[f"el_h0_{w}_weight"] = rng.randn(*shp) * 0.05
        p[f"el_h0_{w}_bias"] = np.zeros(shp[1])
    for ln in ("ln1", "ln2"):
        p[f"el_h0_{ln}_scale"] = np.ones(hd)
        p[f"el_h0_{ln}_bias"] = np.zeros(hd)
    return p

p = mk_params()
cfg = GPTConfig(vocab_size=61, hidden_size=16, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)

def mk_router(replicas, slo_ms=None):
    def factory(i):
        slo = [SLO("ttft", "latency", slo_ms)] if slo_ms else None
        return ServingEngine(p, cfg, slots=4, queue_limit=8,
                             max_seq_len=32, kv_block=4,
                             prefix_share=True, slo=slo)
    return ServingRouter(factory, replicas=replicas, directory=True,
                         shed_on_slo=False, restart_backoff=0.01)

# ---- phase A: chaos-killed scale-up, burn-driven --------------------
r = mk_router(2, slo_ms=0.001)   # any traffic burns the tight budget
auto = FleetAutoscaler(r, fleet_min=1, fleet_max=3, up_ticks=2,
                       down_ticks=10**6, cooldown=3)
specs = TrafficGenerator(seed=7, vocab=61, s_max=32, horizon_s=2.0,
                         base_rps=2.0, peak_rps=40.0, cycle_s=2.0,
                         n_sessions=4, prefix_len=8).trace(dt=0.05)
res, rep = replay(r, specs, step_s=0.01, tail_s=1.0)
snap = r.snapshot()
assert auto.scale_ups >= 1, auto.snapshot()
assert snap["lost"] == 0, snap
assert len(res) + len(rep["shed"]) + len(rep["rejected"]) == len(specs)
assert any(row["restarts"] >= 1 for row in snap["replicas"]), \
    "the scale-up chaos kill never fired"
eng = ServingEngine(p, cfg, slots=4, queue_limit=len(specs) + 1,
                    max_seq_len=32)
off = eng.run([sp.to_request() for sp in specs if sp.request_id in res])
for rid, x in res.items():
    assert list(x.tokens) == list(off[rid].tokens), rid
a_ups, a_fin = auto.scale_ups, snap["finished"]

# ---- phase B: flash crowd lands on the scaled-down fleet ------------
os.environ.pop("HETU_CHAOS", None)
faults.reset_plans()
r = mk_router(1)
auto = FleetAutoscaler(r, fleet_min=1, fleet_max=2, up_pressure=0.2,
                       up_ticks=2, down_pressure=0.1, down_ticks=25,
                       cooldown=10)
specs = TrafficGenerator(seed=21, vocab=61, s_max=32, horizon_s=4.0,
                         base_rps=1.0, peak_rps=80.0, cycle_s=2.0,
                         n_sessions=8, prefix_len=8,
                         flash=((1.9, 0.4, 25.0),)).trace(dt=0.05)
res, rep = replay(r, specs, step_s=0.01, tail_s=3.0)
snap = r.snapshot()
assert snap["lost"] == 0, snap
assert auto.scale_ups >= 2 and auto.scale_downs >= 1, auto.snapshot()
acts = [e["action"] for e in auto.timeline]
assert "scale_up" in acts[acts.index("scale_down"):], \
    f"no regrowth after the scale-down: {acts}"
assert len(res) + len(rep["shed"]) + len(rep["rejected"]) == len(specs)
b_ups, b_downs = auto.scale_ups, auto.scale_downs

# ---- phase C: drain whose subject is chaos-killed mid-drain ---------
os.environ["HETU_CHAOS"] = "seed=12,kill=1,role=autoscale"
faults.reset_plans()
r = mk_router(2)
reqs = [Request(prompt=[2 + i, 5, 9], max_new_tokens=6,
                request_id=f"c{i}") for i in range(8)]
for q in reqs:
    r.submit(q)
out = {}
for _ in range(3):
    for x in r.step():
        out[x.request_id] = x
r.retire_replica(1, reason="scale_down")
assert "chaos autoscale kill" in (r.replicas[1].exit_error or ""), \
    "the drain chaos kill never fired"
for _ in range(4000):
    if not r.pending:
        break
    for x in r.step():
        out[x.request_id] = x
assert r.snapshot()["lost"] == 0
assert set(out) == {q.request_id for q in reqs}
print("autoscale gate OK: chaos scale-up (ups", a_ups, "finished",
      a_fin, ") flash regrowth (ups", b_ups, "downs", b_downs,
      ") chaos drain retired 8/8, zero loss everywhere")
PYEOF
if ! grep -q 'autoscale gate OK' "$LOG/autoscale_gate.log"; then
  echo "elastic-fleet gate FAILED — see $LOG/autoscale_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/autoscale_trace.jsonl" \
    "$LOG/autoscale_failure.jsonl" --check \
    > "$LOG/autoscale_contract.log" || {
  echo "autoscale scale-balance/span check FAILED — see" \
       "$LOG/autoscale_contract.log" >&2
  exit 1
}
python bin/hetu_trace.py "$LOG/autoscale_flight.jsonl" --check \
    > "$LOG/autoscale_flight_contract.log" || {
  echo "autoscale flight-dump contract check FAILED — see" \
       "$LOG/autoscale_flight_contract.log" >&2
  exit 1
}

# 00i. tiered-KV gate (ISSUE 17): one CPU process runs the prefix
#      storm twice through a starved paged pool (2 slots x 8 blocks vs
#      a 12-session zipf working set) behind the full spill ladder
#      (host-RAM ring -> 2-shard PS cold store).  Phase A: the ladder
#      cycles (spills, fetches, ring->PS demotions), zero loss, every
#      finished request token-identical to an offline decode of the
#      same specs.  Phase B: the same storm with HETU_CHAOS
#      role=kvtier killing the PS rung mid-storm — the store must mark
#      the cold rung dead and degrade to drop-on-evict with zero loss,
#      identity intact, and WITHOUT taking the replica down with it.
#      The combined stream must pass the hetu_trace tier-balance rule
#      (every kv_spill closes with exactly one kv_fetch or
#      kv_tier_drop), and the kill must land in the failure log.
run kvtier_gate 600 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/kvtier_trace.jsonl" \
    HETU_FAILURE_LOG="$LOG/kvtier_failure.jsonl" \
    HETU_FLIGHT_LOG="$LOG/kvtier_flight.jsonl" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import os
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.ps import faults
from hetu_tpu.ps.server import PSServer
from hetu_tpu.ps.sharded import ShardedPSClient
from hetu_tpu.serving import (ServingEngine, ServingRouter,
                              TieredKVStore, TrafficGenerator, replay)

def mk_params(seed=0):
    rng, hd = np.random.RandomState(seed), 16
    p = {"kt_wte_table": rng.randn(61, hd) * 0.05,
         "kt_wpe": rng.randn(32, hd) * 0.05,
         "kt_ln_f_scale": np.ones(hd), "kt_ln_f_bias": np.zeros(hd)}
    for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                   ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                   ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
        p[f"kt_h0_{w}_weight"] = rng.randn(*shp) * 0.05
        p[f"kt_h0_{w}_bias"] = np.zeros(shp[1])
    for ln in ("ln1", "ln2"):
        p[f"kt_h0_{ln}_scale"] = np.ones(hd)
        p[f"kt_h0_{ln}_bias"] = np.zeros(hd)
    return p

p = mk_params()
cfg = GPTConfig(vocab_size=61, hidden_size=16, num_hidden_layers=1,
                num_attention_heads=2, max_position_embeddings=32,
                batch_size=1, seq_len=32, dropout_rate=0.0)

def mk_store():
    return TieredKVStore(
        host_bytes=4096, ps_tier=True,
        ps=ShardedPSClient(servers=[PSServer(), PSServer()]))

def mk_router(store):
    def factory(i):
        return ServingEngine(p, cfg, slots=2, queue_limit=64,
                             max_seq_len=32, kv_block=8,
                             pool_blocks=8, prefix_share=True)
    return ServingRouter(factory, replicas=1, kv_tiers=store)

specs = TrafficGenerator(seed=31, vocab=61, s_max=32, horizon_s=2.0,
                         base_rps=12.0, peak_rps=12.0, cycle_s=2.0,
                         n_sessions=12, zipf_a=1.3,
                         prefix_len=8).trace(dt=0.05)
eng = ServingEngine(p, cfg, slots=2, queue_limit=len(specs) + 1,
                    max_seq_len=32)
off = eng.run([sp.to_request() for sp in specs])

# ---- phase A: the full ladder under the storm, no chaos -------------
store = mk_store()
r = mk_router(store)
res, rep = replay(r, specs, step_s=0.01)
snap = r.snapshot()
assert snap["lost"] == 0 and not rep["shed"] and not rep["rejected"]
st = snap["kv_tiers"]
assert sum(st["spills"].values()) > 0, st
assert sum(st["fetches"].values()) > 0, st
assert st["demotes"] > 0, st
for rid, x in res.items():
    assert list(x.tokens) == list(off[rid].tokens), rid
store.close("kvtier_gate_phase_a_done")
a_spills = sum(st["spills"].values())
a_fetches = sum(st["fetches"].values())

# ---- phase B: PS rung chaos-killed mid-storm ------------------------
os.environ["HETU_CHAOS"] = "seed=5,kill=2,role=kvtier"
faults.reset_plans()
store = mk_store()
r = mk_router(store)
res, rep = replay(r, specs, step_s=0.01)
snap = r.snapshot()
os.environ.pop("HETU_CHAOS", None)
faults.reset_plans()
assert snap["lost"] == 0 and not rep["shed"] and not rep["rejected"]
assert snap["kv_tiers"]["ps_dead"] is True, snap["kv_tiers"]
assert all(x["restarts"] == 0 for x in snap["replicas"]), \
    "the PS kill took a replica down with it"
for rid, x in res.items():
    assert list(x.tokens) == list(off[rid].tokens), rid
store.close("kvtier_gate_phase_b_done")
print("kvtier gate OK: ladder cycled (spills", a_spills, "fetches",
      a_fetches, ") then PS chaos kill degraded to drop-on-evict,",
      "zero loss + token identity in both phases")
PYEOF
if ! grep -q 'kvtier gate OK' "$LOG/kvtier_gate.log"; then
  echo "tiered-KV gate FAILED — see $LOG/kvtier_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/kvtier_trace.jsonl" \
    "$LOG/kvtier_failure.jsonl" --check \
    > "$LOG/kvtier_contract.log" || {
  echo "tiered-KV tier-balance check FAILED — see" \
       "$LOG/kvtier_contract.log" >&2
  exit 1
}
if ! grep -q 'kvtier_ps_killed' "$LOG/kvtier_failure.jsonl"; then
  echo "tiered-KV gate: PS chaos kill missing from the failure log" >&2
  exit 1
fi

# 00k. concurrency gate (ISSUE 19): the sanitizer itself, on CPU.
#      Green half: the deterministic interleaving fuzzer must be a
#      pure function of its seed (planted lost-update race reproduces
#      same-seed-twice across a sweep, pinned CI seed loses updates,
#      TracedLock'd variant exact on every seed), then the cstable/PS
#      hammer runs under seeded preemption with LOCKDEP ARMED — every
#      delta lands exactly once (cache == PS row for row) and the
#      acquisition-order graph stays clean; the merged stream must
#      pass hetu_trace --check including the lockdep rule.  Red half:
#      a second process plants a lock-order inversion and its stream
#      must FAIL the same check — the rule is proven live, not just
#      absent.
run concurrency_gate 600 env HETU_TELEMETRY=1 HETU_LOCKDEP=1 \
    HETU_TELEMETRY_LOG="$LOG/concurrency_trace.jsonl" \
    JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np
from hetu_tpu import locks
from hetu_tpu.analysis.concurrency import (assert_lockdep_clean,
                                           run_interleaved)
from hetu_tpu.cache.cstable import CacheSparseTable
from hetu_tpu.ps.server import PSServer

VOCAB, W, CI_SEED = 64, 4, 3

def racy(seed):
    state = {"n": 0}
    def worker():
        for _ in range(10):
            v = state["n"]
            locks.sched_point()
            state["n"] = v + 1
    run_interleaved(worker, worker, worker, seed=seed)
    return state["n"]

def locked(seed):
    state = {"n": 0}
    mu = locks.TracedLock("gate.counter")
    def worker():
        for _ in range(10):
            with mu:
                v = state["n"]
                locks.sched_point()
                state["n"] = v + 1
    run_interleaved(worker, worker, worker, seed=seed)
    return state["n"]

results = set()
for seed in range(6):
    a, b = racy(seed), racy(seed)
    assert a == b, f"seed {seed} not reproducible: {a} vs {b}"
    results.add(a)
    assert locked(seed) == 30, f"locked counter lost updates, seed {seed}"
assert racy(CI_SEED) < 30, "CI seed failed to surface the planted race"
assert len(results) >= 2, "seed sweep explored a single schedule"

class YieldingComm:
    # hands the scheduler token away inside every RPC: preemption
    # lands mid-transaction, where the bugs live
    def __init__(self, server):
        self._server = server
    def __getattr__(self, name):
        fn = getattr(self._server, name)
        def wrapper(*a, **kw):
            locks.sched_point()
            return fn(*a, **kw)
        return wrapper

for seed in range(4):
    server = PSServer()
    server.param_init("emb", (VOCAB, W), "normal", 0.0, 1.0, seed=3)
    t = CacheSparseTable(limit=32, vocab_size=VOCAB, width=W,
                         key="emb", comm=YieldingComm(server),
                         policy="LRU", push_bound=0)
    rngs = [np.random.RandomState(100 * seed + i) for i in range(2)]
    def lookups(rng=rngs[0]):
        for _ in range(6):
            assert t.embedding_lookup(
                rng.randint(0, VOCAB, 8)).shape == (8, W)
    def updates(rng=rngs[1]):
        for _ in range(6):
            ids = rng.randint(0, VOCAB, 4)
            t.embedding_update(ids,
                               rng.randn(4, W).astype(np.float32) * .01)
    run_interleaved(lookups, updates, seed=seed)
    t.flush()
    ids = np.arange(VOCAB)
    np.testing.assert_allclose(t.embedding_lookup(ids),
                               server.sparse_pull("emb", ids),
                               rtol=1e-4, atol=1e-5)
assert_lockdep_clean("suite cstable/PS hammer")
print("concurrency gate OK: fuzzer seed-exact over 6 seeds,",
      "cstable/PS hammer clean over 4 seeds under lockdep")
PYEOF
if ! grep -q 'concurrency gate OK' "$LOG/concurrency_gate.log"; then
  echo "concurrency gate FAILED — see $LOG/concurrency_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/concurrency_trace.jsonl" --check \
    > "$LOG/concurrency_trace_contract.log" || {
  echo "concurrency trace contract/lockdep check FAILED — see" \
       "$LOG/concurrency_trace_contract.log" >&2
  exit 1
}
run lockdep_red 300 env HETU_TELEMETRY=1 HETU_LOCKDEP=1 \
    HETU_TELEMETRY_LOG="$LOG/lockdep_red.jsonl" \
    JAX_PLATFORMS=cpu python - <<'PYEOF'
from hetu_tpu import locks
a = locks.TracedLock("red.A")
b = locks.TracedLock("red.B")
with a:
    with b:
        pass
with b:
    with a:                 # the planted inversion
        pass
(v,) = locks.lockdep_violations()
assert v["kind"] == "order"
rep = locks.format_violation(v)
assert "red.A" in rep and "red.B" in rep
print("lockdep red gate OK: inversion detected and emitted")
PYEOF
if ! grep -q 'lockdep red gate OK' "$LOG/lockdep_red.log"; then
  echo "lockdep red gate FAILED — see $LOG/lockdep_red.log" >&2
  exit 1
fi
if python bin/hetu_trace.py "$LOG/lockdep_red.jsonl" --check \
    > "$LOG/lockdep_red_contract.log" 2>&1; then
  echo "lockdep trace rule FAILED to flag a planted inversion — see" \
       "$LOG/lockdep_red_contract.log" >&2
  exit 1
fi

# 00l. MoE serving gate (ISSUE 20): one CPU process decodes the MoE
#      GPT (top-2 of 4 experts, alternating blocks) through the engine
#      across TWO cache configurations — the block-table pool and
#      the pool with int8 KV — and requires greedy
#      TOKEN-IDENTICAL outputs vs offline generate_fast in every one,
#      plus the routing-attribution invariant on the engine counters
#      (routed + dropped == tokens x top_k x MoE layers).  A second,
#      capacity-starved run (cf=0.25) must actually DROP and its serve
#      stream must still pass hetu_trace --check — the MoE attribution
#      rule is proven against overflow, not just the easy case.
run moe_gate 900 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/moe_trace.jsonl" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models.gpt_decode import generate_fast
from hetu_tpu.models.moe_decode import (MoEDecodeConfig,
                                        init_moe_params, moe_spec_of)
from hetu_tpu.serving import Request, ServingEngine

cfg = MoEDecodeConfig(
    vocab_size=97, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=2, ffn_mult=2, seq_len=48, dropout_rate=0.0,
    max_position_embeddings=48, num_experts=4, top_k=2,
    capacity_factor=2.0, moe_every=2)
p = init_moe_params(cfg, name="moe", seed=0)
prompts = [[5, 9, 2], [7, 1, 4, 3, 8], [11, 6], [13, 2, 2, 7]]
NEW = 8
ref = {i: [int(t) for t in np.asarray(
           generate_fast(p, cfg, [pr], NEW, temperature=0.0, seed=0,
                         name="moe"))[0][len(pr):]]
       for i, pr in enumerate(prompts)}
n_moe = moe_spec_of(cfg).moe_layers(cfg.num_hidden_layers)
mk = lambda: [Request(request_id=str(i), prompt=pr, max_new_tokens=NEW,
                      temperature=0.0, seed=0)
              for i, pr in enumerate(prompts)]
configs = [("paged", dict(fast_path=True)),
           ("paged_int8", dict(fast_path=True, kv_quant="int8"))]
for label, kw in configs:
    eng = ServingEngine(p, cfg, slots=4, name="moe", **kw)
    out = eng.run(mk())
    got = {int(i): [int(t) for t in np.asarray(r.tokens)[r.prompt_len:]]
           for i, r in out.items()}
    assert got == ref, f"{label}: engine diverged from offline"
    tot = int(eng.expert_load.sum() + eng.expert_drops.sum())
    assert tot == eng.moe_tokens * cfg.top_k * n_moe, label
# capacity-overflow arm: cf=0.25 must drop; identity is NOT claimed
# here (dropped tokens ride the residual) but the accounting must
# still close and the stream must pass the trace contract below
scfg = MoEDecodeConfig(
    vocab_size=97, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=2, ffn_mult=2, seq_len=48, dropout_rate=0.0,
    max_position_embeddings=48, num_experts=4, top_k=2,
    capacity_factor=0.25, moe_every=2)
seng = ServingEngine(p, scfg, slots=4, name="moe", fast_path=True)
seng.run(mk())
assert int(seng.expert_drops.sum()) > 0, \
    "cf=0.25 dropped nothing — the overflow path went untested"
stot = int(seng.expert_load.sum() + seng.expert_drops.sum())
assert stot == seng.moe_tokens * scfg.top_k * n_moe
print("moe gate OK: identity over", len(configs), "cache configs,",
      "overflow drops", int(seng.expert_drops.sum()),
      "accounted, imbalance",
      round(float(seng.expert_imbalance), 3))
PYEOF
if ! grep -q 'moe gate OK' "$LOG/moe_gate.log"; then
  echo "MoE serving gate FAILED — see $LOG/moe_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/moe_trace.jsonl" --check \
    > "$LOG/moe_trace_contract.log" || {
  echo "MoE trace contract check FAILED — see" \
       "$LOG/moe_trace_contract.log" >&2
  exit 1
}

# 4e (ordered with the 00-gates: pure-CPU via JAX_PLATFORMS=cpu, so it
#     must pass BEFORE any chip time is spent).  Speculative-decoding
#     trace-replay gate: the draft-propose / batched-verify path must
#     produce GREEDY TOKEN-IDENTICAL outputs vs the plain engine at
#     acceptance 1.0 (layers past the draft output-zeroed so draft
#     logits == target logits), retire every request in fewer waves
#     than tokens, and leave a serve stream that passes the
#     spec-attribution rule (hetu_trace --check: accepted + bonus + 1
#     == n_generated per request).
run spec_gate 900 env HETU_TELEMETRY=1 \
    HETU_TELEMETRY_LOG="$LOG/spec_trace.jsonl" JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import numpy as np
import hetu_tpu as ht  # noqa: F401
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import Request, ServingEngine

rng, hd, L = np.random.RandomState(0), 16, 2
p = {"spg_wte_table": rng.randn(61, hd) * 0.05,
     "spg_wpe": rng.randn(64, hd) * 0.05,
     "spg_ln_f_scale": np.ones(hd), "spg_ln_f_bias": np.zeros(hd)}
for i in range(L):
    for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                   ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                   ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
        p[f"spg_h{i}_{w}_weight"] = rng.randn(*shp) * 0.05
        p[f"spg_h{i}_{w}_bias"] = np.zeros(shp[1])
    for ln in ("ln1", "ln2"):
        p[f"spg_h{i}_{ln}_scale"] = np.ones(hd)
        p[f"spg_h{i}_{ln}_bias"] = np.zeros(hd)
# zero the post-draft layer's outputs: draft logits == target logits,
# acceptance 1.0 — the high-acceptance endpoint of the A/B
for wn in ("attn_proj_weight", "attn_proj_bias",
           "ffn_wo_weight", "ffn_wo_bias"):
    p[f"spg_h1_{wn}"] = np.zeros_like(p[f"spg_h1_{wn}"])
cfg = GPTConfig(vocab_size=61, hidden_size=hd, num_hidden_layers=L,
                num_attention_heads=2, max_position_embeddings=64,
                batch_size=1, seq_len=64, dropout_rate=0.0)
treq = np.random.RandomState(11)
mk = lambda: [Request(prompt=[int(t) for t in treq.randint(0, 61, 4)],
                      max_new_tokens=12, seed=s) for s in range(6)]
treq = np.random.RandomState(11)
plain = ServingEngine(p, cfg, slots=2, fast_path=False).run(mk())
treq = np.random.RandomState(11)
eng = ServingEngine(p, cfg, slots=2, fast_path=False, spec=3,
                    spec_adapt=False, spec_draft_layers=1)
res = eng.run(mk())
a = sorted(r.tokens.tolist() for r in plain.values())
b = sorted(r.tokens.tolist() for r in res.values())
assert a == b, "speculative greedy diverged from the plain engine"
assert eng.spec_proposed > 0 and \
    eng.spec_accepted == eng.spec_proposed, \
    (eng.spec_accepted, eng.spec_proposed)
total = sum(r.n_generated for r in res.values())
assert eng.spec_waves < total, (eng.spec_waves, total)
print("spec gate OK: waves", eng.spec_waves, "of", total, "tokens,",
      "accepted", eng.spec_accepted, "/", eng.spec_proposed)
PYEOF
if ! grep -q 'spec gate OK' "$LOG/spec_gate.log"; then
  echo "speculative-decoding gate FAILED — see $LOG/spec_gate.log" >&2
  exit 1
fi
python bin/hetu_trace.py "$LOG/spec_trace.jsonl" --check \
    > "$LOG/spec_trace_contract.log" || {
  echo "spec-attribution/contract check FAILED — see" \
       "$LOG/spec_trace_contract.log" >&2
  exit 1
}

# 0. the rows an interrupted capture has previously cost us: the Aug-2
#    capture measured bert_base/bert4l/gpt/resnet18 fresh and was cut
#    INSIDE ctr_hybrid — so these rows run first, before the long
#    full-matrix pass
run matrix_gap 3600 env HETU_BENCH_CONFIGS=ctr_hybrid,moe,long_context \
    python bench.py

# 1. full matrix under honest accounting (bert_base probes pick the
#    batch; pin with HETU_BENCH_BERT_BATCH=32 if probes misbehave)
run matrix 7200 python bench.py

# 2. the (batch x attention x head) ablation sweep + planner validation
HETU_BENCH_SWEEP=1 run sweep 5400 python bench.py

# 3. max embedding rows per chip (1M..256M ladder)
HETU_BENCH_CTR_ROWS=1 run ctr_rows 5400 python bench.py

# 4. refresh the chip calibration artifact (raw + clamped curves)
run calibration 3600 python -m hetu_tpu.planner.chip_calibration

# 4b. KV-cached serving throughput (BENCH_DECODE.json)
HETU_BENCH_DECODE=1 run decode 3600 python bench.py

# 4d. quantized-bytes A/B of record (ISSUE 9), the training half: int8 PS
#     push/pull vs the exact f32 wire — bytes via the PR 5
#     ps.rpc.bytes_* counters + step time, >=3.5x reduction asserted —
#     merged into BENCH_PS_SCALING.json as its quant_ab section.
run ps_quant 1800 python examples/ctr/bench_ps_scaling.py --quant-only

# 5. long-context tile tuning: A/B a couple of block shapes at 32k
for blocks in "512,1024" "1024,1024" "1024,2048" "512,2048"; do
  HETU_BENCH_LC_BLOCKS=$blocks HETU_BENCH_CONFIGS=long_context \
    run "lc_${blocks/,/x}" 2700 python bench.py
done

# 6. MoE chip-fill A/B (the recorded config underfilled the chip)
for tok in 1024 2048 4096; do
  HETU_BENCH_MOE_TOKENS=$tok HETU_BENCH_CONFIGS=moe \
    run "moe_t${tok}" 2700 python bench.py
done

# 7. bert4l attention A/B: the Aug-2 fresh row (630/s, flash OFF via
#    the seq>=1024 crossover) is 3x below the Jul-30 record (1987/s,
#    flash ON at seq 128) — decide whether the crossover heuristic is
#    wrong for short sequences.  The winner's flash setting should be
#    folded back into _bench_lm's use_flash rule.  The hypothesized
#    winner (flash) runs LAST so an unattended pass leaves the
#    likely-best row in the matrix, not the suspected loser.
HETU_BENCH_FORCE_FLASH=0 HETU_BENCH_CONFIGS=bert4l \
  run bert4l_noflash 2700 python bench.py
HETU_BENCH_FORCE_FLASH=1 HETU_BENCH_CONFIGS=bert4l \
  run bert4l_flash 2700 python bench.py

# NOTE: stages 5/6/7 leave the LAST A/B variant in BENCH_MATRIX.json —
# read the logs, then re-run the winning setting (its env + the config
# name) so the matrix records the best measured configuration.

echo "done; artifacts: BENCH_MATRIX.json SWEEP_BERT_BASE.json \
BENCH_CTR_ROWS.json CALIBRATION_TPU.json BENCH_DECODE.json \
(logs in $LOG)"
