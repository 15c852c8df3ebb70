"""EmbedServingEngine: batched low-latency recommendation scoring over
the HET embedding cache (the second production workload ROADMAP item 4
calls for — the one the serving substrate went model-agnostic for).

Requests carry ``(user_ids, item_ids, dense_features)`` instead of a
token prompt.  The engine runs in WAVES: each step claims up to
``wave`` queued requests, gathers every embedding row they need through
:class:`~hetu_tpu.cache.cstable.CacheSparseTable` — cache hits are
served locally, misses sparse-pull from the PS (int8 on the wire under
``HETU_PS_QUANT``, the EQuARX-motivated byte diet) — then scores the
whole wave in ONE jitted dense-tower forward, bucket-padded so repeat
wave sizes reuse the compile.  Towers are pure-jax twins of the graph
builders in ``models/ctr.py`` / ``models/ncf.py`` (same param names,
same math), so a PS checkpoint trained by the hybrid path serves
as-is.

Degradation mirrors training exactly, because it IS the training
cache: through a PS outage the cstable serves stale rows within its
staleness budget, unfetchable rows come back as zero vectors (the
standard missing-embedding fallback, never inserted), and the engine
keeps answering — zero request loss, chaos-tested with a mid-trace PS
kill.  Hit-rate / staleness / pull-bytes ride the telemetry registry
(``cache.*`` gauges) next to the serve stream.

Lifecycle telemetry is the GPT engine's vocabulary with the KV phases
replaced by ``gather``/``forward`` (serving/metrics.py
EmbedServingMetrics): submit -> queue -> gather -> forward -> retire,
one req_span per phase, serve_admit/serve_finish pairing intact so
``hetu_trace --check`` span balance, ``hetu_top`` (workload column
"embed"), the SLO monitor, and the fleet router all work unmodified.

Quickstart::

    from hetu_tpu.serving import EmbedServingEngine, EmbedRequest
    eng = EmbedServingEngine(params, tables={"snd_order_embedding": t},
                             model="wdl", embedding_size=8)
    eng.submit(EmbedRequest(item_ids=sparse[i], dense_features=dense[i]))
    results = eng.run()           # {request_id: EmbedResult}
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import flight
from ..telemetry import slo as slo_mod
from .engine import QueueFull, _STORM_REJECTS
from .kv_manager import round_up_pow2
from .metrics import EmbedServingMetrics
from .request import EmbedRequest, EmbedResult

# sparse/dense field counts of the Criteo towers (models/ctr.py)
CRITEO_SPARSE_FIELDS = 26
CRITEO_DENSE_FIELDS = 13


# ------------------------------------------------------------------- #
# pure-jax dense towers — numerically the graph builders in
# models/ctr.py / models/ncf.py with the embedding lookup factored out
# (the cache owns it); param names match the builders so a PS
# checkpoint maps 1:1
# ------------------------------------------------------------------- #

def _mlp_tower(x, params):
    """The shared W1->W2->W3 relu tower of wdl_criteo/dcn_criteo:
    y3 = relu(relu(x @ W1) @ W2) @ W3 (no activation after W3)."""
    y = jax.nn.relu(x @ params["W1"])
    y = jax.nn.relu(y @ params["W2"])
    return y @ params["W3"]


def wdl_forward(params, sparse_emb, dense):
    """wdl_criteo minus lookup: sparse_emb [B, 26*E], dense [B, 13]."""
    y3 = _mlp_tower(dense, params)
    y = jnp.concatenate([sparse_emb, y3], axis=1) @ params["W4"]
    return jax.nn.sigmoid(y)[:, 0]


def dcn_forward(params, sparse_emb, dense, num_cross_layers=3):
    """dcn_criteo minus lookup: cross network over x = [sparse|dense]
    plus the shared MLP tower, fused by W4."""
    x = jnp.concatenate([sparse_emb, dense], axis=1)
    cross = x
    for i in range(num_cross_layers):
        x1w = cross @ params[f"cross{i}_weight"]          # [B, 1]
        cross = x * x1w + cross + params[f"cross{i}_bias"]
    y3 = _mlp_tower(x, params)
    y = jnp.concatenate([cross, y3], axis=1) @ params["W4"]
    return jax.nn.sigmoid(y)[:, 0]


def ncf_forward(params, user_latent, item_latent, embed_dim,
                n_mlp_layers):
    """neural_mf minus lookup: GMF product of the first ``embed_dim``
    factors + MLP over the rest, fused by W{len(layers)}."""
    gmf = user_latent[:, :embed_dim] * item_latent[:, :embed_dim]
    x = jnp.concatenate([user_latent[:, embed_dim:],
                         item_latent[:, embed_dim:]], axis=1)
    for i in range(1, n_mlp_layers):
        x = jax.nn.relu(x @ params[f"W{i}"])
    y = jnp.concatenate([gmf, x], axis=1) @ params[f"W{n_mlp_layers}"]
    return jax.nn.sigmoid(y)[:, 0]


class _WaveSlots:
    """Duck-typed stand-in for the KV-manager surface the fleet tier
    reads off an engine (Replica.live/occupancy, the router's capacity
    probe).  Waves complete synchronously inside step(), so nothing is
    ever "live" between steps; ``s_max`` is None — embedding requests
    have no sequence bound (RequestCore.capacity_tokens)."""

    def __init__(self, n_slots):
        self.n_slots = int(n_slots)
        self.s_max = None

    def live(self):
        return []


class EmbedServingEngine:
    """Continuous-wave embedding inference over one or two
    CacheSparseTables plus a jitted dense tower.

    ``params``: dict of tower weights (numpy/jax arrays) named like the
    graph builders (W1..W4 + cross{i}_* for CTR, W1..Wn for NCF).
    ``tables``: name -> CacheSparseTable; ``"snd_order_embedding"``
    for wdl/dcn, ``"user_embed"`` + ``"item_embed"`` for ncf.
    ``model``: "wdl" | "dcn" | "ncf".  ``wave``: most requests claimed
    per scoring wave (one embedding gather + one jitted tower forward);
    ``queue_limit``: admission-queue depth past which ``submit`` raises
    QueueFull; ``slo`` wires an SLOMonitor exactly like ServingEngine
    (env-declared by default).
    """

    def __init__(self, params, tables, model="wdl", *,
                 embedding_size=None, embed_dim=8,
                 mlp_layers=(64, 32, 16, 8), num_cross_layers=3,
                 wave=8, queue_limit=64, slo=None, tags=None,
                 log_path=None):
        if model not in ("wdl", "dcn", "ncf"):
            raise ValueError(
                f"model must be 'wdl', 'dcn' or 'ncf', got {model!r}")
        self.model = model
        self.tables = dict(tables)
        need = (("user_embed", "item_embed") if model == "ncf"
                else ("snd_order_embedding",))
        for name in need:
            if name not in self.tables:
                raise ValueError(
                    f"model {model!r} needs table {name!r}; got "
                    f"{sorted(self.tables)}")
        self.params = {k: jnp.asarray(v, jnp.float32)
                       for k, v in params.items()}
        if model == "ncf":
            self.embed_dim = int(embed_dim)
            self.n_mlp_layers = len(mlp_layers)
        else:
            self.embedding_size = int(
                embedding_size if embedding_size is not None
                else self.tables["snd_order_embedding"].width)
            self.num_cross_layers = int(num_cross_layers)
        self.wave = int(wave)
        self.queue_limit = int(queue_limit)
        self._queue = collections.deque()
        self.metrics = EmbedServingMetrics(log_path, tags=tags)
        # optional fn(request, slot) called at retirement — same seam
        # the router's GPT engines expose
        self.retire_hook = None
        if isinstance(slo, slo_mod.SLOMonitor):
            self.slo = slo
            self.slo.emit_fn = self.metrics.event
        elif slo is not None:
            self.slo = slo_mod.SLOMonitor(slo,
                                          emit_fn=self.metrics.event)
        else:
            self.slo = slo_mod.SLOMonitor.from_env(
                emit_fn=self.metrics.event)
        self._reject_streak = 0
        self.kv = _WaveSlots(self.wave)
        self.steps = 0
        self.peak_live = 0
        self._fwd_cache = {}        # row bucket -> jitted forward
        # live weight sync: version of the resident tower params
        # (None = unversioned); waves are atomic, so every result of a
        # wave carries the one version it scored under
        self.weight_version = None
        self.last_swap_at = None

    # ------------------------------------------------------------- #
    # live weight sync (serving/weight_sync.py)
    # ------------------------------------------------------------- #

    def set_weight_version(self, version):
        """Stamp the current params; rides ``metrics.tags`` so every
        serve event carries ``weight_version``."""
        self.weight_version = int(version)
        self.metrics.tags["weight_version"] = self.weight_version

    def swap_params(self, params, *, version=None):
        """Replace the tower params between waves (the rolling-swap
        primitive; the jitted forwards take params as arguments, so no
        recompile).  Key-set and shapes must match the resident dict —
        a corrupt push fails here, before anything moves.  Call only on
        a drained engine (``pending == 0``)."""
        new = {}
        for k, v in params.items():
            p = jnp.asarray(v, jnp.float32)
            old = self.params.get(k)
            if old is not None and tuple(p.shape) != tuple(old.shape):
                raise ValueError(
                    f"swap_params: {k} has shape {tuple(p.shape)}, "
                    f"resident is {tuple(old.shape)}")
            new[k] = p
        if set(new) != set(self.params):
            missing = sorted(set(self.params) - set(new))
            extra = sorted(set(new) - set(self.params))
            raise ValueError(
                f"swap_params key mismatch: missing {missing[:4]}, "
                f"unexpected {extra[:4]}")
        self.params = new
        self.last_swap_at = time.perf_counter()
        if version is not None:
            self.set_weight_version(version)
        self.metrics.event("weight_swap", version=self.weight_version)

    # ------------------------------------------------------------- #

    def submit(self, request):
        """Enqueue an EmbedRequest; raises QueueFull at ``queue_limit``
        pending admissions (same bounded-queue backpressure + storm
        flight-dump contract as the GPT engine).  Returns the
        request."""
        req = request
        if not isinstance(req, EmbedRequest):
            raise TypeError(
                f"EmbedServingEngine serves EmbedRequest, got "
                f"{type(req).__name__}")
        if len(self._queue) >= self.queue_limit:
            self.metrics.record_reject(req.request_id, len(self._queue))
            self._reject_streak += 1
            if self._reject_streak == _STORM_REJECTS:
                # once per storm: the streak resets on the next accept
                flight.RECORDER.dump(
                    "queue_storm", rejects=self._reject_streak,
                    queue_depth=len(self._queue),
                    queue_limit=self.queue_limit)
            raise QueueFull(
                f"admission queue at capacity ({self.queue_limit})")
        self._reject_streak = 0
        req.submitted_at = time.perf_counter()
        self._queue.append(req)
        self.metrics.record_submit(req.request_id, len(self._queue))
        return req

    @property
    def pending(self):
        """Requests not yet scored (waves retire synchronously, so
        this is the queue)."""
        return len(self._queue)

    @property
    def queue_depth(self):
        return len(self._queue)

    # ------------------------------------------------------------- #

    def step(self):
        """One scoring wave: claim up to ``wave`` queued requests,
        gather their embedding rows through the cache, run ONE jitted
        tower forward over the bucket-padded wave, retire everything.
        Returns the EmbedResults.  An escaping exception dumps the
        flight recorder first (same black-box contract as the GPT
        engine)."""
        try:
            return self._step_wave()
        except QueueFull:
            raise
        except Exception as e:   # noqa: BLE001 — dump-and-reraise
            flight.RECORDER.dump(
                "engine_exception",
                error=f"{type(e).__name__}: {e}"[:200],
                step=self.steps, live=0,
                queue_depth=len(self._queue))
            raise

    def _claim_wave(self):
        reqs = []
        while self._queue and len(reqs) < self.wave:
            req = self._queue.popleft()
            self.metrics.lc_claimed(req.request_id)
            reqs.append(req)
        return reqs

    def _step_wave(self):
        reqs = self._claim_wave()
        if not reqs:
            return []
        self.peak_live = max(self.peak_live, len(reqs))
        t_wave = time.perf_counter()
        rids = [r.request_id for r in reqs]
        rows = sum(r.n_pairs for r in reqs)

        # ---- gather: every embedding row the wave needs, through the
        # cache (hit = local, miss = PS pull, outage = stale/zero) ----
        hits0, total0 = self._cache_counts()
        t_g = time.perf_counter()
        if self.model == "ncf":
            users = np.concatenate([r.user_ids for r in reqs])
            items = np.concatenate([r.item_ids.reshape(-1)
                                    for r in reqs])
            u_lat = self.tables["user_embed"].embedding_lookup(users)
            i_lat = self.tables["item_embed"].embedding_lookup(items)
            gathered = (u_lat.astype(np.float32),
                        i_lat.astype(np.float32))
        else:
            sparse_ids = np.concatenate(
                [r.item_ids.reshape(r.n_pairs, -1) for r in reqs])
            emb = self.tables["snd_order_embedding"].embedding_lookup(
                sparse_ids)
            gathered = (np.asarray(emb, np.float32).reshape(
                rows, -1),)
            dense = np.concatenate(
                [np.zeros((r.n_pairs, CRITEO_DENSE_FIELDS), np.float32)
                 if r.dense_features is None else r.dense_features
                 for r in reqs])
        gather_s = time.perf_counter() - t_g
        hits1, total1 = self._cache_counts()
        d_total = total1 - total0
        hit_rate = (hits1 - hits0) / d_total if d_total else 1.0
        self.metrics.record_gather(len(reqs), rows, gather_s, hit_rate,
                                   requests=rids)

        # ---- forward: one jitted call over the pow2-padded wave ----
        bucket = round_up_pow2(rows)
        if self.model == "ncf":
            u_pad = self._pad(gathered[0], bucket)
            i_pad = self._pad(gathered[1], bucket)
            scores = self._forward(bucket)(self.params, u_pad, i_pad)
        else:
            s_pad = self._pad(gathered[0], bucket)
            d_pad = self._pad(dense, bucket)
            scores = self._forward(bucket)(self.params, s_pad, d_pad)
        scores = np.asarray(jax.block_until_ready(scores))[:rows]
        wave_s = time.perf_counter() - t_wave

        # ---- retire: scores land for every participant at once ----
        results = []
        now = time.perf_counter()
        offset = 0
        for slot, req in enumerate(reqs):
            s = scores[offset:offset + req.n_pairs].copy()
            offset += req.n_pairs
            req.first_token_at = now
            ttft = now - req.submitted_at
            self.metrics.record_admit(
                req.request_id, slot,
                queue_wait_s=max(t_wave - req.submitted_at, 0.0),
                ttft_s=ttft)
            res = EmbedResult(
                request_id=req.request_id, scores=s,
                n_pairs=req.n_pairs, finish_reason="scored",
                ttft_s=ttft, latency_s=ttft, slot=slot,
                cache_hit_rate=hit_rate,
                weight_version=self.weight_version)
            self.metrics.record_finish(req.request_id, "scored",
                                       req.n_pairs, ttft)
            self.slo.observe(request_id=req.request_id,
                             ttft_ms=ttft * 1e3, tok_s=None)
            if self.retire_hook is not None:
                self.retire_hook(req, slot)
            results.append(res)
        self.metrics.record_step(
            live=len(reqs), slots=self.wave,
            queue_depth=len(self._queue), dt_s=wave_s, rows=rows,
            gather_s=gather_s, step=self.steps, requests=rids)
        self.steps += 1
        return results

    def run(self, requests=()):
        """Submit ``requests`` then step until the queue drains;
        returns {request_id: EmbedResult}."""
        for r in requests:
            self.submit(r)
        out = {}
        while self.pending:
            for res in self.step():
                out[res.request_id] = res
        return out

    # ------------------------------------------------------------- #

    def _cache_counts(self):
        hits = total = 0
        for t in self.tables.values():
            c = t.cache.counters()
            hits += c["hits"]
            total += c["hits"] + c["misses"]
        return hits, total

    @staticmethod
    def _pad(arr, bucket):
        if len(arr) == bucket:
            return arr
        pad = np.zeros((bucket - len(arr), arr.shape[1]), arr.dtype)
        return np.concatenate([arr, pad])

    def _forward(self, bucket):
        """The wave's jitted tower, cached per row bucket (pow2
        padding keeps the compile count logarithmic in wave size)."""
        fn = self._fwd_cache.get(bucket)
        if fn is None:
            if self.model == "wdl":
                fn = jax.jit(wdl_forward)
            elif self.model == "dcn":
                n = self.num_cross_layers
                fn = jax.jit(
                    lambda p, s, d: dcn_forward(p, s, d,
                                                num_cross_layers=n))
            else:
                ed, nl = self.embed_dim, self.n_mlp_layers
                fn = jax.jit(
                    lambda p, u, i: ncf_forward(p, u, i, ed, nl))
            self._fwd_cache[bucket] = fn
        return fn

    def cache_summary(self):
        """Per-table CacheSparseTable.perf_summary() (hit rate,
        pull bytes, staleness, outage counters) — the engine's
        dashboard feed, no private counters."""
        return {name: t.perf_summary()
                for name, t in self.tables.items()}

    def health(self):
        """The admission signal: the SLO monitor's worst-burn state
        ("ok" / "degraded" / "breach"), same contract as
        ServingEngine.health()."""
        return self.slo.health()
