"""The continuous-batching serving engine.

Iteration-level scheduling (Orca-style): between fused waves the
engine retires finished sequences, frees their slots, and admits queued
requests into the holes — a short request leaves the batch the moment
it finishes instead of padding along until the longest one is done, and
a new one takes its slot on the very next step.  There is ONE scheduler
on every backend, the mixed ragged wave: a prompt (or its next chunk),
a spec-verify block and a decode token are q-blocks of one fused
dispatch (``_step_mixed``).

Division of labor: the DEVICE holds only the big cache pair and the
model weights; the HOST owns every piece of scheduling state (queue,
positions, current tokens, per-request rng keys, sampling settings) as
small numpy arrays passed into each jitted call — admission and
retirement are plain python between steps, no recompilation, no
device<->host cache traffic.

One wave ahead: a wave is LAUNCHED (admit, assemble, dispatch) by one
``step()`` and LANDED (its tokens fetched and unpacked) by the next, and
a full engine that nobody is about to leave launches wave t+1 before it
lands wave t, so the device has its next program queued while the host
unpacks (``ServingEngine.step``).  The sampled tokens and rng keys a
wave run ahead needs stay on the device (``_hand_over``).

Determinism: each request samples from its own seed-derived rng stream
with its own traced temperature/top_k, so outputs are a pure function
of the request — identical across arrival orders and slot assignments;
greedy outputs are token-identical to offline ``generate_fast``.
"""

from __future__ import annotations

import collections
import time
import types

import numpy as np
import jax
import jax.numpy as jnp

from .. import envvars, telemetry
from ..telemetry import flight
from ..telemetry import slo as slo_mod
from ..models.gpt_decode import (
    GPT2_BLOCK, block_spec_of, check_block_spec, head_dim_of,
    _infer_name, _prep_param, _pow2, _resolve_fast, resolve_draft_layers,
    resolve_spec_k, serve_mixed_paged_fn, serve_prefill_fn,
    spec_propose_fn, wave_rows, writes_pages,
)
from ..kernels.ragged_attention import (
    mla_rows_tiling, mla_tiling, row_tile_visits, rows_packed_tiling,
    rows_tiling, tile_heights)
from ..models.moe_decode import takes_kernel
from ..models.kda_decode import takes_kernel as kda_takes_kernel
from ..models.retention_decode import takes_kernel as retention_takes_kernel
from ..models.ssm_decode import takes_kernel as ssm_takes_kernel
from .kv_manager import (PagedKVManager, assemble_mixed_wave,
                         resolve_kv_block, resolve_kv_quant)
from .metrics import ServingMetrics
from .request import Request, Result


class QueueFull(RuntimeError):
    """Admission backpressure: the bounded request queue is at capacity.
    Callers shed load or retry after draining (``engine.step()``)."""


# consecutive QueueFull rejections that count as a storm: the flight
# recorder dumps once per storm so the black box captures the records
# leading into sustained overload, not just the steady-state spam
_STORM_REJECTS = 8


@jax.jit
def _hand_over(sampled, after, from_device, tokens, keys):
    """Every launch's input tokens and rng keys, as the device holds
    them.  A slot ``from_device`` marks decodes on the token the wave
    still in flight samples for it (``sampled[s, 0]``, with the key
    after it, ``after[s, 0]``): neither has reached the host.  Every
    other slot takes the host's ``tokens`` row and ``keys`` row.  One
    program a q-block bucket, the same whether a wave runs ahead or in
    order, so that the wave programs take what they always took."""
    tok0 = jnp.where(from_device, sampled[:, 0], tokens[:, 0])
    return (tokens.at[:, 0].set(tok0),
            jnp.where(from_device[:, None], after[:, 0], keys))


class ServingEngine:
    """Continuous-batching engine over one model's weights.

    params: {name: array} (``executor.var_values`` or ``hf.convert_*``
    output — same contract as ``generate_fast``); config: GPTConfig;
    slots: concurrent sequences (pow2-bucketed); queue_limit: bounded
    admission queue — ``submit`` raises QueueFull beyond it;
    max_seq_len: cap on prompt+generation (defaults to the model's
    max_position_embeddings; bucketed, so nearby deployments share
    compiles); dtype: jnp.bfloat16 halves weights AND cache — default
    FOLLOWS the params' own dtype (bf16 params → bf16 cache);
    kv_quant: "int8" (default ``$HETU_KV_QUANT``) stores the KV cache
    as int8 + per-(position, head) f32 scales, ~3.7x more tokens per
    HBM byte — the decode kernels dequantize inside the online-softmax
    loop, greedy outputs stay top-1-identical on the parity gates, and
    the capacity win composes multiplicatively with paged prefix
    sharing; log_path:
    JSONL event stream (default ``$HETU_SERVE_LOG``); donate: donate the
    cache pair to the jitted steps so XLA updates it in place (default
    True — without it every step copies the whole cache, ~3ms per 100MB;
    measured 320x on the scatter alone on the CPU harness); fast_path:
    True scores the wave with the Pallas ragged kernel (each slot
    fetches only its live KV pages instead of streaming all of S_max),
    False with the masked ``jax.numpy`` reference; default consults
    ``$HETU_SERVE_FAST`` then takes the kernel on a TPU and the
    reference elsewhere — the one thing here that follows the platform
    (greedy outputs are identical either way; ``fast_path=True`` is how
    a test runs the kernel in interpret mode); kv_block: the block
    of the ONE KV layout, the block-table pool (``PagedKVManager``,
    default 16 or ``$HETU_KV_BLOCK``, on every backend); spec: > 0
    enables SPECULATIVE DECODING (default ``$HETU_SPEC_K``) — a
    truncated-layer draft (``spec_draft_layers`` of the target's own
    blocks + the shared final LN/tied head; default
    ``$HETU_SPEC_DRAFT_LAYERS`` or max(1, L // 4)) proposes up to ``spec`` tokens per slot per wave
    in ONE scanned dispatch, the target verifies all proposals plus
    the carried token as a k+1 q-block of the wave, and longest-prefix
    acceptance + the bonus token emit 1..spec+1 tokens per wave —
    outputs stay TOKEN-IDENTICAL to the non-speculative engine (greedy
    and sampled alike: accepted tokens are the target's own sequential
    samples from each request's rng stream), rejected positions roll
    back via ``kv.truncate``; spec_adapt (default
    on) moves the per-wave draft length through the pow2 ladder
    1..spec on a sliding acceptance-rate window.  Speculation composes
    with paged/prefix-shared/chunked/int8 KV, the fast path, TP, and
    the fleet router; the draft keeps its own small contiguous cache.

    The scheduler: every iteration packs fresh-prompt prefills, chunk
    continuations, spec-verify blocks, and plain decode into ONE ragged
    wave (per-slot ``q_len``) and launches ONE fused step.  A decode
    slot never stalls behind another request's prompt chunks (the
    ``chunk_stall`` lifecycle component is ~0) and a step costs one
    dispatch regardless of the mode mix.  Greedy outputs are
    token-identical to offline ``generate_fast`` across every
    configuration (int8, chunked, prefix sharing, speculation) — the
    parity suite pins it.

    Block spec: a config that carries one (``config.block_spec()``)
    runs on the wave over the paged pool: ``_mixed_step`` reads the
    spec, the draft's cores do not (ROADMAP C3).  Two kinds do.  A
    ``models.moe_decode.LatentMoEConfig``: RMSNorm, RoPE, latent (MLA)
    attention over ONE paged pool of ``[c_kv | k_r]`` rows, dense
    SwiGLU / dropless routed + shared FFN, untied head.  A
    ``models.moe_decode.HybridMoEConfig``: RMSNorm, every layer's
    operator either grouped-query attention (RoPE over the whole head, a
    per-head q/k norm, ``num_key_value_heads`` K/V heads in a pool that
    holds the ATTENTION layers alone) or a gated short convolution whose
    state (``z`` at a sequence's last ``conv_L_cache - 1`` positions, a
    slot) the same ``PagedKVManager`` owns beside the pool, zeroes on
    admission and hands through the donated step; dense SwiGLU / dropless
    routed FFN, tied head.  Such an engine raises a ``ValueError`` that
    names the path when built with ``spec`` > 0 (the draft, its
    contiguous cache and ``_decode_step``; and state has no rollback) or
    ``kv_quant="int8"``; a latent pool refuses
    ``export_blocks``/``import_blocks`` and the KV tiers (latent rows
    have no wire format); a manager with state refuses those too, and
    ``truncate`` and ``prefix_share=True`` (a prefix-cache hit would
    start a sequence past position 0, and the state at a block boundary
    is not in the pool: no snapshots yet; the default resolves to off).
    Their waves count ``serve.moe.*`` and ``serve.attn.*``
    (``ServingMetrics.record_routed``), the state ``serve.state.resets``
    and ``serve.state.bytes``.  A GPT-2 config carries no spec and runs
    exactly the programs it ran.

    Composes with ``tp_shard_params``: pass the placed dict and the
    fused step runs tensor-parallel (``_prep_param`` preserves the
    NamedShardings; GSPMD propagates them through prefill and decode).

    Observability: every request is lifecycle-traced (queue/kv_alloc/
    prefill/decode/requeue component breakdown per retirement —
    ``metrics.snapshot()["components"]`` and
    ``metrics.explain_tail()``); ``slo=`` takes an
    ``SLOMonitor``/list of ``SLO`` (default: the ``HETU_SLO_*``
    env-declared monitor) and ``health()`` reports its
    ok/degraded/breach state; exceptions escaping ``step()`` and
    QueueFull storms dump the flight recorder to ``$HETU_FLIGHT_LOG``.
    """

    # read by benchmarks/runners/serve*.py and
    # tests/benchmark/test_benchmark.py; nothing branches on them: the
    # mixed ragged wave over the paged pool is the one path there is
    ragged = True
    paged = True

    @telemetry.spanned("serve.engine.build")
    def __init__(self, params, config, *, slots=8, queue_limit=64,
                 max_seq_len=None, name=None, dtype=None, log_path=None,
                 donate=True, fast_path=None, kv_block=None,
                 pool_blocks=None, prefix_share=None, prefill_chunk=0,
                 kv_quant=None, slo=None, tags=None, spec=None,
                 spec_adapt=True, spec_draft_layers=None):
        c = config
        self._name = _infer_name(params, name)
        # dtype=None FOLLOWS the params: bf16 weights stay bf16 and the
        # cache below inherits that dtype (the old f32 default silently
        # upcast bf16 params and doubled the cache)
        self.params = {k: _prep_param(v, dtype) for k, v in params.items()
                       if k.startswith(self._name + "_")}
        # static checks (HETU_VALIDATE=1): params/config consistency
        # validated BEFORE the cache allocation and jit compiles below
        # (analysis/integration.py; no-op when validation is off)
        from ..analysis import validate_serving
        validate_serving(self.params, c, self._name)
        Dh = head_dim_of(c)
        want = int(max_seq_len or c.max_position_embeddings)
        cdtype = self._cdtype = self.params[f"{self._name}_wte_table"].dtype
        # the block the mixed wave runs (GPT-2's unless the config
        # carries another): see the class docstring for what a
        # non-GPT-2 spec refuses
        self.block_spec = block_spec_of(c)
        check_block_spec(self.block_spec, c.num_hidden_layers)
        other = self.block_spec != GPT2_BLOCK
        # kv_quant="int8" (or $HETU_KV_QUANT) stores the cache as int8
        # payload + per-(position, head) f32 scales — ~3.7x more tokens
        # per HBM byte, dequantized inside the decode kernels
        self.kv_quant = resolve_kv_quant(kv_quant)
        kv_dtype = self.kv_quant or cdtype
        block = resolve_kv_block(kv_block)
        self.fast_path = _resolve_fast(fast_path)
        self.spec_k = resolve_spec_k(spec)
        if other:
            for bad, path in (
                    (self.spec_k, "speculation (spec_k > 0): "
                     "_spec_propose, _serve_prefill and the draft's "
                     "contiguous cache"),
                    (self.kv_quant, "an int8 KV cache (kv_quant)")):
                if bad:
                    raise ValueError(
                        f"ServingEngine: a non-GPT-2 block spec runs "
                        f"only on the mixed ragged wave over the paged "
                        f"pool; it cannot run on {path}")
        blk = self.block_spec
        latent = blk.latent
        L = c.num_hidden_layers
        # prompts fill their blocks in chunks of this many tokens beside
        # the decode slots of the same wave (0: a whole prompt at once)
        self.chunk = max(int(prefill_chunk or 0), 0)
        self.kv = PagedKVManager(
            # the pool holds the layers with an attention; the layers
            # with a conv, a state-space mixer or retention keep slot
            # state beside it (a layer may do both; where none holds a
            # page there is no pool, and the manager takes no
            # ``pool_blocks``)
            layers=blk.op_layers(L, "pool"),
            heads=blk.kv_heads or c.num_attention_heads,
            head_dim=Dh, slots=slots, max_seq_len=want,
            pos_cap=c.max_position_embeddings, dtype=kv_dtype,
            block=block, pool_blocks=pool_blocks,
            prefix_share=prefix_share,
            row_shape=(latent.row_width,) if latent else None,
            # a spec whose full layers choose what they read keeps an
            # index key a position beside the latent rows
            **({"index_shape": (latent.index.head_dim,)}
               if latent and latent.index else {}),
            state_shapes=blk.state_shapes(L, c.hidden_size),
            **self._window_pool(blk, L, want, self.chunk))
        self.cfg_tuple = (self._name, c.num_hidden_layers,
                          c.num_attention_heads, Dh, self.kv.s_max)
        # ---- MoE serving (models/moe_decode.py): a MoEDecodeConfig
        # rides the SAME compiled cores — the hashable MoESpec joins
        # the static cfg_tuple and every serve wrapper appends one
        # trailing (load, drop, tokens) stats element the scheduler
        # strips at launch and accounts at landing (_moe_record).
        # Dense configs leave self.moe None and nothing here changes.
        # ---- #
        from ..models.moe_decode import moe_spec_of
        self.moe = moe_spec_of(c)
        # the dropless router of a routed block spec: its wave hands
        # back (load, touched) beside the sample, fetched with it in
        # serve.wave.sync (no sync of its own)
        self.routed = self.block_spec.routed
        if other:
            self.cfg_tuple = self.cfg_tuple + (self.block_spec,)
            self._routed_layers = self.block_spec.routed_layers(
                c.num_hidden_layers)
        # layers with a state-space mixer: their waves count
        # ``serve.ssm.*`` (``ServingMetrics.record_state_scan``)
        self._ssm_layers = sum(
            self.block_spec.op_layers(c.num_hidden_layers, op)
            for op in ("attention+ssm", "ssm"))
        # ... and with power retention: ``serve.ret.*``
        # (the same); layers with an attention of any kind (none:
        # the engine's ``serve.attn.*`` stay 0 and no kernel runs)
        self._ret_layers = self.block_spec.op_layers(
            c.num_hidden_layers, "retention")
        # ... and with the delta rule: ``serve.kda.*`` (``record_kda``)
        self._kda_layers = self.block_spec.op_layers(
            c.num_hidden_layers, "kda")
        self._attn_layers = sum(
            self.block_spec.op_layers(c.num_hidden_layers, pages)
            for pages in ("pool", "window"))
        self._window_recycled_seen = 0
        # layers that read the rows a learned indexer chose, and how
        # many it keeps a row: their waves count ``serve.sparse.*``
        self._index_layers = self.block_spec.op_layers(
            c.num_hidden_layers, "index")
        if self.moe is not None:
            self.cfg_tuple = self.cfg_tuple + (self.moe,)
            E = self.moe.num_experts
            # lifetime per-expert routing outcome (int64 — these count
            # token-assignments, top_k per token per MoE layer)
            self.expert_load = np.zeros(E, np.int64)
            self.expert_drops = np.zeros(E, np.int64)
            self.moe_tokens = 0
            self._moe_layers = self.moe.moe_layers(c.num_hidden_layers)
        self.prefill_dispatches = 0   # waves that carried a prompt
        # q-block (a burst of k arrivals is ONE wave, not k dispatches)
        self.prefill_chunks = 0       # prompt q-blocks written
        self.peak_live = 0            # max concurrent admitted slots
        self.queue_limit = int(queue_limit)
        self._queue = collections.deque()
        # tags (e.g. replica=<k> from the fleet router) ride on every
        # event so N engines sharing one merged stream stay separable
        self.metrics = ServingMetrics(log_path, tags=tags)
        # optional fn(request, slot) called at retirement while the
        # slot is still live — the router's KV-handoff export seam
        self.retire_hook = None
        # SLO monitor: explicit SLOMonitor / list of SLOs / default
        # env-declared (HETU_SLO_*; empty = always "ok").  Violations
        # and health transitions route through metrics.event so they
        # land in the serve stream next to the request records.
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
        B = self.kv.n_slots
        self._pos = np.zeros(B, np.int32)     # input position per slot
        self._tok = np.zeros(B, np.int32)     # next input token per slot
        self._temp = np.zeros(B, np.float32)
        self._topk = np.zeros(B, np.int32)
        self._keys = np.zeros((B, 2), np.uint32)
        self._reqs = [None] * B
        self._gen = [None] * B               # generated ids per slot
        # one host stamp per generated token (Result.token_times_s):
        # the first at its first_token_at, the rest at the post-sync
        # stamp of the wave that emitted them
        self._tok_t = [None] * B
        self._wave_end = 0.0     # the last landed wave's post-sync stamp
        self._land_end = 0.0     # ... and when its unpack ended
        # ---- one wave ahead (``step``) ---- #
        # the wave launched and not landed yet; the Results of a wave
        # that something other than ``step`` had to land
        self._flying = None
        self._held = []
        self._launched = 0       # waves launched (``steps``: landed)
        # tokens a slot's LAUNCHED waves emit (0: mid-prefill), so that
        # the host knows by count who decodes next and who is about to
        # leave; a speculative wave's count is known at landing
        self._emitted = np.zeros(B, np.int64)
        # the last launched wave's samples and keys, as ``_hand_over``
        # takes them (host zeros until the first wave leaves some)
        self._dev_sampled = np.zeros((B, self.spec_k + 1), np.int32)
        self._dev_after = np.zeros((B, self.spec_k + 1, 2), np.uint32)
        # live weight sync (serving/weight_sync.py): the version the
        # current param dict is stamped with (None = unversioned) and
        # the per-slot ADMISSION version a retirement reports — the
        # coordinator only swaps a drained engine, so the two agree
        # unless something upstream broke (exactly what the trace
        # version-coherence rule exists to catch)
        self.weight_version = None
        self.last_swap_at = None
        self._slot_version = [None] * B
        self._prefill_off = np.zeros(B, np.int32)  # next prompt position
        self._prompt_arr = [None] * B              # to prefill
        # admission order: a chunk wave takes its prompt chunks oldest
        # admission first (see ``_launch``)
        self._admit_no = np.zeros(B, np.int64)
        self._admitted = 0
        self.steps = 0
        # ---- speculative decoding (spec=/$HETU_SPEC_K) ---- #
        self.spec_adapt = False
        if self.spec_k:
            dl = resolve_draft_layers(spec_draft_layers,
                                      c.num_hidden_layers)
            self.spec_draft_layers = dl
            self.cfg_tuple_draft = (self._name, dl,
                                    c.num_attention_heads, Dh,
                                    self.kv.s_max)
            if self.moe is not None:
                # the draft SKIPS ROUTING entirely (ISSUE 20): its
                # truncated blocks run attention-only on MoE layers
                # and its wrappers append no stats element
                self.cfg_tuple_draft = self.cfg_tuple_draft + (
                    self.moe._replace(draft=True),)
            self.spec_adapt = bool(spec_adapt) and self.spec_k > 1
            # adaptive runs ramp up from mid-ladder; pinned runs start
            # (and stay) at the configured k
            self._spec_kcur = (max(1, self.spec_k // 2)
                               if self.spec_adapt else self.spec_k)
            # the draft's OWN cache: always the small contiguous
            # layout (L_draft rows, never quantized) regardless of the
            # target's paging/quant — rollback there is pure position
            # bookkeeping, rejected rows are masked until overwritten
            dshape = (dl, B, self.kv.s_max, c.num_attention_heads, Dh)
            self._draft_ck = jnp.zeros(dshape, cdtype)
            self._draft_cv = jnp.zeros(dshape, cdtype)
            self._propose = spec_propose_fn(donate)
            self._draft_prefill = serve_prefill_fn(donate)
            self._acc_window = collections.deque(maxlen=32)
            self.spec_proposed = 0    # draft tokens scored
            self.spec_accepted = 0    # draft tokens emitted
            self.spec_emitted = 0     # tokens emitted by verify waves
            self.spec_waves = 0
            self.spec_k_sum = 0       # sum of per-wave k (mean_k)
            self.spec_draft_prefills = 0
            self._spec_acc = np.zeros(B, np.int64)
            self._spec_prop = np.zeros(B, np.int64)
            self._spec_bonus = np.zeros(B, np.int64)
        # ---- the wave: arrivals, chunk continuations, spec-verify and
        # decode pack into ONE ragged dispatch per step (see class
        # docstring).  ``window`` is the widest sampling window a slot
        # can have: a verify block's spec_k + 1 rows, else the one row a
        # decode slot or a final chunk samples; the wave's head and
        # sampling run over that many rows a slot, not the padded
        # q-block ---- #
        self._mixed = serve_mixed_paged_fn(
            donate, "ragged" if self.fast_path else "masked",
            self.spec_k + 1)
        if envvars.get_bool("HETU_VALIDATE"):
            # recompile sentinel: snapshot()/assert_no_recompile() can
            # now prove the steady state stays ONE compiled core
            from ..analysis import jit_audit
            jit_audit.register_engine(self)

    @staticmethod
    def _window_pool(blk, layers, max_seq_len, chunk):
        """The manager's window-pool arguments for a block spec with
        window layers (none otherwise: such a manager has no window
        pool and no option for one).  The ring is sized for the widest
        q-block a wave writes: the prefill chunk, or with no chunking
        (``chunk`` 0) a whole prompt."""
        n = blk.op_layers(layers, "window")
        if not n:
            return {}
        # a latent block's window layers keep latent rows of their own
        # width in the ring
        rows = {"window_row_shape": (dict(blk.latent_by_op)[
            "window_latent_attention"].row_width,)} if blk.latent else {}
        return {"window_layers": n, "window": blk.window,
                "window_chunk": chunk or int(max_seq_len), **rows}

    # ------------------------------------------------------------- #
    # live weight sync (serving/weight_sync.py)
    # ------------------------------------------------------------- #

    def set_weight_version(self, version):
        """Stamp the CURRENT params with ``version``: rides
        ``metrics.tags`` so every subsequent serve event carries
        ``weight_version`` (the A/B and trace-coherence key)."""
        self.weight_version = int(version)
        self.metrics.tags["weight_version"] = self.weight_version

    def swap_params(self, params, *, version=None):
        """Replace the weights under the engine between steps — the
        rolling-swap primitive.  No recompile: every jitted step takes
        the param dict as an argument, so the next wave simply sees the
        new buffers (the spec-decode draft shares this dict and
        inherits the swap for free).  The new pytree must match the old
        one key-for-key and shape-for-shape (a corrupt push fails HERE,
        before any buffer moves); dtypes follow the resident params so
        the KV cache dtype contract survives the swap.  Call only on a
        drained engine (the coordinator's job) — live slots would mix
        versions mid-request.  A wave in flight is landed first (a token
        is stamped with the weights that made it); what it retires
        comes out of the next ``step()``."""
        self._settle()
        name = self._name
        new = {}
        for k, v in params.items():
            if not k.startswith(name + "_"):
                continue
            old = self.params.get(k)
            p = _prep_param(v, old.dtype if old is not None else None)
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
    # MoE accounting (models/moe_decode.py)
    # ------------------------------------------------------------- #

    def _moe_record(self, stats):
        """Account a capacity-routed wave's ``(load, drop, tokens)``, the
        trailing element the serve wrappers append under a MoE
        ``cfg_tuple`` (the launch strips it; the draft appends nothing,
        it skips routing), fetched here at landing.  Returns the
        ``record_step`` payload, None for a wave that routed nothing.
        ``routed + dropped == tokens * k * layers`` is the hetu_trace
        attribution invariant; ``imb`` (max/mean expert load) and
        ``drop_rate`` are THE MoE health observables: ``imb`` lands as a
        gauge for hetu_top, ``drop_rate`` rides the ``serve_step`` event
        (``moe_drop_rate``), which is what hetu_top reads."""
        load = np.asarray(stats[0], np.int64)
        drop = np.asarray(stats[1], np.int64)
        tokens = int(stats[2])
        self.expert_load += load
        self.expert_drops += drop
        self.moe_tokens += tokens
        routed = int(load.sum())
        dropped = int(drop.sum())
        telemetry.inc("serve.expert_load", routed)
        telemetry.inc("serve.expert_drops", dropped)
        mean = float(load.mean())
        imb = float(load.max()) / mean if mean > 0 else 0.0
        total = routed + dropped
        rate = dropped / total if total else 0.0
        telemetry.set_gauge("serve.expert_imbalance", imb)
        return {"tokens": tokens, "routed": routed, "dropped": dropped,
                "k": self.moe.top_k, "layers": self._moe_layers,
                "imb": imb, "drop_rate": rate,
                "load": [int(x) for x in load],
                "drop": [int(x) for x in drop]}

    def _wave_record(self, wave, rows_computed):
        """Every wave's attention counters (``serve.attn.*``; 0 on an
        engine none of whose layers attends) and, on an engine with
        state-space or retention layers, its ``serve.ssm.*`` /
        ``serve.ret.*`` ones and their ``record_step`` payloads
        ({"ssm": .., "ret": ..}, those it has): the wave descriptor's
        own arithmetic (and ``rows_computed``, the rows the wave's
        program ran over: whether it was packed).  Slot b's ``q_len``
        rows at positions ``pos .. pos
        + q_len - 1`` see ``pos + j + 1`` positions each, and the slot
        holds ``pos + q_len`` positions after the wave's writes (and, a
        q-block a page or more wide, lies in the pages
        ``record_kv_write`` counts); a live
        slot's state moves once a state-space layer.  An engine with
        window layers counts what those really read beside it
        (``record_attention``'s ``window``)."""
        ql = wave["q_len"].astype(np.int64)
        pos = wave["pos"].astype(np.int64)
        attends = self._attn_layers > 0
        ctx = int(np.where(ql > 0, pos + ql, 0).sum()) if attends else 0
        pairs = int((ql * pos + ql * (ql + 1) // 2).sum()) if attends else 0
        window = None
        if self.kv.window_layers:
            # a window layer's rows see ``min(pos + j + 1, W)`` positions
            # each: the first ``W - pos`` rows (if any) as a full
            # layer's, the rest ``W``; a slot's q-block has ``min(pos +
            # q_len, W + q_len - 1)`` positions in sight
            W = self.block_spec.window
            grow = np.clip(W - pos, 0, ql)       # rows still under W
            recycled = self.kv.window_blocks_recycled
            window = (
                int(np.where(ql > 0, np.minimum(pos + ql, W + ql - 1),
                             0).sum()),
                int((grow * pos + grow * (grow + 1) // 2
                     + (ql - grow) * W).sum()),
                recycled - self._window_recycled_seen,
                # the rows at a position of ``W`` or more: the band binds
                int((ql - grow).sum()))
            self._window_recycled_seen = recycled
        self.metrics.record_attention(
            ctx, pairs, window,
            self._attn_tiles(ql, int(wave["q"]), rows_computed)
            if attends else None)
        bs = self.kv.block
        if attends and writes_pages(self.block_spec, self.kv_quant,
                                    int(wave["q"]), bs):
            # the float K/V pool's wide write: the pages the live rows
            # touch (``paged_kv_write.touched_pages``'s count)
            self.metrics.record_kv_write(
                int(np.where(ql > 0, (pos + ql - 1) // bs - pos // bs + 1,
                             0).sum()), int(ql.sum()))
        if self._index_layers:
            # a row reads ``min(pos + j + 1, K)`` cached rows: the first
            # ``K - pos`` rows of a q-block (if any) everything they see
            K = self.block_spec.latent.index.topk
            grow = np.clip(K - pos, 0, ql)
            read = grow * pos + grow * (grow + 1) // 2 + (ql - grow) * K
            self.metrics.record_sparse(*(
                int(v) * self._index_layers for v in (
                    ql.sum(), (ql - grow).sum(), pairs, read.sum(),
                    np.minimum(read, np.where(ql > 0, pos + ql, 0)).sum(),
                    ctx)))
        out = {}
        wide = np.where(ql > 1, ql, 0)
        if self._kda_layers:
            self.metrics.record_kda(
                int((ql == 1).sum()), int(wide.sum()), self._kda_layers,
                kda_takes_kernel(self.block_spec.kda.head_dim,
                                 int(wave["q"])))
        for kind, layers, spec in (
                ("ssm", self._ssm_layers, self.block_spec.ssm),
                ("ret", self._ret_layers, self.block_spec.retention)):
            if not layers:
                continue
            # row pairs (i, j <= i) inside the chunks of the chunked
            # form: a q-block of one row takes the plain step and has
            # none
            c = spec.chunk
            full, rest = wide // c, wide % c
            chunk_pairs = int((full * (c * (c + 1) // 2)
                               + rest * (rest + 1) // 2).sum())
            # the slots the wave's program took through a kernel, by
            # the program's own rule: the wide slots' chunked form and
            # the one-row slots' step through the two kernels of
            # ``kernels/retention_scan``, the one-row slots' step
            # through ``kernels/ssm_step``
            if kind == "ret":
                by_kernel = ((ql > 1) & retention_takes_kernel(
                    spec.head_dim, int(wave["q"]))) | (
                    (ql == 1) & retention_takes_kernel(spec.head_dim))
            else:
                by_kernel = (ql == 1) & ssm_takes_kernel(spec)
            out[kind] = self.metrics.record_state_scan(
                kind, int((ql > 0).sum()), int(ql.sum()), chunk_pairs,
                layers, int(by_kernel.sum()))
        return out

    def _attn_tiles(self, q_len, Q, rows):
        """(live (slot, q-tile) steps, those scored at the short height,
        q-tiles moved) of one call of the hand-paged attention kernel in
        a wave of q-blocks ``Q`` wide computed over ``rows`` rows: the
        kernel's own rule asked of the wave's ``q_len`` and the tile the
        program for ``Q`` has.  A packed wave (``rows`` under slots x
        ``Q``), of the latent kernel or of the K/V rows kernel, moves the
        packed rows' tiles and counts a tile's VISITS to the slots whose
        rows cross it.  None where the engine's waves run no such kernel
        (the masked path, the int8 pool)."""
        if not self.fast_path or self.kv_quant or (
                self.block_spec.latent and self.block_spec.ops):
            # (latent operators by layer: two head counts, two kernels
            # and no page loop in the layers that choose their rows)
            return None
        H, Dh = self.cfg_tuple[2:4]
        groups = H // (self.block_spec.kv_heads or H)
        if rows < len(q_len) * Q:
            if self.block_spec.latent:
                tq, short = mla_rows_tiling(rows, H, self._cdtype)
            else:
                rows, tq, short = rows_packed_tiling(
                    rows, H, Dh, groups, self._cdtype)
            start = np.cumsum(q_len) - q_len
            _, _, live, full = row_tile_visits(
                start[:, None], q_len[:, None],
                np.arange(rows // tq)[None, :], tq, short)
            return int(live.sum()), int((live & ~full).sum()), rows // tq
        if self.block_spec.latent:
            tq, short = mla_tiling(Q, H)
        else:
            # the K/V rows kernel's dense program has one height
            (_, tq), short = rows_tiling(Q, H, self._cdtype), 0
        live, full = tile_heights(q_len[:, None],
                                  np.arange(-(-Q // tq))[None, :], tq, short)
        return int(live.sum()), int((live & ~full).sum()), live.size

    def _routed_record(self, wave, routed_out, rows_computed):
        """A routed wave's counters (``serve.moe.*``) and its
        ``record_step`` payload.  Load and experts touched come out of
        the compiled step; the rows are the wave descriptor's; whether
        the program took the grouped-matmul kernel is the shape rule's
        answer for the rows it ran over.  Of a layer that holds a share
        of its experts the load is over the held ones: ``held`` of the
        ``routed`` assignments landed here (all of them where every
        expert is held)."""
        load = np.asarray(routed_out[0], np.int64)
        touched = int(routed_out[1])
        rows = int(wave["q_len"].astype(np.int64).sum())
        assignments = int(load.sum())
        routed = int(routed_out[2]) if len(routed_out) > 2 else assignments
        self.metrics.record_routed(
            load, touched, routed=routed,
            kernel=takes_kernel(rows_computed * self.routed.top_k))
        mean = assignments / len(load)
        share = {"held": assignments} if self.routed.holds_a_share else {}
        return {"tokens": rows, "routed": routed, "dropped": 0, **share,
                "k": self.routed.top_k, "layers": self._routed_layers,
                "imb": float(load.max()) / mean if mean > 0 else 0.0,
                "drop_rate": 0.0}

    @property
    def expert_imbalance(self):
        """Lifetime max/mean expert-load ratio (None on dense engines
        or before any routed token)."""
        if self.moe is None:
            return None
        mean = float(self.expert_load.mean())
        return float(self.expert_load.max()) / mean if mean > 0 else 0.0

    @property
    def expert_drop_rate(self):
        """Lifetime dropped / (routed + dropped) (None when dense)."""
        if self.moe is None:
            return None
        total = int(self.expert_load.sum() + self.expert_drops.sum())
        return int(self.expert_drops.sum()) / total if total else 0.0

    # ------------------------------------------------------------- #

    def submit(self, request):
        """Enqueue a Request; raises QueueFull at ``queue_limit``
        pending admissions (bounded-queue backpressure), ValueError if
        it can never fit the cache.  Returns the request."""
        req = request
        total = len(req.prompt) + req.max_new_tokens
        if total > self.kv.s_max:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the "
                f"engine's S_max {self.kv.s_max}")
        if self.kv.blocks_needed(total) > self.kv.capacity_blocks:
            raise ValueError(
                f"request needs {self.kv.blocks_needed(total)} KV "
                f"blocks; the pool holds {self.kv.capacity_blocks}")
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
        """Requests not yet handed back: queued, in slots (a slot is
        live until its last wave has landed), or retired by a landing
        outside ``step`` and held for the next one."""
        return len(self._queue) + len(self.kv.live()) + len(self._held)

    @property
    def queue_depth(self):
        """Admissions waiting in the bounded queue (the router's
        backpressure/shedding signal, alongside ``health()``)."""
        return len(self._queue)

    # ------------------------------------------------------------- #

    def step(self):
        """One scheduler iteration (``_step_mixed``).  Returns the
        Results that completed in it.

        A wave is IN FLIGHT from the ``step()`` that launches it (admit
        into free slots, assemble ONE mixed wave over every live slot,
        dispatch) to the ``step()`` that lands it (fetches its tokens,
        runs the stream callbacks, retires who is done).  So a Result
        arrives one ``step()`` after its last wave was launched, and
        between two calls the device works while the caller does.  At
        most one wave is in flight when ``step()`` returns, and every
        slot riding it still counts in ``pending``.

        The rule.  With a wave in flight, ``step()`` launches the next
        one BEFORE it lands that one (one wave ahead: the device never
        waits for the host's unpack) only where that can delay nobody's
        admission: every slot is occupied as ``step()`` is entered, no
        request reaches its ``max_new_tokens`` in the wave in flight
        (the host knows by count), and the engine does not speculate.
        Otherwise it lands first; if that retired a request it returns
        the Result at once, so that the caller can fill the slot before
        the next wave is composed, and if it retired none it launches
        the next wave and returns with it in flight.  An admission so
        happens in exactly the wave an engine that never ran ahead
        would have given it.  An ``eos_id`` ending cannot be foreseen:
        the wave run ahead then carries one dead row.  Counted:
        ``serve.wave.ahead`` and ``serve.wave.rows_dead_ahead``
        (``snapshot()``: ``waves_ahead``, ``rows_dead_ahead``).

        An exception escaping the scheduler dumps the flight recorder
        (``$HETU_FLIGHT_LOG``) before propagating — the black box holds
        the records leading into the fault."""
        try:
            return self._step_mixed()
        except QueueFull:
            raise
        except Exception as e:   # noqa: BLE001 — dump-and-reraise
            flight.RECORDER.dump(
                "engine_exception",
                error=f"{type(e).__name__}: {e}"[:200],
                step=self.steps, live=len(self.kv.live()),
                queue_depth=len(self._queue))
            raise

    # ------------------------------------------------------------- #
    # admission
    # ------------------------------------------------------------- #

    def _admit_paged(self):
        """Claim slots + block tables for queued requests, FIFO, until
        slots or pool blocks run short (the head request then waits —
        backpressure, not loss).  Prefix sharing happens here: a prompt
        starting with a registered prefix attaches those blocks
        refcounted and only prefills the tail."""
        admitted = []
        with telemetry.span("serve.kv_alloc", wave=self._launched + 1,
                            queue=len(self._queue)):
            while self._queue:
                req = self._queue[0]
                if self._defer_for_prefix(req):
                    # waiting on another slot's in-flight prefill: the
                    # requeue clock starts at the FIRST deferral
                    self.metrics.lc_blocked(req.request_id)
                    break
                # tiered KV: a prompt no local prefix covers may be
                # warm in the host ring / PS cold store — fetch and
                # re-import it now so alloc below attaches the blocks
                self._tier_admit(req)
                t_a = time.perf_counter()
                slot, cached = self.kv.alloc(
                    req.request_id, req.prompt,
                    len(req.prompt) + req.max_new_tokens)
                if slot is None:
                    # pool/slot exhaustion: head request waits admitted
                    # capacity frees up (backpressure, not loss)
                    self.metrics.lc_blocked(req.request_id)
                    break
                req.claimed_at = time.perf_counter()
                self.metrics.lc_claimed(
                    req.request_id, (req.claimed_at - t_a) * 1e3)
                self._queue.popleft()
                self._reqs[slot] = req
                self._slot_version[slot] = self.weight_version
                self._gen[slot] = None
                self._prompt_arr[slot] = np.asarray(req.prompt, np.int32)
                self._prefill_off[slot] = cached
                self._admit_no[slot] = self._admitted
                self._admitted += 1
                self._pos[slot] = 0
                self._emitted[slot] = 0
                self._tok[slot] = 0
                self._temp[slot] = req.temperature
                self._topk[slot] = req.top_k
                self._keys[slot] = np.asarray(
                    jax.random.PRNGKey(req.seed), np.uint32)
                admitted.append(slot)
        if admitted:
            telemetry.inc("serve.admission_waves")
        return admitted

    def _tier_admit(self, req):
        """Tier miss-escalation at admission (serving/kv_tiers.py):
        when the tier ladder holds a longer prefix of this prompt than
        the local pool does, fetch it and re-admit through
        ``import_blocks`` — token-identical to the original prefill —
        so the ``kv.alloc`` that follows attaches the blocks
        refcounted.  Local warmth always wins (a fetch never displaces
        an equal-or-longer resident prefix), and every failure mode —
        tier miss, chaos corruption, a pool too full to hold the
        import — degrades to a cold prefill, never an error.  Returns
        True when a span landed."""
        store = getattr(self.kv, "tier_store", None)
        if store is None or not getattr(self.kv, "prefix_share", False) \
                or getattr(req, "prompt", None) is None:
            return False
        hit = store.lookup(req.prompt, self.kv.block)
        if hit is None:
            return False
        toks, length, _tier = hit
        _, cached = self.kv.match_prefix(req.prompt)
        if cached >= length:
            return False   # the pool already covers at least as much
        payload = store.fetch(toks)
        if payload is None:
            return False
        try:
            slot = self.kv.import_blocks(
                payload, f"{req.request_id}~tierfetch",
                prompt=list(toks))
        except ValueError:
            slot = None
        if slot is None:
            store.note_import_failed()
            return False
        # the slot was only a write vehicle: the re-registered prefix
        # keeps the blocks alive (refcounted) for this admission
        self.kv.release(slot)
        return True

    def _defer_for_prefix(self, req):
        """True when ``req`` should WAIT one step rather than duplicate
        work: its first KV block of prompt matches a prompt another slot
        is prefilling right now, and no registered prefix covers it yet
        — once that prefill registers, this request admits with the
        blocks attached instead of recomputing them (this is what makes
        a BURST of same-system-prompt requests store the prefix once)."""
        if not self.kv.prefix_share:
            return False
        bs = self.kv.block
        pr = [int(t) for t in req.prompt]
        if len(pr) <= bs:
            return False
        _, cached = self.kv.match_prefix(pr)
        if cached >= bs:
            return False
        head = pr[:bs]
        for s in self.kv.live():
            if self._gen[s] is None and self._prompt_arr[s] is not None \
                    and len(self._prompt_arr[s]) >= bs \
                    and [int(t) for t in self._prompt_arr[s][:bs]] == head:
                return True
        return False

    def _finish_prefill(self, slot, tok0, key):
        """The final chunk landed: the slot's first token is here (the
        launch already put the slot among the decoding ones), or the
        request retires right here on max_new_tokens=1/instant EOS.
        Registers the prompt's blocks for prefix sharing."""
        req = self._reqs[slot]
        if self.spec_k:
            t_d = time.perf_counter()
            self._draft_prefill_slot(slot, self._prompt_arr[slot])
            self.metrics.lc_prefill(req.request_id,
                                    time.perf_counter() - t_d)
        now = time.perf_counter()
        req.first_token_at = now
        self._tok[slot] = tok0
        self._keys[slot] = key
        self._gen[slot] = [tok0]
        self._tok_t[slot] = [now]
        self.kv.register_prefix(self._prompt_arr[slot], slot)
        self.metrics.record_admit(
            req.request_id, slot, now - req.submitted_at,
            now - req.submitted_at)
        if req.stream_cb:
            req.stream_cb(req, tok0)
        return self._maybe_finish(slot, tok0)

    def _step_mixed(self):
        """The scheduler iteration: admissions, chunk continuations,
        spec-verify blocks, and plain decode pack into ONE ragged wave
        descriptor (per-slot ``q_len``/``first_row``) and launch as ONE
        fused dispatch — no prefill/decode phase barrier, so a decode
        slot never stalls behind another request's prompt chunks.
        Every slot's write positions, attention masks, and rng splits
        are those of a sequential decode of its request alone.

        A wave has two halves, in two iterations.  LAUNCH (``_launch``):
        admit, (draft), assemble, dispatch, and the bookkeeping that
        needs no token VALUE (positions, ``kv.advance``, the prompt
        offsets, who decodes next).  LAND (``_land``): fetch the samples,
        then everything that needs them (``_gen``, the stream callbacks,
        ``register_prefix``, retirement, ``record_step``).  With wave t
        in flight this iteration launches t+1 and then lands t where the
        rule allows (``_may_run_ahead``); else it lands t first, returns
        at once what that retired, and launches t+1 only if it retired
        nobody.  With nothing in flight it launches and returns.

        Spans, a fixed number a WAVE whatever is live, each OPENED with
        the ``wave=`` it belongs to (entry fields reach the profiler's
        trace as the host event's stats, so a wave's launch and its
        landing, in different roots, join by it): ``serve.admit(wave=,
        queue=)`` (holding ``serve.kv_alloc(wave=, queue=)``),
        ``serve.wave.draft`` (speculative engines only),
        ``serve.wave.assemble(wave=, kind=)`` (the descriptor and the
        block-table copy) and ``serve.wave.dispatch(wave=, kind=, q=,
        ahead=)`` (``_hand_over`` and the call into the jitted step
        until it returns: enqueue time) at its launch;
        ``serve.wave.sync(wave=, kind=, ahead=)`` (the host waits for
        the device and fetches samples, keys and routing counts) and
        ``serve.wave.unpack(wave=, kind=)`` at its landing.  ``kind`` is
        what the wave holds: ``chunk`` (a prompt chunk: the
        ``has_fresh`` program over the paged pool), ``verify`` (a
        speculating engine's draft blocks), else ``decode``; ``q`` its
        q-block bucket; ``ahead`` whether another wave was in flight at
        its launch.  One root ``serve.wave(order=)`` an iteration holds
        what it ran: the launch of one wave (``launched=``) and the
        landing of the one before it (``landed=``, with that wave's
        ``live=`` and ``q_*=``; set when known, so JSONL-only).
        ``order`` is the iteration's shape, known at entry: ``ahead``
        (launches t+1, then lands t), ``inorder`` (lands t first, then
        launches t+1 unless t retired somebody) or ``first`` (nothing
        in flight: launches).  An iteration with nothing live ends
        after ``serve.admit``."""
        flying = self._flying
        ahead = flying is not None and self._may_run_ahead(flying)
        order = "first" if flying is None else \
            "ahead" if ahead else "inorder"
        with telemetry.span("serve.wave", order=order) as root:
            done, self._held = self._held, []
            if flying is not None and not ahead:
                done += self._land(flying, root)
                if done:
                    # the caller fills the slot before the next wave is
                    # composed
                    return done
            self._launch(root, ahead)
            if ahead:
                done += self._land(flying, root)
                if self._flying is not None and not self.kv.live():
                    # everybody ended on ``eos_id``: the wave run ahead
                    # carries dead rows alone
                    done += self._land(self._flying, root)
            return done

    def _may_run_ahead(self, flying):
        """THE rule: launch the next wave before landing ``flying`` only
        where that can cost nobody a wave.  Every slot is occupied (so
        admission has nothing to do: it never claims a slot with a wave
        in flight, and sees every ``register_prefix`` and release the
        in-order engine would have seen), no request reaches its
        ``max_new_tokens`` in ``flying`` (known by count; its successor
        would wait out the whole wave run ahead), and the engine does
        not speculate (the next descriptor depends on how many drafts
        were accepted).  A request that ends on ``eos_id`` cannot be
        foreseen: it leaves one dead row in the wave run ahead
        (``serve.wave.rows_dead_ahead``)."""
        return not (self.spec_k or self.kv.free_slots or flying.ends)

    def _settle(self):
        """Land the wave in flight outside ``step`` (a weight swap); the
        next ``step()`` hands out what it retired."""
        if self._flying is not None:
            with telemetry.span("serve.wave", order="inorder") as root:
                self._held += self._land(self._flying, root)

    def _launch(self, root, ahead):
        """A wave's first half.  Leaves it in ``_flying`` (None where
        nothing is live).  ``ahead``: another wave is in flight, and the
        decoding slots' tokens and keys are still on the device."""
        wave_id = self._launched + 1
        # admission claims slots and blocks (prefix sharing/COW, tier
        # fetch, deferral, backpressure); prompts join THIS wave
        with telemetry.span("serve.admit", wave=wave_id,
                            queue=len(self._queue)):
            self._admit_paged()
        live = self.kv.live()
        if not live:
            return
        self.peak_live = max(self.peak_live, len(live))
        B = self.kv.n_slots
        pre = [s for s in live if not self._emitted[s]]
        decoding = [s for s in live if self._emitted[s]]
        t0 = time.perf_counter()
        # speculative draft rides AHEAD of the wave (mid-prefill slots'
        # rows are dead)
        k_cur = self._spec_kcur if decoding and self.spec_k else 0
        draft = None
        # what the wave is, known before it is composed: a wave with a
        # prompt to prefill carries a chunk (the oldest always fits:
        # the ``has_fresh`` program), else a speculating engine's is a
        # verify block, else plain decode
        kind = "chunk" if pre else "verify" if k_cur else "decode"
        if k_cur:
            with telemetry.span("serve.wave.draft", wave=wave_id):
                draft, dck, dcv = self._propose(
                    self.params, self.cfg_tuple_draft,
                    self._draft_ck, self._draft_cv,
                    self._pos.copy(), self._tok.copy(), k=k_cur)
                self._draft_ck, self._draft_cv = dck, dcv
                draft = np.asarray(draft)
        with telemetry.span("serve.wave.assemble", wave=wave_id, kind=kind):
            entries = {}
            qlen_v = {}
            for s in decoding:
                # a wave run ahead overwrites the host's stale token
                # with the device's (``_hand_over``)
                toks = [int(self._tok[s])]
                if k_cur:
                    rem = self._reqs[s].max_new_tokens - len(self._gen[s])
                    ql = min(k_cur + 1, rem,
                             self.kv.s_max - int(self._pos[s]))
                    toks += [int(t) for t in draft[s, :ql - 1]]
                    qlen_v[s] = ql
                entries[s] = (toks, int(self._pos[s]), 0, False)
            # every decoding slot's rows ride every wave; prompt chunks
            # enter WHOLE, oldest admission first, while the wave's live
            # rows stay within the rows of the program the wave then has
            # (``wave_rows``: a chunk wave is computed over that many
            # packed rows, not over slots x the widest q-block).  A
            # chunk that does not fit waits this wave out as a dead slot
            # (``q_len`` 0: its pool and state do not move); the oldest
            # always fits, so none waits longer than the chunks of older
            # admissions last.
            rows_live = sum(len(e[0]) for e in entries.values())
            width = max((len(e[0]) for e in entries.values()), default=1)
            chunk_take = {}   # slot -> (take, final) for prefill q-blocks
            for s in sorted(pre, key=self._admit_no.__getitem__):
                prompt = self._prompt_arr[s]
                P = len(prompt)
                off = int(self._prefill_off[s])
                if self.chunk > 0:
                    C_b = min(_pow2(self.chunk, floor=8), self.kv.s_max)
                    take = min(self.chunk, C_b, P - off)
                else:
                    take = P - off
                if rows_live + take > wave_rows(
                        self.cfg_tuple, B, self.spec_k + 1,
                        _pow2(max(width, take))):
                    continue
                rows_live += take
                width = max(width, take)
                final = off + take >= P
                # only the final chunk samples (and splits the rng) — at
                # its last row; mid-prompt chunks pass first_row == q_len
                entries[s] = ([int(t) for t in prompt[off:off + take]],
                              off, take - 1 if final else take, True)
                chunk_take[s] = (take, final)
            waiting = [s for s in pre if s not in chunk_take]
            pre = [s for s in pre if s in chunk_take]
            wave = assemble_mixed_wave(B, entries)
            tables = self.kv.tables.copy()
            from_device = np.zeros(B, bool)
            if ahead:
                from_device[decoding] = True
        routed_out = moe_stats = None
        with telemetry.span("serve.wave.dispatch", wave=wave_id, kind=kind,
                            q=int(wave["q"]), ahead=ahead):
            tokens, keys = _hand_over(self._dev_sampled, self._dev_after,
                                      from_device, wave["tokens"],
                                      self._keys)
            # a manager with state hands it through beside the pool and
            # gets it back last
            stateful = {"state": self.kv.state} if self.kv.stateful else {}
            if self.kv.window_layers:
                # the window layers' pool pair rides beside the pool
                # and comes back after everything else
                stateful.update(
                    win=(self.kv.win_k, self.kv.win_v),
                    ring=self.kv.win_tables.copy())
            out = self._mixed(
                self.params, self.cfg_tuple,
                self.kv.cache_k, self.kv.cache_v,
                tables, wave["pos"], tokens,
                wave["q_len"], wave["first_row"], wave["self_fresh"],
                self._temp, self._topk, keys,
                has_fresh=bool(pre), **stateful)
            if self.kv.window_layers:
                out, (self.kv.win_k, self.kv.win_v) = out[:-1], out[-1]
            if self.kv.stateful:
                out, self.kv.state = out[:-1], out[-1]
            if self.routed is not None:
                out, routed_out = out[:-1], out[-1]
            if self.moe is not None:
                out, moe_stats = out[:-1], out[-1]
            sampled, ck, cv, after = out
            self.kv.cache_k, self.kv.cache_v = ck, cv
            self._dev_sampled, self._dev_after = sampled, after
        # ---- what the host knows without a token's value ---- #
        if pre:
            self.prefill_dispatches += 1
        for s in pre:
            take, final = chunk_take[s]
            self.kv.advance(s, take)
            self.prefill_chunks += 1
            telemetry.inc("serve.prefill_chunks")
            self._prefill_off[s] += take
            if final:
                self._pos[s] = len(self._prompt_arr[s])
                self._emitted[s] = 1
        if not k_cur:
            # (a verify block's length is known when it lands)
            for s in decoding:
                self._pos[s] += 1
                self._emitted[s] += 1
                self.kv.advance(s)
        reqs = {s: self._reqs[s] for s in live}
        self._launched = wave_id
        root.set(launched=wave_id)
        self._flying = types.SimpleNamespace(
            id=wave_id, kind=kind, t0=t0, ahead=ahead, reqs=reqs, live=live,
            pre=pre, decoding=decoding, waiting=waiting,
            chunk_take=chunk_take, entries=entries, qlen_v=qlen_v,
            k_cur=k_cur, wave=wave, rows_live=rows_live,
            rows_computed=wave_rows(self.cfg_tuple, B, self.spec_k + 1,
                                    wave["q"], bool(pre)),
            sampled=sampled, after=after, routed_out=routed_out,
            moe_stats=moe_stats,
            ends=any(self._emitted[s] >= r.max_new_tokens
                     for s, r in reqs.items()))

    def _land(self, w, root):
        """A wave's second half: fetch what ``w`` sampled, unpack it,
        retire who is done.  Returns the Results.  ``_flying`` is then
        the wave launched ahead of this landing, if any: a request that
        retires here has a dead row in it."""
        if self._flying is w:
            self._flying = None
        done = []
        wave, wave_id = w.wave, w.id
        # a request that ended while this wave was in flight (``eos_id``
        # in the wave before it): its rows here are dead, dropped
        dead = [s for s in w.live if self._reqs[s] is not w.reqs[s]]
        pre, decoding = w.pre, w.decoding
        if dead:
            pre = [s for s in pre if s not in dead]
            decoding = [s for s in decoding if s not in dead]
        with telemetry.span("serve.wave.sync", wave=wave_id, kind=w.kind,
                            ahead=w.ahead):
            sampled = np.asarray(w.sampled)
            after = np.array(w.after, np.uint32)
            moe_rec = None
            if w.routed_out is not None:
                moe_rec = self._routed_record(wave, w.routed_out,
                                              w.rows_computed)
            elif w.moe_stats is not None:
                moe_rec = self._moe_record(w.moe_stats)
            scan_rec = self._wave_record(wave, w.rows_computed)
            self.metrics.record_wave(
                w.rows_live, w.rows_computed, len(w.waiting),
                ahead=w.ahead,
                rows_dead=int(wave["q_len"][dead].sum()) if dead else 0)
        # what the wave ADDED: its landing less the later of its own
        # launch's start and the landing before it.  In order that is
        # launch to landing; run ahead it is the wave's period.
        now = time.perf_counter()
        dt = now - max(w.t0, self._wave_end)
        self._wave_end = now
        # the lifecycle's wall, so that two waves never share any
        t_from = max(w.t0, self._land_end)
        k_cur = w.k_cur
        with telemetry.span("serve.wave.unpack", wave=wave_id, kind=w.kind):
            # ---- per-mode unpack: prefill q-blocks ---- #
            q_pre = 0
            pre_credit = {}
            for s in pre:
                req = self._reqs[s]
                take, final = w.chunk_take[s]
                q_pre += take
                # the whole fused wave IS this request's prefill compute —
                # there is no separate decode phase to stall behind, so
                # the lifecycle's chunk_stall residue collapses to ~0.
                # Credit the elapsed wall since ``t_from``, not just dt:
                # an earlier slot's _finish_prefill in this same loop can
                # compile the draft prefill (~100s of ms once per process)
                # and that wall sits inside THIS request's prefill span
                # too; _retire clamps the credit to the observed wall, so
                # over-crediting is safe and the stall residue stays ~0.
                # (A LATER slot's compile is covered by the end-of-wave
                # top-up below — this eager credit exists so a request
                # that retires AT prefill still carries its share.)
                e = time.perf_counter() - t_from
                self.metrics.lc_prefill(req.request_id, e)
                pre_credit[req.request_id] = e
                if final:
                    # a final chunk's window is its last row alone
                    r = self._finish_prefill(
                        s, int(sampled[s, 0]),
                        np.asarray(after[s, 0], np.uint32))
                    if r:
                        done.append(r)
            if pre:
                self.metrics.record_prefill(len(pre), wave["q"], dt,
                                            batched=True)
            # ---- verify / decode q-blocks ---- #
            n_dec = 0
            wave_emit = wave_acc = wave_prop = 0
            for s in decoding:
                req = self._reqs[s]
                if k_cur:
                    ql = w.qlen_v[s]
                    toks = w.entries[s][0]
                    a = 0
                    while a < ql - 1 and sampled[s, a] == toks[a + 1]:
                        a += 1
                    emit = [int(t) for t in sampled[s, :a + 1]]
                    if req.eos_id is not None and req.eos_id in emit:
                        emit = emit[:emit.index(req.eos_id) + 1]
                    n_emit = len(emit)
                    accepted = min(a, n_emit)
                    wave_emit += n_emit
                    wave_acc += accepted
                    wave_prop += ql - 1
                    self._spec_acc[s] += accepted
                    self._spec_prop[s] += ql - 1
                    self._spec_bonus[s] += n_emit - accepted
                    base = int(self._pos[s])
                    self.kv.advance(s, ql)
                    self.kv.truncate(s, base + n_emit)
                    self._pos[s] = base + n_emit
                    self._emitted[s] += n_emit
                    self._tok[s] = emit[-1]
                    self._keys[s] = after[s, n_emit - 1]
                    self._gen[s].extend(emit)
                    if req.stream_cb:
                        for t in emit:
                            req.stream_cb(req, t)
                    r = self._maybe_finish(s, emit[-1])
                else:
                    t = int(sampled[s, 0])
                    n_dec += 1
                    self._tok[s] = t
                    self._keys[s] = after[s, 0]
                    self._gen[s].append(t)
                    if req.stream_cb:
                        req.stream_cb(req, t)
                    r = self._maybe_finish(s, t)
                if r:
                    done.append(r)
            if pre_credit:
                # top every still-live prefill rider up to the FULL wave
                # elapsed: a later slot's _finish_prefill (draft-prefill
                # compile) or the verify/decode unpack runs after the
                # rider's eager credit above but inside its prefill wall —
                # without this the difference surfaces as a phantom
                # chunk_stall residue (lc_prefill no-ops for requests that
                # already retired; _retire clamps over-credit to the wall)
                t_wave = time.perf_counter() - t_from
                for rid, e in pre_credit.items():
                    if t_wave > e:
                        self.metrics.lc_prefill(rid, t_wave - e,
                                                count=False)
                # a chunk that waited this wave out stalled that long
                for s in w.waiting:
                    self.metrics.lc_stall(self._reqs[s].request_id, t_wave)
            self.steps += 1
            spec = None
            if k_cur:
                self.spec_waves += 1
                self.spec_k_sum += k_cur
                self.spec_proposed += wave_prop
                self.spec_accepted += wave_acc
                self.spec_emitted += wave_emit
                self._acc_window.append((wave_acc, wave_prop))
                self._adapt_k()
                spec = {"k": k_cur, "proposed": wave_prop,
                        "accepted": wave_acc}
            q_ver = sum(w.qlen_v.values())
            q_tot = max(q_pre + q_ver + n_dec, 1)
            n_live = len(w.live) - len(dead)
            self.metrics.record_step(
                live=n_live, slots=self.kv.n_slots,
                queue_depth=len(self._queue),
                dt_s=dt, new_tokens=wave_emit if k_cur else n_dec,
                prefill_s=dt * q_pre / q_tot, step=self.steps,
                requests=[w.reqs[s].request_id for s in w.live
                          if s not in dead],
                end_perf=now, spec=spec,
                mix={"q_prefill": q_pre, "q_verify": q_ver,
                     "q_decode": n_dec},
                moe=moe_rec, **scan_rec,
                # the window pool's ring and the most blocks a slot holds
                window={"ring": self.kv.ring, "held_max": int(
                    np.count_nonzero(self.kv.win_tables, axis=1).max())}
                if self.kv.window_layers else None)
        self._land_end = time.perf_counter()
        root.set(landed=wave_id, live=n_live, q_prefill=q_pre,
                 q_verify=q_ver, q_decode=n_dec)
        return done

    # ------------------------------------------------------------- #
    # speculative decoding (spec=/$HETU_SPEC_K)
    # ------------------------------------------------------------- #

    def _draft_prefill_slot(self, slot, prompt):
        """Prefill the truncated-layer draft's contiguous cache row for
        a newly admitted slot (one teacher-forced scan over the prompt
        bucket; the sampled token and rng split are discarded — the
        draft only ever proposes greedily from its own cache).  Also
        zeroes the slot's per-request speculation attribution: an
        admission passes through here exactly once."""
        P = len(prompt)
        pb = self.kv.bucket_prompt(P)
        arr = np.zeros(pb, np.int32)
        arr[:P] = [int(t) for t in prompt]
        _, dck, dcv, _ = self._draft_prefill(
            self.params, self.cfg_tuple_draft,
            self._draft_ck, self._draft_cv,
            np.int32(slot), arr, np.int32(P), np.float32(0.0),
            np.int32(0), np.asarray(jax.random.PRNGKey(0), np.uint32))
        self._draft_ck, self._draft_cv = dck, dcv
        self.spec_draft_prefills += 1
        self._spec_acc[slot] = 0
        self._spec_prop[slot] = 0
        self._spec_bonus[slot] = 0

    def _adapt_k(self):
        """Sliding-window acceptance-rate controller: raise the draft
        length through the pow2 ladder while acceptance stays high
        (more free tokens per wave), back off while it stays low (a
        rejected draft is a wasted draft step AND a rolled-back verify
        position).  The window clears on every move so the new k is
        judged on its own evidence."""
        if not self.spec_adapt or len(self._acc_window) < 8:
            return
        prop = sum(p for _, p in self._acc_window)
        if prop == 0:
            return
        rate = sum(a for a, _ in self._acc_window) / prop
        if rate >= 0.75 and self._spec_kcur < self.spec_k:
            self._spec_kcur = min(self._spec_kcur * 2, self.spec_k)
            self._acc_window.clear()
        elif rate <= 0.35 and self._spec_kcur > 1:
            self._spec_kcur = max(self._spec_kcur // 2, 1)
            self._acc_window.clear()

    @property
    def spec_acceptance(self):
        """Lifetime draft acceptance rate (None before any proposal)."""
        if not self.spec_k or not self.spec_proposed:
            return None
        return self.spec_accepted / self.spec_proposed

    @property
    def spec_mean_k(self):
        """Mean per-wave draft length (adaptation's observable)."""
        if not self.spec_k or not self.spec_waves:
            return None
        return self.spec_k_sum / self.spec_waves

    def run(self, requests=()):
        """Submit ``requests`` then step until everything (including
        already-pending work) drains; returns {request_id: Result}.  A
        slot stays live, and counts in ``pending``, until the wave that
        carries its last token has LANDED, so the loop ends with
        nothing in flight."""
        for r in requests:
            self.submit(r)
        out = {}
        while self.pending:
            for res in self.step():
                out[res.request_id] = res
        return out

    # ------------------------------------------------------------- #

    def _maybe_finish(self, slot, last_token):
        req = self._reqs[slot]
        n = len(self._gen[slot])
        times = self._tok_t[slot]
        times.extend([self._wave_end] * (n - len(times)))
        if req.eos_id is not None and last_token == req.eos_id:
            reason = "eos"
        elif n >= req.max_new_tokens:
            reason = "length"
        else:
            return None
        now = time.perf_counter()
        tokens = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(self._gen[slot], np.int32)])
        spec = None
        if self.spec_k:
            # per-request speculation attribution: every generated
            # token is the prefill sample, an accepted draft, or a
            # bonus sample — accepted + bonus + 1 == n_generated, the
            # invariant hetu_trace --check enforces (rejected drafts,
            # proposed - accepted, are exempt: they cost compute, not
            # sequence length)
            spec = {"accepted": int(self._spec_acc[slot]),
                    "proposed": int(self._spec_prop[slot]),
                    "bonus": int(self._spec_bonus[slot])}
        res = Result(
            request_id=req.request_id, tokens=tokens,
            prompt_len=len(req.prompt), finish_reason=reason,
            n_generated=n, ttft_s=req.first_token_at - req.submitted_at,
            latency_s=now - req.submitted_at, slot=slot,
            spec_accepted=spec["accepted"] if spec else 0,
            spec_proposed=spec["proposed"] if spec else 0,
            weight_version=self._slot_version[slot],
            queue_wait_s=req.claimed_at - req.submitted_at,
            token_times_s=[t - req.submitted_at for t in times])
        self.metrics.record_finish(req.request_id, reason, n,
                                   res.latency_s, spec=spec)
        decode_s = now - req.first_token_at
        self.slo.observe(
            request_id=req.request_id, ttft_ms=res.ttft_s * 1e3,
            tok_s=((n - 1) / decode_s
                   if n > 1 and decode_s > 0 else None))
        if self._flying is not None and slot in self._flying.reqs:
            # ended on ``eos_id`` with the next wave already launched:
            # its row there is dead (written past the request's end,
            # inside the span reserved for it), and the slot's length is
            # again what has landed
            self.kv.advance(
                slot, -int(self._flying.wave["q_len"][slot]))
        if self.retire_hook is not None:
            # last look at the LIVE slot (the router's KV-handoff
            # export rides this) — release frees the blocks next
            self.retire_hook(req, slot)
        self._reqs[slot] = None
        self._gen[slot] = None
        self._tok_t[slot] = None
        self._slot_version[slot] = None
        self.kv.release(slot)
        return res

    def health(self):
        """The admission signal: the SLO monitor's worst-burn state —
        "ok" / "degraded" / "breach" (always "ok" with no SLOs
        declared).  A router shifts or sheds load on "breach"; see
        telemetry/slo.py for the burn-rate semantics."""
        return self.slo.health()
