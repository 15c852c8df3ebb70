"""Fast autoregressive decoding for the GPT family: KV-cached
incremental steps inside ONE jitted lax.scan.

``greedy_generate`` (gpt.py) re-runs the full fixed-S forward per token
— O(S^2) attention per token, O(S^3) per sequence — which is the
static-shape-simple demo path.  This module is the serving path: a
preallocated [L, B, S_max, H, Dh] KV cache updated at the current
position via dynamic_update_slice, attention masked to the filled
prefix, the WHOLE generation (prompt teacher-forcing + sampling) one
compiled scan.  O(S) attention per token; one compile per
(batch, S_max) shape.

Weights come from the executor's named parameters (the same contract
hf.py's importers target), so a trained-or-imported model decodes with
no re-tracing of the training graph:

    out = generate_fast(ex.var_values, cfg, prompts, num_tokens=50,
                        temperature=0.8, top_k=40, seed=0)

Sampling: greedy (temperature=0), temperature, and top-k; ``eos_id``
stops a sequence at EOS (pad after, per-step compute short-circuits
once the whole batch is done).

``_decode_step`` is the contiguous-cache decode core: the offline scan
above runs it with one scalar position for the whole batch, the
speculative draft with a per-slot position vector.  The
continuous-batching serving engine (``hetu_tpu.serving``) runs ONE
core, ``_mixed_step``: a ragged wave in which a decode stream is a
q-block of 1, a verify block k+1 and a prompt chunk its width, over
the block-table paged pool (``serve_mixed_paged_fn``).
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import envvars
from ..kv_layout import kv_heads, kv_rows
from ..quant import kv_decode, kv_encode

NEG_INF = -1e30


# ----------------------- quantized-cache plumbing ----------------------- #
#
# An int8 KV cache (HETU_KV_QUANT) travels as a ``(int8 data, f32
# scales)`` 2-tuple wherever a plain cache array travels — jit treats it
# as a pytree, donation donates both leaves, and the engine reassigns it
# opaquely.  These helpers are the ONLY places the layout forks: writes
# encode through ``quant.kv_encode`` (one scale per position per head),
# reads either dequantize (reference/masked paths) or hand the raw
# payload + scales to the int8 pool's kernel, which dequantizes inside
# the online-softmax loop.
#
# The PAGED pool of a float dtype holds ROWS, ``[L, N_blocks, block, W]``
# (``kv_layout``: heads side by side, padded to the lane tile, the
# layout the mixed ragged kernel reads in place): a q-block narrower
# than a page is written through ``_kv_scatter``, which lays a ``[.., H,
# Dh]`` slab out as rows, a wider one as pages
# (``kernels/paged_kv_write.py``), and
# every reader but that kernel takes ``_kv_layer``'s ``[.., H, Dh]`` view.
# The contiguous cache ``[L, B, S_max, H, Dh]`` and the int8 pool keep
# their head axes.


def _kv_q(cache):
    """True when ``cache`` is the quantized (data, scales) pair."""
    return isinstance(cache, (tuple, list))


def _kv_shape(cache):
    """The payload shape (scales mirror it minus the head_dim axis)."""
    return cache[0].shape if _kv_q(cache) else cache.shape


def _kv_scatter(cache, idx, val):
    """``cache.at[idx].set(val)`` for either layout: ``val`` is the
    float K/V slab; a quantized cache encodes it and writes payload +
    scales through the SAME index (the scale planes drop only the
    trailing head_dim axis, so any index that selects ``[..., H, Dh]``
    slabs of the payload selects ``[..., H]`` slabs of the scales)."""
    if _kv_q(cache):
        data, sc = cache
        q, s = kv_encode(val)
        return (data.at[idx].set(q), sc.at[idx].set(s))
    if cache.ndim == 4:                # the paged pool's rows
        val = kv_rows(val, cache.shape[-1])
    return cache.at[idx].set(val.astype(cache.dtype))


def _kv_layer(cache, i, H, Dh):
    """Layer ``i`` of a cache as ``(payload [.., H, Dh], scales or
    None)``: a contiguous cache's ``[B, S_max, H, Dh]``, or the paged
    pool's ``[N_blocks, block, H, Dh]`` (the first ``H * Dh`` columns of
    its rows), or the int8 pair's payload and ``[.., H]`` scales."""
    if _kv_q(cache):
        return cache[0][i], cache[1][i]
    layer = cache[i]
    return (kv_heads(layer, H, Dh) if layer.ndim == 3 else layer), None


def _kv_dus(cache, val, i, pos):
    """The offline scan's contiguous dynamic_update_slice write (one
    [B, H, Dh] slab at scalar position ``pos`` of layer ``i``), both
    layouts."""
    if _kv_q(cache):
        data, sc = cache
        q, s = kv_encode(val)
        return (jax.lax.dynamic_update_slice(
                    data, q[None, :, None], (i, 0, pos, 0, 0)),
                jax.lax.dynamic_update_slice(
                    sc, s[None, :, None], (i, 0, pos, 0)))
    return jax.lax.dynamic_update_slice(
        cache, val[None, :, None], (i, 0, pos, 0, 0))


def _kv_slot_slice(cache, slot, sizes):
    """One slot's [L, 1, S_max, H, Dh] view of a contiguous cache (the
    reference prefill works on this slice), both layouts."""
    if _kv_q(cache):
        data, sc = cache
        return (jax.lax.dynamic_slice(data, (0, slot, 0, 0, 0), sizes),
                jax.lax.dynamic_slice(sc, (0, slot, 0, 0), sizes[:-1]))
    return jax.lax.dynamic_slice(cache, (0, slot, 0, 0, 0), sizes)


def _kv_slot_update(cache, sub, slot):
    """Write a slot view (from :func:`_kv_slot_slice`) back."""
    if _kv_q(cache):
        data, sc = cache
        return (jax.lax.dynamic_update_slice(data, sub[0],
                                             (0, slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(sc, sub[1],
                                             (0, slot, 0, 0)))
    return jax.lax.dynamic_update_slice(cache, sub, (0, slot, 0, 0, 0))


def _pow2(n, floor=1):
    """Smallest power of two >= max(n, floor) (kv_manager.round_up_pow2
    re-exports this shape policy; duplicated here to keep models ->
    serving import-free)."""
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


# Under about 240 rows a wave's weight products are bound by the
# weights' bytes and not by its rows (a v5e's 197 TFLOP/s over 819 GB/s,
# at 2 B and 2 operations a weight a row): a wave packed tighter than
# that computes no faster and only makes chunks wait.
_PACKED_ROWS_FLOOR = 256


def wave_rows(cfg_tuple, slots, window, q, has_fresh=True):
    """How many rows the row-wise operators of a mixed wave run over:
    the one place that says it, read by ``_mixed_step`` (the program's
    static row count) and by the engine's scheduler (the capacity it
    keeps a wave's live rows within).  A wave that carries a prompt
    chunk (``has_fresh``) is PACKED into ``min(slots *
    q, max(256, pow2(slots * window + 2 * q)))`` rows: every slot's
    sampling window and two chunks of the bucket always fit (1,024 rows
    at 32 slots and q 256, where the padded block is 8,192), and the
    count is a function of the bucket, so the program set stays one a
    (q bucket, ``has_fresh``).  Every other wave runs over ``slots *
    q``: decode and verify blocks have most of their rows live; and
    the capacity router of a
    ``MoESpec`` sizes each expert's slots from the rows it is handed
    (``moe_capacity``), so its waves stay padded and drop what they
    dropped."""
    dense = slots * q
    if not has_fresh or _moe_of(cfg_tuple) is not None:
        return dense
    return min(dense, max(_PACKED_ROWS_FLOOR,
                          _pow2(slots * window + 2 * q)))


def writes_pages(blk, quant, q, block):
    """Whether a wave of q-blocks ``q`` wide writes its K/V rows as
    PAGES (``kernels/paged_kv_write``) and not as rows (``_kv_scatter``):
    a q-block a page or more wide over a float K/V pool.  The one place
    that says it, read by ``_mixed_wave`` and by the engine's count of
    the pages written.  (PR 31 measured one row a slot at 0.023 ms as
    rows against 0.033 as pages; the int8 pool's scale planes have no
    page a kernel can copy; the latent pool's rows are
    ``_latent_attention``'s own to write.)"""
    return not quant and q >= block and blk.attention != "latent"


class _Rows(NamedTuple):
    """A packed wave's row layout, built on the device from ``q_len``
    alone: slot-major, each slot's ``q_len`` live rows in sequence
    order, dead rows at the tail.  ``slot``/``off`` [R]: the slot and
    the place in its q-block of every packed row (a dead row's are
    clipped into range and mean nothing); ``live`` [R]; ``start`` [B]:
    the packed row a slot's q-block starts at; ``q``: the q-block's
    padded width."""

    slot: jax.Array
    off: jax.Array
    live: jax.Array
    start: jax.Array
    q: int

    @classmethod
    def of(cls, q_len, q, rows):
        ends = jnp.cumsum(q_len)
        start = ends - q_len
        r = jnp.arange(rows)
        slot = jnp.minimum(
            jnp.sum(r[:, None] >= ends[None, :], axis=1), len(q_len) - 1)
        off = jnp.clip(r - start[slot], 0, q - 1)
        return cls(slot, off, r < ends[-1], start, q)

    def pack(self, x):
        """``x`` [B, Q, ...] -> [1, R, ...]: the live rows, packed."""
        flat = x.reshape((-1,) + x.shape[2:])
        return flat[self.slot * self.q + self.off][None]

    def unpack(self, x):
        """``x`` [1, R, ...] -> [B, Q, ...]: slot b's rows back at
        ``[b, :q_len[b]]``; what lies past them belongs to a neighbour
        and is read by nobody (dead rows are masked by every reader)."""
        at = self.start[:, None] + jnp.arange(self.q)[None, :]
        return x[0][jnp.minimum(at, x.shape[1] - 1)]


def _resolve_fast(mode=None):
    """Serving fast-path selection, shared by ``generate_fast`` and the
    serving engine: an explicit argument wins; else ``$HETU_SERVE_FAST``
    ("1" forces the Pallas kernels: flash prefill offline, the ragged
    kernel in the engine's wave; "0" forces the masked/scan reference);
    else auto — kernels on TPU, reference elsewhere.  This is the one
    serving choice that follows the platform: off-TPU the kernels run
    in interpret mode, correct (the parity suite pins it) but emulated,
    so the reference stays the off-TPU default."""
    if mode is None:
        mode = envvars.get_str("HETU_SERVE_FAST")
    if isinstance(mode, bool):
        return mode
    s = str(mode).strip().lower()
    if s in ("1", "on", "true", "fast", "ragged", "flash"):
        return True
    if s in ("0", "off", "false", "masked", "scan", "slow"):
        return False
    return jax.default_backend() == "tpu"


def resolve_spec_k(spec=None):
    """Speculative-decoding depth shared by the engine and offline
    ``generate_fast``: an explicit ``spec`` wins (None falls back to
    ``$HETU_SPEC_K``); 0 = off.  The value is the MAXIMUM draft tokens
    per wave — the adaptive controller moves within [1, k]."""
    if spec is None:
        spec = envvars.get_int("HETU_SPEC_K")
    return max(int(spec or 0), 0)


def resolve_draft_layers(layers, total_layers):
    """Truncated-layer draft depth: explicit ``layers`` wins, then
    ``$HETU_SPEC_DRAFT_LAYERS``, then the auto policy max(1, L // 4).
    The draft IS the target's first ``layers`` blocks plus the shared
    final LN and tied embedding head — no separate weights, tokenizer,
    or loading path to maintain (early-exit style drafting)."""
    if not layers:
        layers = envvars.get_int("HETU_SPEC_DRAFT_LAYERS")
    if not layers or int(layers) <= 0:
        layers = max(1, int(total_layers) // 4)
    return min(int(layers), int(total_layers))


def _ln(x, scale, bias, eps=1e-5):
    # statistics in f32 regardless of the compute dtype: bf16 mean/var
    # over outlier channels (GPT-2 residual streams have them) loses
    # enough mantissa to flip close argmax decisions; the cast costs
    # nothing next to the matmuls
    x32 = x.astype(jnp.float32)
    m = x32.mean(axis=-1, keepdims=True)
    v = ((x32 - m) ** 2).mean(axis=-1, keepdims=True)
    y = (x32 - m) * jax.lax.rsqrt(v + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _gelu_tanh(x):
    # tanh approximation — the framework's gelu_op (reference kernel
    # parity; equals HF gelu_new)
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


# ------------------------- MoE plumbing ------------------------- #
#
# A MoE GPT rides the SAME six compiled cores: the cfg_tuple grows an
# optional sixth element — a hashable ``moe_decode.MoESpec`` — and the
# FFN sublayer (factored into ``_ffn_block`` below) swaps the dense
# wi/wo matmuls for top-k routed expert dispatch on the spec's MoE
# layers.  Every existing 5-tuple stays a dense GPT bit for bit; the
# spec is jit-static, so dense and MoE models compile separate programs
# through one code path.  A ``draft=True`` spec (the truncated-layer
# speculative draft) SKIPS ROUTING ENTIRELY — its MoE blocks are
# attention-only (zero FFN contribution), so drafting needs no
# dispatch, no capacity, and no expert reads; verification still owns
# every emitted token, so acceptance semantics are untouched.


# ------------------------- block spec ------------------------- #
#
# What kind of decoder block the mixed wave runs is DATA: a hashable,
# jit-static ``BlockSpec`` that rides the cfg_tuple's sixth place (where
# a ``MoESpec`` rides for the capacity-routed GPT).  A 5-tuple, and a
# 6-tuple whose sixth element is a ``MoESpec``, mean GPT-2's block
# (``GPT2_BLOCK``) and lower to exactly the program they lowered to
# before there was a spec.  Only ``_mixed_step`` over the paged pool
# reads the spec; the six other cores are GPT-2's alone (the engine
# refuses any other spec on their paths; folding them is ROADMAP C3/C4).


class IndexSpec(NamedTuple):
    """The learned indexer of a latent attention that reads a SUBSET of
    its sequence (the DeepSeek-V3.2 family's ``index_*`` keys): ``n_heads``
    index heads of ``head_dim`` columns, the first ``rope_dim`` of them
    rotated, ONE index key a cached position; a query row reads the
    ``topk`` positions of largest ``sum_j w_j relu(q_j . k_s)`` (all of
    them while it has no more in sight)."""

    n_heads: int
    head_dim: int
    topk: int
    rope_dim: int


class LatentSpec(NamedTuple):
    """Multi-head latent attention's five sizes (the source's own
    ``config.json`` keys) and, of a spec whose latent operators differ BY
    LAYER (``BlockSpec.latent_by_op``), what else an operator has of its
    own: ``heads`` (0: the model's), ``gate`` (a head-wise sigmoid gate
    ``sigmoid(x W_g)`` on the attention's output, before ``W_o``),
    ``rescale`` (the two low-rank norms' outputs multiplied by
    ``sqrt(hidden / rank)``) and ``index`` (an ``IndexSpec``: the layer
    reads the rows its indexer chose).  ``q_lora_rank`` 0: the query has
    no low-rank step (the source's ``q_lora_rank`` null): ONE projection
    ``{us}_attn_q_weight`` [hidden, heads x (nope + rope)] and no query
    norm (no indexer either: its queries are made from the low-rank
    query).  The defaults trace nothing."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    heads: int = 0
    gate: bool = False
    rescale: bool = False
    index: Optional[IndexSpec] = None

    @property
    def row_width(self):
        """One cached row a token a layer: ``[c_kv | k_r]``, padded with
        zeros to the next multiple of the 128 lanes (576 -> 640).  The
        device pads a row's last dimension to that anyway, so the pad
        costs no memory; stated in the shape it keeps the pool's
        default layout row-major (an unaligned last dimension makes the
        compiler lay the pool out block-index-minor, and every wave
        then copies the whole pool to and from the layout the kernel
        reads: PERF.md section 6, PR 28) and lets the kernel copy a
        page by hand."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


class MuP(NamedTuple):
    """The forward multipliers of a block trained under maximal-update
    parametrisation (the ``falcon_h1`` family's ``config.json`` keys):
    constants the forward multiplies by, not weights.  ``embedding`` on
    the embedded tokens, ``attention_in`` / ``ssm_in`` on the normed
    input of each mixer, ``key`` on the projected keys, ``ssm`` on the
    five slices ``z | x | B | C | dt`` of the state-space projection,
    ``attention_out`` / ``ssm_out`` on each mixer's output, ``mlp_gate``
    inside the SiLU, ``mlp_down`` on the FFN's output and ``lm_head`` on
    the logits."""

    embedding: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_gate: float = 1.0
    mlp_down: float = 1.0
    lm_head: float = 1.0


# Every operator a layer of ``BlockSpec.ops`` may name, with what such a
# layer KEEPS from one wave to the next: "pool" (K/V pages of every
# position), "window" (K/V pages in the window pool), "state" (slot state
# beside the pool), "index" (an index key a position beside the pool's
# row, where the layer's latent spec has an indexer); "none" is a layer
# with no operator at all (an FFN alone on its one norm), which keeps
# nothing.  ``BlockSpec.holds`` and ``check_block_spec`` read this
# table; so does the latter's error text.
OPERATORS = {
    "attention": ("pool",),
    "window_attention": ("window",),
    "latent_attention": ("pool", "index"),
    "window_latent_attention": ("window",),
    "conv": ("state",),
    "attention+ssm": ("pool", "state"),
    "retention": ("state",),
    "ssm": ("state",),
    "kda": ("state",),
    "none": (),
}
# every kind of FFN a layer may have; "none": a layer that is its
# operator alone on its one norm
FFN_KINDS = ("gelu", "swiglu", "routed", "none")
# the operators that keep slot state, by the kind of state: one manager
# holds ONE set, so a spec names operators of one kind alone
STATE_KINDS = {"conv": "conv", "attention+ssm": "ssm", "ssm": "ssm",
               "retention": "retention", "kda": "kda"}
# the operators of a latent block (``attention`` "latent" with ``ops``)
# that read latent rows, and the state operators that may stand BESIDE
# them, layer by layer, in such a block
LATENT_OPERATORS = ("latent_attention", "window_latent_attention")
LATENT_STATE_OPERATORS = ("kda",)


class BlockSpec(NamedTuple):
    """norm: "layernorm" (scale and bias) | "rmsnorm" | "layernorm_nobias"
    (mean-centred, a scale alone, ``norm_eps``: the ``cohere2`` family's);
    positions:
    "learned" (a table added to the embedding) | "none" (nothing added
    and nothing rotated: the ``nemotron_h`` family's attention) | "rope" (rotate-half
    with ``rope_theta``: over ``latent.qk_rope_head_dim``, or over the
    whole head of a K/V attention; ``rope_by_op`` gives the rotary
    parameters BY OPERATOR instead, ``((operator, inv_freq, factor),
    ...)`` as ``rope_frequencies`` makes them on the host: the kinds
    "default" and "yarn" exist, a layer whose operator is not named
    rotates with ``rope_theta``, and an entry whose ``inv_freq`` is
    "none" says that operator's layers rotate NOTHING); attention: "mha" (as many K/V heads
    as query heads) | "gqa" (``kv_heads`` K/V heads, query head ``n``
    reading K/V head ``n // (H / kv_heads)``) | "latent"; bias: the
    K/V attention's projections carry biases (GPT-2's do); qk_norm:
    every head's q and k RMS-normalised over its columns with a learned
    scale before the rotation; ops: the OPERATOR of every layer, a tuple
    of ``OPERATORS``' names
    (None: attention everywhere), a "window_attention" layer being that
    K/V attention over the last ``window`` positions alone (a query sees
    itself and the ``window - 1`` before it), its K/V pages in the
    WINDOW pool, a ring a slot; a "conv" layer being the gated short
    convolution of
    ``conv_kernel`` taps whose state lives beside the pool, an
    "attention+ssm" layer running that K/V attention AND the state-space
    mixer ``ssm`` (a ``ssm_decode.SSMSpec``) side by side on one norm,
    their outputs summed into the residual, so that it holds K/V pages
    and slot state both, a "retention" layer running that attention's
    front end (projections, q/k norm, rotation) into power retention
    (``retention``, a ``retention_decode.RetentionSpec``) in place of
    the softmax over pages, so that it holds slot state and NO page (a
    spec of such layers alone has no pool), an "ssm" layer running the
    state-space mixer ALONE on the layer's one norm (state, no page), a
    "kda" layer running the gated delta rule ``kda`` (a
    ``kda_decode.KDASpec``: a conv tail and a matrix state a slot, no
    page; it stands beside latent operators in a latent block, or beside
    plain "attention" layers in the grouped-query block, whose K/V pool
    the one manager then holds beside the delta rule's states) and
    a "none" layer running no operator (it keeps nothing); head_dim: the head size where it is the
    configuration's own key and not ``hidden / heads`` (0: that
    quotient); mup: the block's forward multipliers (``MuP``; None: no
    multiplication anywhere); ffn: the kind of every layer from
    ``leading_dense`` on, "gelu" | "swiglu" | "routed" (a
    ``moe_decode.RoutedSpec`` in ``routed``; the leading layers are
    dense SwiGLU); ffns: the FFN kind BY LAYER instead, a tuple of
    ``FFN_KINDS``' names, "none" being a layer that is its operator
    alone (None: ``ffn`` as above; with it ``ffn`` says whether any
    layer routes); residual: "sequential" (``h <- h + op(ln1(h))``, then
    ``h <- h + ffn(ln2(h))``: a norm for each part a layer has) |
    "parallel" (``x = ln1(h)``, ``h <- h + op(x) + ffn(x)``: ONE norm a
    layer, the operator and the FFN side by side on its rows, one
    addition into the residual); head: "tied" (the embedding table) |
    "untied" (``{name}_lm_head_weight`` [hidden, vocab]); attn_gate: the
    K/V attention's output times ``sigmoid(x W_gate)``, one a COLUMN
    (``{us}_attn_gate_weight`` [hidden, H Dh], of the layer's normed
    rows), before ``W_o`` (the ``solar_open2`` family's
    ``use_gqa_gate``)."""

    norm: str = "layernorm"
    norm_eps: float = 1e-5
    positions: str = "learned"
    rope_theta: float = 10000.0
    attention: str = "mha"
    latent: Optional[LatentSpec] = None
    ffn: str = "gelu"
    leading_dense: int = 0
    routed: Optional[tuple] = None
    head: str = "tied"
    bias: bool = True
    kv_heads: int = 0
    qk_norm: bool = False
    ops: Optional[tuple] = None
    conv_kernel: int = 0
    head_dim: int = 0
    ssm: Optional[tuple] = None
    mup: Optional[MuP] = None
    window: int = 0
    rope_by_op: Optional[tuple] = None
    retention: Optional[tuple] = None
    ffns: Optional[tuple] = None
    latent_by_op: Optional[tuple] = None
    residual: str = "sequential"
    kda: Optional[tuple] = None
    attn_gate: bool = False

    def latent_of(self, i):
        """Layer ``i``'s ``LatentSpec``: its operator's entry of
        ``latent_by_op`` (``((operator, LatentSpec), ...)``), else
        ``latent``."""
        for op, la in self.latent_by_op or ():
            if op == self.op_kind(i):
                return la
        return self.latent

    def ffn_kind(self, i):
        """Layer ``i``'s FFN: its entry of ``ffns``; else ``ffn``, the
        leading layers of a routed model being dense SwiGLU."""
        if self.ffns:
            return self.ffns[i]
        if self.ffn == "routed" and i < self.leading_dense:
            return "swiglu"
        return self.ffn

    def routed_layers(self, L):
        return sum(1 for i in range(L) if self.ffn_kind(i) == "routed")

    def op_kind(self, i):
        """Layer ``i``'s operator, one of ``OPERATORS``' names."""
        return self.ops[i] if self.ops else "attention"

    def holds(self, i, what):
        """Whether layer ``i`` keeps ``what``: "pool" (K/V pages of every
        position: a layer with an attention over everything), "window"
        (K/V pages in the window pool: a window layer) or "state" (slot
        state beside the pool: a conv, a state-space mixer or a
        retention layer) or "index" (index keys beside the pool: a
        latent layer whose spec has an indexer).  ``OPERATORS`` says
        which; a layer with no operator keeps none of them."""
        return what in OPERATORS[self.op_kind(i)] and (
            what != "index" or self.latent_of(i).index is not None)

    def op_index(self, i, what=None):
        """Layer ``i``'s place among the layers that keep what it keeps:
        an attention layer's index into the K/V pool, a window layer's
        into the window pool, a conv, mixer or retention layer's into
        the state; a layer that keeps two says ``what`` (else its
        pages'); None for a layer that keeps nothing."""
        what = what or next(iter(OPERATORS[self.op_kind(i)]), None)
        if what is None:
            return None
        return sum(1 for j in range(i) if self.holds(j, what))

    def rope_of(self, i):
        """Layer ``i``'s (inv_freq or None, factor): its operator's entry
        of ``rope_by_op`` ("none" in ``inv_freq``'s place: the layer
        rotates nothing), else ``rope_theta``'s own frequencies."""
        for op, inv, factor in self.rope_by_op or ():
            if op == self.op_kind(i):
                return inv, factor
        return None, 1.0

    def state_shapes(self, L, hidden):
        """The set of slot states ``L`` layers of this spec keep beside
        the pool, as ``PagedKVManager(state_shapes=)`` takes it (None:
        none): a conv layer's last ``conv_kernel - 1`` inputs, or a
        state-space mixer's set (``SSMSpec.state_shapes``), or the
        retention layers' (``RetentionSpec.state_shapes``), or the
        delta-rule layers' (``KDASpec.state_shapes``)."""
        n = self.op_layers(L, "state")
        if not n:
            return None
        for spec in (self.ssm, self.retention, self.kda):
            if spec is not None:
                return spec.state_shapes(n)
        return (((n, self.conv_kernel - 1, hidden), None),)

    def op_layers(self, L, what):
        """How many of ``L`` layers keep ``what`` ("pool" | "window" |
        "state"; or an operator's name: the layers of that operator)."""
        if what in ("pool", "window", "state", "index"):
            return sum(1 for i in range(L) if self.holds(i, what))
        return sum(1 for i in range(L) if self.op_kind(i) == what)


GPT2_BLOCK = BlockSpec()

# every kind of rotary frequencies ``rope_frequencies`` makes
ROPE_KINDS = ("default", "yarn")


def rope_frequencies(head_dim, rope_type="default", rope_theta=10000.0,
                     factor=1.0, original_max_position_embeddings=0,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                     **ignored):
    """(inv_freq: ``head_dim / 2`` floats, the factor ``cos`` and ``sin``
    are both multiplied by) of one ``rope_parameters`` section, on the
    host, once: no table over positions.  "default": ``theta ** (-2i /
    d)`` and 1.  "yarn": frequency ``i`` is the default one below
    ``low``, the default over ``factor`` above ``high`` and a linear
    ramp between, ``low`` / ``high`` the indices whose wavelength fits
    ``beta_fast`` / ``beta_slow`` times into
    ``original_max_position_embeddings``, clamped to the head; the
    factor is ``attention_factor``, else ``0.1 ln(factor) + 1``.  The
    frequencies do not depend on a sequence's length."""
    if rope_type not in ROPE_KINDS:
        raise ValueError(f"rope_type={rope_type!r}; there are "
                         f"{', '.join(ROPE_KINDS)}")
    d = int(head_dim)
    base = float(rope_theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope_type == "default":
        return tuple(float(v) for v in base), 1.0

    def index(rotations):
        return d * math.log(original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(index(beta_fast)), 0)
    high = min(math.ceil(index(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 - ramp) * base + ramp * base / float(factor)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(float(factor)) + 1.0
    return tuple(float(v) for v in inv), float(attention_factor)


def _block_of(cfg_tuple):
    """The cfg_tuple's ``BlockSpec`` (``GPT2_BLOCK`` when it carries
    none: every 5-tuple and every ``MoESpec`` 6-tuple)."""
    extra = cfg_tuple[5] if len(cfg_tuple) > 5 else None
    return extra if isinstance(extra, BlockSpec) else GPT2_BLOCK


def block_spec_of(config):
    """The ``BlockSpec`` a configuration object carries
    (``config.block_spec()``), ``GPT2_BLOCK`` for one that carries
    none."""
    make = getattr(config, "block_spec", None)
    return make() if callable(make) else GPT2_BLOCK


# the norms and the residual forms a block may have
NORMS = ("layernorm", "rmsnorm", "layernorm_nobias")
RESIDUALS = ("sequential", "parallel")


def check_block_spec(blk, layers=None):
    """Raise for a spec the mixed wave cannot run.  It runs GPT-2's
    block; the LATENT block: latent attention with RMSNorm and RoPE over
    any FFN kind of ``ffn``, every layer alike or each one operator of
    ``LATENT_OPERATORS`` (a "window_latent_attention" layer over the
    last ``window`` positions in a latent ring, a "latent_attention"
    layer over everything or, where its ``LatentSpec`` has an indexer,
    over the rows that chose; a ``LatentSpec`` whose ``q_lora_rank`` is 0
    projects its query in one step) or of ``LATENT_STATE_OPERATORS`` (a
    "kda" layer: the gated delta rule ``kda``, a conv tail and a matrix
    state a slot BESIDE the latent pool, in one manager; such a spec
    names at least one latent operator too);
    and the grouped-query block: RMSNorm or the bias-free LayerNorm
    ("layernorm_nobias"), no biases, an
    optional per-head q/k norm, positions "rope" (over the whole head)
    or "none", every layer ONE of ``OPERATORS`` but the latent block's
    and one of
    ``FFN_KINDS``, at least one of the two not "none".  Its residual is
    one of ``RESIDUALS``: "sequential", or "parallel" (one norm a layer,
    operator and FFN side by side) where every layer is a K/V attention
    ("attention" or "window_attention") beside a SwiGLU or a routed FFN;
    a parallel block over a latent or a state operator is not run.  A
    "kda" layer stands beside plain "attention" layers alone (at least
    one), and an output gate a column (``attn_gate``) goes with the
    sequential grouped-query block without multipliers.
    What each
    operator needs is ``_OPERATOR_NEEDS``' entry; operators that keep
    slot state are of ONE kind a spec (``STATE_KINDS``: the manager
    holds one set), and a window or a retention layer is mixed with
    plain "attention" layers alone.  Multipliers (``mup``), a head size
    of the configuration's own and rotary parameters by operator
    (``rope_by_op``, of ``ROPE_KINDS``, or "none" for an operator whose
    layers rotate nothing) go with the grouped-query block
    alone; a routed FFN scores by one of ``moe_decode.SCORINGS``, has
    experts of one of ``moe_decode.EXPERT_FORMS``, holds all its
    experts or a contiguous share of them and chooses among all of them
    or among the ``topk_group`` best of ``n_group`` equal groups.
    ``layers``: the model's
    depth, which ``ops`` and ``ffns`` then name layer by layer."""
    if blk == GPT2_BLOCK:
        return
    from .moe_decode import EXPERT_FORMS, SCORINGS
    rt = blk.routed
    kinds = set(blk.ffns or (blk.ffn,))
    common = (blk.ffn == "routed") == (rt is not None) \
        and kinds <= set(FFN_KINDS) \
        and (blk.ffns is None or (
            ("routed" in kinds) == (rt is not None)
            and not blk.leading_dense
            and (layers is None or len(blk.ffns) == layers))) \
        and blk.head in ("tied", "untied") \
        and (rt is None or (
            rt.scoring in SCORINGS and rt.expert in EXPERT_FORMS
            and rt.latent >= 0 and 0 <= rt.held_first
            and 0 <= rt.held and rt.shared_scale > 0
            and rt.held_first + rt.held <= rt.num_experts
            and 1 <= rt.topk_group <= rt.n_group
            and (rt.n_group == 1 or rt.scoring == "sigmoid")
            and rt.num_experts % rt.n_group == 0
            and rt.top_k <= rt.topk_group * (rt.num_experts // rt.n_group)))
    ops = blk.ops or ()
    needs = all(need(blk, ops) for need in _OPERATOR_NEEDS.values()) \
        and all(o in OPERATORS for o in ops) \
        and all(op in ops and factor > 0
                for op, _, factor in blk.rope_by_op or ()) \
        and (layers is None or not ops or len(ops) == layers)
    if blk.attention == "latent":
        # one latent spec for every layer, or latent operators BY LAYER
        # (``LATENT_OPERATORS``), each with a spec, a head count and
        # rotary parameters of its own, and delta-rule layers beside
        # them (``LATENT_STATE_OPERATORS``)
        ok = common and blk.norm == "rmsnorm" \
            and blk.residual == "sequential" and blk.positions == "rope" \
            and blk.latent is not None and blk.ffns is None \
            and blk.ssm is None and blk.mup is None and not blk.head_dim \
            and blk.retention is None and needs and not blk.attn_gate \
            and all(inv != "none" for _, inv, _ in blk.rope_by_op or ()) \
            and (bool(ops) or (
                not blk.window and blk.rope_by_op is None
                and blk.latent_by_op is None
                and blk.latent.index is None and not blk.latent.heads)) \
            and blk.latent.q_lora_rank >= 0
    else:
        n = max(len(ops), len(blk.ffns or ()))
        ok = common and blk.attention == "gqa" and blk.latent is None \
            and blk.norm in ("rmsnorm", "layernorm_nobias") \
            and blk.positions in ("rope", "none") \
            and not blk.bias and blk.kv_heads >= 1 and needs \
            and len({STATE_KINDS[o] for o in ops if o in STATE_KINDS}) <= 1 \
            and all(blk.op_kind(i) != "none" or blk.ffn_kind(i) != "none"
                    for i in range(n)) \
            and (blk.positions == "rope" or blk.rope_by_op is None) \
            and (not blk.attn_gate or (
                blk.residual == "sequential" and blk.mup is None)) \
            and (blk.residual == "sequential" or (
                blk.residual == "parallel"
                and set(ops) <= {"attention", "window_attention"}
                and kinds <= {"swiglu", "routed"}))
    if not ok:
        gqa_ops = [o for o in OPERATORS if o not in LATENT_OPERATORS]
        states = sorted(set(STATE_KINDS.values()))
        raise ValueError(
            f"the mixed wave runs GPT-2's block; the latent block with "
            f"rmsnorm and rope, every layer alike or a layer one operator "
            f"of {', '.join(LATENT_OPERATORS)} (an indexer on the first; "
            f"q_lora_rank 0: the query in one projection, no indexer) or "
            f"of {', '.join(LATENT_STATE_OPERATORS)} (slot state beside "
            f"the latent pool; at least one latent operator with it); or "
            f"the grouped-query block with rmsnorm or layernorm_nobias "
            f"(of the norms {', '.join(NORMS)}) and positions rope or "
            f"none, a layer one operator of {', '.join(gqa_ops)} (state "
            f"of one kind a spec, of {', '.join(states)}; "
            f"window and retention layers beside plain attention alone) "
            f"and one FFN of {', '.join(FFN_KINDS)}, not both none; "
            f"residuals {', '.join(RESIDUALS)}, parallel in the "
            f"grouped-query block where every layer is attention or "
            f"window_attention beside a swiglu or routed FFN; rotary kinds "
            f"{', '.join(ROPE_KINDS)}, or none by operator in the "
            f"grouped-query block; routers {', '.join(SCORINGS)}, over "
            f"all the experts or the topk_group best of n_group groups; "
            f"experts {', '.join(EXPERT_FORMS)}: it cannot run {blk}")


def _retention_fits(blk, ops):
    return set(ops) <= {"attention", "retention"} \
        and blk.retention.degree == 2 \
        and blk.retention.kv_heads == blk.kv_heads \
        and blk.retention.head_dim == blk.head_dim \
        and blk.head_dim % 2 == 0 and blk.mup is None


def _latent_ops_fit(blk, ops):
    by_op = dict(blk.latent_by_op or ())
    specs = {op: by_op.get(op, blk.latent)
             for op in set(ops) & set(LATENT_OPERATORS)}
    return blk.attention == "latent" \
        and set(ops) <= set(LATENT_OPERATORS + LATENT_STATE_OPERATORS) \
        and set(by_op) <= set(specs) \
        and ("window_latent_attention" in ops) == (blk.window >= 1) \
        and all(la is not None and la.heads >= 0
                and la.qk_rope_head_dim % 2 == 0
                for la in specs.values()) \
        and all(la.index is None or (
                    op == "latent_attention" and la.index.topk >= 1
                    and la.q_lora_rank > 0
                    and la.index.rope_dim % 2 == 0
                    and la.index.rope_dim <= la.index.head_dim)
                for op, la in specs.items())


# What a spec that names an operator must carry (and one that does not
# must not), asked of (spec, its ops): ``check_block_spec``'s one table.
_OPERATOR_NEEDS = {
    "conv": lambda blk, ops: "conv" not in ops or blk.conv_kernel >= 2,
    "ssm": lambda blk, ops: (blk.ssm is not None) == bool(
        {"attention+ssm", "ssm"} & set(ops)),
    "window_attention": lambda blk, ops:
        blk.attention == "latent" and "window_attention" not in ops
        or ("window_attention" in ops) == (blk.window >= 1)
        and ("window_attention" not in ops
             or set(ops) <= {"attention", "window_attention"}),
    "latent_attention": lambda blk, ops:
        not set(ops) & set(LATENT_OPERATORS) or _latent_ops_fit(blk, ops),
    "retention": lambda blk, ops:
        ("retention" in ops) == (blk.retention is not None)
        and ("retention" not in ops or _retention_fits(blk, ops)),
    "kda": lambda blk, ops:
        ("kda" in ops) == (blk.kda is not None)
        and ("kda" not in ops or (blk.kda.fits() and (
            blk.attention == "latent"
            and bool(set(ops) & set(LATENT_OPERATORS))
            or blk.attention == "gqa"
            and "attention" in ops and set(ops) <= {"attention", "kda"}))),
}


def head_dim_of(config):
    """A configuration's head size: its block spec's own key where it
    states one, else ``hidden_size / num_attention_heads``."""
    return block_spec_of(config).head_dim \
        or config.hidden_size // config.num_attention_heads


def _rms(x, scale, eps):
    """RMSNorm, statistics in f32 (as ``_ln``)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(blk, params, prefix, x):
    """The block's norm over ``x`` with the leaves ``{prefix}_scale``
    (and ``_bias`` for LayerNorm; "layernorm_nobias" has the scale
    alone and ``blk.norm_eps``)."""
    if blk.norm == "rmsnorm":
        return _rms(x, params[f"{prefix}_scale"], blk.norm_eps)
    if blk.norm == "layernorm_nobias":
        return _ln(x, params[f"{prefix}_scale"], None, blk.norm_eps)
    return _ln(x, params[f"{prefix}_scale"], params[f"{prefix}_bias"])


def _rope(x, posns, theta, inv=None, factor=1.0):
    """Rotate-half RoPE over the whole last axis of ``x`` [B, Q, ..., d]
    at positions ``posns`` [B, Q]: pairs (j, j + d/2), frequency
    ``theta ** (-2j/d)``, computed in f32.  With ``inv`` (``d / 2``
    floats: ``BlockSpec.rope_of``) those are the frequencies, and
    ``cos`` and ``sin`` are both multiplied by ``factor``."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        inv = jnp.asarray(inv, jnp.float32)
    ang = posns.astype(jnp.float32)[..., None] * inv        # [B, Q, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down, mup=None):
    """``(silu(x W_gate) * x W_up) W_down``; under multipliers
    ``(silu(mlp_gate * x W_gate) * x W_up) W_down * mlp_down``."""
    if mup is None:
        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
    gate = jax.nn.silu((x @ w_gate) * mup.mlp_gate)
    return ((gate * (x @ w_up)) @ w_down) * mup.mlp_down


def _moe_of(cfg_tuple):
    """The cfg_tuple's optional sixth element: a ``MoESpec`` routing
    descriptor, or None for a dense GPT (every pre-MoE tuple)."""
    extra = cfg_tuple[5] if len(cfg_tuple) > 5 else None
    return None if isinstance(extra, BlockSpec) else extra


def _moe_active(cfg_tuple):
    """True when this core ROUTES (and therefore reports per-expert
    load/drop stats): a MoE spec that is not the routing-skipping
    draft."""
    moe = _moe_of(cfg_tuple)
    return moe is not None and not moe.draft


def _strip_moe(out, cfg_tuple):
    """Drop the trailing (load, drop, tokens) stats element the serve
    wrappers append under an active MoE cfg_tuple — the offline
    callers (``_generate_spec``) discard routing telemetry."""
    return out[:-1] if _moe_active(cfg_tuple) else out


def _moe_stats_out(stats, moe, tokens):
    """The serve wrappers' trailing return element: (load [E] int32,
    drop [E] int32, routed-token count scalar int32) summed over every
    MoE layer of the call."""
    z = jnp.zeros((moe.num_experts,), jnp.int32)
    return (jnp.asarray(stats.get("load", z), jnp.int32),
            jnp.asarray(stats.get("drop", z), jnp.int32),
            jnp.asarray(tokens, jnp.int32))


def _ffn_block(params, us, h, i, moe=None, valid=None, stats=None):
    """The FFN sublayer every core shares: LN2 then dense
    wi→gelu→wo normally; on a MoE block (``moe`` set and layer ``i``
    routed), the top-k expert dispatch of ``moe_decode.moe_ffn`` over
    ALL of the call's token positions flattened (capacity is per
    dispatch, matching training's per-batch capacity); a draft spec
    returns ``h`` untouched (attention-only block).  ``valid`` (bool,
    h's leading shape) masks pad/dead positions out of routing so they
    never compete for expert capacity; ``stats`` accumulates the
    per-expert load/drop counts."""
    if moe is not None and moe.is_moe_layer(i):
        if moe.draft:
            return h
        from .moe_decode import moe_ffn
        x = _ln(h, params[f"{us}_ln2_scale"], params[f"{us}_ln2_bias"])
        shp = x.shape
        xf = x.reshape(-1, shp[-1])
        vf = None if valid is None else jnp.broadcast_to(
            valid, shp[:-1]).reshape(-1)
        y = moe_ffn(params, us, xf, moe, valid=vf, stats=stats)
        return h + y.reshape(shp)
    x = _ln(h, params[f"{us}_ln2_scale"], params[f"{us}_ln2_bias"])
    f = _gelu_tanh(x @ params[f"{us}_ffn_wi_weight"]
                   + params[f"{us}_ffn_wi_bias"])
    f = f @ params[f"{us}_ffn_wo_weight"] + params[f"{us}_ffn_wo_bias"]
    return h + f


def _decode_step(params, cfg_tuple, cache_k, cache_v, pos, token,
                 moe_stats=None):
    """One incremental position over a CONTIGUOUS cache ``[L, B, S_max,
    H, Dh]``: token [B] int32 at position ``pos``.  Returns (logits
    [B, V], new cache_k, new cache_v).

    ``pos`` is a scalar (offline scan: the whole batch sits at one
    position) OR an int32 [B] vector (the draft: every slot proposes at
    its own filled length).  Scalar positions keep the contiguous
    dynamic_update_slice write; vector positions scatter one row per
    slot and mask attention per slot.

    ``moe_stats`` (dict) accumulates per-expert load/drop across the
    MoE layers; a dense cfg_tuple ignores it.  The serving engine's
    wave, paged pool included, is ``_mixed_step``."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe = _moe_of(cfg_tuple)
    B = token.shape[0]
    hdim = H * Dh
    per_slot = jnp.ndim(pos) > 0
    h = params[f"{name}_wte_table"][token] + params[f"{name}_wpe"][pos]

    if per_slot:
        live = jnp.arange(S_max)[None, None, :] <= pos[:, None, None]
        bidx = jnp.arange(B)
    else:
        live = (jnp.arange(S_max) <= pos)[None, None, :]   # [1,1,S]
    for i in range(L):
        us = f"{name}_h{i}"
        x = _ln(h, params[f"{us}_ln1_scale"], params[f"{us}_ln1_bias"])
        q = x @ params[f"{us}_attn_q_weight"] + params[f"{us}_attn_q_bias"]
        k = x @ params[f"{us}_attn_k_weight"] + params[f"{us}_attn_k_bias"]
        v = x @ params[f"{us}_attn_v_weight"] + params[f"{us}_attn_v_bias"]
        q = q.reshape(B, H, Dh)
        k = k.reshape(B, H, Dh)
        v = v.reshape(B, H, Dh)
        # write this position's k/v into the cache (quantized caches
        # encode payload + per-(position, head) scales in one helper)
        if per_slot:
            cache_k = _kv_scatter(cache_k, (i, bidx, pos), k)
            cache_v = _kv_scatter(cache_v, (i, bidx, pos), v)
        else:
            cache_k = _kv_dus(cache_k, k, i, pos)
            cache_v = _kv_dus(cache_v, v, i, pos)
        ks, ksc = _kv_layer(cache_k, i, H, Dh)              # [B,S,H,Dh]
        vs, vsc = _kv_layer(cache_v, i, H, Dh)
        if ksc is not None:
            ks = kv_decode(ks, ksc)
            vs = kv_decode(vs, vsc)
        s = jnp.einsum("bhd,bshd->bhs", q, ks) * (Dh ** -0.5)
        s = jnp.where(live, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", p, vs).reshape(B, hdim)
        o = o @ params[f"{us}_attn_proj_weight"] \
            + params[f"{us}_attn_proj_bias"]
        h = h + o
        h = _ffn_block(params, us, h, i, moe=moe, stats=moe_stats)

    h = _ln(h, params[f"{name}_ln_f_scale"], params[f"{name}_ln_f_bias"])
    # logits in f32 regardless of compute dtype: sampling compares and
    # exponentiates them
    logits = (h @ params[f"{name}_wte_table"].T).astype(jnp.float32) \
        + params.get(f"{name}_head_bias", 0.0)
    return logits, cache_k, cache_v


def _prep_param(v, dtype=None):
    """``dtype`` on device, PRESERVING any existing placement: a
    tp_shard_params NamedSharding must survive into the scan (a
    np.asarray round-trip would gather the shards to host and re-place
    them replicated on one device, silently killing tensor-parallel
    decode).  ``dtype=None`` KEEPS the param's own dtype — bf16 params
    stay bf16, so the cache that "follows the weights" actually does
    (the old f32 default silently upcast bf16 weights AND doubled the
    cache); f64 numpy inputs still land as f32 via jax's default dtype
    canonicalization."""
    if isinstance(v, jax.Array):
        return v if dtype is None or v.dtype == dtype else v.astype(dtype)
    return jnp.asarray(np.asarray(v), dtype)


def _sample(logits, temperature, top_k, key):
    """``temperature`` is a TRACED scalar (0 = greedy, selected inside
    the program — no recompile per setting); ``top_k`` is static (XLA's
    top_k needs a static k; a handful of k settings is a handful of
    compiles)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t_safe = jnp.maximum(temperature, 1e-6)
    scaled = logits / t_safe
    if top_k:
        kth = jax.lax.top_k(scaled, int(top_k))[0][:, -1:]   # O(V)
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    sampled = jax.random.categorical(key, scaled,
                                     axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _sample_slot(logits, temperature, top_k, key):
    """Per-slot sampling with temperature AND top_k TRACED (unlike the
    offline ``_sample``, whose static top_k would force one compile per
    distinct request setting — a serving batch mixes settings freely).
    The kth-largest threshold comes from a full sort of the vocabulary,
    O(V log V), taken whether or not the slot uses it (greedy, top_k=0):
    about 1 ms for sixteen slots at V = 50257 on a v5e (PERF.md), so
    callers sample only the rows a request reads (``_spec_sample``
    takes a slot's window, never a padded q-block).  top_k=0 disables
    the mask."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    t_safe = jnp.maximum(temperature, 1e-6)
    scaled = logits / t_safe
    desc = -jnp.sort(-scaled)
    kth = desc[jnp.clip(top_k - 1, 0, logits.shape[-1] - 1)]
    masked = jnp.where((top_k > 0) & (scaled < kth), NEG_INF, scaled)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _sample_slot_unmasked(logits, temperature, key):
    """``_sample_slot`` for a slot that asks for no top_k mask (greedy,
    or top_k 0): the same token from the same key, without the sort."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@functools.partial(jax.jit,
                   static_argnames=("cfg_tuple", "top_k", "use_eos"))
def _generate_scan(params, cfg_tuple, prompt_padded, prompt_len,
                   temperature, top_k, rng, eos_id=0, pad_id=0,
                   use_eos=False):
    """The whole generation as one scan over ALL S_max-1 positions: at
    positions < prompt_len the next input token is the PROMPT's
    (teacher forcing); beyond it, the sampled one.  Scanning to the
    static S_max (rather than the request's length) keeps prompt length
    and num_tokens TRACED — one compile serves every request shape at
    this (batch, S_max); the host slices the requested span after.

    With ``use_eos`` (static: the default program is unchanged), a
    sequence that samples ``eos_id`` past its prompt emits the EOS and
    then pads with ``pad_id``; once EVERY row is done the per-step body
    is skipped via lax.cond — a runtime short-circuit inside the single
    compiled scan."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    B = prompt_padded.shape[0]
    # cache dtype follows the weights: bf16 decode halves the KV cache
    # and runs the matmuls on the fast MXU path
    cdtype = params[f"{name}_wte_table"].dtype
    cache_k = jnp.zeros((L, B, S_max, H, Dh), cdtype)
    cache_v = jnp.zeros((L, B, S_max, H, Dh), cdtype)

    def step(carry, t):
        def live_step(carry):
            cache_k, cache_v, token, rng, done = carry
            logits, cache_k, cache_v = _decode_step(
                params, cfg_tuple, cache_k, cache_v, t, token)
            rng, sub = jax.random.split(rng)
            sampled = _sample(logits, temperature, top_k, sub)
            # next input: prompt token while still inside the prompt;
            # pad once this row already emitted its EOS
            in_prompt = t + 1 < prompt_len
            nxt = jnp.where(
                in_prompt,
                prompt_padded[:, jnp.minimum(t + 1, S_max - 1)],
                jnp.where(done, jnp.int32(pad_id), sampled))
            if use_eos:
                done = done | (~in_prompt & (sampled == eos_id))
            return (cache_k, cache_v, nxt, rng, done), nxt

        if not use_eos:
            return live_step(carry)
        return jax.lax.cond(
            jnp.all(carry[4]),
            lambda c: (c, jnp.full((B,), pad_id, jnp.int32)),
            live_step, carry)

    first = prompt_padded[:, 0]
    done0 = jnp.zeros((B,), bool)
    _, toks = jax.lax.scan(
        step, (cache_k, cache_v, first, rng, done0), jnp.arange(S_max - 1))
    # toks[t] is the input token for position t+1
    return jnp.concatenate([first[:, None], toks.T], axis=1)


# --------------------------- flash prefill --------------------------- #


def _prefill_forward(params, cfg_tuple, tokens, kv_lens,
                     row_valid=None, moe_stats=None):
    """ONE full-prompt forward over a bucket-padded token block: every
    layer's K/V for all positions in one batched pass — the MXU sees
    [P, D] matmuls instead of P sequential launches of [1, D], and
    attention is the Pallas flash kernel (causal + kv_lens, so blocks
    wholly past a row's prompt length skip compute AND DMA).

    tokens: [N, P_b] int32 (positions >= kv_lens[n] are pad — their
    K/V are deterministic garbage the decode mask never admits before
    overwrite); kv_lens: [N] int32.  Returns (logits [N, V] f32 at each
    row's prompt_len-1, ks, vs [L, N, P_b, H, Dh]).

    ``row_valid`` ([N] bool) marks REAL rows: the engine pads a group
    to a pow2 N by replicating entry 0, and while those duplicate rows'
    cache writes are order-safe no-ops, a MoE cfg must keep them (and
    every pad position) out of expert routing — they would compete for
    capacity and skew the load counters.  ``moe_stats`` as in
    ``_decode_step``."""
    from ..kernels.flash_attention import flash_attention
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe = _moe_of(cfg_tuple)
    N, P_b = tokens.shape
    hdim = H * Dh
    kv_lens = kv_lens.astype(jnp.int32)
    tok_valid = jnp.arange(P_b)[None, :] < kv_lens[:, None]  # [N, P_b]
    if row_valid is not None:
        tok_valid = tok_valid & row_valid[:, None]
    h = params[f"{name}_wte_table"][tokens] \
        + params[f"{name}_wpe"][jnp.arange(P_b)][None]
    ks, vs = [], []
    for i in range(L):
        us = f"{name}_h{i}"
        x = _ln(h, params[f"{us}_ln1_scale"], params[f"{us}_ln1_bias"])
        q = (x @ params[f"{us}_attn_q_weight"]
             + params[f"{us}_attn_q_bias"]).reshape(N, P_b, H, Dh)
        k = (x @ params[f"{us}_attn_k_weight"]
             + params[f"{us}_attn_k_bias"]).reshape(N, P_b, H, Dh)
        v = (x @ params[f"{us}_attn_v_weight"]
             + params[f"{us}_attn_v_bias"]).reshape(N, P_b, H, Dh)
        o = flash_attention(q, k, v, causal=True, kv_lens=kv_lens)
        o = o.reshape(N, P_b, hdim) @ params[f"{us}_attn_proj_weight"] \
            + params[f"{us}_attn_proj_bias"]
        h = h + o
        h = _ffn_block(params, us, h, i, moe=moe, valid=tok_valid,
                       stats=moe_stats)
        ks.append(k)
        vs.append(v)
    h = _ln(h, params[f"{name}_ln_f_scale"], params[f"{name}_ln_f_bias"])
    last = h[jnp.arange(N), jnp.maximum(kv_lens - 1, 0)]     # [N, hdim]
    logits = (last @ params[f"{name}_wte_table"].T).astype(jnp.float32) \
        + params.get(f"{name}_head_bias", 0.0)
    return logits, jnp.stack(ks), jnp.stack(vs)


@functools.partial(jax.jit,
                   static_argnames=("cfg_tuple", "top_k", "use_eos"))
def _generate_flash(params, cfg_tuple, prompt_bucket, prompt_len,
                    temperature, top_k, rng, eos_id=0, pad_id=0,
                    use_eos=False):
    """``_generate_scan``'s fast-prefill twin: the prompt phase is ONE
    batched ``_prefill_forward`` pass (cache positions 0..P_b-1 filled
    via dynamic_update_slice, first token sampled from the logits at
    prompt_len-1), and the scan runs DECODE-ONLY steps — positions
    inside the prompt are skipped with lax.cond instead of
    teacher-forced one token at a time.  Compiles per (B, S_max, P_b)
    with P_b pow2-bucketed by the caller; greedy outputs match the
    teacher-forced scan (same per-position arithmetic, batched).

    Returns (first_gen [B] — the token at position prompt_len — and
    toks [B, S_max-1] where toks[:, t] is the token at position t+1,
    junk for t < prompt_len; the caller overlays the prompt)."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    B, P_b = prompt_bucket.shape
    cdtype = params[f"{name}_wte_table"].dtype
    logits, ks, vs = _prefill_forward(
        params, cfg_tuple, prompt_bucket,
        jnp.broadcast_to(prompt_len, (B,)))
    cache_k = jax.lax.dynamic_update_slice(
        jnp.zeros((L, B, S_max, H, Dh), cdtype), ks.astype(cdtype),
        (0, 0, 0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        jnp.zeros((L, B, S_max, H, Dh), cdtype), vs.astype(cdtype),
        (0, 0, 0, 0, 0))
    rng, sub = jax.random.split(rng)
    first_gen = _sample(logits, temperature, top_k, sub)
    done0 = (first_gen == eos_id) if use_eos else jnp.zeros((B,), bool)

    def step(carry, t):
        def live_step(carry):
            cache_k, cache_v, token, rng, done = carry
            logits, cache_k, cache_v = _decode_step(
                params, cfg_tuple, cache_k, cache_v, t, token)
            rng, sub = jax.random.split(rng)
            sampled = _sample(logits, temperature, top_k, sub)
            nxt = jnp.where(done, jnp.int32(pad_id), sampled)
            if use_eos:
                done = done | (sampled == eos_id)
            return (cache_k, cache_v, nxt, rng, done), nxt

        skip = t < prompt_len
        if use_eos:
            skip = skip | jnp.all(carry[4])
        return jax.lax.cond(
            skip, lambda c: (c, jnp.full((B,), pad_id, jnp.int32)),
            live_step, carry)

    _, toks = jax.lax.scan(
        step, (cache_k, cache_v, first_gen, rng, done0),
        jnp.arange(S_max - 1))
    return first_gen, toks.T


# ------------------------- serving entry points ------------------------- #
#
# What the serving engine and offline speculation run beside the mixed
# wave further down: a teacher-forced prefill of one sequence into its
# cache slot (the engine's draft), a batched flash prefill and a batched
# verify (``_generate_spec``).  Host code owns the tiny scheduling state
# (positions, tokens, rng keys as numpy); the device owns only the big
# cache pair, which threads through each call.


def _serve_prefill(params, cfg_tuple, cache_k, cache_v, slot, prompt,
                   prompt_len, temperature, top_k, rng_key):
    """Teacher-forced prefill of ONE sequence into cache row ``slot``:
    scan the (bucket-padded) prompt writing each position's K/V, then
    sample the first generated token from the logits at prompt_len-1.
    Positions at or past prompt_len are skipped via lax.cond (the
    bucket's padded tail costs no compute); recompiles once per prompt-
    length BUCKET, not per length.  Returns (first_token, cache_k,
    cache_v, new_rng_key[, moe stats] — the trailing (load, drop,
    tokens) element appears only under an active MoE cfg_tuple)."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe_on = _moe_active(cfg_tuple)
    P_b = prompt.shape[0]
    V = params[f"{name}_wte_table"].shape[0]
    ck = _kv_slot_slice(cache_k, slot, (L, 1, S_max, H, Dh))
    cv = _kv_slot_slice(cache_v, slot, (L, 1, S_max, H, Dh))
    if moe_on:
        E = _moe_of(cfg_tuple).num_experts
        st0 = (jnp.zeros((E,), jnp.int32), jnp.zeros((E,), jnp.int32),
               jnp.int32(0))

    def step(carry, t):
        def live(carry):
            if moe_on:
                ck, cv, last, st = carry
                sd = {}
                logits, ck, cv = _decode_step(
                    params, cfg_tuple, ck, cv, t, prompt[t][None],
                    moe_stats=sd)
                st = (st[0] + sd["load"], st[1] + sd["drop"],
                      st[2] + 1)
                last = jnp.where(t == prompt_len - 1, logits[0], last)
                return ck, cv, last, st
            ck, cv, last = carry
            logits, ck, cv = _decode_step(
                params, cfg_tuple, ck, cv, t, prompt[t][None])
            last = jnp.where(t == prompt_len - 1, logits[0], last)
            return ck, cv, last
        return jax.lax.cond(t < prompt_len, live, lambda c: c, carry), None

    carry0 = (ck, cv, jnp.zeros((V,), jnp.float32))
    if moe_on:
        carry0 = carry0 + (st0,)
    carry, _ = jax.lax.scan(step, carry0, jnp.arange(P_b))
    ck, cv, last = carry[:3]
    cache_k = _kv_slot_update(cache_k, ck, slot)
    cache_v = _kv_slot_update(cache_v, cv, slot)
    rng_key, sub = jax.random.split(rng_key)
    first = _sample_slot(last, temperature, top_k, sub)
    out = (first, cache_k, cache_v, rng_key)
    if moe_on:
        out = out + (carry[3],)
    return out


def _serve_prefill_batch(params, cfg_tuple, cache_k, cache_v, slots,
                         prompts, prompt_lens, temperature, top_k,
                         rng_keys, row_valid=None):
    """Flash prefill of a BUCKETED GROUP of admissions in one dispatch:
    ``_prefill_forward`` computes every layer's K/V for all N prompts
    at once, the rows scatter into their cache slots, and each request
    samples its first token from its own rng stream.  slots [N] int32;
    prompts [N, P_b]; prompt_lens/temperature/top_k [N]; rng_keys
    [N, 2].  The engine pads a group to a pow2 N by REPLICATING entry 0
    (duplicate scatter indices write identical values, so the pad rows
    are order-safe no-ops).  ``row_valid`` [N] bool marks the REAL
    rows (MoE routing exclusion — see ``_prefill_forward``).  Returns
    (first_tokens [N], cache_k, cache_v, new_rng_keys[, moe stats])."""
    N, P_b = prompts.shape
    moe_on = _moe_active(cfg_tuple)
    sd = {} if moe_on else None
    logits, ks, vs = _prefill_forward(params, cfg_tuple, prompts,
                                      prompt_lens, row_valid=row_valid,
                                      moe_stats=sd)
    cache_k = _kv_scatter(cache_k,
                          (slice(None), slots, slice(0, P_b)), ks)
    cache_v = _kv_scatter(cache_v,
                          (slice(None), slots, slice(0, P_b)), vs)
    splits = jax.vmap(jax.random.split)(rng_keys)          # [N,2,2]
    new_keys, subs = splits[:, 0], splits[:, 1]
    first = jax.vmap(_sample_slot)(logits, temperature, top_k, subs)
    out = (first, cache_k, cache_v, new_keys)
    if moe_on:
        lens = jnp.clip(prompt_lens, 0, P_b)
        if row_valid is not None:
            lens = jnp.where(row_valid, lens, 0)
        out = out + (_moe_stats_out(sd, _moe_of(cfg_tuple),
                                    jnp.sum(lens)),)
    return out


# ---------------------- speculative decoding ---------------------- #
#
# Draft-propose / batched-verify (ISSUE 10): a truncated-layer DRAFT —
# the target's first ``L_draft`` blocks plus the shared final LN and
# tied embedding head, i.e. the same param dict under a shorter
# cfg_tuple — proposes ``k`` greedy tokens per slot inside ONE scanned
# dispatch (``_spec_propose``), and the target scores all ``k+1``
# positions in ONE batched step (``_verify_step``: the teacher-forced
# forward over a per-slot ragged q-block, causal inside the block).
# Longest-prefix acceptance plus the bonus token keeps outputs
# TOKEN-IDENTICAL to the non-speculative path — greedy trivially, and
# sampled too, because every emitted token is the target's OWN
# sequential sample: position j consumes the j-th split of the
# request's rng stream (``_spec_sample`` returns the key after every
# split so the host can resume the stream at exactly the accepted
# count), and the logits at the first mismatch are conditioned on an
# all-accepted prefix, so the "bonus" sample is the true next token.


def _verify_step(params, cfg_tuple, cache_k, cache_v, pos, tokens,
                 q_len, moe_stats=None):
    """Multi-position verify: slot b consumes ``tokens[b, :q_len[b]]``
    at positions ``pos[b] .. pos[b]+q_len[b]-1`` in ONE batched step.
    Returns (logits [B, Q, V] f32, new cache_k, new cache_v) — row
    ``logits[b, j]`` is the next-token distribution after input j,
    exactly what ``j+1`` sequential ``_decode_step`` calls would yield
    (each query attends to the whole written prefix INCLUDING the
    q-block's own causal positions).

    Dead positions (``j >= q_len[b]``) are written at their natural
    ``pos+j`` slots of the contiguous cache — beyond the slot's live
    length, never admitted by a mask, overwritten before use — with the
    writes issued LAST-LIVE-WINS (descending j), so a dead tail clipped
    to ``S_max-1`` can never clobber a live boundary write."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe = _moe_of(cfg_tuple)
    B, Q = tokens.shape
    hdim = H * Dh
    bidx = jnp.arange(B)
    posns = pos[:, None] + jnp.arange(Q)[None, :]          # [B, Q]
    valid = jnp.arange(Q)[None, :] < q_len[:, None]        # [B, Q]
    wpe = params[f"{name}_wpe"]
    h = params[f"{name}_wte_table"][tokens] \
        + wpe[jnp.clip(posns, 0, wpe.shape[0] - 1)]        # [B, Q, hd]
    ctx = jnp.arange(S_max)[None, None, :]
    live = ctx <= posns[:, :, None]                        # [B, Q, S]
    for i in range(L):
        us = f"{name}_h{i}"
        x = _ln(h, params[f"{us}_ln1_scale"], params[f"{us}_ln1_bias"])
        q = (x @ params[f"{us}_attn_q_weight"]
             + params[f"{us}_attn_q_bias"]).reshape(B, Q, H, Dh)
        k = (x @ params[f"{us}_attn_k_weight"]
             + params[f"{us}_attn_k_bias"]).reshape(B, Q, H, Dh)
        v = (x @ params[f"{us}_attn_v_weight"]
             + params[f"{us}_attn_v_bias"]).reshape(B, Q, H, Dh)
        # descending j so the (clipped) dead tail is written FIRST and
        # any live boundary write lands last and wins
        for jq in reversed(range(Q)):
            pw = jnp.minimum(posns[:, jq], S_max - 1)
            cache_k = _kv_scatter(cache_k, (i, bidx, pw), k[:, jq])
            cache_v = _kv_scatter(cache_v, (i, bidx, pw), v[:, jq])
        ks, ksc = _kv_layer(cache_k, i, H, Dh)
        vs, vsc = _kv_layer(cache_v, i, H, Dh)
        if ksc is not None:
            ks = kv_decode(ks, ksc)
            vs = kv_decode(vs, vsc)
        s = jnp.einsum("bqhd,bshd->bqhs", q, ks) * (Dh ** -0.5)
        s = jnp.where(live[:, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqhs,bshd->bqhd", p, vs).reshape(B, Q, hdim)
        o = o @ params[f"{us}_attn_proj_weight"] \
            + params[f"{us}_attn_proj_bias"]
        h = h + o
        h = _ffn_block(params, us, h, i, moe=moe, valid=valid,
                       stats=moe_stats)
    h = _ln(h, params[f"{name}_ln_f_scale"], params[f"{name}_ln_f_bias"])
    logits = (h @ params[f"{name}_wte_table"].T).astype(jnp.float32) \
        + params.get(f"{name}_head_bias", 0.0)
    return logits, cache_k, cache_v


@jax.named_scope("sample")
def _spec_sample(logits, temperature, top_k, rng_keys, count):
    """Sequential sampling over each slot's SAMPLING WINDOW: ``logits``
    [B, W, V] holds the window's rows only (``_window_logits`` gathers
    them; a verify q-block is its own window) and ``count`` [B] says how
    many of them are live.  Row w's token comes from the (w+1)-th split
    of the slot's rng stream — EXACTLY the splits w+1 non-speculative
    steps would consume — and ``keys_after[b, w]`` is the stream after
    those splits, so the host resumes at the accepted count and the
    stream stays aligned with the non-speculative path token for token.

    Slot b splits its stream once for each row ``w < count[b]`` and at
    no other: 1 for a decode slot and for a prompt's FINAL chunk (one
    split a prompt, as ``_serve_prefill`` and ``generate_fast`` make),
    up to k+1 for spec-verify, 0 for a mid-prompt chunk and a
    dead slot (the returned keys equal the input and the host carries
    the stream forward untouched).  Rows at or past ``count`` return a
    sample nobody reads; there are at most W - 1 of them a slot, where W
    is 1 on an engine that does not speculate.

    Returns (sampled [B, W], keys_after [B, W, 2])."""
    def row(keys, w):
        splits = jax.vmap(jax.random.split)(keys)          # [B,2,2]
        keys = jnp.where((w < count)[:, None], splits[:, 0], keys)
        row_logits = jax.lax.dynamic_index_in_dim(logits, w, 1,
                                                  keepdims=False)
        # the kth-largest threshold is a sort of the vocabulary a slot
        # (7.3 of a 17 ms decode wave at 32 x 154,880: PERF.md section
        # 6, PR 28); it is taken only when a live row of this step
        # samples with a top_k.  One branch for the whole step, outside
        # the vmap: a cond a slot would become a select that runs both
        tok = jax.lax.cond(
            jnp.any((top_k > 0) & (temperature > 0.0) & (w < count)),
            lambda: jax.vmap(_sample_slot)(row_logits, temperature, top_k,
                                           splits[:, 1]),
            lambda: jax.vmap(_sample_slot_unmasked)(row_logits, temperature,
                                                    splits[:, 1]))
        return keys, (tok, keys)

    # a scan, not a Python loop: every row sorts the vocabulary once a
    # slot, and unrolled sorts compile slowly
    _, (toks, after) = jax.lax.scan(row, rng_keys,
                                    jnp.arange(logits.shape[1]))
    return jnp.swapaxes(toks, 0, 1), jnp.swapaxes(after, 0, 1)


def _serve_verify(params, cfg_tuple, cache_k, cache_v, pos, tokens,
                  q_len, temperature, top_k, rng_keys):
    """One fused VERIFY wave over all slots (contiguous layout): write
    + score the q-block, then sample every position from each slot's
    own rng stream.  Returns (sampled [B, Q], cache_k, cache_v,
    keys_after [B, Q, 2][, moe stats])."""
    moe_on = _moe_active(cfg_tuple)
    sd = {} if moe_on else None
    logits, cache_k, cache_v = _verify_step(
        params, cfg_tuple, cache_k, cache_v, pos, tokens, q_len,
        moe_stats=sd)
    sampled, after = _spec_sample(logits, temperature, top_k, rng_keys,
                                  q_len)
    out = (sampled, cache_k, cache_v, after)
    if moe_on:
        out = out + (_moe_stats_out(
            sd, _moe_of(cfg_tuple),
            jnp.sum(jnp.clip(q_len, 0, tokens.shape[1]))),)
    return out


def _spec_propose(params, cfg_tuple, cache_k, cache_v, pos, token, k):
    """``k`` greedy draft steps inside ONE dispatch: a lax.scan over
    the (truncated-layer) draft's ``_decode_step``, each step feeding
    its own argmax forward — one jitted call per wave instead of k
    sequential dispatches, which is what makes drafting cheap enough
    to pay for itself even off-chip.  The draft always proposes
    greedily (no rng): acceptance, not sampling fidelity, is its job —
    the target's verify pass owns the actual sampling.  Returns
    (draft_tokens [B, k], cache_k, cache_v).

    The scan runs k+1 steps and DISCARDS the last proposal: step k
    exists to write the k-th draft token's OWN K/V into the draft
    cache, so that after a fully-accepted wave (all k drafts kept) the
    draft's history has no hole at position pos+k — without it the
    next wave's proposals attend over stale garbage there and the
    acceptance rate quietly collapses."""
    def step(carry, _):
        ck, cv, tok, p = carry
        logits, ck, cv = _decode_step(params, cfg_tuple, ck, cv, p, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (ck, cv, nxt, p + 1), nxt

    (cache_k, cache_v, _, _), toks = jax.lax.scan(
        step, (cache_k, cache_v, token, pos.astype(jnp.int32)), None,
        length=k + 1)
    return jnp.swapaxes(toks, 0, 1)[:, :k], cache_k, cache_v


# --- the mixed ragged wave (ISSUE 18) ------------------------------ #
# ONE jitted core for the engine's whole hot loop.  The mixed step
# consumes a RAGGED WAVE DESCRIPTOR — per-slot q_len + a token block —
# in which a decode stream is a q-block of 1, a spec-verify wave k+1,
# and a prompt (or prompt chunk) its chunk width, all scored by one
# dispatch.  ``_verify_step`` is this computation for the uniform-mode
# case over a contiguous cache; ``_mixed_step`` runs over the paged
# pool and adds the block spec and per-slot SELF-FRESHNESS (see below).
# Greedy outputs are token-identical to offline ``generate_fast``
# across float/int8/spec/chunked configs.


def _window_logits(params, name, h, first_row, window, blk=GPT2_BLOCK,
                   rows=None):
    """The head over each slot's sampling window ALONE: rows
    ``first_row[b] + w`` (``w < window``, clipped to the q-block) of the
    last block's output ``h`` [B, Q, hd] are gathered FIRST, then the
    final LN and the tied head run over ``[B, window, hd]``.  Returns
    logits [B, window, V] f32.  The padded q-block never meets the
    vocabulary: at 16 slots x 256 rows x 50257 that was 0.82 GB of f32
    and 255 of every 256 rows were read by nobody.  Of a packed wave
    (``rows``, ``h`` [1, R, hd]) the window is gathered straight from
    the packed rows, ``rows.start[b]`` further on."""
    with jax.named_scope("lm_head"):
        Q = h.shape[1] if rows is None else rows.q
        at = jnp.clip(first_row[:, None] + jnp.arange(window)[None, :],
                      0, Q - 1)                            # [B, W]
        if rows is None:
            hw = jnp.take_along_axis(h, at[:, :, None], axis=1)
        else:
            hw = h[0][jnp.minimum(rows.start[:, None] + at,
                                  h.shape[1] - 1)]
        hw = _norm(blk, params, f"{name}_ln_f", hw)
        if blk.head == "untied":
            logits = (hw @ params[f"{name}_lm_head_weight"]
                      ).astype(jnp.float32)
            return logits if blk.mup is None \
                else logits * blk.mup.lm_head
        logits = (hw @ params[f"{name}_wte_table"].T
                  ).astype(jnp.float32) \
            + params.get(f"{name}_head_bias", 0.0)
        # a tied head's ``logit_scale``, where the spec has one
        return logits if blk.mup is None or blk.mup.lm_head == 1.0 \
            else logits * blk.mup.lm_head


def _latent_attention(params, us, blk, H, h, pool, i, wblk, woff, posns,
                      live, lens, q_len, block_tables, attn, rows=None,
                      layer=0, index_pool=None):
    """One layer's multi-head latent attention over the paged LATENT
    pool ``[L, N_blocks, block, LatentSpec.row_width]``, every row of
    the wave in the ABSORBED form: ``q_nope`` is carried into latent space
    through ``W_uk`` (``W_kvb``'s key half, split here in the step, not
    at load), so a cached row ``[c_kv | k_r]`` is key and, in its first
    ``kv_lora_rank`` columns, value, for all ``H`` heads at once; the
    output leaves latent space through ``W_uv``.  The wave's own rows
    are written first and read back from the pool (one path for chunk
    and decode rows).  Of a packed wave (``rows``; ``h``, ``wblk``,
    ``woff`` and ``posns`` [1, R, ..]) the rows are written as they
    lie and the kernel scores them as they lie
    (``ragged_paged_mla_rows``, handed the layout's slot starts: no
    ``[B, Q]`` block of the query or of the result exists); the masked
    path alone unpacks the query for its scores and packs the result
    back.  Returns (h + attention, pool, index_pool).

    ``layer`` is the layer's number (``i`` its place in ITS pool): of a
    spec with latent operators BY LAYER it takes the layer's own
    ``LatentSpec`` (``blk.latent_of``: sizes, head count, the rescale
    of the two low-rank norms, the head-wise gate under ``mla_gate``)
    and its operator's rotary parameters (``blk.rope_of``).  A
    "window_latent_attention" layer is handed the latent RING as
    ``pool`` with its table, write blocks and band (``live``), and the
    kernels' ``window``.  A layer whose spec has an indexer
    (``IndexSpec``) writes an index key a row into ``index_pool``
    (``mla_index``, ``index_write``), scores every row's index queries
    against the keys its slot has in sight (``index_score``:
    ``index_decode.index_select``), takes the ``topk`` largest
    (``index_topk``: ``chosen_mask``) and attends over those rows of
    the pool alone, a walk of its slots' pages under the chosen rows'
    mask (``sparse_mla`` inside ``attention``:
    ``ragged_paged_mla_rows(allowed=)``).  A spec without latent
    operators traces nothing of this.  A ``LatentSpec`` whose
    ``q_lora_rank`` is 0 makes its query in ONE projection
    (``{us}_attn_q_weight``), with no query norm."""
    la = blk.latent_of(layer)
    inv, factor = blk.rope_of(layer)
    H = la.heads or H
    window = blk.window \
        if blk.op_kind(layer) == "window_latent_attention" else 0
    B, Q, _ = h.shape
    dn, dr, dv, dc = (la.qk_nope_head_dim, la.qk_rope_head_dim,
                      la.v_head_dim, la.kv_lora_rank)
    with jax.named_scope("mla_qkv"):
        x = _norm(blk, params, f"{us}_ln1", h)
        if la.q_lora_rank:
            cq = _rms(x @ params[f"{us}_attn_q_a_weight"],
                      params[f"{us}_attn_q_a_norm_scale"], blk.norm_eps)
            if la.rescale:
                cq = cq * jnp.asarray(
                    (h.shape[-1] / la.q_lora_rank) ** 0.5, cq.dtype)
            q = cq @ params[f"{us}_attn_q_b_weight"]
        else:
            # no low-rank step: one projection, no query norm
            q = x @ params[f"{us}_attn_q_weight"]
        q = q.reshape(B, Q, H, dn + dr)
        kva = x @ params[f"{us}_attn_kv_a_weight"]          # [B, Q, dc+dr]
        ckv = _rms(kva[..., :dc], params[f"{us}_attn_kv_a_norm_scale"],
                   blk.norm_eps)
        if la.rescale:
            ckv = ckv * jnp.asarray((h.shape[-1] / dc) ** 0.5, ckv.dtype)
        k_r = _rope(kva[..., dc:], posns, blk.rope_theta, inv, factor)
        q_rope = _rope(q[..., dn:], posns, blk.rope_theta, inv, factor)
        # the pool's rows are padded to the lane tile with zeros, and
        # so is the query: the pad adds nothing to a score
        pad = pool.shape[-1] - dc - dr
        row = jnp.concatenate(
            [ckv, k_r, jnp.zeros((B, Q, pad), ckv.dtype)], axis=-1)
    w_kvb = params[f"{us}_attn_kv_b_weight"].reshape(dc, H, dn + dv)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bqhn,chn->bqhc", q[..., :dn],
                           w_kvb[:, :, :dn])
        qf = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((B, Q, H, pad), q_lat.dtype)],
            axis=-1)                                        # [B, Q, H, W]
    with jax.named_scope("kv_write"):
        pool = pool.at[i, wblk, woff].set(row.astype(pool.dtype))
    scale = (dn + dr) ** -0.5
    allowed = None
    if la.index is not None:
        from . import index_decode
        select, index_pool = index_decode.index_select(
            params, us, blk, la, x, cq, index_pool, i, wblk, woff, posns,
            q_len, block_tables, rows, (inv, factor))
        # a walk of the slot's pages under the chosen rows' mask (what
        # a chunk's rows chose is nearly all their slot holds); the
        # masked path's ``live`` is narrowed to it
        allowed = index_decode.chosen_mask(select, la.index.topk)
        live = live & ((allowed.reshape(B, Q, -1) if rows is None
                        else rows.unpack(allowed[None])) > 0.5)
    with jax.named_scope("attention"):
        if attn == "ragged" and allowed is not None:
            from ..kernels.ragged_attention import ragged_paged_mla_rows
            with jax.named_scope("sparse_mla"):
                o_lat = ragged_paged_mla_rows(
                    qf.reshape((B * Q,) + qf.shape[2:]), pool, lens, q_len,
                    jnp.arange(len(q_len)) * Q if rows is None
                    else rows.start, block_tables, value_width=dc,
                    scale=scale, layer=i, allowed=allowed).reshape(
                        B, Q, H, dc)
        elif attn == "ragged" and rows is not None:
            from ..kernels.ragged_attention import ragged_paged_mla_rows
            o_lat = ragged_paged_mla_rows(
                qf[0], pool, lens, q_len, rows.start, block_tables,
                value_width=dc, scale=scale, layer=i, window=window)[None]
        elif attn == "ragged":
            from ..kernels.ragged_attention import ragged_paged_mla
            o_lat = ragged_paged_mla(qf, pool, lens, q_len,
                                     block_tables, value_width=dc,
                                     scale=scale, layer=i, window=window)
        else:
            if rows is not None:
                qf = rows.unpack(qf)
            T, bs = block_tables.shape[1], pool.shape[2]
            kg = pool[i][block_tables].reshape(-1, T * bs, dc + dr + pad)
            s = jnp.einsum("bqhc,bsc->bqhs", qf, kg) * scale
            p = jax.nn.softmax(
                jnp.where(live[:, :, None, :], s, NEG_INF), axis=-1)
            o_lat = jnp.einsum("bqhs,bsc->bqhc", p, kg[..., :dc])
            if rows is not None:
                o_lat = rows.pack(o_lat)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bqhc,chv->bqhv", o_lat.astype(h.dtype),
                       w_kvb[:, :, dn:]).reshape(B, Q, H * dv)
    if la.gate:
        with jax.named_scope("mla_gate"):
            g = jax.nn.sigmoid((x @ params[f"{us}_attn_gate_weight"]
                                ).astype(jnp.float32))      # [B, Q, H]
            o = (o.reshape(B, Q, H, dv).astype(jnp.float32)
                 * g[..., None]).astype(h.dtype).reshape(B, Q, H * dv)
    with jax.named_scope("attn_out"):
        h = h + o @ params[f"{us}_attn_proj_weight"]
    return h, pool, index_pool


def _ffn_of_kind(params, us, blk, h, i, valid, stats, moe=None, x=None):
    """The FFN sublayer by ``blk.ffn_kind(i)``: GPT-2's GELU FFN (or,
    under a ``MoESpec``, its capacity-routed experts) or a dense SwiGLU
    under the scope ``mlp``, or the dropless routed FFN with its shared
    expert (``moe_decode.routed_ffn``, scopes ``moe_route``,
    ``moe_experts``, ``moe_shared``, and ``moe_latent_in`` /
    ``moe_latent_out`` where the experts work at a latent width), or, of
    a layer that is its operator alone ("none"), nothing: ``h`` as it
    came.  A parallel block hands in ``x``, the rows its one norm made:
    the FFN reads them as they are and its output ALONE is returned (the
    caller adds it to the residual beside the operator's)."""
    kind = blk.ffn_kind(i)
    if kind == "none":
        return h
    if kind == "gelu":
        with jax.named_scope("mlp"):
            return _ffn_block(params, us, h, i, moe=moe, valid=valid,
                              stats=stats)
    parallel = x is not None
    if not parallel:
        x = _norm(blk, params, f"{us}_ln2", h)
    if kind == "swiglu":
        with jax.named_scope("mlp"):
            y = swiglu(x, params[f"{us}_ffn_gate_weight"],
                       params[f"{us}_ffn_up_weight"],
                       params[f"{us}_ffn_down_weight"], blk.mup)
            return y if parallel else h + y
    from .moe_decode import routed_ffn
    shp = x.shape
    y = routed_ffn(params, us, x.reshape(-1, shp[-1]), blk.routed,
                   valid=jnp.broadcast_to(valid, shp[:-1]).reshape(-1),
                   stats=stats)
    return y.reshape(shp) if parallel else h + y.reshape(shp)


def _causal_conv(z, hist, w, q_len, rows=None, mix="conv_mix",
                 write="state_write"):
    """A depthwise causal convolution of ``K = w.shape[0]`` taps over
    the wave's rows ``z`` ([B, Q, d], or a packed wave's [1, R, d] with
    ``rows``): ``y_t = sum_j w[j] * z_{t - (K - 1) + j}``, a slot's
    history before its q-block's first row being ``hist`` [B, K - 1, d].
    Returns (y, the slot's last ``K - 1`` LIVE rows after the wave:
    ``z`` at rows ``q_len - (K - 1) .. q_len - 1``, reaching back into
    the old history where the q-block is shorter, so that a dead row
    and a dead slot leave it where it was).  A packed wave needs no
    unpacking: slot-major rows keep ``z_{t-1}``, ``z_{t-2}`` next door,
    and a row nearer than a tap's reach to its slot's first takes the
    slot's history instead.  ``mix`` / ``write`` name the two scopes."""
    K = w.shape[0]
    Q = z.shape[1]
    hist = hist.astype(z.dtype)
    if rows is None:
        with jax.named_scope(mix):
            zz = jnp.concatenate([hist, z], axis=1)
            y = sum(w[j] * zz[:, j:j + Q] for j in range(K))
        with jax.named_scope(write):
            last = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
                rows, n, K - 1, 0))(zz, q_len)              # [B, K-1, d]
        return y, last
    # ``zz`` above, a slot: index m is the history's row m under
    # K - 1 and the slot's own row m - (K - 1) from there on
    zp = z[0]                                               # [R, d]
    with jax.named_scope(mix):
        taps = []
        for j in range(K - 1):
            back = K - 1 - j
            own = jnp.pad(zp, ((back, 0), (0, 0)))[:Q]      # z_{t - back}
            old = hist[rows.slot, jnp.minimum(rows.off + j, K - 2)]
            taps.append(jnp.where((rows.off >= back)[:, None], own,
                                  old))
        taps.append(zp)
        y = sum(w[j] * taps[j] for j in range(K))[None]
    with jax.named_scope(write):
        m = q_len[:, None] + jnp.arange(K - 1)[None, :]     # [B, K-1]
        own = zp[jnp.clip(rows.start[:, None] + m - (K - 1), 0, Q - 1)]
        old = jnp.take_along_axis(
            hist, jnp.minimum(m, K - 2)[:, :, None], axis=1)
        last = jnp.where((m >= K - 1)[:, :, None], own, old)
    return y, last


def _conv_operator(params, us, blk, h, state, si, q_len, rows=None):
    """One layer's gated short convolution over the wave's ``[B, Q]``
    rows: ``[b | c | x] = u W_in`` (``u`` the norm of ``h``), ``z = b *
    x``, ``y_t = sum_j w[j] * z_{t - (K - 1) + j}`` (depthwise, causal,
    ``K = blk.conv_kernel`` taps: ``_causal_conv``), ``h + (c * y)
    W_out``.  What a sequence carries from one q-block to its next is
    ``z`` at its last ``K - 1`` positions: ``state`` ``[conv layers,
    slots, K - 1, hidden]``, of which this layer's rows ``si`` are READ
    as the history before the q-block's first row and WRITTEN with the
    ``z`` of the slot's last live rows.  A dead row lies past ``q_len``
    and a dead slot has ``q_len`` 0, so neither moves the state; a
    slot's rows are zero when its sequence starts (the manager zeroes
    them on admission).  Returns (h + operator, state)."""
    with jax.named_scope("conv_in"):
        u = _norm(blk, params, f"{us}_ln1", h)
        bg, cg, x = jnp.split(u @ params[f"{us}_conv_in_weight"], 3, axis=-1)
        z = bg * x                                          # [B, Q, d]
    y, last = _causal_conv(z, state[si], params[f"{us}_conv_weight"],
                           q_len, rows)
    with jax.named_scope("state_write"):
        state = state.at[si].set(last.astype(state.dtype))
    with jax.named_scope("conv_out"):
        h = h + (cg * y) @ params[f"{us}_conv_out_weight"]
    return h, state


def _proj(params, prefix, x, bias):
    """``x W`` (``+ b`` where the block's projections carry biases)."""
    y = x @ params[f"{prefix}_weight"]
    return y + params[f"{prefix}_bias"] if bias else y


def _qkv_heads(params, us, blk, i, x, H, Hkv, Dh, posns):
    """The K/V attention's front end over the normed rows ``x`` [Br, Qr,
    hidden]: q [.., H, Dh], k and v [.., Hkv, Dh] by the spec's
    projections, multipliers, per-head q/k norm and rotation (layer
    ``i``'s frequencies) at ``posns`` [Br, Qr].  A retention layer's
    front end is this one."""
    Br, Qr = x.shape[:2]
    mup = blk.mup
    if mup is not None:
        x = x * mup.attention_in
    q = _proj(params, f"{us}_attn_q", x, blk.bias).reshape(Br, Qr, H, Dh)
    k = _proj(params, f"{us}_attn_k", x, blk.bias).reshape(Br, Qr, Hkv, Dh)
    v = _proj(params, f"{us}_attn_v", x, blk.bias).reshape(Br, Qr, Hkv, Dh)
    if mup is not None:
        k = k * mup.key
    if blk.qk_norm:
        q = _rms(q, params[f"{us}_attn_q_norm_scale"], blk.norm_eps)
        k = _rms(k, params[f"{us}_attn_k_norm_scale"], blk.norm_eps)
    inv, factor = blk.rope_of(i)
    if blk.positions == "rope" and inv != "none":
        q = _rope(q, posns, blk.rope_theta, inv, factor)
        k = _rope(k, posns, blk.rope_theta, inv, factor)
    return q, k, v


def _retention_operator(params, us, blk, i, h, H, Hkv, Dh, posns, state,
                        si, q_len, rows=None):
    """One layer's power retention over the wave's rows ``h``: the K/V
    attention's front end and the gate ``log sigmoid(x W_g + b_g)``
    (float32, one a K/V head a row) under ``ret_qkvg``,
    ``retention_decode.retention_mixer`` over ``state`` (``ret_expand``,
    ``ret_scan``, ``state_write``), ``W_o`` under ``ret_out``.  No page
    is written or read.  Returns (h + operator, state)."""
    from .retention_decode import retention_mixer
    with jax.named_scope("ret_qkvg"):
        x = _norm(blk, params, f"{us}_ln1", h)
        q, k, v = _qkv_heads(params, us, blk, i, x, H, Hkv, Dh, posns)
        lg = jax.nn.log_sigmoid(
            (x @ params[f"{us}_ret_gate_weight"]).astype(jnp.float32)
            + params[f"{us}_ret_gate_bias"].astype(jnp.float32))
    y, state = retention_mixer(blk.retention, q, k, v, lg, state, si,
                               q_len, rows)
    with jax.named_scope("ret_out"):
        h = h + _proj(params, f"{us}_attn_proj", y, blk.bias)
    return h, state


def _mixed_step(params, cfg_tuple, cache_k, cache_v, pos, tokens,
                q_len, first_row, self_fresh, window=1, attn="masked",
                block_tables=None, has_fresh=False, moe_stats=None,
                state=None, win=None, ring=None):
    """One MIXED wave: slot b consumes ``tokens[b, :q_len[b]]`` at
    positions ``pos[b] .. pos[b]+q_len[b]-1`` — whatever mode those
    tokens are (prompt chunk, draft+bonus verify block, single decode
    token).  Returns (logits [B, W, V] f32, cache_k, cache_v, state) for
    the slots' SAMPLING WINDOWS only (``_window_logits``): row
    ``logits[b, w]`` is the next-token distribution after input
    ``first_row[b] + w``, for ``w < window`` = W (static; 1, or
    ``spec_k + 1`` on an engine that speculates — a constant of the
    engine, so it adds no program).  Dead positions and dead slots
    (``q_len`` 0) follow ``_verify_step``'s write/mask conventions
    exactly.

    What the blocks run over.  A decode or verify wave (``has_fresh``
    False) runs every block over the whole padded q-block ``[B, Q]``;
    only the final LN and the head are narrowed to the windows.  A wave
    that carries a prompt chunk is PACKED once, before the block stack,
    into ``wave_rows(...)`` = R rows (``_Rows``: slot-major, live rows first;
    static, a function of B, ``window`` and Q, so it adds no program;
    the SCHEDULER keeps a wave's live rows within it, and rows past R
    would be lost).  Everything row-wise then runs over ``[1, R]``:
    embedding, norms, projections, per-head norm and RoPE, the latent
    projections and absorbs, the conv operator, every FFN and the
    router (``T x k`` assignments of R rows, not of B x Q).  Only what
    needs a slot's rows as a block unpacks to ``[B, Q]``, and only off
    the float pool's kernels: q, k and v for the masked path's scoring
    and fresh-self softmax, q for the int8 pool's kernel
    (``_ragged_paged_blocked``), whose result is packed back.  The float
    pool's kernels take the packed rows AS THEY LIE: the page write
    (``paged_kv_write`` over ``touched_pages``, ISSUE 56: the pages the
    live rows touch, K and V in one call a layer; a q-block narrower
    than a page, and the int8 pool, keep ``_kv_scatter``'s row scatter,
    of the rows as they lie too), and the query rows, whose result comes
    back so (the K/V rows kernel's ``ragged_paged_attention_rows``,
    ISSUE 54; the latent kernel's ``ragged_paged_mla_rows``, ISSUE 46):
    no q-block of the query, of K, of V or of the result is there.
    ``_window_logits`` gathers the windows straight from the packed
    rows.  Where R is ``B x Q`` packing is the identity and is skipped.

    The masked path's DEFAULT attention is ``_verify_step``'s full
    causal mask over the just-written cache, bit for bit — so decode
    and spec-verify slots produce sequential ``_decode_step``'s logits
    (write-then-read self arithmetic, including the int8 round-trip).
    PROMPT-CHUNK slots are the
    one mode that keeps the chunk's own K/V FRESH (an int8 pool scores
    a chunk's own rows before their round-trip, as a one-pass prefill
    does); when a wave carries any
    (``has_fresh``, static — steady-state decode waves skip the extra
    compute entirely), the fresh-self two-part variant (context masked
    strictly below ``pos`` + causal scores over the in-flight q-block)
    is computed as well and selected for the slots ``self_fresh`` [B]
    marks.  The ragged path hands the whole wave to the mixed-mode
    kernel, which reads everything back from the pool (the fast path's
    existing round-trip semantics): it takes the pool pair WHOLE, rows
    ``[L, N_blocks, block, W]`` where they lie, with ``layer=i`` an
    index in its page copies (no ``cache_k[i]``).

    The device trace finds the wave's parts under a handful of
    ``jax.named_scope`` names, the same for every layer and every kind
    of wave (each under the wave's own scope, below): ``embed``,
    ``attn_qkv``, ``kv_write``, ``attention``, ``attn_out``, ``mlp``,
    ``lm_head`` (the window's gather, final LN and head) and ``sample``,
    ``_spec_sample``'s own.

    The block itself is the cfg_tuple's ``BlockSpec``, GPT-2's when it
    carries none.  Each layer is its OPERATOR, then its FFN
    (``_ffn_of_kind``: ``mlp``, or ``moe_route``, ``moe_experts``,
    ``moe_shared``), and the head follows the spec.  The operator is
    the K/V attention written here, which reads the spec for its norm,
    biases, positions, per-head q/k norm and group (``kv_heads`` K/V
    heads under ``H`` query heads; GPT-2's is LayerNorm, biases, learned
    positions, group 1); or ``_latent_attention`` (scopes ``mla_qkv``,
    ``mla_absorb``, ``kv_write``, ``attention``, ``attn_out``) over ONE
    pool, ``cache_v`` None; or, where ``blk.ops`` says "conv",
    ``_conv_operator`` (``conv_in``, ``conv_mix``, ``state_write``,
    ``conv_out``) over ``state``; or, where it says "attention+ssm",
    that K/V attention AND ``ssm_decode.ssm_mixer`` (``ssm_in``,
    ``ssm_conv``, ``ssm_scan``, ``state_write``, ``ssm_out``) on ONE
    norm, ``state`` then being the mixer's set (a conv tail and a
    float32 matrix state a layer) and the two outputs summed into the
    residual; or, where it says "ssm", that mixer ALONE on the layer's
    one norm; or, where it says "none", no operator at all (the layer is
    its FFN alone, on ``ln2``; a layer whose FFN kind is "none" is its
    operator alone, on ``ln1``); or, where it says "retention", ``_retention_operator``
    (``ret_qkvg``, ``ret_expand``, ``ret_scan``, ``state_write``,
    ``ret_out``): the K/V attention's front end (``_qkv_heads``, shared
    with it) into ``retention_decode.retention_mixer`` over ``state``,
    which writes and reads no page, so that a spec of such layers alone
    is handed NO pool (``cache_k`` and ``cache_v`` None, the tables
    unread) and builds no mask over positions.  With
    ``ops`` the pool holds the layers with an attention alone and the
    state the layers with a conv or a mixer alone, each layer finding
    its own by ``blk.op_index``.  A spec's multipliers (``blk.mup``)
    scale the embedding, each mixer's input and output, the keys, the
    FFN and the logits; a spec without them multiplies nowhere.

    A "window_attention" layer is that K/V attention over the WINDOW
    pool: ``win`` is its pool pair ``[window layers, N, block, W]``,
    handed back as a fifth element, ``ring`` its table ``[B, ring
    entries]``.  A slot's ring
    entry ``j mod ring`` holds logical page ``j``, so the table over
    logical pages the write and the scoring take is the ring repeated
    (a gather of ``[B, T]`` indices a wave); a query at ``p`` admits
    ``p - window < kv <= p`` (the masked path's band, the kernel's
    ``window``), so a page that a later one has overwritten is never in
    sight.  Rotary frequencies follow the layer's operator
    (``BlockSpec.rope_of``; an operator whose entry says "none" rotates
    nothing).  Without ``win`` nothing here changes.

    A PARALLEL block (``blk.residual`` "parallel": K/V attention layers
    beside a SwiGLU or a routed FFN) makes a layer's ONE norm once under
    the scope ``par_norm``; the attention and ``_ffn_of_kind(x=)`` both
    read its rows (packed rows stay packed through both) and the
    residual takes ``a + f`` in one addition.  A sequential spec traces
    none of it.

    A latent block with operators BY LAYER (``blk.ops`` of
    ``LATENT_OPERATORS``): a "window_latent_attention" layer is
    ``_latent_attention`` over the latent RING (``win`` = (ring pool,
    None), its own ``LatentSpec`` and head count), a "latent_attention"
    layer the same over the pool, where its spec has an indexer with
    ``cache_v`` as the index keys' pool (scopes ``mla_index``,
    ``index_write``, ``index_score``, ``index_topk``, ``sparse_mla``
    inside ``attention``) and, where gated, ``mla_gate``.  A "kda" layer
    of such a block is ``kda_decode.kda_operator`` (``kda_qkvg``,
    ``kda_conv``, ``kda_scan``, ``state_write``, ``kda_out``) over
    ``state``, the manager's set BESIDE the latent pool: it writes and
    reads no page, and the latent layers find their place in the pool by
    ``blk.op_index`` as ever.  In the grouped-query block a "kda" layer
    is the same operator beside "attention" layers, whose pages are the
    K/V pool's; a spec with ``attn_gate`` multiplies the attention's
    output by a sigmoid gate a column under the scope ``gqa_gate``.

    ONE outer scope names the wave's PROGRAM in the device trace, by the
    static facts the body branches on: ``wave_chunk`` (``has_fresh``),
    ``wave_verify`` (``window > 1``: every wave of an engine that
    speculates that carries no chunk), ``wave_decode``.  It is the first
    component of every operation's name stack below ``jit(...)``, so a
    trace's reader tells a chunk wave's program from a decode wave's
    (both are ``jit__serve_mixed_paged`` on the ``XLA Modules`` line).
    Metadata alone: the lowered text does not change."""
    scope = "wave_chunk" if has_fresh else \
        "wave_verify" if window > 1 else "wave_decode"
    with jax.named_scope(scope):
        return _mixed_wave(params, cfg_tuple, cache_k, cache_v, pos, tokens,
                           q_len, first_row, self_fresh, window, attn,
                           block_tables, has_fresh, moe_stats, state, win,
                           ring)


def _mixed_wave(params, cfg_tuple, cache_k, cache_v, pos, tokens, q_len,
                first_row, self_fresh, window, attn, block_tables,
                has_fresh, moe_stats, state, win=None, ring=None):
    """``_mixed_step``'s body, traced under the wave's own scope."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe = _moe_of(cfg_tuple)
    blk = _block_of(cfg_tuple)
    mup = blk.mup
    parallel = blk.residual == "parallel"
    B, Q = tokens.shape
    hdim = H * Dh
    Hkv = blk.kv_heads or H
    group = H // Hkv
    R = wave_rows(cfg_tuple, B, window, Q, has_fresh)
    bidx = jnp.arange(B)
    posns = pos[:, None] + jnp.arange(Q)[None, :]          # [B, Q]
    valid = jnp.arange(Q)[None, :] < q_len[:, None]        # [B, Q]
    lens = (pos + q_len).astype(jnp.int32)   # filled after the writes
    # the rows every row-wise operator runs over: the padded q-blocks
    # [B, Q], or a chunk wave's live rows packed into [1, R]
    rows = _Rows.of(q_len, Q, R) if R < B * Q else None
    if rows is None:
        tokens_r, posns_r, valid_r = tokens, posns, valid
    else:
        tokens_r, posns_r = rows.pack(tokens), rows.pack(posns)
        valid_r = rows.live[None]
    Br, Qr = tokens_r.shape
    with jax.named_scope("embed"):
        h = params[f"{name}_wte_table"][tokens_r]          # [Br, Qr, hd]
        if mup is not None:
            h = h * mup.embedding
        if blk.positions == "learned":
            wpe = params[f"{name}_wpe"]
            h = h + wpe[jnp.clip(posns_r, 0, wpe.shape[0] - 1)]
    if attn == "ragged":
        from ..kernels.ragged_attention import (
            ragged_paged_attention, ragged_paged_attention_rows)
    # a spec none of whose layers holds a page has no pool, and its wave
    # none of what follows up to the layers
    pooled = blk.op_layers(L, "pool") + blk.op_layers(L, "window") > 0
    if pooled:
        bs_blk = _kv_shape(cache_k)[2]
        T = block_tables.shape[1]
        posc = jnp.clip(posns, 0, S_max - 1)
        wblk = jnp.where(valid,
                         block_tables[bidx[:, None], posc // bs_blk], 0)
        woff = posc % bs_blk
        span = T * bs_blk
        # a row scatter writes the rows where they lie (a packed wave's
        # dead tail to scratch block 0, as a padded wave's dead rows)
        wblk_r, woff_r = wblk, woff
        if rows is not None:
            wblk_r = jnp.where(valid_r, rows.pack(wblk), 0)
            woff_r = rows.pack(woff)
        if win is not None:
            # the window layers' table over logical pages: the ring
            # repeated, and the blocks a row scatter writes through it
            win_k, win_v = win
            win_tables = ring[:, jnp.arange(T) % ring.shape[1]]
            wblk_w = jnp.where(valid,
                               win_tables[bidx[:, None], posc // bs_blk], 0)
            if rows is not None:
                wblk_w = jnp.where(valid_r, rows.pack(wblk_w), 0)
        # a q-block a page or more wide is written to the float pool as
        # the PAGES its live rows touch, from the rows as they lie (a
        # padded wave's slot b at row ``b Q``): the list once a table
        wide_write = writes_pages(blk, _kv_q(cache_k), Q, bs_blk)
        if wide_write:
            from ..kernels.paged_kv_write import paged_kv_write, touched_pages
            with jax.named_scope("kv_write"):
                row0 = bidx * Q if rows is None else rows.start
                touched = touched_pages(pos, q_len, row0, block_tables,
                                        bs_blk, Br * Qr, Q)
                if win is not None:
                    touched_w = touched_pages(pos, q_len, row0, win_tables,
                                              bs_blk, Br * Qr, Q)
        ctx = jnp.arange(span)[None, None, :]
        live = ctx <= posns[:, :, None]                    # [B, Q, S]
        # fresh-self variant: context strictly below the write window
        # plus a causal mask over the in-flight q-block
        ctx_live = (jnp.arange(span)[None, :] < pos[:, None])  # [B, S]
        jj = jnp.arange(Q)
        self_live = (jj[None, None, :] <= jj[None, :, None]) \
            & valid[:, None, :]                            # [B, Q, Q]
    if win is not None:
        # the masked path's band: a query admits the ``blk.window``
        # positions that end at its own
        near = ctx > posns[:, :, None] - blk.window        # [B, Q, S]
        self_near = jj[None, :] > jj[:, None] - blk.window  # [Q, Q]
    scale = Dh ** -0.5
    quant = pooled and _kv_q(cache_k)

    def per_query_head(kv):
        """K/V heads ``[.., Hkv, Dh]`` as the query heads read them."""
        return kv if group == 1 else jnp.repeat(kv, group, axis=-2)

    for i in range(L):
        us = f"{name}_h{i}"
        if blk.op_kind(i) == "conv":
            h, state = _conv_operator(params, us, blk, h, state,
                                      blk.op_index(i), q_len, rows)
            h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats)
            continue
        if blk.op_kind(i) == "ssm":
            # the state-space mixer alone, on the layer's one norm
            from .ssm_decode import ssm_mixer
            with jax.named_scope("ssm_in"):
                x = _norm(blk, params, f"{us}_ln1", h)
            y_ssm, state = ssm_mixer(params, us, blk, x, state,
                                     blk.op_index(i), q_len, rows)
            h = _ffn_of_kind(params, us, blk, h + y_ssm, i, valid_r,
                             moe_stats)
            continue
        if blk.op_kind(i) == "none":
            h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats)
            continue
        if blk.op_kind(i) == "retention":
            h, state = _retention_operator(
                params, us, blk, i, h, H, Hkv, Dh, posns_r, state,
                blk.op_index(i), q_len, rows)
            h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats)
            continue
        if blk.op_kind(i) == "kda":
            # the gated delta rule over its slot state: no page
            from .kda_decode import kda_operator
            h, state = kda_operator(params, us, blk, h, state,
                                    blk.op_index(i), q_len, rows)
            h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats)
            continue
        if blk.attention == "latent":
            # latent attention over ONE pool (``cache_v`` is None);
            # ``check_block_spec`` keeps out the combinations this wave
            # does not run
            if blk.holds(i, "window"):
                # the window layers' latent rows lie in the ring, under
                # its table and the band
                h, win_k, _ = _latent_attention(
                    params, us, blk, H, h, win_k, blk.op_index(i), wblk_w,
                    woff_r, posns_r, live & near, lens, q_len, win_tables,
                    attn, rows, layer=i)
            else:
                # ``cache_v`` is the index keys' pool of a spec whose
                # full layers choose what they read (else None)
                h, cache_k, cache_v = _latent_attention(
                    params, us, blk, H, h, cache_k, blk.op_index(i),
                    wblk_r, woff_r, posns_r, live, lens, q_len,
                    block_tables, attn, rows, layer=i, index_pool=cache_v)
            h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats)
            continue
        # the pool holds the layers with an attention alone (all of
        # them, in their order, where the spec names no operators); a
        # window layer's pages are in the window pool, under its table
        windowed = blk.op_kind(i) == "window_attention"
        if windowed:
            pi = blk.op_index(i, "window")
            ck, cv, tables, wb = win_k, win_v, win_tables, wblk_w
            live_i = live & near
        else:
            pi = blk.op_index(i, "pool")
            ck, cv, tables, wb = cache_k, cache_v, block_tables, wblk_r
            live_i = live
        # a parallel layer's ONE norm: the operator and the FFN both
        # read its rows
        with jax.named_scope("par_norm" if parallel else "attn_qkv"):
            x = _norm(blk, params, f"{us}_ln1", h)
        y_ssm = None
        if blk.op_kind(i) == "attention+ssm":
            # the state-space mixer reads the same normed rows; its
            # output joins the attention's in the residual below
            from .ssm_decode import ssm_mixer
            y_ssm, state = ssm_mixer(params, us, blk, x, state,
                                     blk.op_index(i, "state"), q_len, rows)
        with jax.named_scope("attn_qkv"):
            q, k, v = _qkv_heads(params, us, blk, i, x, H, Hkv, Dh, posns_r)
        with jax.named_scope("kv_write"):
            # both writes take the rows as they lie
            if wide_write:
                W = ck.shape[-1]
                ck, cv = paged_kv_write(
                    ck, cv, pi, kv_rows(k, W).reshape(-1, W),
                    kv_rows(v, W).reshape(-1, W),
                    touched_w if windowed else touched)
            else:
                ck = _kv_scatter(ck, (pi, wb, woff_r), k)
                cv = _kv_scatter(cv, (pi, wb, woff_r), v)
        # the float pool's kernel takes a packed wave's query rows as
        # they lie and hands its result back so
        # (``ragged_paged_attention_rows``)
        as_rows = attn == "ragged" and not quant and rows is not None
        if rows is not None and not as_rows:
            # a slot's rows as a block: what the masked path's scoring
            # and fresh-self softmax and the int8 pool's kernel take
            with jax.named_scope("attention"):
                q = rows.unpack(q)
                if attn != "ragged":
                    # (the chunk's own fresh K/V)
                    k, v = rows.unpack(k), rows.unpack(v)
        with jax.named_scope("attention"):
            if as_rows:
                o = ragged_paged_attention_rows(
                    q[0], ck, cv, lens, q_len, rows.start, tables, layer=pi,
                    groups=group,
                    window=blk.window if windowed else 0)[None]
            elif attn == "ragged":
                # the pool pair whole, the layer an index in the page
                # copy: no ``cache_k[i]`` is materialised (an int8 pair
                # hands its scale planes over beside its payload)
                pk, ksc = ck if quant else (ck, None)
                pv, vsc = cv if quant else (cv, None)
                o = ragged_paged_attention(
                    q, pk, pv, lens, q_len, tables, layer=pi,
                    k_scale=ksc, v_scale=vsc,
                    groups=group,
                    window=blk.window if windowed else 0).reshape(
                        B, Q, hdim)
            else:
                ks, ksc = _kv_layer(ck, pi, Hkv, Dh)
                vs, vsc = _kv_layer(cv, pi, Hkv, Dh)
                kg = ks[tables].reshape(B, span, Hkv, Dh)
                vg = vs[tables].reshape(B, span, Hkv, Dh)
                if ksc is not None:
                    kg = kg.astype(jnp.float32) * ksc[
                        tables].reshape(B, span, Hkv)[..., None]
                    vg = vg.astype(jnp.float32) * vsc[
                        tables].reshape(B, span, Hkv)[..., None]
                kg, vg = per_query_head(kg), per_query_head(vg)
                # default: _verify_step's full mask over the written cache
                s_raw = jnp.einsum("bqhd,bshd->bqhs", q, kg) * scale
                sw = jnp.where(live_i[:, :, None, :], s_raw, NEG_INF)
                p = jax.nn.softmax(sw, axis=-1)
                o = jnp.einsum("bqhs,bshd->bqhd", p, vg)
                if has_fresh:
                    # chunk slots: read-back context + the chunk's own
                    # FRESH K/V
                    kf, vf = per_query_head(k), per_query_head(v)
                    if windowed:
                        ctx_i = (ctx_live[:, None, :] & near)[:, :, None, :]
                        self_i = self_live & self_near[None]
                    else:
                        ctx_i = ctx_live[:, None, None, :]
                        self_i = self_live
                    s1 = jnp.where(ctx_i, s_raw, NEG_INF)
                    s2 = jnp.einsum("bqhd,bjhd->bqhj", q, kf) * scale
                    s2 = jnp.where(self_i[:, :, None, :], s2, NEG_INF)
                    pf = jax.nn.softmax(
                        jnp.concatenate([s1, s2], axis=-1), axis=-1)
                    o_fresh = jnp.einsum("bqhs,bshd->bqhd",
                                         pf[..., :span], vg) \
                        + jnp.einsum("bqhj,bjhd->bqhd", pf[..., span:], vf)
                    o = jnp.where(self_fresh[:, None, None, None],
                                  o_fresh, o)
                o = o.reshape(B, Q, hdim)
            if rows is not None and not as_rows:
                o = rows.pack(o)
        if windowed:
            win_k, win_v = ck, cv
        else:
            cache_k, cache_v = ck, cv
        if blk.attn_gate:
            with jax.named_scope("gqa_gate"):
                gate = jax.nn.sigmoid((x @ params[f"{us}_attn_gate_weight"]
                                       ).astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate).astype(h.dtype)
        with jax.named_scope("attn_out"):
            o = _proj(params, f"{us}_attn_proj", o, blk.bias)
            if mup is not None:
                o = o * mup.attention_out
            if not parallel:
                h = h + o
        if parallel:
            # the FFN of the SAME normed rows; one addition
            h = h + (o + _ffn_of_kind(params, us, blk, h, i, valid_r,
                                      moe_stats, x=x))
            continue
        if y_ssm is not None:
            h = h + y_ssm
        h = _ffn_of_kind(params, us, blk, h, i, valid_r, moe_stats, moe)
    logits = _window_logits(params, name, h, first_row, window, blk, rows)
    if win is not None:
        return logits, cache_k, cache_v, state, (win_k, win_v)
    return logits, cache_k, cache_v, state


def _serve_mixed_paged(params, cfg_tuple, cache_k, cache_v, tables,
                       pos, tokens, q_len, first_row, self_fresh,
                       temperature, top_k, rng_keys, attn="masked",
                       has_fresh=False, window=1, state=None, win=None,
                       ring=None):
    """One fused MIXED wave over all slots of the block-table paged
    pool: write + score every slot's ragged q-block, then sample each
    slot's sampling window — rows ``first_row[b] <= j < q_len[b]``, at
    most ``window`` of them — from its own rng stream
    (``_spec_sample``).  Returns (sampled [B, W], cache_k, cache_v,
    keys_after [B, W, 2][, moe stats][, state][, window pool pair]),
    the first and the keys indexed FROM THE WINDOW'S FIRST ROW: ``[b,
    0]`` is a decode slot's token and a final chunk's first token,
    ``[b, :a + 1]`` a verify block's accepted run; a mid-prompt chunk
    and a dead slot (``q_len`` 0: its writes route to scratch block 0)
    have an empty window and get their key back untouched.
    ``has_fresh`` (static) marks waves carrying
    prompt-chunk slots — see ``_mixed_step``.  ``state`` is the slot
    state of a block spec that keeps any (an array, or a tuple of them:
    the manager's set, donated like the pool, returned LAST); every
    other spec passes none and gets none back.  ``win`` / ``ring`` are
    the window layers' pool pair and ring table of a spec that has any
    (the pair donated, returned after everything else)."""
    moe_on = _moe_active(cfg_tuple)
    routed = _block_of(cfg_tuple).routed
    sd = {} if moe_on or routed is not None else None
    band = {} if win is None else {"win": win, "ring": ring}
    logits, cache_k, cache_v, state, *win_out = _mixed_step(
        params, cfg_tuple, cache_k, cache_v, pos, tokens, q_len,
        first_row, self_fresh, window=window, attn=attn,
        block_tables=tables, has_fresh=has_fresh, moe_stats=sd,
        state=state, **band)
    sampled, after = _spec_sample(logits, temperature, top_k, rng_keys,
                                  q_len - first_row)
    out = (sampled, cache_k, cache_v, after)
    if moe_on:
        out = out + (_moe_stats_out(
            sd, _moe_of(cfg_tuple),
            jnp.sum(jnp.clip(q_len, 0, tokens.shape[1]))),)
    if routed is not None:
        # (load [held] summed over the routed layers, experts touched
        # summed over them): dropless, so there is no drop element; a
        # spec that holds a share of its experts adds ALL the
        # assignments routed, of which the load's are the ones that
        # landed here
        z = jnp.zeros((routed.held_experts,), jnp.int32)
        rs = (jnp.asarray(sd.get("load", z), jnp.int32),
              jnp.asarray(sd.get("touched", 0), jnp.int32))
        if routed.holds_a_share:
            rs = rs + (jnp.asarray(sd.get("routed", 0), jnp.int32),)
        out = out + (rs,)
    if state is not None:
        out = out + (state,)
    return out + tuple(win_out)


@functools.lru_cache(maxsize=None)
def serve_mixed_paged_fn(donate=True, attn="masked", window=1):
    """Jitted ``_serve_mixed_paged`` — the block-table mixed wave, the
    engine's one dispatch (see ``serve_prefill_fn`` for the donation
    rationale).  Compiles per (Q bucket, has_fresh): the engine
    pow2-buckets the wave width, so the ladder is log-bounded, and
    steady-state decode waves skip the chunk-slot variant's extra
    softmax entirely.  ``window`` (the widest sampling window,
    ``spec_k + 1`` or 1) is bound here, once an engine, never per
    wave."""
    kw = {"static_argnames": ("cfg_tuple", "attn", "has_fresh", "window")}
    if donate:
        kw["donate_argnums"] = (2, 3)
        kw["donate_argnames"] = ("state", "win")
    fn = jax.jit(_serve_mixed_paged, **kw)
    return functools.partial(fn, attn=attn, window=window)


@functools.lru_cache(maxsize=None)
def serve_prefill_fn(donate=True):
    """Jitted ``_serve_prefill``; ``donate=True`` donates the cache pair
    so XLA updates it in place — without donation every call pays a
    full-cache copy (the scatter/update allocates a fresh buffer),
    which dwarfs the step's matmuls at serving cache sizes."""
    kw = {"static_argnames": ("cfg_tuple",)}
    if donate:
        kw["donate_argnums"] = (2, 3)
    return jax.jit(_serve_prefill, **kw)


@functools.lru_cache(maxsize=None)
def serve_prefill_batch_fn(donate=True):
    """Jitted ``_serve_prefill_batch`` — the fast path's admission
    dispatch (see ``serve_prefill_fn`` for the donation rationale).
    Compiles per (group bucket N, prompt bucket P_b) pair; both are
    pow2-bucketed by the engine, so the ladder bounds the cache."""
    kw = {"static_argnames": ("cfg_tuple",)}
    if donate:
        kw["donate_argnums"] = (2, 3)
    return jax.jit(_serve_prefill_batch, **kw)


@functools.lru_cache(maxsize=None)
def serve_verify_fn(donate=True):
    """Jitted ``_serve_verify`` — the speculative wave's batched
    verification step over the contiguous cache (see
    ``serve_prefill_fn`` for the donation rationale).  Compiles per
    q-block width Q = spec_k + 1; adaptive k varies per-slot ``q_len``
    INSIDE one compile, so the ladder is one entry per engine."""
    kw = {"static_argnames": ("cfg_tuple",)}
    if donate:
        kw["donate_argnums"] = (2, 3)
    return jax.jit(_serve_verify, **kw)


@functools.lru_cache(maxsize=None)
def spec_propose_fn(donate=True):
    """Jitted ``_spec_propose`` (draft cache pair donated).  Compiles
    per draft length k — the adaptive controller moves k through a
    pow2 ladder, so at most log2(spec_k)+1 entries exist."""
    kw = {"static_argnames": ("cfg_tuple", "k")}
    if donate:
        kw["donate_argnums"] = (2, 3)
    return jax.jit(_spec_propose, **kw)


def teacher_forced_logits(params, config, seq, kv_fake_quant=False,
                          name=None):
    """Per-position next-token logits [P, V] of ONE sequence under
    teacher forcing, optionally with every layer's K/V FAKE-QUANTIZED
    (``quant.kv_encode`` → ``kv_decode``) before attention.

    Storing KV as int8 and dequantizing inside the decode kernel is
    arithmetically identical to fake-quantizing K/V here, so this is
    the margin-gate ORACLE for ``HETU_KV_QUANT``: measure
    ``delta = max |logits_q - logits_exact|`` over a corpus, and every
    position whose exact top-2 logit margin exceeds ``2 * delta`` is
    GUARANTEED top-1-identical under int8 KV — the "tolerance-tested
    threshold" the quant_ab quality gate asserts.  Positions inside the
    threshold are genuine near-ties where either token is defensible.
    """
    c = config
    from .moe_decode import moe_spec_of
    moe = moe_spec_of(c)
    name = _infer_name(params, name)
    params = {k: _prep_param(v) for k, v in params.items()
              if k.startswith(name + "_")}
    L, H = c.num_hidden_layers, c.num_attention_heads
    Dh = head_dim_of(c)
    seq = jnp.asarray(seq, jnp.int32)
    P = seq.shape[0]
    hdim = H * Dh
    h = params[f"{name}_wte_table"][seq] \
        + params[f"{name}_wpe"][jnp.arange(P)]
    causal = jnp.tril(jnp.ones((P, P), bool))
    for i in range(L):
        us = f"{name}_h{i}"
        x = _ln(h, params[f"{us}_ln1_scale"], params[f"{us}_ln1_bias"])
        q = (x @ params[f"{us}_attn_q_weight"]
             + params[f"{us}_attn_q_bias"]).reshape(P, H, Dh)
        k = (x @ params[f"{us}_attn_k_weight"]
             + params[f"{us}_attn_k_bias"]).reshape(P, H, Dh)
        v = (x @ params[f"{us}_attn_v_weight"]
             + params[f"{us}_attn_v_bias"]).reshape(P, H, Dh)
        if kv_fake_quant:
            k = kv_decode(*kv_encode(k)).astype(k.dtype)
            v = kv_decode(*kv_encode(v)).astype(v.dtype)
        s = jnp.einsum("phd,shd->hps", q, k) * (Dh ** -0.5)
        s = jnp.where(causal[None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hps,shd->phd", p, v).reshape(P, hdim)
        o = o @ params[f"{us}_attn_proj_weight"] \
            + params[f"{us}_attn_proj_bias"]
        h = h + o
        h = _ffn_block(params, us, h, i, moe=moe)
    h = _ln(h, params[f"{name}_ln_f_scale"], params[f"{name}_ln_f_bias"])
    logits = (h @ params[f"{name}_wte_table"].T).astype(jnp.float32) \
        + params.get(f"{name}_head_bias", 0.0)
    return logits


def _infer_name(params, name=None):
    """The model's parameter-name prefix; explicit ``name`` wins, else
    inferred when exactly one ``*_wte_table`` is present."""
    if name is not None:
        return name
    tables = [k[:-len("_wte_table")] for k in params
              if k.endswith("_wte_table")]
    if len(tables) != 1:
        raise ValueError(
            f"params hold {len(tables)} *_wte_table entries ({tables}); "
            f"pass name= to pick the model")
    return tables[0]


def tp_shard_params(params, mesh, config, axis="tp", name=None):
    """Place a GPT parameter dict for TENSOR-PARALLEL decoding: the
    Megatron column/row split by name (q/k/v and ffn_wi column-split
    over ``axis``, attn_proj and ffn_wo row-split, embeddings/LNs
    replicated).  ``generate_fast`` needs no other change — GSPMD
    propagates the shardings through the decode scan, splitting the
    per-head attention and FFN across the mesh (multi-chip serving).

    Requires num_attention_heads % mesh.shape[axis] == 0 so the column
    split lands on head boundaries."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tp = mesh.shape[axis]
    if config.num_attention_heads % tp:
        raise ValueError(
            f"num_attention_heads={config.num_attention_heads} not "
            f"divisible by {axis}={tp}: the column split must land on "
            f"head boundaries")
    name = _infer_name(params, name)

    def spec_for(k):
        if any(t in k for t in ("_attn_q_weight", "_attn_k_weight",
                                "_attn_v_weight", "_ffn_wi_weight")):
            return P(None, axis)
        if any(t in k for t in ("_attn_proj_weight", "_ffn_wo_weight")):
            return P(axis, None)
        if any(t in k for t in ("_attn_q_bias", "_attn_k_bias",
                                "_attn_v_bias", "_ffn_wi_bias")):
            return P(axis)
        return P()

    return {k: jax.device_put(np.asarray(v),
                              NamedSharding(mesh, spec_for(k)))
            for k, v in params.items() if k.startswith(name + "_")}


def _generate_spec(params, cfg_tuple, draft_layers, prompts, num_tokens,
                   temperature, top_k, seed, eos_id, pad_id, spec_k):
    """Offline speculative generation: the serving building blocks
    (batched flash prefill, scanned draft propose, batched verify)
    driven by a host loop over the whole batch in lockstep — rows whose
    acceptance differs simply sit at different positions (the per-slot
    position vectors are the hard part, and they already exist).
    Greedy outputs are token-identical to the non-speculative
    ``generate_fast`` paths; sampling draws per-row rng streams
    (PRNGKey(seed + row)), so sampled outputs match the serving
    engine's per-request streams, not the offline batch-keyed scan.
    Finished rows ride along with q_len 0 (their state frozen)."""
    name, L, H, Dh, S_max = cfg_tuple[:5]
    moe = _moe_of(cfg_tuple)
    cfg_d = (name, draft_layers, H, Dh, S_max)
    if moe is not None:
        # the draft skips routing entirely: attention-only MoE blocks
        cfg_d = cfg_d + (moe._replace(draft=True),)
    B, P = prompts.shape
    cdtype = params[f"{name}_wte_table"].dtype
    Q = spec_k + 1
    ck = jnp.zeros((L, B, S_max, H, Dh), cdtype)
    cv = jnp.zeros((L, B, S_max, H, Dh), cdtype)
    dck = jnp.zeros((draft_layers, B, S_max, H, Dh), cdtype)
    dcv = jnp.zeros((draft_layers, B, S_max, H, Dh), cdtype)
    P_b = min(_pow2(P, floor=8), S_max)
    padb = np.zeros((B, P_b), np.int32)
    padb[:, :P] = prompts
    padb = jnp.asarray(padb)
    slots = np.arange(B, dtype=np.int32)
    lens = np.full(B, P, np.int32)
    temps = np.full(B, temperature, np.float32)
    topks = np.full(B, top_k, np.int32)
    keys = np.stack([np.asarray(jax.random.PRNGKey(seed + r), np.uint32)
                     for r in range(B)])
    prefill = serve_prefill_batch_fn(True)
    first, ck, cv, keys = _strip_moe(
        prefill(params, cfg_tuple, ck, cv, slots, padb, lens, temps,
                topks, keys), cfg_tuple)
    # draft cache prefill: same prompts, truncated depth; its sampled
    # tokens and key splits are discarded (the draft never samples;
    # a draft MoE spec appends no stats either)
    _, dck, dcv, _ = prefill(params, cfg_d, dck, dcv, slots, padb,
                             lens, temps, topks, np.array(keys))
    propose = spec_propose_fn(True)
    verify = serve_verify_fn(True)
    first = np.asarray(first, np.int32)
    keys = np.array(keys, np.uint32)
    pos = np.full(B, P, np.int32)
    tok = first.copy()
    total = P + int(num_tokens)
    out = np.full((B, total), pad_id, np.int32)
    out[:, :P] = prompts
    out[:, P] = first
    emitted = np.ones(B, np.int32)
    done = emitted >= num_tokens
    if eos_id is not None:
        done |= first == eos_id
    while not done.all():
        draft, dck, dcv = propose(params, cfg_d, dck, dcv, pos, tok,
                                  k=spec_k)
        draft = np.asarray(draft)
        tokens = np.zeros((B, Q), np.int32)
        tokens[:, 0] = tok
        tokens[:, 1:] = draft
        qlen = np.where(done, 0,
                        np.minimum(Q, num_tokens - emitted)).astype(
                            np.int32)
        tgt, ck, cv, after = _strip_moe(
            verify(params, cfg_tuple, ck, cv, pos, tokens, qlen,
                   temps, topks, keys), cfg_tuple)
        tgt = np.asarray(tgt)
        after = np.array(after, np.uint32)
        for b in range(B):
            if done[b]:
                continue
            ql = int(qlen[b])
            a = 0
            while a < ql - 1 and tgt[b, a] == tokens[b, a + 1]:
                a += 1
            emit = [int(t) for t in tgt[b, :a + 1]]
            if eos_id is not None and eos_id in emit:
                emit = emit[:emit.index(eos_id) + 1]
            n = len(emit)
            out[b, P + emitted[b]:P + emitted[b] + n] = emit
            emitted[b] += n
            pos[b] += n
            tok[b] = emit[-1]
            keys[b] = after[b, n - 1]
            if emitted[b] >= num_tokens or \
                    (eos_id is not None and emit[-1] == eos_id):
                done[b] = True
    return out


def generate_fast(params, config, prompts, num_tokens, temperature=0.0,
                  top_k=0, seed=0, name=None, dtype=None, eos_id=None,
                  pad_id=0, prefill=None, spec=None,
                  spec_draft_layers=None):
    """KV-cached generation.

    params: {name: array} (e.g. ``executor.var_values`` — pass it
      directly — or the output of ``hf.convert_gpt2``); config:
      GPTConfig (hidden size, layers, heads, max_position_embeddings);
      prompts: non-empty list of token-id lists (same length each, or a
      [B, P] array); name: the model's parameter-name prefix — inferred
      when the params hold exactly one ``*_wte_table``; dtype:
      ``jnp.bfloat16`` halves weights AND the KV cache and takes the
      fast MXU path (logits/sampling stay f32); default FOLLOWS the
      params' own dtype (bf16 weights → bf16 cache);
      eos_id: a row that samples this id past its prompt emits it, then
      ``pad_id`` for the rest of the requested span (and per-step
      compute short-circuits once every row is done) — both traced, so
      different EOS/pad ids share one compile; prefill: "flash" runs
      the prompt as ONE batched full-prompt pass (Pallas flash
      attention, pow2-bucketed prompt length), "scan" teacher-forces it
      token by token inside the scan (the reference), default consults
      ``$HETU_SERVE_FAST`` then auto-selects flash on TPU — greedy
      outputs are identical either way; spec: > 0 enables speculative
      decoding (default ``$HETU_SPEC_K``): a truncated-layer draft
      (``spec_draft_layers`` of this model's own blocks, default
      ``$HETU_SPEC_DRAFT_LAYERS`` / max(1, L // 4)) proposes ``spec``
      tokens per wave and the target verifies them in one batched
      step — greedy outputs stay token-identical to the
      non-speculative paths; sampled outputs draw per-ROW rng streams
      (seed + row), matching the serving engine rather than the
      batch-keyed offline scan.
      Returns [B, P + num_tokens] numpy int32.
    """
    prompts = np.asarray(prompts, np.int32)
    if prompts.ndim == 1:
        prompts = prompts[None]
    B, P = prompts.shape
    if P < 1:
        raise ValueError("prompt must hold at least one token")
    if num_tokens < 1:
        raise ValueError(f"num_tokens must be >= 1, got {num_tokens}")
    total = P + int(num_tokens)
    c = config
    name = _infer_name(params, name)
    S_max = c.max_position_embeddings
    if total > S_max:
        raise ValueError(f"prompt + num_tokens = {total} exceeds "
                         f"max_position_embeddings {S_max}")
    Dh = head_dim_of(c)
    cfg_tuple = (name, c.num_hidden_layers, c.num_attention_heads,
                 Dh, S_max)
    from .moe_decode import moe_spec_of
    mspec = moe_spec_of(c)
    if mspec is not None:
        # the hashable MoESpec rides the jit-static cfg_tuple as an
        # optional sixth element — dense 5-tuples compile unchanged
        cfg_tuple = cfg_tuple + (mspec,)
    # dtype=None FOLLOWS the params (bf16 weights decode bf16 with a
    # bf16 cache — the "follow the weights" contract; the old default
    # silently upcast everything to f32)
    params = {k: _prep_param(v, dtype)
              for k, v in params.items() if k.startswith(name + "_")}
    spec_k = resolve_spec_k(spec)
    if spec_k:
        dl = resolve_draft_layers(spec_draft_layers, c.num_hidden_layers)
        return _generate_spec(params, cfg_tuple, dl, prompts,
                              int(num_tokens), float(temperature),
                              int(top_k), int(seed), eos_id,
                              int(pad_id), spec_k)
    common = dict(eos_id=jnp.int32(-1 if eos_id is None else eos_id),
                  pad_id=jnp.int32(pad_id), use_eos=eos_id is not None)
    if _resolve_fast(prefill):
        P_b = min(_pow2(P, floor=8), S_max)
        padb = np.zeros((B, P_b), np.int32)
        padb[:, :P] = prompts
        first, toks = _generate_flash(
            params, cfg_tuple, jnp.asarray(padb), jnp.int32(P),
            jnp.float32(temperature), int(top_k),
            jax.random.PRNGKey(seed), **common)
        out = np.zeros((B, total), np.int32)
        out[:, :P] = prompts
        out[:, P] = np.asarray(first)
        if total > P + 1:
            out[:, P + 1:] = np.asarray(toks)[:, P:total - 1]
        return out
    pad = np.zeros((B, S_max), np.int32)
    pad[:, :P] = prompts
    out = _generate_scan(params, cfg_tuple, jnp.asarray(pad),
                         jnp.int32(P), jnp.float32(temperature),
                         int(top_k), jax.random.PRNGKey(seed), **common)
    return np.asarray(out[:, :total])
