"""KV-cache management for continuous batching: the block-table paged
layout.

``PagedKVManager`` is the engine's one layout: a fixed pool of
``[L, N_blocks, block, W]`` KV blocks (one lane-dense row a position,
``kv_layout``) with a free list, a
per-request BLOCK TABLE mapping sequence positions to pool blocks, and
refcounted copy-on-write prefix sharing keyed by a prompt-prefix hash —
N requests with the same system prompt reference its KV blocks once.
Concurrent sequences per HBM byte are a function of *actual* tokens
held (prompt + generation, shared prefixes amortized) instead of the
worst-case ``S_max`` a slot-contiguous cache pays (offline
``generate_fast``'s layout), which is the number that caps serving
occupancy.

Shapes are BUCKETED to powers of two (``B_slots`` and ``S_max``
independently) so engines configured for nearby workloads land on the
same jit cache entries — the compile cache stays bounded by the ladder,
not by the number of distinct deployment configs.
"""

from __future__ import annotations

import collections
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .. import envvars, quant, telemetry
from ..kv_layout import kv_heads, kv_row_width, kv_rows


def round_up_pow2(n, floor=1):
    """Smallest power of two >= max(n, floor)."""
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


def _bucket_prompt(p, s_max, pos_cap):
    """Prompt-length bucket for prefill compiles: pow2, floor 8, capped
    at BOTH ``s_max`` and the model's position table ``pos_cap``.  The
    pos_cap clamp is load-bearing: when ``s_max`` was capped to a
    non-pow2 position-table size, the pow2 round-up alone could pad a
    prompt past the positions the wpe table can index (silent clamp =
    wrong embeddings), so the bucket must never exceed the cap."""
    b = min(round_up_pow2(p, floor=8), int(s_max))
    if pos_cap is not None:
        b = min(b, int(pos_cap))
    return b


def assemble_mixed_wave(n_slots, entries, q_floor=1):
    """Lay per-slot ragged q-blocks out as ONE mixed-wave descriptor,
    every slot's q-block padded to the widest (the engine's hot loop).
    The descriptor is what the HOST hands over; what the device computes
    over is the step's own business: a wave that carries a prompt chunk
    packs the live rows on the device (``gpt_decode._mixed_step``,
    ``wave_rows``), and the engine lets into ``entries`` only as many
    chunks as that row count holds.

    ``entries`` maps slot -> ``(tokens, pos, first_row, self_fresh)``:

    * ``tokens``     the slot's q-block this step — a full prompt, a
                     prompt chunk, ``[cur] + draft`` for spec-verify,
                     or ``[cur]`` for plain decode (len >= 1);
    * ``pos``        cache position of ``tokens[0]``;
    * ``first_row``  index of the first row whose rng stream splits:
                     the start of the slot's sampling window, the only
                     rows the wave's head and sampling run over
                     (== ``len(tokens)``, an empty window, for
                     mid-prompt chunks that sample nothing);
    * ``self_fresh`` True when the q-block's own K/V must be read
                     through the two-part fresh-self softmax (paged
                     prompt chunks) rather than the written cache.

    Width is bucketed to a power of two so waves with nearby shapes
    land on the same jit entry.  Slots absent from ``entries`` ride
    along inactive (``q_len = 0``): the kernel masks their attention
    and their clipped writes land on dead positions.  A prompt chunk
    the wave had no rows for is such a slot for one wave.
    """
    width = max((len(t) for t, *_ in entries.values()), default=1)
    q = round_up_pow2(width, floor=q_floor)
    tokens = np.zeros((n_slots, q), np.int32)
    pos = np.zeros(n_slots, np.int32)
    q_len = np.zeros(n_slots, np.int32)
    first_row = np.zeros(n_slots, np.int32)
    self_fresh = np.zeros(n_slots, bool)
    for s, (toks, p, fr, fresh) in entries.items():
        n = len(toks)
        tokens[s, :n] = toks
        pos[s] = p
        q_len[s] = n
        first_row[s] = fr
        self_fresh[s] = fresh
    return {
        "q": q,
        "tokens": tokens,
        "pos": pos,
        "q_len": q_len,
        "first_row": first_row,
        "self_fresh": self_fresh,
    }


def _is_int8(dtype):
    """True when ``dtype`` selects the quantized int8 cache layout
    (the string sentinel "int8" or jnp.int8 itself)."""
    if dtype is None:
        return False
    if isinstance(dtype, str):
        return dtype.strip().lower() == "int8"
    try:
        return jnp.dtype(dtype) == jnp.int8
    except TypeError:
        return False


def resolve_kv_quant(kv_quant=None, dtype=None):
    """Serving KV quantization selection shared by the engine and
    bench: an explicit ``kv_quant`` ("int8"/None) wins, then an int8
    ``dtype``, then ``$HETU_KV_QUANT``.  Returns "int8" or None."""
    if _is_int8(dtype):
        return "int8"
    return quant.resolve_quant(kv_quant, "HETU_KV_QUANT")


def _alloc_cache(shape, dtype, quantized):
    """One cache array — or, quantized, the ``(int8 data, f32 scales)``
    pair with one scale per (layer, slot/block, position, head): the
    payload keeps ``shape``, the scales drop the head_dim axis.  The
    pair is a pytree, so it threads through the jitted decode/prefill
    functions (and their donation) exactly like a plain array."""
    if quantized:
        return (jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:-1], jnp.float32))
    return jnp.zeros(shape, dtype)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_slot(states, slot):
    """Every member of ``states`` (each ``[layers, slots, ...]``) with
    slot ``slot``'s part zeroed, in place (donated; ``slot`` traced: one
    program a set)."""
    return tuple(s.at[:, slot].set(0) for s in states)


def cache_nbytes(cache):
    """HBM bytes of a cache value (plain array, quantized pair, or the
    None a latent pool has in place of a second pool)."""
    if cache is None:
        return 0
    if isinstance(cache, (tuple, list)):
        return sum(int(a.nbytes) for a in cache)
    return int(cache.nbytes)


def resolve_handoff_quant(mode=None):
    """Replica-to-replica KV handoff WIRE selection.  "auto" (default,
    ``$HETU_HANDOFF_QUANT``) ships the pool's native bytes — an int8
    pool's (payload, scales) pair already IS the cheap wire, an exact
    pool ships exact; "int8" forces an exact (f32/bf16) pool's export
    through the per-head codec (:func:`quant.kv_encode`, ~4x fewer
    bytes, small quantization error); "0"/"off" pins the exact wire.
    Returns "auto", "int8", or None."""
    if mode is None:
        mode = envvars.get_str("HETU_HANDOFF_QUANT")
    s = str(mode).strip().lower() if mode is not None else "auto"
    if s in ("", "auto"):
        return "auto"
    if s in ("0", "off", "none", "false"):
        return None
    if s == "int8":
        return "int8"
    raise ValueError(f"unknown handoff quant mode {mode!r} "
                     "(expected 'auto', 'int8', or 'off')")


def _wire_repr(gathered, pool_quant, mode):
    """Resolve one exported cache value to its wire form.  Returns
    (value, wire_quant) where ``value`` is an exact host array or an
    (int8, scales) pair and ``wire_quant`` is "int8" or None."""
    if pool_quant:                      # native pair is already int8
        return gathered, "int8"
    if mode == "int8":
        q, s = quant.kv_encode(jnp.asarray(np.asarray(gathered,
                                                      np.float32)))
        return (np.asarray(q), np.asarray(s)), "int8"
    return gathered, None


def _wire_to_pool(wire, wire_quant, pool_cache):
    """Convert a wire value into the destination pool's representation:
    (q, scales) for an int8 pool, an array in the pool dtype otherwise.
    Requantizing an exact wire / dequantizing an int8 wire as needed —
    so handoffs compose across mixed-precision fleets."""
    if isinstance(pool_cache, (tuple, list)):           # int8 pool
        if wire_quant:
            q, s = wire
        else:
            q, s = quant.kv_encode(jnp.asarray(np.asarray(wire,
                                                          np.float32)))
        return jnp.asarray(q, jnp.int8), jnp.asarray(s, jnp.float32)
    if wire_quant:
        vals = quant.kv_decode(jnp.asarray(wire[0]), jnp.asarray(wire[1]))
    else:
        vals = jnp.asarray(np.asarray(wire))
    return vals.astype(pool_cache.dtype)


def resolve_kv_block(block=None):
    """The paged pool's block size in tokens.  An explicit ``block``
    wins; else ``$HETU_KV_BLOCK`` (an integer, or "auto" = 16, on every
    backend).  A block of 0 or less once selected the slot-contiguous
    layout, which is gone: it is refused."""
    if block is None:
        raw = str(envvars.get_str("HETU_KV_BLOCK") or "auto").strip().lower()
        block = 16 if raw in ("auto", "") else int(raw)
    block = int(block)
    if block <= 0:
        raise ValueError(
            f"kv_block / $HETU_KV_BLOCK must be a positive block size in "
            f"tokens (or \"auto\"), got {block}: 0 selected the "
            f"slot-contiguous layout, and the contiguous layout is gone "
            f"(the paged pool is the one KV layout)")
    return block


class _PrefixEntry:
    """One registered prompt prefix: the tokens (collision-proof key
    verification), the pool blocks holding its KV (each refcounted on
    behalf of the cache so they outlive the registering request), and
    an LRU stamp for eviction under pool pressure."""

    __slots__ = ("tokens", "blocks", "length", "used")

    def __init__(self, tokens, blocks, length, used):
        self.tokens = tokens
        self.blocks = blocks
        self.length = length
        self.used = used


class PagedKVManager:
    """Block-pool allocator with per-request block tables.

    The cache pair is ``[L, N_blocks, block, W]``: one ROW a position,
    head h in lanes ``[h * Dh, (h + 1) * Dh)``, ``W = kv_row_width(H,
    Dh)`` the next multiple of the 128 lanes (GPT-2 XL 1600 -> 1664,
    zeros in the pad; 768 and 1024 need none).  That is the layout the
    mixed ragged kernel reads in place: a row of whole lane tiles keeps
    the pool's default layout row-major, so the donated pool is neither
    copied to the kernel's layout and back every wave nor sliced a
    layer at a time (``[.., 25, 64]`` was: ledger, PR 30, ``copy`` 1.995
    s and ``slice_bitcast_fusion`` 0.912 s of a 6 s trace), and a page
    is one contiguous copy.  ``kv_layout.kv_heads`` is the ``[.., H,
    Dh]`` view every other reader takes, and the WIRE (export / import,
    tiers) stays ``[L, n, block, H, Dh]`` with the pad stripped.  The
    int8 pool keeps ``[L, N_blocks, block, H, Dh]`` int8 with ``[..,
    H]`` f32 scales (its scale page cannot be copied by hand: see
    ``ragged_paged_attention``).  A request holds ``ceil(tokens /
    block)`` blocks listed in its slot's block-table row, so pool bytes
    bound the TOKENS held, not slots * S_max.  Block id 0 is a
    permanent scratch block: dead table entries point at it and inert
    slots' ride-along decode writes land in it, so nothing a mask
    admits is ever clobbered.

    Admission RESERVES the request's whole span (prompt +
    max_new_tokens, minus shared prefix blocks) up front, so decode
    waves never allocate and never preempt — the engine requeues an
    admission the pool cannot hold yet (backpressure), and ``submit``
    rejects one it can never hold.

    Prefix sharing (``prefix_share``): completed prompts register their
    blocks keyed by the prompt-token hash; a later request whose prompt
    starts with a registered prefix attaches those blocks refcounted
    instead of recomputing them.  A shared block whose tail the new
    request must overwrite (the prefix ends mid-block) is COPY-ON-WRITE
    forked at admission.  Retirement decrements refcounts and returns a
    block to the free list only at zero; registered prefixes are
    LRU-evicted when the pool runs short.  Both sides of that cost what
    they touch, not the size of the cache: a lookup reads the entries
    under the prompt's first block (``_by_head``), an eviction pops the
    least recently used end of ``_prefix`` (an admission into a full
    pool of ten thousand entries was 150 ms of scans, the device idle).

    Blocks by LAYER KIND (``window_layers`` > 0): the layers that attend
    over a sliding ``window`` keep their K/V in a pool pair of their own,
    ``win_k`` / ``win_v`` ``[window_layers, N_win, block, W]``, with its
    own free list and its own table a slot, ``win_tables`` ``[slots,
    ring]``: a RING.  Position ``p`` of a slot lives in ring entry ``(p
    // block) mod ring``, ``ring = ceil((window + window_chunk) / block)
    + 1`` blocks (never more than a full table), where ``window_chunk``
    is the widest q-block a wave writes: a q-block's first row still
    sees ``window - 1`` positions before it, so the page a write
    overwrites holds nothing that this or a later wave can see.  The
    ring is claimed whole at ``alloc`` and returned at ``release``: a
    window layer's reservation is a constant, whatever the prompt's
    length, while ``cache_k`` / ``cache_v`` and ``tables`` hold the
    full-attention layers alone, every position, as ever.  What would
    need the ring's past is refused by name (``_refuse_window``):
    ``prefix_share=True``, ``truncate`` below the ring's oldest
    position, the wire, an int8 pool.  A manager without window layers
    has none of this: its arrays, tables and programs are what they
    were.  Beside LATENT rows (``row_shape``) the window layers' ring is
    latent too: ONE pool ``win_k`` of rows ``window_row_shape`` wide (the
    window layers' own latent width), ``win_v`` None.  ``index_shape``
    (with ``row_shape``) adds the index keys of a spec whose full layers
    choose what they read: ``cache_v`` is then ``[layers, N_blocks,
    block, *index_shape]`` on the latent pool's own blocks and tables
    (gauge ``serve.kv.index_bytes``).

    NO pool layer (``layers=0``: a model whose every layer keeps slot
    state and no page, ``state_shapes`` then being all it holds):
    ``cache_k`` and ``cache_v`` are None, a token takes no block
    (``blocks_needed`` is 0, the tables one zero column wide), admission
    is by SLOT alone and ``max_seq_len`` is bounded by ``pos_cap`` only.
    The slot count of a manager with state, with pool layers or without,
    is EXACT, not rounded up to a power of two: a slot of such a manager
    costs its whole state whether or not a request is in it (34 MB a
    layer for the retention state, 2.1 MB a layer for the delta rule's),
    where a stateless pooled manager's idle slot costs a table row.
    Everything the state refuses stays refused (``_refuse_state``).
    """

    @telemetry.spanned("serve.kv.build")
    def __init__(self, *, layers, heads, head_dim, slots, max_seq_len,
                 pos_cap=None, dtype=jnp.float32, bucket=True,
                 block=16, pool_blocks=None, prefix_share=None,
                 row_shape=None, state_shape=None, state_shapes=None,
                 window_layers=0, window=0, window_chunk=0,
                 window_row_shape=None, index_shape=None):
        self.pool_layers = int(layers)
        if self.pool_layers < 0 or not (self.pool_layers or state_shapes
                                        or state_shape):
            raise ValueError(
                f"PagedKVManager: layers={layers} and no slot state: a "
                f"manager holds pool layers, slot state, or both")
        if not self.pool_layers and (pool_blocks is not None
                                     or window_layers):
            raise ValueError(
                "PagedKVManager: layers=0 holds no block: pool_blocks and "
                "window_layers go with pool layers")
        if bucket:
            # (a slot that carries state costs it whether or not a
            # request is in it: the count is then exact, with a pool or
            # without)
            if self.pool_layers and not (state_shapes or state_shape):
                slots = round_up_pow2(slots)
            s = round_up_pow2(max_seq_len, floor=16)
        else:
            s = int(max_seq_len)
        if pos_cap is not None:
            s = min(s, int(pos_cap))
        if s < max_seq_len:
            raise ValueError(
                f"max_seq_len={max_seq_len} exceeds the position-table "
                f"cap {pos_cap}")
        self.n_slots = int(slots)
        self.s_max = int(s)
        self.pos_cap = int(pos_cap) if pos_cap is not None else self.s_max
        self.block = int(block)
        if self.block < 1:
            raise ValueError(f"block size must be >= 1, got {block}")
        # table width: blocks needed for a brim-full sequence (without
        # pool layers: one column that stays zero, and the scratch block
        # alone)
        self.table_width = -(-self.s_max // self.block) \
            if self.pool_layers else 1
        if pool_blocks is None:
            # contiguous-equivalent capacity (+1 for the scratch block)
            pool_blocks = self.n_slots * self.table_width + 1 \
                if self.pool_layers else 1
        self.n_blocks = int(pool_blocks)
        if self.n_blocks < 2 and self.pool_layers:
            raise ValueError("pool needs at least 2 blocks "
                             "(scratch + one allocatable)")
        # state beside the pool: a SET of arrays indexed by SLOT, not by
        # position, each ``[layers that carry it, slots, ...]`` of any
        # rank and dtype.  ``state_shapes`` names the members as
        # ((layers, ...a slot's shape), dtype or None for the pool's);
        # ``state_shape`` = (layers, rows a slot, width) is the
        # one-member set in the pool's dtype (a short convolution's
        # last inputs).  The same manager owns all of it: ``alloc``
        # zeroes every member of a claimed slot, the engine threads the
        # set through the donated step beside the pool.  It has no copy
        # at a block boundary, so what would need one is refused by
        # name: a shared prefix (explicit ``prefix_share=True`` raises;
        # the default resolves to off), ``truncate`` and the wire.
        if state_shape is not None and state_shapes is not None:
            raise ValueError("PagedKVManager: state_shape OR state_shapes")
        if state_shape is not None:
            state_shapes = ((tuple(state_shape), None),)
        self.stateful = bool(state_shapes)
        if self.stateful and prefix_share:
            raise ValueError(
                "PagedKVManager: prefix_share with slot-indexed state: a "
                "prefix-cache hit would start a sequence past position 0 "
                "and the state at that block boundary is not in the pool "
                "(no snapshots yet)")
        self.window_layers = int(window_layers)
        if self.window_layers and prefix_share:
            raise ValueError(
                "PagedKVManager: prefix_share with window layers: a "
                "prefix-cache hit would need the last window of the "
                "prefix in the window layers' ring, which holds the "
                "slot's own last positions alone")
        if prefix_share is None:
            prefix_share = not self.stateful and not self.window_layers
        self.prefix_share = bool(prefix_share)
        self.quant = "int8" if _is_int8(dtype) else None
        if self.window_layers and (self.quant or (
                row_shape is None) != (window_row_shape is None)):
            raise ValueError(
                "PagedKVManager: window layers beside an int8 pool, or "
                "latent rows in one pool and K/V heads in the other: the "
                "window kernels read float rows of the full pool's kind "
                "(kv_quant with window layers; row_shape without "
                "window_row_shape)")
        if index_shape is not None and row_shape is None:
            raise ValueError(
                "PagedKVManager: index_shape goes with latent rows "
                "(row_shape): the index keys lie where a K/V pool has "
                "its values")
        if self.stateful and self.quant:
            raise ValueError(
                "PagedKVManager: an int8 pool beside slot-indexed state: "
                "the state has no codec (kv_quant with a conv operator)")
        # what one token keeps a layer: a K/V pair of [heads, head_dim]
        # in two pools, or, with ``row_shape`` (a latent spec's
        # ``(LatentSpec.row_width,)``), ONE row in ONE pool: ``cache_k``
        # is that pool and ``cache_v`` is None.  Allocation, tables,
        # prefix sharing, COW, truncate and release never look inside a
        # row, so they hold for either
        self.latent = row_shape is not None
        if not self.pool_layers:
            self.cache_k = self.cache_v = None
        elif self.latent:
            if self.quant:
                raise ValueError(
                    "an int8 pool of latent rows is not supported: the "
                    "codec scales a (position, head) slab and a latent "
                    "row has no heads (kv_quant with a latent spec)")
            shape = (layers, self.n_blocks, self.block) + tuple(row_shape)
            self.cache_k = _alloc_cache(shape, dtype, None)
            # a spec with an indexer keeps ONE index key a position a
            # layer on the same blocks and tables, where a K/V pool has
            # its values: allocated, shared, forked and freed with the
            # latent rows
            self.cache_v = None if index_shape is None else _alloc_cache(
                shape[:3] + tuple(index_shape), dtype, None)
            if index_shape is not None:
                telemetry.set_gauge("serve.kv.index_bytes",
                                    self.index_bytes)
        else:
            self.heads, self.head_dim = int(heads), int(head_dim)
            row = ((heads, head_dim) if self.quant
                   else (kv_row_width(heads, head_dim),))
            shape = (layers, self.n_blocks, self.block) + row
            self.cache_k = _alloc_cache(shape, dtype, self.quant)
            self.cache_v = _alloc_cache(shape, dtype, self.quant)
        self.states = ()
        self.state_resets = 0
        if self.stateful:
            self.states = tuple(
                jnp.zeros((int(shape[0]), self.n_slots)
                          + tuple(int(v) for v in shape[1:]),
                          dtype if member_dtype is None else member_dtype)
                for shape, member_dtype in state_shapes)
            telemetry.set_gauge("serve.state.bytes", self.state_bytes)
        self.window = self.ring = 0
        self.win_k = self.win_v = self.win_tables = None
        self.window_blocks_recycled = 0
        if self.window_layers:
            self.window = int(window)
            if self.window < 1:
                raise ValueError(f"window={window}: a window layer sees "
                                 f"at least itself")
            self.ring = min(
                -(-(self.window + int(window_chunk)) // self.block) + 1,
                self.table_width)
            # every slot's ring + the scratch block 0
            n_win = self.n_slots * self.ring + 1
            # a latent ring holds ONE row a position a layer (of the
            # window layers' own width) and has no second pool
            row = (kv_row_width(heads, head_dim),) \
                if window_row_shape is None else tuple(window_row_shape)
            shape = (self.window_layers, n_win, self.block) + row
            self.win_k = _alloc_cache(shape, dtype, None)
            self.win_v = None if self.latent \
                else _alloc_cache(shape, dtype, None)
            self._win_free = list(range(1, n_win))
            self.win_tables = np.zeros((self.n_slots, self.ring), np.int32)
            telemetry.set_gauge("serve.kv.window_bytes", self.window_bytes)
        self._free = list(range(1, self.n_blocks))   # 0 = scratch
        self.ref = np.zeros(self.n_blocks, np.int32)
        self.tables = np.zeros((self.n_slots, self.table_width), np.int32)
        self.n_table = np.zeros(self.n_slots, np.int32)
        self.lengths = np.zeros(self.n_slots, np.int32)
        self.owner = [None] * self.n_slots
        self._free_slots = list(range(self.n_slots))
        # tokens -> entry, least recently used FIRST (a use moves an
        # entry to the end: ``_touch``), so eviction pops from the front
        # and costs what it frees, not a scan of the cache a victim
        self._prefix = collections.OrderedDict()
        # head -> {tokens: entry}: the entries under their first block
        # of tokens (an entry shorter than a block under all of its
        # tokens), so a lookup reads the prompts that start like this
        # one, not every registered prefix
        self._by_head = {}
        self._clock = 0
        self.total_allocs = 0
        self.cow_copies = 0
        self.prefix_hits = 0
        self.evictions = 0
        # fleet directory feed: the router's PrefixDirectory wires
        # these so registrations/evictions on THIS replica become
        # fleet-visible hints (None = standalone engine, no directory)
        self.on_prefix_register = None   # fn(tokens, entry)
        self.on_prefix_evict = None      # fn(tokens)
        # tiered KV (serving/kv_tiers.py): eviction-to-tier instead of
        # eviction-to-drop.  The spill hook gets the doomed prefix's
        # wire payload BEFORE its blocks are freed; tier_store is the
        # engine admission path's fetch handle.  Both None = today's
        # drop-on-evict, byte-identical
        self.on_prefix_spill = None      # fn(tokens, payload) -> bool
        self.tier_store = None
        self.spills = 0
        self.prefix_hit_tokens = 0       # recompute tokens saved
        # replica-to-replica handoff accounting
        self.exports = 0
        self.imports = 0
        self.export_bytes = 0
        self.import_bytes = 0

    # ------------------------------------------------------------- #

    @property
    def capacity_blocks(self):
        """Blocks a single request could ever hold (pool minus scratch)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def free_slots(self):
        return len(self._free_slots)

    @property
    def blocks_shared(self):
        """Blocks referenced by more than one holder (requests and/or
        the prefix cache)."""
        return int(np.sum(self.ref > 1))

    @property
    def full_bytes(self):
        """HBM bytes of the pool pair that holds every position (scales
        included when quantized): all there is without window layers."""
        return cache_nbytes(self.cache_k) + cache_nbytes(self.cache_v)

    @property
    def index_bytes(self):
        """HBM bytes of a latent pool's index keys (0 without any)."""
        return cache_nbytes(self.cache_v) if self.latent else 0

    @property
    def window_bytes(self):
        """HBM bytes of the window layers' pool pair (0 without any)."""
        return cache_nbytes(self.win_k) + cache_nbytes(self.win_v)

    @property
    def cache_bytes(self):
        """Total HBM bytes of K/V: ``full_bytes + window_bytes``."""
        return self.full_bytes + self.window_bytes

    @property
    def free_window_blocks(self):
        return len(self._win_free) if self.window_layers else 0

    @property
    def occupancy(self):
        return 1.0 - len(self._free_slots) / self.n_slots

    def live(self):
        return [i for i in range(self.n_slots) if self.owner[i] is not None]

    def window_blocks_held(self, slot):
        """Window-pool blocks ``slot`` holds: its ring, or 0."""
        return int(np.count_nonzero(self.win_tables[slot])) \
            if self.window_layers else 0

    def blocks_needed(self, tokens):
        """Blocks ``tokens`` positions take: none without pool layers."""
        return -(-int(tokens) // self.block) if self.pool_layers else 0

    def bucket_prompt(self, p):
        """The prompt-length bucket (``_bucket_prompt``: pos_cap clamp
        included)."""
        return _bucket_prompt(p, self.s_max, self.pos_cap)

    def _gauges(self):
        telemetry.set_gauge("serve.occupancy", round(self.occupancy, 4))
        telemetry.set_gauge("serve.blocks_free", self.free_blocks)
        telemetry.set_gauge("serve.blocks_shared", self.blocks_shared)
        telemetry.set_gauge("serve.prefix_entries", len(self._prefix))
        if self.window_layers:
            telemetry.set_gauge("serve.blocks_free.window",
                                self.free_window_blocks)

    # ------------------------------------------------------------- #
    # prefix cache
    # ------------------------------------------------------------- #

    def match_prefix(self, prompt):
        """Longest registered prefix of ``prompt`` (token-verified, so
        a hash collision can never attach wrong KV); returns
        (entry, usable_len) or (None, 0).  ``usable_len`` is capped at
        len(prompt) - 1: the LAST prompt position is always recomputed,
        because sampling the first token needs its logits (KV alone is
        not enough)."""
        if not self.prefix_share:
            return None, 0
        p = tuple(int(t) for t in prompt)
        best, best_len = None, 0
        for n in range(1, min(self.block, len(p)) + 1):
            for key, e in self._by_head.get(p[:n], {}).items():
                if e.length <= len(p) - 1 and e.length > best_len \
                        and key == p[:e.length]:
                    best, best_len = e, e.length
        if best is not None:
            self._touch(best)
        return best, best_len

    def _touch(self, entry):
        """``entry`` was just used: the last the eviction reaches."""
        self._clock += 1
        entry.used = self._clock
        self._prefix.move_to_end(entry.tokens)

    def register_prefix(self, prompt, slot):
        """Register ``slot``'s prompt blocks for future sharing (called
        once the prompt's KV is fully written).  An entry is keyed at
        EVERY full-block boundary of the prompt plus its full length —
        a later prompt sharing only the system-prompt head still finds
        the longest common block run, and one extending this prompt
        verbatim attaches its partial tail block too (COW-forked at
        admission).  The cache takes its own refcount on each block so
        the blocks survive the registering request's retirement."""
        if not self.prefix_share:
            return
        p = tuple(int(t) for t in prompt)
        cuts = {k * self.block
                for k in range(1, len(p) // self.block + 1)}
        cuts.add(len(p))
        for n in sorted(cuts):
            key = p[:n]
            if key in self._prefix:
                self._touch(self._prefix[key])
                if self.on_prefix_register is not None:
                    # re-registration refreshes the directory's
                    # last-use stamp (TTL staleness tracks real use)
                    self.on_prefix_register(key, self._prefix[key])
                continue
            blocks = [int(b)
                      for b in self.tables[slot, :self.blocks_needed(n)]]
            for b in blocks:
                self.ref[b] += 1
            self._clock += 1
            e = _PrefixEntry(key, blocks, n, self._clock)
            self._prefix[key] = e
            self._by_head.setdefault(key[:self.block], {})[key] = e
            if self.on_prefix_register is not None:
                self.on_prefix_register(key, e)
        self._gauges()

    def _evict_for(self, need, keep=None):
        """LRU-drop registered prefixes until ``need`` blocks are free
        (blocks still referenced by live requests stay allocated):
        ``_prefix`` from its front, ``keep`` passed over."""
        while len(self._free) < need:
            # the least recently used entry but ``keep``
            key = next((k for k, e in self._prefix.items()
                        if e is not keep), None)
            if key is None:
                break
            if self.on_prefix_spill is not None:
                # eviction-to-tier: serialize the doomed prefix while
                # its blocks are still resident (export_prefix is a
                # pure read) and offer it to the tier ladder; a
                # declined spill proceeds as today's drop
                try:
                    payload = self.export_prefix(key, count=False)
                except ValueError:
                    payload = None
                if payload is not None \
                        and self.on_prefix_spill(key, payload):
                    self.spills += 1
                    telemetry.inc("serve.prefix_spills")
            e = self._prefix.pop(key)
            head = self._by_head[key[:self.block]]
            del head[key]
            if not head:
                del self._by_head[key[:self.block]]
            for b in e.blocks:
                self.ref[b] -= 1
                if self.ref[b] == 0:
                    self._free.append(b)
            self.evictions += 1
            if self.on_prefix_evict is not None:
                self.on_prefix_evict(key)

    # ------------------------------------------------------------- #
    # alloc / fork / release
    # ------------------------------------------------------------- #

    def alloc(self, owner, prompt, reserve):
        """Claim a slot plus blocks for a request reserving ``reserve``
        total positions (prompt + max_new_tokens).  Attaches the longest
        registered prefix refcounted, COW-forks a mid-block prefix tail,
        and materializes fresh blocks for the rest of the span.  Returns
        (slot, cached_len) — cached_len prompt positions already hold
        valid KV — or (None, 0) when slots or blocks are short (the
        engine requeues: backpressure, not failure)."""
        if reserve > self.s_max:
            raise ValueError(
                f"sequence length {reserve} exceeds S_max {self.s_max}")
        if not self._free_slots:
            return None, 0
        entry, cached = self.match_prefix(prompt)
        n_shared = cached // self.block          # full shared blocks
        straddle = cached % self.block != 0      # mid-block tail -> COW
        total = self.blocks_needed(reserve)
        need = total - n_shared                  # fork counts as fresh
        if len(self._free) < need:
            self._evict_for(need, keep=entry)
            # eviction may have dropped the matched entry's blocks to
            # ref 0 only if it was not kept — `keep` pins it
            if len(self._free) < need:
                return None, 0
        if self.window_layers and len(self._win_free) < self.ring:
            return None, 0
        slot = self._free_slots.pop()
        if self.window_layers:
            # the ring whole: a constant a slot, whatever the prompt
            self.win_tables[slot] = [self._win_free.pop()
                                     for _ in range(self.ring)]
        row = []
        for j in range(n_shared):
            b = entry.blocks[j]
            self.ref[b] += 1
            row.append(b)
        if straddle:
            src = entry.blocks[n_shared]
            dst = self._free.pop()
            self.ref[dst] = 1
            # device-side block copy: the forked block starts as an
            # exact copy of the shared one, then takes private writes
            # (a quantized pool copies payload AND scale planes)
            self.cache_k = self._block_copy(self.cache_k, src, dst)
            self.cache_v = self._block_copy(self.cache_v, src, dst)
            row.append(dst)
            self.cow_copies += 1
            telemetry.inc("serve.cow_copies")
        for _ in range(total - len(row)):
            b = self._free.pop()
            self.ref[b] = 1
            row.append(b)
        self.tables[slot, :] = 0
        self.tables[slot, :len(row)] = row
        self.n_table[slot] = len(row)
        self.owner[slot] = owner
        self.lengths[slot] = cached
        self.total_allocs += 1
        if self.stateful:
            # a sequence starts with no history
            self.states = _zero_slot(self.states, np.int32(slot))
            self.state_resets += 1
            telemetry.inc("serve.state.resets")
        if cached:
            self.prefix_hits += 1
            self.prefix_hit_tokens += cached
            telemetry.inc("serve.prefix_hits")
        self._gauges()
        return slot, cached

    @staticmethod
    def _block_copy(cache, src, dst):
        """Copy pool block ``src`` onto ``dst`` (plain array or the
        quantized (data, scale) pair — both leaves move together so a
        COW fork never mixes one block's payload with another's
        scales; None, a latent pool's absent second pool, stays
        None)."""
        if cache is None:
            return None
        if isinstance(cache, (tuple, list)):
            return tuple(a.at[:, dst].set(a[:, src]) for a in cache)
        return cache.at[:, dst].set(cache[:, src])

    def advance(self, slot, n=1):
        """Record ``n`` more filled positions (blocks were reserved at
        admission — nothing to allocate).  With window layers the pages
        past the ring's first turn each overwrote an older one of the
        slot's own: counted (``serve.kv.window_blocks_recycled``)."""
        if self.window_layers:
            old = self.blocks_needed(self.lengths[slot])
            new = self.blocks_needed(self.lengths[slot] + n)
            turned = max(new - self.ring, 0) - max(old - self.ring, 0)
            if turned:
                self.window_blocks_recycled += turned
                telemetry.inc("serve.kv.window_blocks_recycled", turned)
        self.lengths[slot] += n

    def truncate(self, slot, n):
        """Roll ``slot`` back to ``n`` filled positions at refcount
        discipline (speculative-decode rejection rollback).  The slot's
        whole-span reservation is KEPT — a never-speculated replay
        holds the same blocks, so rollback must not shrink it — but any
        reserved block the slot will now REWRITE (covering positions at
        or past ``n``) that is still SHARED (refcount > 1: attached
        from the prefix cache or another request) is detached and
        replaced with a private block: the boundary block still holding
        live positions below ``n`` is copy-on-write FORKED (content
        preserved), wholly-dead trailing blocks are swapped for fresh
        blocks with no copy.  A shared block is NEVER freed here — its
        refcount drops by one and every other holder keeps it.  In the
        engine's speculative path the one shared block a slot rewrites
        is its prompt's partial tail block, which ``register_prefix``
        took a refcount on (``match_prefix`` caps what OTHER requests
        attach below the last prompt position); the discipline holds
        for any caller.
        Quantized pools move payload and scale planes together
        (``_block_copy``)."""
        if self.owner[slot] is None:
            raise ValueError(f"slot {slot} is free")
        self._refuse_state("truncate")
        old = int(self.lengths[slot])
        n = int(n)
        if self.window_layers and n < old \
                and self.blocks_needed(old) > self.ring:
            # the ring has turned: the pages a rollback would uncover
            # were overwritten by the positions it takes back
            self._refuse_window(
                f"truncate to {n} of {old} positions, below the ring's "
                f"oldest")
        if not 0 <= n <= old:
            raise ValueError(
                f"cannot truncate slot {slot} to {n} (filled {old})")
        first_w = n // self.block   # first block future writes touch
        for j in range(first_w, int(self.n_table[slot])):
            b = int(self.tables[slot, j])
            if self.ref[b] <= 1:
                continue
            partial = j == first_w and n % self.block != 0
            if not self._free:
                self._evict_for(1)
                if self.ref[b] <= 1:
                    # the evicted prefix entry was the other holder:
                    # the block is private now and needs no fork
                    continue
            if not self._free:
                raise RuntimeError(
                    f"pool exhausted un-COWing rollback of slot {slot} "
                    f"(block {b} shared at ref {int(self.ref[b])})")
            dst = self._free.pop()
            self.ref[dst] = 1
            self.ref[b] -= 1
            if partial:
                # live positions below n survive in the private fork
                self.cache_k = self._block_copy(self.cache_k, b, dst)
                self.cache_v = self._block_copy(self.cache_v, b, dst)
                self.cow_copies += 1
                telemetry.inc("serve.cow_copies")
            self.tables[slot, j] = dst
        self.lengths[slot] = n
        self._gauges()

    def release(self, slot):
        """Retire a sequence: decrement each held block's refcount and
        free it only at zero — blocks shared with other requests or the
        prefix cache stay resident."""
        if self.owner[slot] is None:
            raise ValueError(f"slot {slot} is already free")
        for j in range(int(self.n_table[slot])):
            b = int(self.tables[slot, j])
            self.ref[b] -= 1
            if self.ref[b] == 0:
                self._free.append(b)
        if self.window_layers:
            self._win_free.extend(int(b) for b in self.win_tables[slot])
            self.win_tables[slot] = 0
        self.tables[slot, :] = 0
        self.n_table[slot] = 0
        self.owner[slot] = None
        self.lengths[slot] = 0
        self._free_slots.append(slot)
        self._gauges()

    # ------------------------------------------------------------- #
    # replica-to-replica handoff (block export / import)
    # ------------------------------------------------------------- #

    def export_blocks(self, slot, quant_mode=None):
        """Serialize ``slot``'s FILLED blocks to a host-side payload a
        peer replica can :meth:`import_blocks`.  Ships exactly
        ``blocks_needed(length)`` blocks (the filled span, not the
        whole reservation), as ``[L, n, block, H, Dh]`` host arrays
        (the pool's rows seen through ``kv_heads``, pad stripped) — or
        the (int8, scales) pair when the pool is quantized or the
        wire mode forces int8 (:func:`resolve_handoff_quant`), ~4x
        fewer bytes with scale planes moving in lockstep.  A pure
        read: refcounts, tables, and the prefix cache are untouched,
        so COW-shared blocks stay shared on the source."""
        if self.owner[slot] is None:
            raise ValueError(f"slot {slot} is free")
        length = int(self.lengths[slot])
        n = self.blocks_needed(length)
        idx = np.asarray([int(b) for b in self.tables[slot, :n]], np.int32)
        return self._export_span(idx, length, quant_mode)

    def export_prefix(self, tokens, quant_mode=None, *, count=True):
        """Serialize a REGISTERED prefix's blocks to the same wire
        payload as :meth:`export_blocks` — no live slot required (the
        prefix cache holds its own refcounts), which is how a fleet
        moves warmth without a resident request: elastic scale-up
        warming and retirement export (serving/router.py) both ride
        this.  Returns None when the prefix is not registered here (or
        sharing is off).  A pure read."""
        if not self.prefix_share:
            return None
        e = self._prefix.get(tuple(int(t) for t in tokens))
        if e is None:
            return None
        idx = np.asarray([int(b) for b in e.blocks], np.int32)
        return self._export_span(idx, int(e.length), quant_mode,
                                 count=count)

    def _refuse_latent(self, what):
        if self.latent:
            raise ValueError(
                f"{what}: latent rows have no wire format yet (the "
                f"handoff payload is a K/V pair of heads); a latent "
                f"pool neither exports nor imports blocks")

    @property
    def state(self):
        """What the step is handed and hands back: None, the set's one
        member, or the tuple of its members."""
        if len(self.states) <= 1:
            return self.states[0] if self.states else None
        return self.states

    @state.setter
    def state(self, value):
        self.states = (tuple(value) if isinstance(value, (tuple, list))
                       else (value,))

    @property
    def state_bytes(self):
        return int(sum(s.nbytes for s in self.states))

    def _refuse_state(self, what):
        if self.stateful:
            raise ValueError(
                f"{what}: this manager holds slot-indexed state beside "
                f"the pool, of which there is one copy a slot and none a "
                f"position: it can neither be rolled back nor shipped "
                f"with a span of blocks (no snapshots yet)")

    def _refuse_window(self, what):
        if self.window_layers:
            raise ValueError(
                f"{what}: this manager's window layers keep a ring of "
                f"{self.ring} blocks a slot, the last {self.window} "
                f"positions and a q-block: older positions are "
                f"overwritten, so they can be neither rolled back to nor "
                f"shipped with a span of blocks")

    def _export_span(self, idx, length, quant_mode, *, count=True):
        """Gather pool blocks ``idx`` into the wire payload (shared by
        the slot and prefix export paths).  ``count=False`` keeps the
        gather out of the handoff ledger — the tier-spill path uses it
        so spill bytes don't masquerade as replica-to-replica wire
        traffic (the tier store keeps its own byte counters)."""
        self._refuse_latent("export_blocks/export_prefix")
        self._refuse_state("export_blocks/export_prefix")
        self._refuse_window("export_blocks/export_prefix")
        mode = resolve_handoff_quant(quant_mode)

        def gather(cache):
            if isinstance(cache, (tuple, list)):
                return tuple(np.asarray(a[:, idx]) for a in cache)
            # the wire is [L, n, block, H, Dh]: the rows' pad stays here
            return kv_heads(np.asarray(cache[:, idx]), self.heads,
                            self.head_dim)

        k, kq = _wire_repr(gather(self.cache_k), self.quant, mode)
        v, _ = _wire_repr(gather(self.cache_v), self.quant, mode)
        nbytes = cache_nbytes(k) + cache_nbytes(v)
        shape = (k[0] if isinstance(k, tuple) else k).shape
        raw = 2 * 4 * int(np.prod(shape))        # f32-equivalent bytes
        if count:
            self.exports += 1
            self.export_bytes += nbytes
        return {"layout": "paged", "block": self.block, "length": length,
                "quant": kq, "k": k, "v": v,
                "nbytes": nbytes, "raw_nbytes": raw}

    def import_blocks(self, payload, owner, *, reserve=None, prompt=None):
        """Materialize an exported span into THIS pool: claims a slot
        plus fresh blocks for ``reserve`` positions (default: the
        payload's filled length), writes the wire blocks (requantizing
        an exact wire into an int8 pool / dequantizing an int8 wire
        into an exact pool as needed), and — given ``prompt`` — re-
        registers the prompt's prefix over the imported blocks so later
        admissions here attach them refcounted (the whole point of a
        prefill→decode handoff).  Returns the slot, or None when slots
        or blocks are short (backpressure, same contract as ``alloc``).
        Block size and layout must match; a mismatch raises."""
        self._refuse_latent("import_blocks")
        self._refuse_state("import_blocks")
        self._refuse_window("import_blocks")
        if payload.get("layout") != "paged":
            raise ValueError(
                f"cannot import a {payload.get('layout')!r} payload "
                "into a paged manager")
        if int(payload["block"]) != self.block:
            raise ValueError(
                f"payload block size {payload['block']} != pool block "
                f"size {self.block}")
        length = int(payload["length"])
        reserve = length if reserve is None else int(reserve)
        if reserve < length:
            raise ValueError(
                f"reserve {reserve} below payload length {length}")
        if reserve > self.s_max:
            raise ValueError(
                f"sequence length {reserve} exceeds S_max {self.s_max}")
        if not self._free_slots:
            return None
        n_pay = self.blocks_needed(length)
        total = self.blocks_needed(reserve)
        if len(self._free) < total:
            self._evict_for(total)
            if len(self._free) < total:
                return None
        slot = self._free_slots.pop()
        row = []
        for _ in range(total):
            b = self._free.pop()
            self.ref[b] = 1
            row.append(b)
        dst = np.asarray(row[:n_pay], np.int32)
        wq = payload["quant"]
        for name in ("cache_k", "cache_v"):
            cache = getattr(self, name)
            vals = _wire_to_pool(payload["k" if name == "cache_k" else "v"],
                                 wq, cache)
            if isinstance(cache, (tuple, list)):
                cache = (cache[0].at[:, dst].set(vals[0]),
                         cache[1].at[:, dst].set(vals[1]))
            else:
                cache = cache.at[:, dst].set(
                    kv_rows(vals, cache.shape[-1]))
            setattr(self, name, cache)
        self.tables[slot, :] = 0
        self.tables[slot, :len(row)] = row
        self.n_table[slot] = len(row)
        self.owner[slot] = owner
        self.lengths[slot] = length
        self.total_allocs += 1
        self.imports += 1
        self.import_bytes += int(payload["nbytes"])
        if prompt is not None and len(prompt) <= length:
            self.register_prefix(prompt, slot)
        self._gauges()
        return slot

    # ------------------------------------------------------------- #

    def stats(self):
        """JSON-able pool view (bench/telemetry surface)."""
        return {
            "block": self.block,
            "n_blocks": self.n_blocks,
            "blocks_free": self.free_blocks,
            "blocks_shared": self.blocks_shared,
            "prefix_entries": len(self._prefix),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            "spills": self.spills,
            "exports": self.exports,
            "imports": self.imports,
            "export_bytes": self.export_bytes,
            "import_bytes": self.import_bytes,
            "quant": self.quant or "off",
            "cache_bytes": self.cache_bytes,
            "full_bytes": self.full_bytes,
            "window_bytes": self.window_bytes,
            "window_layers": self.window_layers,
            "window_ring": self.ring,
            "window_blocks_free": self.free_window_blocks,
            "window_blocks_recycled": self.window_blocks_recycled,
            "latent": self.latent,
            "index_bytes": self.index_bytes,
            "state_bytes": self.state_bytes,
            "state_resets": self.state_resets,
        }
