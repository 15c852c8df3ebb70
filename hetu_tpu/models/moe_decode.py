"""MoE serving: top-k routed expert FFN inside the ONE compiled core.

The fork is the MoE-oriented Hetu branch, yet PRs 2–18 built the whole
serving stack dense-GPT-only.  This module threads the flagship model
family through it: ``MoEDecodeConfig`` describes a GPT whose FFN blocks
(every ``moe_every``-th layer, the BertMoE alternation) are top-k
routed expert stacks, and :func:`moe_ffn` is the pure-jax serving twin
of ``layers/moe.py``'s graph-op gate math — same softmax gate, same
``capacity = k * ceil(tokens/E * cf)`` static capacity, same
rank-offset cumsum slotting, same drop rule (a token past capacity
takes the residual path, never a wrong token).  Every serving core in
``models/gpt_decode.py`` (decode step, flash prefill, verify, chunk,
mixed wave) swaps its dense FFN for this function through the shared
``_ffn_block`` seam, so offline ``generate_fast`` and the continuous-
batching engine keep decoding token-identically through ONE compiled
core — the MoE spec rides the jit-static ``cfg_tuple`` as a sixth,
hashable element.

Expert parallelism follows the ``tp_shard_params`` idiom:
:func:`ep_shard_params` places the ``*_moe_expert_stack_w1/w2`` leaves
with the expert dim over an ``ep`` mesh axis and GSPMD materializes
the dispatch/combine all-to-all around the per-expert matmuls — the
model code needs no annotations.  :func:`moe_ffn_ep_reference` is the
EXPLICIT ``shard_map`` + ``lax.all_to_all`` formulation (reference
moe_layer.py:74 placement), parity-tested against :func:`moe_ffn` and
carrying the optional int8 wire (``HETU_MOE_QUANT`` — the PR 9 codec:
quantize → all_to_all → dequantize, the EQuARX direction).

Routing statistics (per-expert load/drop counts) are computed IN the
compiled step and surfaced by the serving wrappers, so expert
imbalance — THE MoE production failure mode — is a first-class
observable in telemetry, ``hetu_top``, and the bench artifact.

Two routers live here.  The CAPACITY router above (``MoESpec``,
:func:`moe_ffn`: GPT-2's block, static capacity, dropped tokens) always
holds every expert.  The DROPLESS router (``RoutedSpec``,
:func:`routed_ffn`: the block-spec families' FFN, no capacity, grouped
matmuls over sorted assignments) is the one that can be told it holds a
SHARE of its experts (``RoutedSpec.held``): it still scores and chooses
over all of them and computes the part its own give, as one chip of an
expert-parallel deployment does; it can also put its experts at a latent
width and give them a squared-ReLU form.

Speculative decoding: the truncated-layer draft SKIPS ROUTING ENTIRELY
(``MoESpec.draft``) — its MoE layers contribute zero FFN (attention +
residual only), so drafting needs no dispatch, no capacity, and no
expert weights beyond what the target already holds; acceptance stays
exact because the target's verify pass owns every emitted token.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import envvars
from .gpt import GPTConfig


class MoESpec(NamedTuple):
    """Hashable MoE routing descriptor — the sixth, jit-static element
    of the serving ``cfg_tuple``.  ``draft=True`` marks the truncated-
    layer speculative draft, whose MoE layers skip the FFN sublayer
    entirely (zero contribution; the residual stream carries)."""

    num_experts: int
    top_k: int
    capacity_factor: float
    moe_every: int
    draft: bool = False
    ep_axis: Optional[str] = None

    def is_moe_layer(self, i):
        """BertMoE alternation: block i carries the MoE FFN when
        ``i % moe_every == moe_every - 1`` (1 = every block)."""
        return i % self.moe_every == self.moe_every - 1

    def moe_layers(self, L):
        """How many of the first ``L`` blocks are MoE blocks."""
        return sum(1 for i in range(L) if self.is_moe_layer(i))


class MoEDecodeConfig(GPTConfig):
    """GPTConfig + MoE routing for the serving stack.  ``ffn_size``
    keeps GPTConfig's meaning for the DENSE interleaved blocks;
    ``expert_size`` (default ``ffn_size``) is each expert's hidden
    width — equal-active-params A/Bs shrink it so that
    ``top_k * expert_size ≈ dense ffn_size``."""

    def __init__(self, num_experts=4, top_k=2, capacity_factor=1.0,
                 moe_every=1, expert_size=None, ep_axis=None, **kw):
        super().__init__(**kw)
        if num_experts < 2:
            raise ValueError(
                f"num_experts must be >= 2, got {num_experts}")
        if not 1 <= top_k <= num_experts:
            raise ValueError(
                f"top_k={top_k} outside [1, num_experts={num_experts}]")
        if not 1 <= moe_every <= self.num_hidden_layers:
            raise ValueError(
                f"moe_every={moe_every} outside [1, num_hidden_layers="
                f"{self.num_hidden_layers}]")
        if capacity_factor <= 0:
            raise ValueError(
                f"capacity_factor must be > 0, got {capacity_factor}")
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.moe_every = int(moe_every)
        self.expert_size = int(expert_size or self.ffn_size)
        self.ep_axis = ep_axis


def resolve_moe_capacity(cf=None):
    """Serving capacity-factor override: an explicit value wins, else
    ``$HETU_MOE_CAPACITY`` (> 0), else None (the config's own)."""
    if cf is not None:
        return float(cf)
    raw = envvars.get_str("HETU_MOE_CAPACITY")
    if raw:
        v = float(raw)
        if v > 0:
            return v
    return None


def moe_spec_of(config, draft=False):
    """The :class:`MoESpec` a config implies, or None for a dense one.
    Duck-typed on ``num_experts`` so ``MoEDecodeConfig`` subclasses and
    hand-rolled config objects both route."""
    e = getattr(config, "num_experts", None)
    if not e:
        return None
    cf = resolve_moe_capacity() or float(
        getattr(config, "capacity_factor", 1.0))
    return MoESpec(
        num_experts=int(e),
        top_k=int(getattr(config, "top_k", 1)),
        capacity_factor=cf,
        moe_every=int(getattr(config, "moe_every", 1)),
        draft=bool(draft),
        ep_axis=getattr(config, "ep_axis", None))


def moe_capacity(spec, num_tokens):
    """Static per-expert slot count for a wave of ``num_tokens``
    (python int) — ``layers/moe.py topkgating``'s formula verbatim:
    ``k * ceil(tokens/E * capacity_factor)``, floored at ``k`` so a
    single-token wave always fits its own top-k."""
    cap = spec.top_k * math.ceil(
        (num_tokens / spec.num_experts) * spec.capacity_factor)
    return max(int(cap), spec.top_k)


def moe_ffn(params, us, x, spec, valid=None, stats=None):
    """Top-k routed expert FFN over a flat token block (the serving
    twin of ``layers/moe.py``'s gate → capacity dispatch → batched
    expert matmul → weighted combine).

    x: [T, D] (the post-LN FFN input); valid: [T] bool or None — False
    rows (pad positions, dead slots, inert ride-alongs) are excluded
    from routing so they never compete for expert capacity and never
    perturb another request's output (batch-company independence, the
    engine's core determinism contract).  Returns y [T, D]; a token
    dropped by EVERY rank contributes exactly 0 — its residual stream
    carries it unchanged, never a wrong token.

    Combine weights are the RAW softmax gate probabilities (reference
    topkgating: ``gates_s`` are un-renormalized) — so with
    ``top_k == num_experts`` the weights sum to 1 and replicated
    experts reproduce the dense FFN exactly (the oracle test).

    ``stats`` (optional dict, mutated at trace time): accumulates
    ``load``/``drop`` int32 [E] — per-expert tokens kept / tokens past
    capacity THIS call.  load + drop sums to valid_tokens * top_k per
    MoE layer, the invariant ``hetu_trace --check`` enforces.
    """
    E, k = spec.num_experts, spec.top_k
    T, D = x.shape
    cap = moe_capacity(spec, T)
    x32 = x.astype(jnp.float32)
    gw = params[f"{us}_moe_gate_weight"].astype(jnp.float32)
    gates = jax.nn.softmax(x32 @ gw, axis=-1)              # [T, E] f32
    topv, topi = jax.lax.top_k(gates, k)                   # [T, k]
    vmask = (jnp.ones((T,), bool) if valid is None
             else valid.reshape(T).astype(bool))
    acc = jnp.zeros((E,), jnp.int32)     # slots claimed by prior ranks
    dispatch = jnp.zeros((T, E, cap), jnp.float32)         # 0/1
    combine = jnp.zeros((T, E, cap), jnp.float32)          # gate-weighted
    load = jnp.zeros((E,), jnp.int32)
    drop = jnp.zeros((E,), jnp.int32)
    for r in range(k):
        mask = jax.nn.one_hot(topi[:, r], E,
                              dtype=jnp.int32) * vmask[:, None]
        # exclusive cumsum down the token axis + the slots prior ranks
        # already claimed: one shared [E, cap] pool, exactly
        # topkgating's locations1/locations2 arithmetic
        loc = jnp.cumsum(mask, axis=0) - mask + acc[None, :]
        pos = jnp.sum(loc * mask, axis=1)                  # [T]
        kept = mask * (pos < cap)[:, None]                 # [T, E]
        oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)   # [T, cap]
        d = kept.astype(jnp.float32)[:, :, None] * oh[:, None, :]
        dispatch = dispatch + d
        combine = combine + d * topv[:, r][:, None, None]
        acc = acc + jnp.sum(mask, axis=0)
        load = load + jnp.sum(kept, axis=0)
        drop = drop + jnp.sum(mask - kept, axis=0)
    cdt = x.dtype
    w1 = params[f"{us}_moe_expert_stack_w1"]               # [E, D, F]
    w2 = params[f"{us}_moe_expert_stack_w2"]               # [E, F, D]
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), x)
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    b1 = params.get(f"{us}_moe_expert_stack_b1")
    if b1 is not None:
        h = h + b1[:, None, :]
    h = _gelu_tanh(h)
    h = jnp.einsum("ecf,efd->ecd", h, w2)
    b2 = params.get(f"{us}_moe_expert_stack_b2")
    if b2 is not None:
        # the bias must not leak into EMPTY capacity slots' combine
        # terms — it doesn't (their combine weight is exactly 0) — but
        # a DROPPED token's residual path must also see zero, which the
        # all-zero combine row guarantees
        h = h + b2[:, None, :]
    y = jnp.einsum("tec,ecd->td", combine.astype(cdt), h)
    if stats is not None:
        stats["load"] = stats.get("load", 0) + load
        stats["drop"] = stats.get("drop", 0) + drop
    return y.astype(x.dtype)


def _gelu_tanh(x):
    # local twin of gpt_decode._gelu_tanh (kept here so models.gpt_decode
    # -> models.moe_decode stays a one-way import)
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


# ------------------- dropless routed FFN ------------------- #
#
# The other router there is (one for both is ROADMAP C8): no capacity,
# no dropped token, no [T, E, cap] tensor.  Cost follows the rows routed
# and the experts touched: the T x k assignments are sorted by expert and
# run through grouped matmuls over the stacked expert leaves.  It is THIS
# router, and not the capacity router above, that can be told it holds a
# SHARE of the experts (``RoutedSpec.held``): one chip's part of an
# expert-parallel deployment, computed as that chip computes it.

# how a router scores, and what an expert is
SCORINGS = ("sigmoid", "softmax")
EXPERT_FORMS = ("gated_silu", "relu2")


class RoutedSpec(NamedTuple):
    """The dropless routed FFN of a ``gpt_decode.BlockSpec`` (a layer
    whose FFN kind is "routed"; the capacity router's spec is
    ``MoESpec``, which holds every expert always).
    ``scoring`` "sigmoid": sigmoid scores, the ``top_k`` largest of
    ``score + bias`` chosen (of the scores alone where the layer has no
    ``_moe_router_bias`` leaf: the bias-free sigmoid router); "softmax":
    a softmax over all the experts,
    the ``top_k`` largest chosen, no selection bias.  Either way the
    weights are the scores at the chosen, normalised when ``norm_topk``
    and scaled by ``scale``; ``n_shared`` shared experts' width is
    ``n_shared`` times an expert's (the leaves carry the width, so a
    configuration whose shared width is its own key says 1): the widened
    expert is their SUM, and ``shared_scale`` multiplies it before it
    joins the routed part (``1 / n_shared``: their average; 1.0 traces
    nothing).

    What the layer is told beside that.  ``held`` / ``held_first``: the
    experts ``[held_first, held_first + held)`` are the ones this layer
    HOLDS (0: all ``num_experts``).  The router still scores all
    ``num_experts`` and chooses ``top_k`` of them, the weights are
    normalised over all the chosen, and the layer computes the part its
    held experts give: the expert leaves are ``[held, ...]``.
    ``latent``: the experts work at this width, between a projection
    ``hidden -> latent`` before them and one ``latent -> hidden`` behind
    them (0: at the hidden width, no projection).  ``expert``:
    "gated_silu" (three matrices, ``silu(x W_gate) * (x W_up)`` then
    ``W_down``) | "relu2" (two matrices, ``relu(x W_up) ** 2`` then
    ``W_down``); the shared expert has the same form."""

    num_experts: int
    top_k: int
    scale: float = 1.0
    norm_topk: bool = True
    n_shared: int = 0
    scoring: str = "sigmoid"
    held_first: int = 0
    held: int = 0
    latent: int = 0
    expert: str = "gated_silu"
    shared_scale: float = 1.0
    n_group: int = 1
    topk_group: int = 1

    @property
    def held_experts(self):
        """How many experts the layer holds."""
        return self.held or self.num_experts

    @property
    def holds_a_share(self):
        return self.held_experts < self.num_experts


def route(x, w_router, bias, spec):
    """(chosen experts [T, k] int32, their weights [T, k] f32) for the
    rows ``x`` [T, D]: ``s = sigmoid(float32(x) W_g)``; the ``k`` largest
    of ``s + b`` are CHOSEN; the weights are ``s`` at the chosen,
    normalised to sum 1 (``norm_topk``) and scaled.  A sigmoid router
    with no selection bias hands ``bias`` None and the ``k`` largest of
    ``s`` itself are chosen.  With
    ``spec.scoring`` "softmax" ``s`` is the softmax over all the experts
    and its ``k`` largest are chosen (``bias`` is not read: None).
    With ``spec.n_group`` over 1 the sigmoid router chooses among the
    kept groups' experts alone (``group_limited``, scope
    ``moe_group_select``)."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if spec.scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, sel = jax.lax.top_k(s, spec.top_k)
    else:
        s = jax.nn.sigmoid(logits)
        pick = s if bias is None else s + bias.astype(jnp.float32)
        if spec.n_group > 1:
            pick = group_limited(pick, spec.n_group, spec.topk_group)
        _, sel = jax.lax.top_k(pick, spec.top_k)
    # s at the chosen, by comparison and not by gather (a gather of
    # T x k scalars is 0.33 ms a layer at 8192 rows on a v5e)
    hit = sel[:, :, None] == jnp.arange(s.shape[1])[None, None, :]
    w = jnp.sum(jnp.where(hit, s[:, None, :], 0.0), axis=-1)
    if spec.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * spec.scale


def group_limited(pick, n_group, topk_group):
    """The choice scores ``pick`` [T, E] with every expert outside the
    kept groups at ``-inf``: the experts in ``n_group`` equal groups in
    their order, a group's score the SUM OF ITS TWO LARGEST choice
    scores, the ``topk_group`` largest groups kept (ties by the lower
    group, as ``top_k`` breaks them)."""
    with jax.named_scope("moe_group_select"):
        T, E = pick.shape
        grouped = pick.reshape(T, n_group, E // n_group)
        score = jax.lax.top_k(grouped, min(2, E // n_group))[0].sum(-1)
        _, best = jax.lax.top_k(score, topk_group)          # [T, kept]
        keep = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)                              # [T, n_group]
        return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)


def takes_kernel(rows):
    """The shape rule: whether a grouped matmul of ``rows`` sorted rows
    runs through ``kernels/grouped_matmul`` (else through
    ``jax.lax.ragged_dot``): wherever the rows are a whole number of the
    kernel's row tiles, however few of them a group holds.  The kernel's
    time follows the bytes of the groups that have rows; on the chip, at
    loads taken from served decode waves (ms a layer's pair of products,
    ``ragged_dot`` | the kernel | the touched experts' bytes at 819
    GB/s; PERF.md section 6, PR 49): 1,408 rows over 128 groups of
    [1024, 2688], 64 touched, 2.78 | 1.04 | 0.87; 256 rows over 64 of
    [2304, 896] gated, 52 touched, 4.19 | 0.93 | 0.79; 128 rows over 64
    of [2048, 1536] gated, 32 touched, 1.04 | 0.87 | 0.74; with 4 to 10
    touched both take 0.2 ms.  A static shape alone decides, so a
    program is one or the other, and the engine can ask the same
    question of a wave's row count (``serve.moe.kernel_waves``)."""
    from ..kernels.grouped_matmul import TILE_M
    return rows % TILE_M == 0


def kernel_tiles(group_sizes, rows):
    """The Pallas kernel's (group, row tile) steps for ``rows`` sorted
    rows in groups of ``group_sizes``, or None where the shape rule
    leaves the product with ``jax.lax.ragged_dot``.  A caller with
    several products over the same groups makes them once."""
    if not takes_kernel(rows):
        return None
    from ..kernels.grouped_matmul import group_tiles
    return group_tiles(group_sizes, rows)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def grouped_matmul(lhs, rhs, group_sizes, up=None, tiles=None, act=None):
    """``lhs`` [M, K] rows sorted by group times ``rhs`` [G, K, N], group
    ``g`` owning the next ``group_sizes[g]`` rows; rows past the groups'
    sum come out as anything.  With ``up`` [G, K, N] the gated pair
    ``silu(lhs rhs) * (lhs up)``; with ``act`` "relu2" the product's
    squared ReLU (in the Pallas kernel an epilogue on the float32
    accumulator before its one rounding; behind ``ragged_dot`` an
    elementwise pass of the compiler's).  One algorithm, two tilings,
    chosen by :func:`takes_kernel` from ``M``: rows that are whole row
    tiles run through the Pallas kernel of ``kernels/grouped_matmul``,
    128-row tiles against a whole-K block of the expert's matrix read
    once a call, the gated pair in one call, at 1.2 times the touched
    experts' bytes from a decode wave's 2 rows a group to a chunk
    wave's hundreds (PR 41, PR 49: the compiler's kernel took 1.4 to
    5.3 times them); other row counts (the small shapes of the tests)
    through ``jax.lax.ragged_dot``.  ``tiles``: ``kernel_tiles(
    group_sizes, M)`` if the caller has made them, False if it asked
    and the shape rule said no."""
    if tiles is None:
        tiles = kernel_tiles(group_sizes, lhs.shape[0])
    if not tiles:
        y = jax.lax.ragged_dot(lhs, rhs, group_sizes)
        if act == "relu2":
            return _relu2(y)
        if up is None:
            return y
        return jax.nn.silu(y) * jax.lax.ragged_dot(lhs, up, group_sizes)
    from ..kernels.grouped_matmul import grouped_matmul_tiled
    return grouped_matmul_tiled(lhs, rhs, tiles, up=up, act=act)


def routed_ffn(params, us, x, spec, valid=None, stats=None):
    """Dropless routed FFN plus the shared expert over the flat rows
    ``x`` [T, D] (the post-norm FFN input).  Rows with ``valid`` False
    (padding, dead slots) are routed NOWHERE: they sort behind every
    expert's group, no expert counts them and their output is 0 from
    the routed part (batch company changes no live row's result: each
    row's experts and weights depend on that row alone).  Where the
    layer holds a share of the experts (``spec.held``) an assignment to
    an expert NOT held goes where an invalid row's goes: the router has
    scored all ``num_experts`` and normalised over all the chosen, and
    the layer adds what its own experts give (a row none of whose
    chosen experts is held gets the shared expert alone).  Nothing here
    stands in for the chips that hold the others.  Leaves:
    ``{us}_moe_router_weight`` [D, E], ``{us}_moe_router_bias`` [E] (the
    selection bias; a softmax router has none, and a sigmoid router
    without the leaf chooses by its scores alone),
    ``{us}_moe_experts_gate``/``_up`` [held, W, F],
    ``{us}_moe_experts_down`` [held, F, W] (``W`` the latent width, else
    D; a "relu2" expert has no gate), ``{us}_moe_latent_in_weight`` [D,
    W] and ``_latent_out_weight`` [W, D] where there is a latent width,
    ``{us}_moe_shared_gate_weight`` / ``_up_weight`` [D, Fs],
    ``_down_weight`` [Fs, D].

    ``stats`` (dict, mutated at trace time) accumulates over the routed
    layers ``load`` [held] int32 (assignments a held expert, so that
    where all are held the sum of ``load`` is valid rows x top_k x
    layers), ``touched`` (held experts with load > 0, summed over
    layers) and, of a layer that holds a share, ``routed`` (ALL the
    valid rows' assignments, wherever they went)."""
    E, k = spec.held_experts, spec.top_k
    T, D = x.shape
    vmask = (jnp.ones((T,), bool) if valid is None
             else valid.reshape(T).astype(bool))
    relu2 = spec.expert == "relu2"
    xe = x
    if spec.latent:
        with jax.named_scope("moe_latent_in"):
            xe = x @ params[f"{us}_moe_latent_in_weight"]
    with jax.named_scope("moe_route"):
        sel, w = route(x, params[f"{us}_moe_router_weight"],
                       params.get(f"{us}_moe_router_bias"), spec)
        # an invalid row's assignments go to group E, past the last; so
        # do the assignments to experts this layer does not hold
        here = vmask[:, None]
        if spec.holds_a_share:
            sel = sel - spec.held_first
            here = here & (sel >= 0) & (sel < E)
        expert = jnp.where(here, sel, E).reshape(-1)        # [T k]
        order = jnp.argsort(expert, stable=True)
        load = jnp.sum(expert[:, None] == jnp.arange(E)[None, :], axis=0,
                       dtype=jnp.int32)
        xs = xe[order // k]                                 # [T k, W]
    with jax.named_scope("moe_experts"):
        # once for the layer
        tiles = kernel_tiles(load, T * k) or False
        if relu2:
            a = grouped_matmul(xs, params[f"{us}_moe_experts_up"], load,
                               tiles=tiles, act="relu2")
        else:
            a = grouped_matmul(xs, params[f"{us}_moe_experts_gate"], load,
                               up=params[f"{us}_moe_experts_up"],
                               tiles=tiles)
        ys = grouped_matmul(a, params[f"{us}_moe_experts_down"], load,
                            tiles=tiles)
    with jax.named_scope("moe_route"):
        # the inverse permutation by a second sort (a scatter of T k
        # indices costs seven times as much on the chip); a row past
        # the groups may hold anything, so it is zeroed, not weighted 0
        back = jnp.argsort(order)
        live = jnp.arange(T * k) < jnp.sum(load)
        y = jnp.where(live[:, None], ys, 0)[back].reshape(
            T, k, ys.shape[-1])
        wv = jnp.where(vmask[:, None], w, 0.0)
        # k weighted rows a token, summed in float32 (one fused pass)
        y = sum(y[:, j].astype(jnp.float32) * wv[:, j, None]
                for j in range(k)).astype(x.dtype)
    if spec.latent:
        with jax.named_scope("moe_latent_out"):
            y = y @ params[f"{us}_moe_latent_out_weight"]
    if spec.n_shared:
        with jax.named_scope("moe_shared"):
            if relu2:
                sh = _relu2(x @ params[f"{us}_moe_shared_up_weight"]) \
                    @ params[f"{us}_moe_shared_down_weight"]
            else:
                from .gpt_decode import swiglu
                sh = swiglu(x, params[f"{us}_moe_shared_gate_weight"],
                            params[f"{us}_moe_shared_up_weight"],
                            params[f"{us}_moe_shared_down_weight"])
            if spec.shared_scale != 1.0:
                sh = sh * spec.shared_scale
            y = y + sh
    if stats is not None:
        stats["load"] = stats.get("load", 0) + load
        stats["touched"] = stats.get("touched", 0) + jnp.sum(load > 0)
        if spec.holds_a_share:
            stats["routed"] = stats.get("routed", 0) \
                + k * jnp.sum(vmask, dtype=jnp.int32)
    return y


class LatentMoEConfig:
    """A decoder of latent-attention (MLA) blocks with a dropless routed
    FFN, built from the source's own ``config.json`` keys (the
    ``glm4_moe_lite``/DeepSeek-V3 family's names).  It yields the
    jit-static ``BlockSpec`` the mixed wave reads (``block_spec()``);
    the engine takes the rest from the attributes a ``GPTConfig`` has
    too (``num_hidden_layers``, ``num_attention_heads``,
    ``hidden_size``, ``vocab_size``, ``max_position_embeddings``).
    Unsupported values raise: ``n_group``/``topk_group`` other than 1
    (group-limited selection), ``rope_scaling`` set,
    ``partial_rotary_factor`` other than 1, a ``topk_method`` other than
    ``noaux_tc``, ``attention_bias``, an activation other than SiLU.
    ``num_nextn_predict_layers`` is accepted and not served: the MTP
    layer takes no part in the next-token logits."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size,
                 n_routed_experts, num_experts_per_tok,
                 n_shared_experts=0, routed_scaling_factor=1.0,
                 norm_topk_prob=True, first_k_dense_replace=0,
                 rope_theta=10000.0, rms_norm_eps=1e-6,
                 tie_word_embeddings=False,
                 max_position_embeddings=4096, n_group=1, topk_group=1,
                 rope_scaling=None, partial_rotary_factor=1,
                 topk_method="noaux_tc", attention_bias=False,
                 hidden_act="silu", **ignored):
        for key, value, want in (
                ("n_group", n_group, 1), ("topk_group", topk_group, 1),
                ("rope_scaling", rope_scaling, None),
                ("partial_rotary_factor", partial_rotary_factor, 1),
                ("topk_method", topk_method, "noaux_tc"),
                ("attention_bias", attention_bias, False),
                ("hidden_act", hidden_act, "silu")):
            if value != want:
                raise ValueError(
                    f"LatentMoEConfig: {key}={value!r} is not supported "
                    f"(only {want!r})")
        if not 1 <= num_experts_per_tok <= n_routed_experts:
            raise ValueError(
                f"num_experts_per_tok={num_experts_per_tok} outside "
                f"[1, n_routed_experts={n_routed_experts}]")
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace={first_k_dense_replace} outside "
                f"[0, num_hidden_layers={num_hidden_layers}]")
        if qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.max_position_embeddings = int(max_position_embeddings)
        self.q_lora_rank = int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.tie_word_embeddings = bool(tie_word_embeddings)

    @classmethod
    def from_hf(cls, config):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise)."""
        return cls(**config)

    def routed_spec(self):
        return RoutedSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor,
            norm_topk=self.norm_topk_prob, n_shared=self.n_shared_experts)

    def block_spec(self):
        from .gpt_decode import BlockSpec, LatentSpec
        all_dense = self.first_k_dense_replace >= self.num_hidden_layers
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="latent",
            latent=LatentSpec(self.q_lora_rank, self.kv_lora_rank,
                              self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim),
            ffn="swiglu" if all_dense else "routed",
            leading_dense=0 if all_dense else self.first_k_dense_replace,
            routed=None if all_dense else self.routed_spec(),
            head="tied" if self.tie_word_embeddings else "untied")

    def param_shapes(self, name="glm"):
        """{leaf: shape} of the serving parameter dict: the one list
        ``init_latent_moe_params`` and ``hf.convert_glm4_moe_lite`` agree
        on."""
        d, H = self.hidden_size, self.num_attention_heads
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        dc, dq = self.kv_lora_rank, self.q_lora_rank
        f, fe, E = (self.intermediate_size, self.moe_intermediate_size,
                    self.n_routed_experts)
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,)}
        if not self.tie_word_embeddings:
            shapes[f"{name}_lm_head_weight"] = (d, self.vocab_size)
        for i in range(self.num_hidden_layers):
            us = f"{name}_h{i}"
            shapes.update({
                f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,),
                f"{us}_attn_q_a_weight": (d, dq),
                f"{us}_attn_q_a_norm_scale": (dq,),
                f"{us}_attn_q_b_weight": (dq, H * (dn + dr)),
                f"{us}_attn_kv_a_weight": (d, dc + dr),
                f"{us}_attn_kv_a_norm_scale": (dc,),
                f"{us}_attn_kv_b_weight": (dc, H * (dn + dv)),
                f"{us}_attn_proj_weight": (H * dv, d)})
            if i < self.first_k_dense_replace:
                shapes.update({f"{us}_ffn_gate_weight": (d, f),
                               f"{us}_ffn_up_weight": (d, f),
                               f"{us}_ffn_down_weight": (f, d)})
                continue
            fs = fe * self.n_shared_experts
            shapes.update({f"{us}_moe_router_weight": (d, E),
                           f"{us}_moe_router_bias": (E,),
                           f"{us}_moe_experts_gate": (E, d, fe),
                           f"{us}_moe_experts_up": (E, d, fe),
                           f"{us}_moe_experts_down": (E, fe, d)})
            if fs:
                shapes.update({f"{us}_moe_shared_gate_weight": (d, fs),
                               f"{us}_moe_shared_up_weight": (d, fs),
                               f"{us}_moe_shared_down_weight": (fs, d)})
        return shapes


def init_latent_moe_params(config, name="glm", seed=0, scale=0.02,
                           bias_scale=0.1, dtype=jnp.float32):
    """Seeded random serving params for a ``LatentMoEConfig``, made on
    the device in one jitted call: weights normal(``scale``), norm
    scales 1, the selection bias normal(``bias_scale``) so that choosing
    by ``s + b`` and weighting by ``s`` differ.  The router's weight and
    bias stay float32 whatever ``dtype`` is."""
    return _init_routed_params(config.param_shapes(name), seed, scale,
                               bias_scale, dtype)


def _init_routed_params(shapes, seed, scale, bias_scale, dtype):
    def make(key):
        out = {}
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif n.endswith("_moe_router_bias"):
                out[n] = bias_scale * jax.random.normal(k, shape,
                                                        jnp.float32)
            elif n.endswith("_moe_router_weight"):
                out[n] = scale * jax.random.normal(k, shape, jnp.float32)
            else:
                out[n] = (scale * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# ------------- gated short convolutions beside attention ------------- #


class HybridMoEConfig:
    """A decoder whose layers are EACH a gated short convolution, a
    grouped-query attention over everything before it, or the same
    attention over a sliding window (RoPE over the whole head), each
    over a dense SwiGLU (the leading ``num_dense_layers``) or a dropless
    routed FFN with no shared expert: built from the source's own
    ``config.json`` keys.  It yields the jit-static ``BlockSpec`` the
    mixed wave reads (``block_spec()``: the operator of every layer is
    in it); the engine takes the rest from the attributes a ``GPTConfig``
    has too.  Two families write such a file, told apart by
    ``model_type`` (``FAMILIES``: what a family's files do not say):
    ``lfm2_moe`` (the default: conv and full-attention layers, RMSNorm on
    every head's q and k, sigmoid scores with a selection bias, the
    embedding table as the head, ``norm_eps``) and ``mellum``
    ("sliding_attention" layers of ``sliding_window`` positions beside
    "full_attention" ones, rotary parameters BY LAYER KIND in
    ``rope_parameters``, YaRN among them, no q/k norm, a softmax router
    with no bias, ``rms_norm_eps``, ``head_dim`` and
    ``tie_word_embeddings`` its own keys).  Values it cannot run raise:
    ``conv_bias`` or ``attention_bias`` true, a ``layer_types`` entry it
    has no operator for, a list that is not ``num_hidden_layers`` long,
    query heads that are not a whole number a K/V head, a "conv" layer
    without ``conv_L_cache``, a sliding layer without ``sliding_window``,
    a ``rope_type`` other than "default" / "yarn", an ``mlp_layer_types``
    entry other than "sparse" past the dense layers, an activation other
    than SiLU.  ``use_expert_bias`` false is run with a zero selection
    bias (``init_hybrid_moe_params`` and the converter make it)."""

    OPERATORS = {"conv": "conv", "full_attention": "attention",
                 "sliding_attention": "window_attention"}
    FAMILIES = {
        "lfm2_moe": {"qk_norm": True, "scoring": "sigmoid", "tied": True},
        "mellum": {"qk_norm": False, "scoring": "softmax", "tied": False},
    }

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, layer_types,
                 intermediate_size, moe_intermediate_size,
                 num_experts, num_experts_per_tok, conv_L_cache=0,
                 num_dense_layers=0,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0, rope_theta=1000000.0,
                 norm_eps=None, rms_norm_eps=None, conv_bias=False,
                 max_position_embeddings=128000, model_type="lfm2_moe",
                 head_dim=None, sliding_window=0, rope_parameters=None,
                 tie_word_embeddings=None, mlp_layer_types=None,
                 attention_bias=False, hidden_act="silu", **ignored):
        if model_type not in self.FAMILIES:
            raise ValueError(
                f"HybridMoEConfig: model_type={model_type!r}; it runs "
                f"{sorted(self.FAMILIES)}")
        family = self.FAMILIES[model_type]
        unknown = sorted(set(layer_types) - set(self.OPERATORS))
        if unknown:
            raise ValueError(
                f"HybridMoEConfig: layer_types holds {unknown}; it runs "
                f"{sorted(self.OPERATORS)}")
        if len(layer_types) != num_hidden_layers:
            raise ValueError(
                f"HybridMoEConfig: {len(layer_types)} layer_types for "
                f"num_hidden_layers={num_hidden_layers}")
        for key, value in (("conv_bias", conv_bias),
                           ("attention_bias", attention_bias)):
            if value:
                raise ValueError(f"HybridMoEConfig: {key}=True is not "
                                 f"supported (only False)")
        if hidden_act != "silu":
            raise ValueError(f"HybridMoEConfig: hidden_act={hidden_act!r} "
                             f"is not supported (only 'silu')")
        if "conv" in layer_types and conv_L_cache < 2:
            raise ValueError(f"conv_L_cache={conv_L_cache}: a short "
                             f"convolution has at least 2 taps")
        if "sliding_attention" in layer_types and sliding_window < 1:
            raise ValueError(
                f"sliding_window={sliding_window}: a sliding_attention "
                f"layer sees at least itself")
        if num_attention_heads % num_key_value_heads \
                or (head_dim is None and hidden_size % num_attention_heads):
            raise ValueError(
                f"hidden_size={hidden_size}, num_attention_heads="
                f"{num_attention_heads} and num_key_value_heads="
                f"{num_key_value_heads} do not divide")
        head_dim = int(head_dim or hidden_size // num_attention_heads)
        if head_dim % 2:
            raise ValueError("the head size must be even (RoPE)")
        if not 1 <= num_experts_per_tok <= num_experts:
            raise ValueError(
                f"num_experts_per_tok={num_experts_per_tok} outside "
                f"[1, num_experts={num_experts}]")
        if not 0 <= num_dense_layers <= num_hidden_layers:
            raise ValueError(
                f"num_dense_layers={num_dense_layers} outside "
                f"[0, num_hidden_layers={num_hidden_layers}]")
        if mlp_layer_types is not None and (
                len(mlp_layer_types) != num_hidden_layers
                or set(mlp_layer_types[num_dense_layers:]) - {"sparse"}):
            raise ValueError(
                f"HybridMoEConfig: mlp_layer_types={list(mlp_layer_types)}"
                f": every layer past the {num_dense_layers} dense ones is "
                f"'sparse', one entry a layer")
        self.model_type = model_type
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.max_position_embeddings = int(max_position_embeddings)
        self.layer_types = tuple(layer_types)
        self.conv_L_cache = int(conv_L_cache)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        # not ``num_experts``: ``moe_spec_of`` reads that attribute as
        # the capacity router's
        self.n_routed_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_dense_layers = int(num_dense_layers)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.scoring = family["scoring"]
        # a softmax router chooses by its scores alone
        self.use_expert_bias = bool(use_expert_bias) \
            and self.scoring == "sigmoid"
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_theta = float(rope_theta)
        eps = norm_eps if norm_eps is not None else rms_norm_eps
        self.norm_eps = float(1e-5 if eps is None else eps)
        self.head_dim = head_dim
        self.qk_norm = family["qk_norm"]
        self.tie_word_embeddings = family["tied"] \
            if tie_word_embeddings is None else bool(tie_word_embeddings)
        self.sliding_window = int(sliding_window) \
            if "sliding_attention" in layer_types else 0
        # rotary parameters BY LAYER KIND (the source's nested
        # ``rope_parameters``), None where one ``rope_theta`` serves all
        self.rope_parameters = rope_parameters

    @classmethod
    def from_hf(cls, config):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise).  Newer exports keep
        ``rope_theta`` inside ``rope_parameters``; a file whose
        ``rope_parameters`` has a section a ``layer_types`` name keeps
        them by layer kind."""
        config = dict(config)
        rope = config.pop("rope_parameters", None) or {}
        if any(k in rope for k in cls.OPERATORS):
            config["rope_parameters"] = rope
            first = next(iter(rope.values()))
            config.setdefault("rope_theta", first.get("rope_theta",
                                                      1000000.0))
        else:
            config.setdefault("rope_theta",
                              rope.get("rope_theta", 1000000.0))
        return cls(**config)

    def operators(self):
        """("conv" | "attention" | "window_attention") for every
        layer."""
        return tuple(self.OPERATORS[t] for t in self.layer_types)

    def rope_by_op(self):
        """((operator, inv_freq, factor), ...) for a file with rotary
        parameters by layer kind (``gpt_decode.rope_frequencies``,
        computed once, on the host), else None."""
        if not self.rope_parameters:
            return None
        from .gpt_decode import rope_frequencies
        return tuple(
            (self.OPERATORS[kind],) + rope_frequencies(self.head_dim, **p)
            for kind, p in sorted(self.rope_parameters.items())
            if kind in self.layer_types)

    def routed_spec(self):
        return RoutedSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor,
            norm_topk=self.norm_topk_prob, n_shared=0,
            scoring=self.scoring)

    def block_spec(self):
        from .gpt_decode import BlockSpec
        all_dense = self.num_dense_layers >= self.num_hidden_layers
        own_head = self.head_dim * self.num_attention_heads \
            != self.hidden_size
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="gqa", bias=False,
            kv_heads=self.num_key_value_heads, qk_norm=self.qk_norm,
            ops=self.operators(), conv_kernel=self.conv_L_cache,
            ffn="swiglu" if all_dense else "routed",
            leading_dense=0 if all_dense else self.num_dense_layers,
            routed=None if all_dense else self.routed_spec(),
            head="tied" if self.tie_word_embeddings else "untied",
            head_dim=self.head_dim if own_head else 0,
            window=self.sliding_window, rope_by_op=self.rope_by_op())

    def param_shapes(self, name="lfm"):
        """{leaf: shape} of the serving parameter dict: the one list
        ``init_hybrid_moe_params`` and ``hf.convert_lfm2_moe`` agree
        on."""
        d, dh = self.hidden_size, self.head_dim
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        f, fe, E = (self.intermediate_size, self.moe_intermediate_size,
                    self.n_routed_experts)
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,)}
        if not self.tie_word_embeddings:
            shapes[f"{name}_lm_head_weight"] = (d, self.vocab_size)
        for i, op in enumerate(self.operators()):
            us = f"{name}_h{i}"
            shapes.update({f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,)})
            if op == "conv":
                shapes.update({
                    f"{us}_conv_in_weight": (d, 3 * d),
                    f"{us}_conv_weight": (self.conv_L_cache, d),
                    f"{us}_conv_out_weight": (d, d)})
            else:
                shapes.update({
                    f"{us}_attn_q_weight": (d, hq * dh),
                    f"{us}_attn_k_weight": (d, hkv * dh),
                    f"{us}_attn_v_weight": (d, hkv * dh),
                    f"{us}_attn_proj_weight": (hq * dh, d)})
                if self.qk_norm:
                    shapes.update({
                        f"{us}_attn_q_norm_scale": (dh,),
                        f"{us}_attn_k_norm_scale": (dh,)})
            if i < self.num_dense_layers:
                shapes.update({f"{us}_ffn_gate_weight": (d, f),
                               f"{us}_ffn_up_weight": (d, f),
                               f"{us}_ffn_down_weight": (f, d)})
            else:
                shapes.update({f"{us}_moe_router_weight": (d, E),
                               f"{us}_moe_experts_gate": (E, d, fe),
                               f"{us}_moe_experts_up": (E, d, fe),
                               f"{us}_moe_experts_down": (E, fe, d)})
                if self.scoring == "sigmoid":
                    shapes[f"{us}_moe_router_bias"] = (E,)
        return shapes


def init_hybrid_moe_params(config, name="lfm", seed=0, scale=0.02,
                           bias_scale=0.1, dtype=jnp.float32):
    """Seeded random serving params for a ``HybridMoEConfig``, as
    ``init_latent_moe_params`` makes them (one jitted call on the
    device; norm scales 1; the router's weight and selection bias
    float32, the bias zero where the config has ``use_expert_bias``
    false); the conv taps are weights like any other."""
    return _init_routed_params(
        config.param_shapes(name), seed, scale,
        bias_scale if config.use_expert_bias else 0.0, dtype)


# ------------------- expert-parallel placement ------------------- #


def ep_shard_params(params, mesh, config, axis="ep", name=None):
    """Place a MoE-GPT parameter dict for EXPERT-PARALLEL decoding: the
    ``*_moe_expert_stack_*`` leaves shard their leading expert dim over
    ``axis``; everything else (gate, attention, embeddings, dense FFN
    blocks) replicates.  Like ``tp_shard_params``, the decode cores
    need no other change — GSPMD propagates the expert sharding
    through the dispatch/combine einsums and materializes the token
    all-to-all at the resharding boundary.

    Validated up front by ``analysis.shard_check.check_expert_mesh``
    (axis exists, num_experts divisible) so a bad mesh is rejected
    before any buffer moves or compiles."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..analysis.shard_check import check_expert_mesh
    check_expert_mesh(mesh, int(config.num_experts), axis=axis)
    from .gpt_decode import _infer_name
    name = _infer_name(params, name)

    def spec_for(k):
        if "_moe_expert_stack_w" in k:
            return P(axis, None, None)
        if "_moe_expert_stack_b" in k:
            return P(axis, None)
        return P()

    return {k: jax.device_put(np.asarray(v),
                              NamedSharding(mesh, spec_for(k)))
            for k, v in params.items() if k.startswith(name + "_")}


def resolve_moe_quant(mode=None):
    """int8 dispatch/combine all-to-all wire: explicit ``mode`` wins,
    else ``$HETU_MOE_QUANT`` (the shared quant-knob grammar)."""
    from ..quant import resolve_quant
    return resolve_quant(mode, "HETU_MOE_QUANT")


def _a2a_wire(x, axis, split_axis, concat_axis, quant):
    """One all-to-all hop, optionally int8 on the wire (the PR 9
    codec: per-row symmetric quantize → exchange payload AND scales →
    dequantize).  Exactness note: quantization error is bounded by
    amax/254 per element (quant.py); the parity test pins the
    tolerance."""
    if not quant:
        return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                                  concat_axis=concat_axis)
    from ..quant import dequantize_jax, quantize_jax
    d = x.shape[-1]
    q, scales = quantize_jax(x.astype(jnp.float32), chunk=d)
    q = jax.lax.all_to_all(q, axis, split_axis=split_axis,
                           concat_axis=concat_axis)
    scales = jax.lax.all_to_all(scales, axis, split_axis=split_axis,
                                concat_axis=concat_axis)
    return dequantize_jax(q, scales, chunk=d).astype(x.dtype)


def moe_ffn_ep_reference(params, us, x, spec, mesh, quant=None):
    """The EXPLICIT expert-parallel formulation: tokens sharded over
    the ``ep`` axis, per-shard gate + capacity dispatch, ``lax.
    all_to_all`` to expert-major, local expert matmuls over the expert
    shard, all-to-all back, per-shard combine — reference
    moe_layer.py's ``_stacked_forward`` collective placement, written
    in ``shard_map``.  ``quant``/"$HETU_MOE_QUANT" rides the exchange
    in int8 (payload + per-row scales).

    This is the parity/wire REFERENCE, not the serving hot path (the
    jitted cores use GSPMD propagation from :func:`ep_shard_params`):
    capacity is per-shard (each shard's ``T/n`` tokens), so it equals
    :func:`moe_ffn` exactly only while capacity is un-binding — which
    is precisely the regime the parity tests pin.

    x: [T, D] with T divisible by the axis size.  Returns y [T, D].
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    axis = spec.ep_axis or "ep"
    n = int(mesh.shape[axis])
    E = spec.num_experts
    if E % n:
        raise ValueError(
            f"num_experts={E} not divisible by {axis}={n}")
    T = x.shape[0]
    if T % n:
        raise ValueError(
            f"token count {T} not divisible by {axis}={n}")
    quant = resolve_moe_quant(quant)
    gw = params[f"{us}_moe_gate_weight"]
    w1 = params[f"{us}_moe_expert_stack_w1"]
    w2 = params[f"{us}_moe_expert_stack_w2"]
    b1 = params.get(f"{us}_moe_expert_stack_b1")
    b2 = params.get(f"{us}_moe_expert_stack_b2")
    if b1 is None:
        b1 = jnp.zeros((E, w1.shape[-1]), x.dtype)
    if b2 is None:
        b2 = jnp.zeros((E, w2.shape[-1]), x.dtype)
    k = spec.top_k
    cap = moe_capacity(spec, T // n)

    def local(xs, gw, w1, b1, w2, b2):
        # xs [T/n, D]; w1/w2/b1/b2 hold THIS shard's E/n experts
        t = xs.shape[0]
        x32 = xs.astype(jnp.float32)
        gates = jax.nn.softmax(x32 @ gw.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(gates, k)
        acc = jnp.zeros((E,), jnp.int32)
        dispatch = jnp.zeros((t, E, cap), jnp.float32)
        combine = jnp.zeros((t, E, cap), jnp.float32)
        for r in range(k):
            mask = jax.nn.one_hot(topi[:, r], E, dtype=jnp.int32)
            loc = jnp.cumsum(mask, axis=0) - mask + acc[None, :]
            pos = jnp.sum(loc * mask, axis=1)
            kept = mask * (pos < cap)[:, None]
            oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)
            d = kept.astype(jnp.float32)[:, :, None] * oh[:, None, :]
            dispatch = dispatch + d
            combine = combine + d * topv[:, r][:, None, None]
            acc = acc + jnp.sum(mask, axis=0)
        xe = jnp.einsum("tec,td->ecd", dispatch, x32)      # [E, cap, D]
        # DISPATCH all-to-all: expert-major — each device keeps its
        # E/n experts' slots from every peer: [E/n, n*cap, D]
        xe = _a2a_wire(xe, axis, 0, 1, quant)
        h = jnp.einsum("ecd,edf->ecf", xe,
                       w1.astype(jnp.float32)) + b1.astype(
                           jnp.float32)[:, None, :]
        h = _gelu_tanh(h)
        h = jnp.einsum("ecf,efd->ecd", h,
                       w2.astype(jnp.float32)) + b2.astype(
                           jnp.float32)[:, None, :]
        # COMBINE all-to-all: the exact inverse hop, back to
        # token-major [E, cap, D]
        h = _a2a_wire(h, axis, 1, 0, quant)
        y = jnp.einsum("tec,ecd->td", combine, h)
        return y.astype(xs.dtype)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis))
    return fn(x, gw, w1, b1, w2, b2)


# ------------------------- param builders ------------------------- #


def init_moe_params(config, name="moe", seed=0, scale=0.02):
    """Random MoE-GPT serving params (numpy): the dense-GPT naming
    contract (``{name}_wte_table`` .. per-layer attention/LN/dense FFN)
    plus, on each MoE block, the gate ``{us}_moe_gate_weight`` [D, E]
    and the StackedExperts-named stacks ``{us}_moe_expert_stack_w1``
    [E, D, F] / ``_w2`` [E, F, D] / ``_b1`` [E, F] / ``_b2`` [E, D].
    Dense interleaved blocks keep ``ffn_wi/wo`` only."""
    c = config
    spec = moe_spec_of(c)
    rng = np.random.default_rng(seed)
    D = c.hidden_size
    F_dense, F_exp = c.ffn_size, c.expert_size
    E = c.num_experts

    def r(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    p = {
        f"{name}_wte_table": r(c.vocab_size, D),
        f"{name}_wpe": r(c.max_position_embeddings, D),
        f"{name}_ln_f_scale": np.ones(D, np.float32),
        f"{name}_ln_f_bias": np.zeros(D, np.float32),
    }
    for i in range(c.num_hidden_layers):
        us = f"{name}_h{i}"
        p.update({
            f"{us}_ln1_scale": np.ones(D, np.float32),
            f"{us}_ln1_bias": np.zeros(D, np.float32),
            f"{us}_ln2_scale": np.ones(D, np.float32),
            f"{us}_ln2_bias": np.zeros(D, np.float32),
            f"{us}_attn_q_weight": r(D, D),
            f"{us}_attn_q_bias": np.zeros(D, np.float32),
            f"{us}_attn_k_weight": r(D, D),
            f"{us}_attn_k_bias": np.zeros(D, np.float32),
            f"{us}_attn_v_weight": r(D, D),
            f"{us}_attn_v_bias": np.zeros(D, np.float32),
            f"{us}_attn_proj_weight": r(D, D),
            f"{us}_attn_proj_bias": np.zeros(D, np.float32),
        })
        if spec.is_moe_layer(i):
            p.update({
                f"{us}_moe_gate_weight": r(D, E),
                f"{us}_moe_expert_stack_w1": r(E, D, F_exp),
                f"{us}_moe_expert_stack_b1": np.zeros((E, F_exp),
                                                      np.float32),
                f"{us}_moe_expert_stack_w2": r(E, F_exp, D),
                f"{us}_moe_expert_stack_b2": np.zeros((E, D),
                                                      np.float32),
            })
        else:
            p.update({
                f"{us}_ffn_wi_weight": r(D, F_dense),
                f"{us}_ffn_wi_bias": np.zeros(F_dense, np.float32),
                f"{us}_ffn_wo_weight": r(F_dense, D),
                f"{us}_ffn_wo_bias": np.zeros(D, np.float32),
            })
    return p


def convert_dense_to_moe(params, config, moe_config, name=None):
    """Replicate a DENSE GPT's FFN blocks into expert stacks: every
    expert of every MoE block carries the dense layer's exact wi/wo
    (and biases).  With ``top_k == num_experts`` the raw softmax
    combine weights sum to 1, so routing reproduces the dense FFN —
    the oracle the acceptance criteria pin (and a regression anchor
    for the gate math: any renormalization bug breaks it).  Gate
    weights are zero → uniform gates, maximally-even routing."""
    from .gpt_decode import _infer_name
    name = _infer_name(params, name)
    spec = moe_spec_of(moe_config)
    E = spec.num_experts
    out = {k: np.asarray(v) for k, v in params.items()
           if k.startswith(name + "_")}
    for i in range(moe_config.num_hidden_layers):
        if not spec.is_moe_layer(i):
            continue
        us = f"{name}_h{i}"
        wi = out.pop(f"{us}_ffn_wi_weight")
        bi = out.pop(f"{us}_ffn_wi_bias")
        wo = out.pop(f"{us}_ffn_wo_weight")
        bo = out.pop(f"{us}_ffn_wo_bias")
        D = wi.shape[0]
        out[f"{us}_moe_gate_weight"] = np.zeros((D, E), np.float32)
        out[f"{us}_moe_expert_stack_w1"] = np.broadcast_to(
            wi, (E,) + wi.shape).copy()
        out[f"{us}_moe_expert_stack_b1"] = np.broadcast_to(
            bi, (E,) + bi.shape).copy()
        out[f"{us}_moe_expert_stack_w2"] = np.broadcast_to(
            wo, (E,) + wo.shape).copy()
        out[f"{us}_moe_expert_stack_b2"] = np.broadcast_to(
            bo, (E,) + bo.shape).copy()
    return out
