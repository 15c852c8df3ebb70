"""The ``cohere2_moe`` family for the mixed ragged wave: a decoder whose
every layer is a PARALLEL block on one bias-free, mean-centred LayerNorm,

    x = LN(h)      h <- h + Attn(x) + FFN(x)

(``BlockSpec.residual`` "parallel", ``norm`` "layernorm_nobias"), the
attention grouped-query with positions BY LAYER KIND (``layer_types``):

  sliding_attention   rotated (``rope_theta``, over the whole head), a
                      query sees itself and the ``sliding_window - 1``
                      positions before it: operator "window_attention",
                      K/V pages in the window pool's ring
  full_attention      NO positions of any kind (its entry of
                      ``rope_by_op`` says "none"), every position before
                      the query in sight: operator "attention", K/V pages
                      in the pool

and the FFN a sigmoid router WITHOUT a selection bias over all
``num_experts`` gated-SiLU experts (``num_experts_per_tok`` chosen,
``norm_topk_prob``) beside ``num_shared_experts`` shared experts of the
same width whose outputs are AVERAGED (``RoutedSpec.shared_scale`` = 1 /
their number, over the widened expert that is their sum).  The head is
the embedding table under ``logit_scale`` after a final LayerNorm.

A layer may be told which experts it HOLDS (``held_experts``: first and
count) and the table which rows (``vocab_rows``): one chip's share of an
expert-parallel deployment.  The router's width stays ``num_experts``.

The published leaves rotate INTERLEAVED pairs (``rope_gptj``: columns
``2j`` and ``2j + 1`` of a head); the wave's ``_rope`` rotates halves
(columns ``j`` and ``j + d / 2``, lane-contiguous on the chip).  One
permutation of the columns of ``W_q`` and ``W_k`` inside every head, even
columns first and then odd, makes the two the same rotation, and ``q .
k`` does not change under one permutation of both:
``ParallelMoEConfig.permute_rotary`` is that step, taken ONCE at set-up
on the leaves of the layers that rotate.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_OPS = {"sliding_attention": "window_attention",
             "full_attention": "attention"}

# what each weight product's output is, in units of its input's RMS, at
# the seeded weights: a weight's deviation is ``gain / sqrt(fan_in)``
# (the tied table's ``embedding / sqrt(hidden)``: the final norm's rows
# have RMS 1, so the logits' deviation is ``embedding``).  A query's and
# a key's columns have RMS ``attn_q`` and ``attn_k``, so the scores'
# deviation is their product and the softmax is PEAKED: which positions
# a layer has in sight moves its output.
DEFAULT_GAINS = {
    "embedding": 1.0, "attn_q": 1.75, "attn_k": 1.75, "attn_v": 1.0,
    "attn_out": 0.5, "router": 1.0, "experts_up": 1.0,
    "experts_down": 0.5, "shared_up": 1.0, "shared_down": 1.0}

_GAIN_OF = {
    "_wte_table": "embedding", "_attn_q_weight": "attn_q",
    "_attn_k_weight": "attn_k", "_attn_v_weight": "attn_v",
    "_attn_proj_weight": "attn_out", "_moe_router_weight": "router",
    "_moe_experts_gate": "experts_up", "_moe_experts_up": "experts_up",
    "_moe_experts_down": "experts_down",
    "_moe_shared_gate_weight": "shared_up",
    "_moe_shared_up_weight": "shared_up",
    "_moe_shared_down_weight": "shared_down"}


class ParallelMoEConfig:
    """Built from the source's own ``config.json`` keys (``from_hf``).
    It yields the jit-static ``BlockSpec`` the mixed wave reads; the
    engine takes the rest from the attributes a ``GPTConfig`` has too
    (``vocab_size`` is the rows HELD).  Values it cannot run raise by
    name: ``first_k_dense_replace`` > 0, ``use_qk_norm``, ``rotary_pct``
    other than 1, a ``shared_expert_combination_strategy`` other than
    "average", ``use_parallel_block`` false, ``attention_bias``, an
    ``expert_selection_fn`` other than "sigmoid", an ungated or non-SiLU
    expert, a ``position_embedding_type`` other than "rope_gptj", a
    ``rope_type`` other than "default", an untied head, a layer type
    other than the two, sizes that do not divide."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 layer_types, num_attention_heads, num_key_value_heads,
                 head_dim, intermediate_size, num_experts,
                 num_experts_per_tok, num_shared_experts, sliding_window,
                 rope_theta=50000.0, layer_norm_eps=1e-5, logit_scale=1.0,
                 norm_topk_prob=True, first_k_dense_replace=0,
                 use_qk_norm=False, rotary_pct=1,
                 shared_expert_combination_strategy="average",
                 use_parallel_block=True, attention_bias=False,
                 expert_selection_fn="sigmoid", hidden_act="silu",
                 use_gated_activation=True,
                 position_embedding_type="rope_gptj", rope_parameters=None,
                 tie_word_embeddings=True, max_position_embeddings=200000,
                 held_experts=None, vocab_rows=None, **ignored):
        rope_type = (rope_parameters or {}).get("rope_type", "default")
        bad = [f"{k}={v!r}" for k, v, want in (
            ("first_k_dense_replace", first_k_dense_replace, 0),
            ("use_qk_norm", use_qk_norm, False),
            ("rotary_pct", rotary_pct, 1),
            ("shared_expert_combination_strategy",
             shared_expert_combination_strategy, "average"),
            ("use_parallel_block", use_parallel_block, True),
            ("attention_bias", attention_bias, False),
            ("expert_selection_fn", expert_selection_fn, "sigmoid"),
            ("hidden_act", hidden_act, "silu"),
            ("use_gated_activation", use_gated_activation, True),
            ("position_embedding_type", position_embedding_type,
             "rope_gptj"),
            ("rope_type", rope_type, "default"),
            ("tie_word_embeddings", tie_word_embeddings, True))
            if v != want]
        bad += [f"layer type {t!r}" for t in sorted(set(layer_types))
                if t not in LAYER_OPS]
        if bad:
            raise ValueError(f"ParallelMoEConfig cannot run {bad}")
        first, held = held_experts or (0, num_experts)
        row0, rows = vocab_rows or (0, vocab_size)
        windowed = "sliding_attention" in layer_types
        if len(layer_types) != num_hidden_layers \
                or num_attention_heads % num_key_value_heads \
                or head_dim % 2 or (windowed and sliding_window < 1) \
                or num_shared_experts < 1 \
                or not 1 <= num_experts_per_tok <= num_experts \
                or not (0 <= first and 1 <= held
                        and first + held <= num_experts) \
                or not (0 <= row0 and 1 <= rows
                        and row0 + rows <= vocab_size):
            raise ValueError(
                f"ParallelMoEConfig: sizes do not fit: {len(layer_types)} "
                f"layer_types for {num_hidden_layers} layers, "
                f"{num_attention_heads} over {num_key_value_heads} heads "
                f"of {head_dim}, a window of {sliding_window}, "
                f"{num_shared_experts} shared experts, "
                f"{num_experts_per_tok} of {num_experts} experts, held "
                f"{first, held}, rows {row0, rows} of {vocab_size}")
        self.model_type = "cohere2_moe"
        self.published_vocab_size = int(vocab_size)
        self.vocab_rows = (int(row0), int(rows))
        self.vocab_size = int(rows)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.layer_types = tuple(layer_types)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.intermediate_size = int(intermediate_size)
        # not ``num_experts``: ``moe_spec_of`` reads that attribute as
        # the capacity router's
        self.n_routed_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_shared_experts = int(num_shared_experts)
        self.held_experts = (int(first), int(held))
        self.sliding_window = int(sliding_window) if windowed else 0
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(layer_norm_eps)
        self.logit_scale = float(logit_scale)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.max_position_embeddings = int(max_position_embeddings)

    @classmethod
    def from_hf(cls, config, held_experts=None, vocab_rows=None):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise).  ``held_experts`` /
        ``vocab_rows`` (first, count): the experts every layer holds and
        the table's rows held (all, by default).  Newer exports keep
        ``rope_theta`` inside ``rope_parameters``."""
        config = dict(config)
        rope = config.get("rope_parameters") or {}
        if config.get("rope_theta") is None and "rope_theta" in rope:
            config["rope_theta"] = rope["rope_theta"]
        return cls(**dict(config, held_experts=held_experts,
                          vocab_rows=vocab_rows))

    def operators(self):
        return tuple(LAYER_OPS[t] for t in self.layer_types)

    def routed_spec(self):
        from .moe_decode import RoutedSpec
        first, held = self.held_experts
        return RoutedSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok, norm_topk=self.norm_topk_prob,
            n_shared=self.num_shared_experts, scoring="sigmoid",
            held_first=first,
            held=0 if held == self.n_routed_experts else held,
            shared_scale=1.0 / self.num_shared_experts)

    def block_spec(self):
        from .gpt_decode import BlockSpec, MuP, rope_frequencies
        ops = self.operators()
        # the sliding layers rotate; the full layers rotate nothing
        ropes = tuple(
            (op,) + (rope_frequencies(self.head_dim,
                                      rope_theta=self.rope_theta)
                     if op == "window_attention" else ("none", 1.0))
            for op in sorted(set(ops)))
        return BlockSpec(
            norm="layernorm_nobias", norm_eps=self.norm_eps,
            positions="rope", rope_theta=self.rope_theta, attention="gqa",
            bias=False, kv_heads=self.num_key_value_heads, ops=ops,
            ffn="routed", routed=self.routed_spec(), head="tied",
            head_dim=self.head_dim, window=self.sliding_window,
            rope_by_op=ropes, residual="parallel",
            mup=None if self.logit_scale == 1.0
            else MuP(lm_head=self.logit_scale))

    def param_shapes(self, name="cmd"):
        """{leaf: shape} of the serving parameter dict: ONE norm a layer
        (``ln1``), no router bias, the shared experts as one widened
        expert (their sum; the spec's ``shared_scale`` averages)."""
        d, dh, f = self.hidden_size, self.head_dim, self.intermediate_size
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        held, fs = self.held_experts[1], self.num_shared_experts * f
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,)}
        for i in range(self.num_hidden_layers):
            us = f"{name}_h{i}"
            shapes.update({
                f"{us}_ln1_scale": (d,),
                f"{us}_attn_q_weight": (d, hq * dh),
                f"{us}_attn_k_weight": (d, hkv * dh),
                f"{us}_attn_v_weight": (d, hkv * dh),
                f"{us}_attn_proj_weight": (hq * dh, d),
                f"{us}_moe_router_weight": (d, self.n_routed_experts),
                f"{us}_moe_experts_gate": (held, d, f),
                f"{us}_moe_experts_up": (held, d, f),
                f"{us}_moe_experts_down": (held, f, d),
                f"{us}_moe_shared_gate_weight": (d, fs),
                f"{us}_moe_shared_up_weight": (d, fs),
                f"{us}_moe_shared_down_weight": (fs, d)})
        return shapes

    def permute_rotary(self, params, name="cmd", inverse=False):
        """The parameter dict with ``W_q`` and ``W_k`` of every layer
        that rotates in the layout the wave's rotate-half reads: inside
        each head the even columns first, then the odd (``inverse``:
        back to the published, interleaved layout).  Every other leaf is
        the same array."""
        dh = self.head_dim
        perm = np.concatenate([np.arange(0, dh, 2), np.arange(1, dh, 2)])
        if inverse:
            perm = np.argsort(perm)
        out = dict(params)
        for i, kind in enumerate(self.layer_types):
            if kind != "sliding_attention":
                continue
            for leaf in ("q", "k"):
                key = f"{name}_h{i}_attn_{leaf}_weight"
                out[key] = _permute_heads(params[key], tuple(perm))
        return out


@jax.jit
def _take_columns(w, cols):
    return jnp.take(w, cols, axis=1)


def _permute_heads(w, perm):
    """``w`` [d, heads * dh] with the columns of every head reordered by
    ``perm`` (``dh`` indices)."""
    dh = len(perm)
    cols = (np.arange(w.shape[1] // dh)[:, None] * dh
            + np.asarray(perm)[None, :]).reshape(-1)
    return _take_columns(w, jnp.asarray(cols, jnp.int32))


def init_parallel_moe_params(config, name="cmd", seed=0, gains=None,
                             dtype=jnp.float32):
    """Seeded random serving params for a ``ParallelMoEConfig`` in the
    PUBLISHED layout, made on the device in one jitted call.  Every
    weight matrix is ``normal(gain / sqrt(fan_in))`` (``DEFAULT_GAINS``;
    ``gains`` overrides entries), the tied table ``normal(embedding /
    sqrt(hidden))``, norm scales 1.  The router's weight is float32
    whatever ``dtype`` is."""
    g = dict(DEFAULT_GAINS, **(gains or {}))
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
                continue
            gain = g[next(v for s, v in _GAIN_OF.items() if n.endswith(s))]
            fan_in = shape[-1] if n.endswith("_wte_table") else shape[-2]
            out[n] = (gain / math.sqrt(fan_in) * jax.random.normal(
                k, shape, jnp.float32)).astype(
                    jnp.float32 if "_moe_router_" in n else dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
