"""Delta-rule layers BESIDE grouped-query attention in one block, for the
mixed ragged wave: a decoder (the ``solar_open2`` family's ``config.json``
keys) whose layers are

  GQA   where the layer's number is in ``gqa_layers``: softmax attention
        of ``num_attention_heads`` query heads over ``num_key_value_heads``
        K/V heads, NO positions (``use_rope`` false: nothing is added and
        nothing rotated), its output times a sigmoid gate a COLUMN from a
        projection of the layer's normed rows (``use_gqa_gate``), before
        ``W_o``: operator "attention" with ``BlockSpec.attn_gate``, K/V
        pages in the pool
  KDA   everywhere else (``gqa_interval`` of them between two GQA
        layers): Kimi Delta Attention as published (``kda_decode`` with
        ``KDASpec.decay`` "softplus", which has no lower bound; LOW-RANK
        decay and output-gate projections, ``kda_use_full_proj`` false;
        the gate a column; ``kda_allow_neg_eigval``: beta in (0, 2)):
        operator "kda", a conv tail and a float32 matrix state a slot,
        no page

over a routed FFN in EVERY layer (``first_k_dense_replace`` 0): a sigmoid
router with a selection bias over all ``n_routed_experts``, plain top-k
(no groups), ``n_shared_experts`` shared experts, an expert layer holding
all experts or a contiguous SHARE of them (``held_experts``), the
vocabulary all rows or a slice (``vocab_rows``): one chip's part of an
expert-parallel deployment.

Why a class of its own and not ``KDALatentConfig`` widened: that one
yields the LATENT block (latent rows in one pool, RoPE, leading dense
layers, a group-limited router) from another family's keys; this yields
the grouped-query block (a K/V pool pair, no positions) from keys that
share three names with it.  What the two share is code, not a class:
``kda_decode``, ``moe_decode`` and ``kda_latent.init_kda_latent_params``'
seeded draw, which takes this module's gains and leaf table.

``KDAGQAConfig`` yields the jit-static ``BlockSpec``; the engine's one
manager holds the K/V pool of the GQA layers AND the slot states of the
KDA layers.
"""

from __future__ import annotations

import jax.numpy as jnp

# what each weight product's output is, in units of its input's RMS, at
# the seeded weights (a weight's deviation is ``gain / sqrt(fan_in)``).
# The GQA layer's q and k are wide so that the scores' deviation is
# several units and the softmax PEAKED over thousands of unrotated rows:
# which rows the layer read, and in which order the layers stand, moves
# its output; the outputs are narrow so that a mixer is about a quarter
# of the residual.
DEFAULT_GAINS = {
    "embedding": 1.0, "kda_qkv": 1.0, "kda_conv": 1.0, "kda_f": 1.0,
    "kda_beta": 1.0, "kda_gate": 1.0, "kda_gate_bias": 0.5,
    "kda_out": 0.5, "attn_q": 2.5, "attn_k": 2.5, "attn_v": 1.0,
    "attn_gate": 1.0, "attn_out": 0.5, "router": 1.0, "router_bias": 0.1,
    "experts_up": 1.0, "experts_down": 0.5, "shared_up": 1.0,
    "shared_down": 0.5, "lm_head": 1.0}

# leaf suffix -> the gain its deviation ``gain / sqrt(fan_in)`` takes
GAIN_OF = {
    "_kda_qkv_weight": "kda_qkv", "_kda_conv_weight": "kda_conv",
    "_kda_f_a_weight": "kda_f", "_kda_f_b_weight": "kda_f",
    "_kda_f_weight": "kda_f", "_kda_beta_weight": "kda_beta",
    "_kda_gate_a_weight": "kda_gate", "_kda_gate_b_weight": "kda_gate",
    "_kda_gate_weight": "kda_gate", "_kda_out_weight": "kda_out",
    "_attn_q_weight": "attn_q", "_attn_k_weight": "attn_k",
    "_attn_v_weight": "attn_v", "_attn_gate_weight": "attn_gate",
    "_attn_proj_weight": "attn_out", "_moe_router_weight": "router",
    "_moe_experts_gate": "experts_up", "_moe_experts_up": "experts_up",
    "_moe_experts_down": "experts_down",
    "_moe_shared_gate_weight": "shared_up",
    "_moe_shared_up_weight": "shared_up",
    "_moe_shared_down_weight": "shared_down", "_lm_head_weight": "lm_head"}

# what the class cannot run, by key: (key, the one value it runs)
_ONLY = (
    ("use_rope", False), ("tie_word_embeddings", False),
    ("first_k_dense_replace", 0), ("norm_topk_prob", True),
    ("score_function", "sigmoid"), ("scoring_func", "sigmoid"),
    ("n_group", 1), ("topk_group", 1), ("rope_scaling", None),
    ("attention_bias", False), ("hidden_act", "silu"),
    ("use_qk_norm", False), ("kda_safe_gate", False),
    ("sliding_window", None))


class KDAGQAConfig:
    """Built from the source's own ``config.json`` keys (``from_hf``).
    Keys read: the sizes (``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``moe_intermediate_size``, ``n_routed_experts``,
    ``n_shared_experts``, ``num_experts_per_tok``, ``vocab_size``), the
    pattern (``gqa_layers``), the delta rule's (``linear_attn_config``:
    ``num_heads``, ``head_dim``, ``short_conv_kernel_size``,
    ``num_kv_heads``; ``kda_use_full_proj``: false makes the decay's and
    the gate's projections low-rank, of rank ``head_dim`` as the
    published implementation has them; ``kda_allow_neg_eigval``), the
    gate's (``use_gqa_gate``) and the router's
    (``routed_scaling_factor``).  Values it cannot run raise BY NAME
    (``_ONLY``: rotated attention, a tied head, leading dense layers, an
    unnormalised top-k, another scoring, router groups, a RoPE scaling,
    biases, another activation, a per-head q/k norm, the safe gate's
    keys, a sliding window; ``linear_attn_config.num_kv_heads`` that is
    not the KDA layers' head count; a KDA head that is not the
    attention's; ``gqa_layers`` outside the depth or leaving no layer of
    one kind).  ``intermediate_size``, ``partial_rotary_factor`` and
    ``rope_theta`` are read by nothing here (no dense layer, nothing
    rotated) and ignored, as are keys it does not know."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, gqa_layers, linear_attn_config,
                 n_shared_experts=0, routed_scaling_factor=1.0,
                 use_gqa_gate=False, kda_use_full_proj=False,
                 kda_allow_neg_eigval=False, rms_norm_eps=1e-5,
                 max_position_embeddings=4096,
                 held_experts=None, vocab_rows=None, state_dtype="float32",
                 **rest):
        L = int(num_hidden_layers)
        la = dict(linear_attn_config)
        H, D = int(num_attention_heads), int(head_dim)
        bad = [f"{k}={rest[k]!r}" for k, want in _ONLY
               if k in rest and rest[k] != want]
        bad += [f"{k}={v!r}" for k, v, want in (
            ("linear_attn_config.num_kv_heads",
             la.get("num_kv_heads") or la["num_heads"], la["num_heads"]),
            ("linear_attn_config.num_heads", la["num_heads"], H),
            ("linear_attn_config.head_dim", la["head_dim"], D))
            if v != want]
        layers = tuple(int(i) for i in gqa_layers)
        if any(not 0 <= i < L for i in layers) \
                or len(set(layers)) in (0, L) or len(set(layers)) != len(
                    layers):
            bad.append(f"gqa_layers={list(gqa_layers)!r} in "
                       f"{L} layers (a layer of each kind, each once)")
        if bad:
            raise ValueError(f"KDAGQAConfig cannot run {bad}")
        from .kda_decode import KDASpec
        E = int(n_routed_experts)
        first, held = held_experts or (0, E)
        row0, rows = vocab_rows or (0, vocab_size)
        kda = KDASpec(
            H, D, int(la["short_conv_kernel_size"]),
            state_dtype=str(jnp.dtype(state_dtype)), decay="softplus",
            rank=0 if kda_use_full_proj else D,
            gate_by="channel",
            beta_scale=2.0 if kda_allow_neg_eigval else 1.0)
        if H % int(num_key_value_heads) \
                or not 1 <= num_experts_per_tok <= E \
                or not (0 <= first and 1 <= held and first + held <= E) \
                or not (0 <= row0 and 1 <= rows
                        and row0 + rows <= vocab_size) \
                or not kda.fits():
            raise ValueError(
                f"KDAGQAConfig: sizes do not fit: {H} query heads over "
                f"{num_key_value_heads} K/V heads, {num_experts_per_tok} "
                f"of {E} experts, experts held {first, held}, vocabulary "
                f"rows held {row0, rows} of {vocab_size}, a conv of "
                f"{la['short_conv_kernel_size']} taps, a low rank of "
                f"{kda.rank}")
        self.published_vocab_size = int(vocab_size)
        self.vocab_rows = (int(row0), int(rows))
        self.vocab_size = int(rows)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = L
        self.num_attention_heads = H
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = D
        self.gqa_layers = layers
        self.attn_gate = bool(use_gqa_gate)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.kda = kda
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_shared_experts = int(n_shared_experts)
        # not ``num_experts``: ``moe_spec_of`` reads that attribute as
        # the capacity router's
        self.n_routed_experts = E
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.held_experts = (int(first), int(held))

    @classmethod
    def from_hf(cls, config, held_experts=None, vocab_rows=None, **over):
        """From a ``config.json`` dict.  ``held_experts`` (first, count):
        the experts every layer holds; ``vocab_rows`` (first, count): the
        rows of the embedding table and the columns of the head that are
        held (all, by default; the engine then sees a vocabulary of
        ``count`` ids).  ``over`` lays keys over the configuration
        (``state_dtype="bfloat16"``: the control the comparison has to
        refuse)."""
        return cls(**dict(config, held_experts=held_experts,
                          vocab_rows=vocab_rows, **over))

    def op_of(self, i):
        """Layer ``i``'s operator."""
        return "attention" if i in self.gqa_layers else "kda"

    def routed_spec(self):
        from .moe_decode import RoutedSpec
        first, held = self.held_experts
        return RoutedSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor, norm_topk=True,
            n_shared=self.n_shared_experts, held_first=first,
            held=0 if held == self.n_routed_experts else held)

    def block_spec(self):
        from .gpt_decode import BlockSpec
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="none",
            attention="gqa", bias=False,
            kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            ops=tuple(self.op_of(i) for i in range(self.num_hidden_layers)),
            kda=self.kda, attn_gate=self.attn_gate, ffn="routed",
            routed=self.routed_spec(), head="untied")

    def param_shapes(self, name="slr"):
        """{leaf: shape} of the serving parameter dict."""
        d, H, D = self.hidden_size, self.num_attention_heads, self.head_dim
        Hkv, K, r = self.num_key_value_heads, self.kda.conv_kernel, \
            self.kda.rank
        fe = self.moe_intermediate_size
        fs = fe * self.n_shared_experts
        E, held = self.n_routed_experts, self.held_experts[1]
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,),
                  f"{name}_lm_head_weight": (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            us = f"{name}_h{i}"
            shapes.update({f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,)})
            if self.op_of(i) == "kda":
                shapes.update({
                    f"{us}_kda_qkv_weight": (d, 3 * H * D),
                    f"{us}_kda_conv_weight": (K, 3 * H * D),
                    f"{us}_kda_dt_bias": (H * D,),
                    f"{us}_kda_A_log": (H,),
                    f"{us}_kda_beta_weight": (d, H),
                    f"{us}_kda_norm_scale": (H * D,),
                    f"{us}_kda_out_weight": (H * D, d)})
                if r:
                    shapes.update({
                        f"{us}_kda_f_a_weight": (d, r),
                        f"{us}_kda_f_b_weight": (r, H * D),
                        f"{us}_kda_gate_a_weight": (d, r),
                        f"{us}_kda_gate_b_weight": (r, H * D),
                        f"{us}_kda_gate_bias": (H * D,)})
                else:
                    shapes.update({f"{us}_kda_f_weight": (d, H * D),
                                   f"{us}_kda_gate_weight": (d, H * D)})
            else:
                shapes.update({
                    f"{us}_attn_q_weight": (d, H * D),
                    f"{us}_attn_k_weight": (d, Hkv * D),
                    f"{us}_attn_v_weight": (d, Hkv * D),
                    f"{us}_attn_proj_weight": (H * D, d)})
                if self.attn_gate:
                    shapes[f"{us}_attn_gate_weight"] = (d, H * D)
            shapes.update({f"{us}_moe_router_weight": (d, E),
                           f"{us}_moe_router_bias": (E,),
                           f"{us}_moe_experts_gate": (held, d, fe),
                           f"{us}_moe_experts_up": (held, d, fe),
                           f"{us}_moe_experts_down": (held, fe, d)})
            if fs:
                shapes.update({f"{us}_moe_shared_gate_weight": (d, fs),
                               f"{us}_moe_shared_up_weight": (d, fs),
                               f"{us}_moe_shared_down_weight": (fs, d)})
        return shapes


def init_kda_gqa_params(config, name="slr", seed=0, gains=None,
                        dtype=jnp.float32, **ranges):
    """Seeded random serving params for a ``KDAGQAConfig``:
    ``kda_latent.init_kda_latent_params``' draw (every matrix ``normal(
    gain / sqrt(fan_in))``, the decay's constants as the family
    initialises them, the selection bias ``normal(router_bias)``, the
    router and the decay's constants float32) over this module's gains
    and leaves; the output gate's bias ``normal(kda_gate_bias)``."""
    from .kda_latent import init_kda_latent_params
    return init_kda_latent_params(
        config, name=name, seed=seed, gains=gains, dtype=dtype,
        defaults=DEFAULT_GAINS, gain_of=GAIN_OF, **ranges)
