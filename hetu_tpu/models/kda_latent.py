"""Delta-rule layers BESIDE latent attention in one block, for the mixed
ragged wave: a decoder (the ``Ling-3.0-flash`` family's ``config.json``
keys) whose layers come in groups of ``layer_group_size``, each group

  KDA x (group - 1)   Kimi Delta Attention (``kda_decode``): operator
                      "kda", a conv tail and a float32 matrix state a
                      slot, no page
  MLA x 1             multi-head latent attention whose query has NO
                      low-rank step (``q_lora_rank`` null), a head-wise
                      sigmoid gate on its output, its last
                      ``rotary_dim`` query and key columns rotated:
                      operator "latent_attention", latent rows in the
                      pool

over ``LatentMoEConfig``'s kind of FFN (leading dense gated-SiLU layers,
then a sigmoid router with a selection bias, one shared expert) whose
router chooses GROUP-LIMITED (``n_group`` / ``topk_group``:
``moe_decode.group_limited``), an expert layer holding all experts or a
contiguous SHARE of them (``held_experts``), the vocabulary all rows or
a slice (``vocab_rows``): one chip's part of an expert-parallel
deployment.

``KDALatentConfig`` yields the jit-static ``BlockSpec``; the engine's one
manager holds the latent pool of the MLA layers AND the slot states of
the KDA layers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# what each weight product's output is, in units of its input's RMS, at
# the seeded weights: a weight's deviation is ``gain / sqrt(fan_in)``.
# The MLA query and ``W_kvb`` are wide so that the scores' deviation is
# several units and the softmax PEAKED (the family has no rescale to do
# it): which rows the layer read moves its output; ``W_kva`` is wide
# for its ROTATED columns' sake (the latent ones are normalised after
# it), so that the rotated part of a score weighs what the other does
# and a rotation left out is seen.
DEFAULT_GAINS = {
    "embedding": 1.0, "kda_qkv": 1.0, "kda_conv": 1.0, "kda_f": 1.0,
    "kda_beta": 1.0, "kda_gate": 1.0, "kda_out": 0.5, "attn_q": 2.5,
    "attn_kv_a": 3.0, "attn_kv_b": 2.5, "attn_gate": 1.0, "attn_out": 0.2,
    "router": 1.0, "router_bias": 0.1, "ffn_up": 1.0, "ffn_down": 0.5,
    "experts_up": 1.0, "experts_down": 0.5, "shared_up": 1.0,
    "shared_down": 0.5, "lm_head": 1.0}

# what the class cannot run, by key: (key, the one value it runs)
_ONLY = (
    ("score_function", "sigmoid"), ("use_qk_norm", True),
    ("linear_silu", True), ("kda_safe_gate", True), ("no_kda_lora", True),
    ("use_kda_lora", False), ("use_mla_nope", False), ("use_nGPT", False),
    ("scale_router_input", False), ("value_norm", False),
    ("up_proj_norm", False), ("group_norm_size", 1),
    ("gated_attention_proj_granularity_type", "head_wise"),
    ("tie_word_embeddings", False), ("rope_scaling", None),
    ("attention_bias", False), ("hidden_act", "silu"))


class KDALatentConfig:
    """Built from the source's own ``config.json`` keys (``from_hf``).
    Keys read: the sizes (``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``head_dim``, the latent attention's five,
    ``intermediate_size``, ``moe_intermediate_size``,
    ``moe_shared_expert_intermediate_size``, ``num_experts``,
    ``num_experts_per_tok``, ``first_k_dense_replace``), the pattern
    (``layer_group_size``), the delta rule's (``short_conv_kernel_size``,
    ``kda_lower_bound``), the rotation's (``rope_theta``, ``rotary_dim``,
    ``partial_rotary_factor``), the router's (``n_group``,
    ``topk_group``, ``routed_scaling_factor``, ``norm_topk_prob``,
    ``moe_router_enable_expert_bias``) and the two clamp lists.  Values it
    cannot run raise BY NAME (``_ONLY``: another scoring, no q/k
    normalisation, a low-rank decay, an unsafe gate, ``use_mla_nope``,
    nGPT, a scaled router input, value or up-projection norms, a gate
    that is not head-wise, a norm group other than one head, a tied
    head, a RoPE scaling, biases, another activation; K/V head counts
    that are not the query's; ``rotary_dim`` that is not the latent
    attention's rotated width; a NONZERO entry of
    ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
    among the layers served: the clamped SwiGLU is not run).  Keys it
    does not know (the tower's token ids, the multi-token module's) are
    ignored: they take no part in the next-token logits of text."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, head_dim, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 intermediate_size, moe_intermediate_size, num_experts,
                 num_experts_per_tok, layer_group_size, q_lora_rank=None,
                 moe_shared_expert_intermediate_size=0,
                 first_k_dense_replace=0, n_group=1, topk_group=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 moe_router_enable_expert_bias=True, rope_theta=10000.0,
                 rotary_dim=None, partial_rotary_factor=None,
                 rms_norm_eps=1e-6, max_position_embeddings=4096,
                 short_conv_kernel_size=4, kda_lower_bound=-5.0,
                 num_key_value_heads=None, num_kv_heads_for_linear_attn=0,
                 expert_swiglu_limit_list=(),
                 share_expert_swiglu_limit_list=(), held_experts=None,
                 vocab_rows=None, state_dtype="float32", **rest):
        L = int(num_hidden_layers)
        bad = [f"{k}={rest[k]!r}" for k, want in _ONLY
               if k in rest and rest[k] != want]
        bad += [f"{k}={v!r}" for k, v, want in (
            ("num_key_value_heads", num_key_value_heads
             or num_attention_heads, num_attention_heads),
            ("num_kv_heads_for_linear_attn", num_kv_heads_for_linear_attn
             or num_attention_heads, num_attention_heads),
            ("rotary_dim", rotary_dim or qk_rope_head_dim, qk_rope_head_dim),
            ("partial_rotary_factor", (partial_rotary_factor or 0) * head_dim
             or qk_rope_head_dim, qk_rope_head_dim)) if v != want]
        bad += [f"{k}[{i}]={v!r}" for k, limits in (
            ("expert_swiglu_limit_list", expert_swiglu_limit_list),
            ("share_expert_swiglu_limit_list",
             share_expert_swiglu_limit_list))
            for i, v in enumerate(tuple(limits)[:L]) if v]
        if bad:
            raise ValueError(f"KDALatentConfig cannot run {bad}")
        from .gpt_decode import LatentSpec
        from .kda_decode import KDASpec
        first, held = held_experts or (0, num_experts)
        row0, rows = vocab_rows or (0, vocab_size)
        E, G = int(num_experts), int(n_group)
        kda = KDASpec(
            int(num_attention_heads), int(head_dim),
            int(short_conv_kernel_size), float(kda_lower_bound),
            state_dtype=str(jnp.dtype(state_dtype)))
        if not 2 <= layer_group_size <= L \
                or qk_rope_head_dim % 2 \
                or not 1 <= num_experts_per_tok <= E \
                or not 0 <= first_k_dense_replace <= L \
                or not 1 <= topk_group <= G or E % G \
                or num_experts_per_tok > topk_group * (E // G) \
                or not (0 <= first and 1 <= held and first + held <= E) \
                or not (0 <= row0 and 1 <= rows
                        and row0 + rows <= vocab_size) \
                or not kda.fits():
            raise ValueError(
                f"KDALatentConfig: sizes do not fit: groups of "
                f"{layer_group_size} layers in {L} (a whole group has its "
                f"latent layer), a rotary width of {qk_rope_head_dim}, "
                f"{num_experts_per_tok} of {E} experts in {G} groups of "
                f"which {topk_group} are kept, {first_k_dense_replace} "
                f"dense layers, experts held {first, held}, vocabulary "
                f"rows held {row0, rows} of {vocab_size}, a conv of "
                f"{short_conv_kernel_size} taps, a decay bound of "
                f"{kda_lower_bound} (the chunked form's sub-blocks keep "
                f"e^(rows x bound) inside float32)")
        self.published_vocab_size = int(vocab_size)
        self.vocab_rows = (int(row0), int(rows))
        self.vocab_size = int(rows)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = L
        self.num_attention_heads = int(num_attention_heads)
        self.head_dim = int(head_dim)
        self.layer_group_size = int(layer_group_size)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.latent = LatentSpec(
            int(q_lora_rank or 0), int(kv_lora_rank), int(qk_nope_head_dim),
            int(qk_rope_head_dim), int(v_head_dim), gate=True)
        self.kda = kda
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.shared_intermediate_size = int(
            moe_shared_expert_intermediate_size or 0)
        # not ``num_experts``: ``moe_spec_of`` reads that attribute as
        # the capacity router's
        self.n_routed_experts = E
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_group, self.topk_group = G, int(topk_group)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.router_bias = bool(moe_router_enable_expert_bias)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.held_experts = (int(first), int(held))

    @classmethod
    def from_hf(cls, config, held_experts=None, vocab_rows=None, **over):
        """From a ``config.json`` dict.  ``held_experts`` (first, count):
        the experts every expert layer holds; ``vocab_rows`` (first,
        count): the rows of the embedding table and the columns of the
        head that are held (all, by default; the engine then sees a
        vocabulary of ``count`` ids).  ``over`` lays keys over the
        configuration (``state_dtype="bfloat16"``: the control the
        comparison has to refuse)."""
        return cls(**dict(config, held_experts=held_experts,
                          vocab_rows=vocab_rows, **over))

    def op_of(self, i):
        """Layer ``i``'s operator: the last of every group is latent
        attention, the others the delta rule."""
        return "latent_attention" if (i + 1) % self.layer_group_size == 0 \
            else "kda"

    def routed_spec(self):
        from .moe_decode import RoutedSpec
        first, held = self.held_experts
        return RoutedSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor, norm_topk=self.norm_topk_prob,
            n_shared=1 if self.shared_intermediate_size else 0,
            held_first=first,
            held=0 if held == self.n_routed_experts else held,
            n_group=self.n_group, topk_group=self.topk_group)

    def block_spec(self):
        from .gpt_decode import BlockSpec
        L = self.num_hidden_layers
        all_dense = self.first_k_dense_replace >= L
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="latent",
            latent=self.latent, ops=tuple(self.op_of(i) for i in range(L)),
            kda=self.kda, ffn="swiglu" if all_dense else "routed",
            leading_dense=0 if all_dense else self.first_k_dense_replace,
            routed=None if all_dense else self.routed_spec(),
            head="untied")

    def param_shapes(self, name="lng"):
        """{leaf: shape} of the serving parameter dict."""
        d, H, D = self.hidden_size, self.num_attention_heads, self.head_dim
        la, K = self.latent, self.kda.conv_kernel
        dn, dr, dv = la.qk_nope_head_dim, la.qk_rope_head_dim, la.v_head_dim
        dc, dq = la.kv_lora_rank, la.q_lora_rank
        f, fe, fs = (self.intermediate_size, self.moe_intermediate_size,
                     self.shared_intermediate_size)
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
                    f"{us}_kda_f_weight": (d, H * D),
                    f"{us}_kda_dt_bias": (H * D,),
                    f"{us}_kda_A_log": (H,),
                    f"{us}_kda_beta_weight": (d, H),
                    f"{us}_kda_gate_weight": (d, H),
                    f"{us}_kda_norm_scale": (H * D,),
                    f"{us}_kda_out_weight": (H * D, d)})
            else:
                if dq:
                    shapes.update({
                        f"{us}_attn_q_a_weight": (d, dq),
                        f"{us}_attn_q_a_norm_scale": (dq,),
                        f"{us}_attn_q_b_weight": (dq, H * (dn + dr))})
                else:
                    shapes[f"{us}_attn_q_weight"] = (d, H * (dn + dr))
                shapes.update({
                    f"{us}_attn_kv_a_weight": (d, dc + dr),
                    f"{us}_attn_kv_a_norm_scale": (dc,),
                    f"{us}_attn_kv_b_weight": (dc, H * (dn + dv)),
                    f"{us}_attn_gate_weight": (d, H),
                    f"{us}_attn_proj_weight": (H * dv, d)})
            if i < self.first_k_dense_replace:
                shapes.update({f"{us}_ffn_gate_weight": (d, f),
                               f"{us}_ffn_up_weight": (d, f),
                               f"{us}_ffn_down_weight": (f, d)})
                continue
            shapes.update({f"{us}_moe_router_weight": (d, E),
                           f"{us}_moe_experts_gate": (held, d, fe),
                           f"{us}_moe_experts_up": (held, d, fe),
                           f"{us}_moe_experts_down": (held, fe, d)})
            if self.router_bias:
                shapes[f"{us}_moe_router_bias"] = (E,)
            if fs:
                shapes.update({f"{us}_moe_shared_gate_weight": (d, fs),
                               f"{us}_moe_shared_up_weight": (d, fs),
                               f"{us}_moe_shared_down_weight": (fs, d)})
        return shapes


# leaf suffix -> the gain its deviation ``gain / sqrt(fan_in)`` takes
_GAIN_OF = {
    "_kda_qkv_weight": "kda_qkv", "_kda_conv_weight": "kda_conv",
    "_kda_f_weight": "kda_f", "_kda_beta_weight": "kda_beta",
    "_kda_gate_weight": "kda_gate", "_kda_out_weight": "kda_out",
    "_attn_q_weight": "attn_q", "_attn_q_a_weight": "attn_q",
    "_attn_q_b_weight": "attn_q", "_attn_kv_a_weight": "attn_kv_a",
    "_attn_kv_b_weight": "attn_kv_b", "_attn_gate_weight": "attn_gate",
    "_attn_proj_weight": "attn_out", "_ffn_gate_weight": "ffn_up",
    "_ffn_up_weight": "ffn_up", "_ffn_down_weight": "ffn_down",
    "_moe_router_weight": "router", "_moe_experts_gate": "experts_up",
    "_moe_experts_up": "experts_up", "_moe_experts_down": "experts_down",
    "_moe_shared_gate_weight": "shared_up",
    "_moe_shared_up_weight": "shared_up",
    "_moe_shared_down_weight": "shared_down", "_lm_head_weight": "lm_head"}

# float32 whatever the serving dtype: the recurrence's own constants and
# the router
F32_LEAVES = ("_kda_dt_bias", "_kda_A_log", "_moe_router_weight",
              "_moe_router_bias")


def init_kda_latent_params(config, name="lng", seed=0, gains=None,
                           dtype=jnp.float32, a_range=(1.0, 16.0),
                           dt_range=(0.001, 0.1), defaults=None,
                           gain_of=None):
    """Seeded random serving params for a ``KDALatentConfig``, made on
    the device in one jitted call.  Every weight matrix is ``normal(gain
    / sqrt(fan_in))`` (``DEFAULT_GAINS``; ``gains`` overrides entries;
    the conv's fan-in is its taps), the embedding ``normal(embedding)``,
    norm scales 1, the selection bias ``normal(router_bias)`` so that
    choosing by ``s + b`` and weighting by ``s`` differ.  The decay's
    constants as the family initialises them: ``A_log = log(uniform(
    a_range))`` a head, ``dt_bias`` the inverse softplus of ``dt``
    log-uniform in ``dt_range`` a channel (a zero decay input then gives
    ``g`` from about -0.05 a step, a memory of some twenty tokens, up to
    nothing at all).  The router's weight and bias and the decay's
    constants are float32 whatever ``dtype`` is.  ``defaults`` /
    ``gain_of``: another configuration class's gains and leaf table
    (``kda_gqa``'s, whose low-rank output gate has a bias,
    ``normal(kda_gate_bias)``)."""
    g = dict(DEFAULT_GAINS if defaults is None else defaults,
             **(gains or {}))
    gain_of = _GAIN_OF if gain_of is None else gain_of
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif n.endswith("_kda_A_log"):
                out[n] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, *a_range))
            elif n.endswith("_kda_dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(dt_range[0]),
                    math.log(dt_range[1])))
                out[n] = dt + jnp.log(-jnp.expm1(-dt))
            elif n.endswith("_moe_router_bias"):
                out[n] = g["router_bias"] * jax.random.normal(
                    k, shape, jnp.float32)
            elif n.endswith("_kda_gate_bias"):
                out[n] = (g["kda_gate_bias"] * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
            elif n.endswith("_wte_table"):
                out[n] = (g["embedding"] * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
            else:
                gain = g[next(v for s, v in gain_of.items()
                              if n.endswith(s))]
                out[n] = (gain / math.sqrt(shape[-2]) * jax.random.normal(
                    k, shape, jnp.float32)).astype(
                        jnp.float32 if n.endswith(F32_LEAVES) else dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
