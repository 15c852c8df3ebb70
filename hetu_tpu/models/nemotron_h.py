"""The ``nemotron_h`` family for the mixed ragged wave: a decoder whose
layers are EACH one part on one norm (``hybrid_override_pattern``, a
letter a layer):

  M   a Mamba-2 mixer alone (``ssm_decode.ssm_mixer``, no multipliers):
      slot state, no page
  *   grouped-query attention alone, NO positional embedding of any kind
      (``BlockSpec.positions`` "none"): K/V pages, no state
  E   a latent routed FFN alone (``moe_decode.routed_ffn``): a sigmoid
      router with a selection bias over all ``n_routed_experts``, the
      experts two matrices with squared ReLU at the latent width
      ``moe_latent_size`` between one projection down and one up, a
      shared expert of the same form at the hidden width; it keeps
      nothing

``h <- h + part(RMSNorm(h))`` a layer, the residual in the activations'
dtype; the embedding unscaled; a final RMSNorm and an untied head.  The
family's dense-MLP letter "-" is not run (no published configuration of
this repo's has it); ``num_nextn_predict_layers`` is accepted and not
served: the next-token module takes no part in the next-token logits.

An expert layer may be told which experts it HOLDS (``held_experts``:
first and count): one chip's share of an expert-parallel deployment.
The router's width stays ``n_routed_experts``; the expert leaves are
``[held, ...]`` (``RoutedSpec.held``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ssm_decode import (  # noqa: F401 (F32_LEAVES is this family's too)
    F32_LEAVES, SSMSpec, init_recurrence_constant)

# a letter of the pattern: (the layer's operator, its FFN kind)
LAYER_KINDS = {"M": ("ssm", "none"), "*": ("attention", "none"),
               "E": ("none", "routed")}

# what each weight product's output is, in units of its input's RMS, at
# the seeded weights (``init_nemotron_h_params``): the weight's deviation
# is ``gain / sqrt(fan_in)``.  The residual starts at RMS 1 and every
# layer adds about a third of that: a mixer's output is its gated norm
# (RMS 1) times ``ssm_out``; an attention's is an average of values
# times ``attn_out``; a squared-ReLU expert with inputs of RMS ``s``
# gives ``1.22 s**2`` times its ``down`` gain, and the routed part is
# ``routed_scaling_factor`` times a normalised mix of the chosen.
DEFAULT_GAINS = {
    "embedding": 1.0, "attn_q": 1.25, "attn_k": 1.25, "attn_v": 1.0,
    "attn_out": 2.0, "ssm_z": 1.0, "ssm_x": 1.0, "ssm_B": 1.0,
    "ssm_C": 1.0, "ssm_dt": 0.5, "ssm_conv": 1.0, "ssm_conv_bias": 0.1,
    "ssm_out": 0.3, "router": 1.0, "router_bias": 0.1, "latent_in": 1.0,
    "experts_up": 1.0, "experts_down": 0.3, "latent_out": 1.0,
    "shared_up": 1.0, "shared_down": 0.17, "lm_head": 1.0}


class NemotronHConfig:
    """Built from the source's own ``config.json`` keys (``from_hf``).
    It yields the jit-static ``BlockSpec`` the mixed wave reads; the
    engine takes the rest from the attributes a ``GPTConfig`` has too.
    Values it cannot run raise: a pattern letter other than M, * and E,
    biases, group-limited selection, an activation other than squared
    ReLU in the FFN or SiLU in the mixer, a tied head, a sliding window,
    ``residual_in_fp32``, sizes that do not divide."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 hybrid_override_pattern, num_attention_heads,
                 num_key_value_heads, head_dim, mamba_num_heads,
                 mamba_head_dim, ssm_state_size, n_groups, conv_kernel,
                 moe_intermediate_size, moe_latent_size,
                 moe_shared_expert_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_shared_experts=1, chunk_size=128,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 n_group=1, topk_group=1, layer_norm_epsilon=1e-5,
                 max_position_embeddings=262144, attention_bias=False,
                 mamba_proj_bias=False, mlp_bias=False, use_bias=False,
                 use_conv_bias=True, mlp_hidden_act="relu2",
                 mamba_hidden_act="silu", tie_word_embeddings=False,
                 sliding_window=None, residual_in_fp32=False,
                 held_experts=None, **ignored):
        bad = [k for k, v in (
            ("attention_bias", attention_bias),
            ("mamba_proj_bias", mamba_proj_bias), ("mlp_bias", mlp_bias),
            ("use_bias", use_bias), ("sliding_window", sliding_window),
            ("tie_word_embeddings", tie_word_embeddings),
            ("residual_in_fp32", residual_in_fp32)) if v]
        bad += [f"{k}={v!r}" for k, v, want in (
            ("n_group", n_group, 1), ("topk_group", topk_group, 1),
            ("n_shared_experts", n_shared_experts, 1),
            ("use_conv_bias", use_conv_bias, True),
            ("mlp_hidden_act", mlp_hidden_act, "relu2"),
            ("mamba_hidden_act", mamba_hidden_act, "silu")) if v != want]
        pattern = str(hybrid_override_pattern)
        bad += [f"pattern letter {c!r}" for c in sorted(set(pattern))
                if c not in LAYER_KINDS]
        if bad:
            raise ValueError(f"NemotronHConfig cannot run {bad}")
        first, held = held_experts or (0, n_routed_experts)
        if len(pattern) != num_hidden_layers \
                or mamba_num_heads % n_groups \
                or num_attention_heads % num_key_value_heads \
                or conv_kernel < 2 \
                or not 1 <= num_experts_per_tok <= n_routed_experts \
                or not (0 <= first and 1 <= held
                        and first + held <= n_routed_experts):
            raise ValueError(
                f"NemotronHConfig: sizes do not fit: a pattern of "
                f"{len(pattern)} letters for {num_hidden_layers} layers, "
                f"{mamba_num_heads} mixer heads in {n_groups} groups, "
                f"{num_attention_heads} over {num_key_value_heads} heads, "
                f"{conv_kernel} taps, {num_experts_per_tok} of "
                f"{n_routed_experts} experts, held {first, held}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.pattern = pattern
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.max_position_embeddings = int(max_position_embeddings)
        self.norm_eps = float(layer_norm_epsilon)
        self.ssm = SSMSpec(int(mamba_num_heads), int(mamba_head_dim),
                           int(ssm_state_size), int(n_groups),
                           int(conv_kernel), int(chunk_size))
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.moe_latent_size = int(moe_latent_size)
        self.shared_intermediate_size = int(
            moe_shared_expert_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.held_experts = (int(first), int(held))

    @classmethod
    def from_hf(cls, config, held_experts=None):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise).  ``held_experts``
        (first, count): the experts every expert layer holds (all, by
        default)."""
        return cls(**dict(config, held_experts=held_experts))

    def routed_spec(self):
        from .moe_decode import RoutedSpec
        first, held = self.held_experts
        return RoutedSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor,
            norm_topk=self.norm_topk_prob, n_shared=1, scoring="sigmoid",
            held_first=first,
            held=0 if held == self.n_routed_experts else held,
            latent=self.moe_latent_size, expert="relu2")

    def block_spec(self):
        from .gpt_decode import BlockSpec
        routes = "E" in self.pattern
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.norm_eps, positions="none",
            attention="gqa", bias=False,
            kv_heads=self.num_key_value_heads,
            ops=tuple(LAYER_KINDS[c][0] for c in self.pattern),
            ffns=tuple(LAYER_KINDS[c][1] for c in self.pattern),
            ffn="routed" if routes else "none",
            routed=self.routed_spec() if routes else None,
            head="untied", head_dim=self.head_dim,
            ssm=self.ssm if "M" in self.pattern else None)

    def param_shapes(self, name="nmh"):
        """{leaf: shape} of the serving parameter dict.  A layer has the
        ONE norm of its one part: ``ln1`` before a mixer or an
        attention, ``ln2`` before an FFN."""
        d, dh = self.hidden_size, self.head_dim
        hq, hkv, sp = (self.num_attention_heads, self.num_key_value_heads,
                       self.ssm)
        lat, fe, fs = (self.moe_latent_size, self.moe_intermediate_size,
                       self.shared_intermediate_size)
        held = self.held_experts[1]
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,),
                  f"{name}_lm_head_weight": (d, self.vocab_size)}
        for i, letter in enumerate(self.pattern):
            us = f"{name}_h{i}"
            if letter == "M":
                shapes.update({
                    f"{us}_ln1_scale": (d,),
                    f"{us}_ssm_in_weight": (d, sp.proj_width),
                    f"{us}_ssm_conv_weight": (sp.conv_kernel,
                                              sp.conv_width),
                    f"{us}_ssm_conv_bias": (sp.conv_width,),
                    f"{us}_ssm_dt_bias": (sp.heads,),
                    f"{us}_ssm_A_log": (sp.heads,),
                    f"{us}_ssm_D": (sp.heads,),
                    f"{us}_ssm_norm_scale": (sp.width,),
                    f"{us}_ssm_out_weight": (sp.width, d)})
            elif letter == "*":
                shapes.update({
                    f"{us}_ln1_scale": (d,),
                    f"{us}_attn_q_weight": (d, hq * dh),
                    f"{us}_attn_k_weight": (d, hkv * dh),
                    f"{us}_attn_v_weight": (d, hkv * dh),
                    f"{us}_attn_proj_weight": (hq * dh, d)})
            else:
                shapes.update({
                    f"{us}_ln2_scale": (d,),
                    f"{us}_moe_router_weight": (d, self.n_routed_experts),
                    f"{us}_moe_router_bias": (self.n_routed_experts,),
                    f"{us}_moe_latent_in_weight": (d, lat),
                    f"{us}_moe_latent_out_weight": (lat, d),
                    f"{us}_moe_experts_up": (held, lat, fe),
                    f"{us}_moe_experts_down": (held, fe, lat),
                    f"{us}_moe_shared_up_weight": (d, fs),
                    f"{us}_moe_shared_down_weight": (fs, d)})
        return shapes


def init_nemotron_h_params(config, name="nmh", seed=0, gains=None,
                           dtype=jnp.float32, dt_range=(0.001, 0.1),
                           a_range=(1.0, 16.0)):
    """Seeded random serving params for a ``NemotronHConfig``, made on
    the device in one jitted call.  Every weight matrix is ``normal(gain
    / sqrt(fan_in))`` (``DEFAULT_GAINS``; ``gains`` overrides entries),
    ``W_in``'s five slices each by their own; norm scales 1; the
    selection bias ``normal(router_bias)`` so that choosing by ``s + b``
    and weighting by ``s`` differ; and the recurrence's constants by the
    family's initialisation: ``dt`` log-uniform in ``dt_range``
    (``dt_bias`` its inverse softplus), ``A`` uniform in ``a_range``
    (``A_log`` its logarithm), ``D`` 1.  The router's weight and bias
    and the recurrence's three constants are float32 whatever ``dtype``
    is."""
    g = dict(DEFAULT_GAINS, **(gains or {}))
    c, sp = config, config.ssm
    d, lat = c.hidden_size, c.moe_latent_size
    gn = sp.groups * sp.state
    root = math.sqrt
    dev = {
        "_wte_table": g["embedding"],
        "_lm_head_weight": g["lm_head"] / root(d),
        "_attn_q_weight": g["attn_q"] / root(d),
        "_attn_k_weight": g["attn_k"] / root(d),
        "_attn_v_weight": g["attn_v"] / root(d),
        "_attn_proj_weight": g["attn_out"] / root(
            c.num_attention_heads * c.head_dim),
        "_ssm_conv_weight": g["ssm_conv"] / root(sp.conv_kernel),
        "_ssm_conv_bias": g["ssm_conv_bias"],
        "_ssm_out_weight": g["ssm_out"] / root(sp.width),
        "_moe_router_weight": g["router"] / root(d),
        "_moe_router_bias": g["router_bias"],
        "_moe_latent_in_weight": g["latent_in"] / root(d),
        "_moe_latent_out_weight": g["latent_out"] / root(lat),
        "_moe_experts_up": g["experts_up"] / root(lat),
        "_moe_experts_down": g["experts_down"] / root(
            c.moe_intermediate_size),
        "_moe_shared_up_weight": g["shared_up"] / root(d),
        "_moe_shared_down_weight": g["shared_down"] / root(
            c.shared_intermediate_size),
    }
    widths = (sp.width, sp.width, gn, gn, sp.heads)
    slices = [g[k] / root(d) for k in
              ("ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt")]
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        in_cols = jnp.concatenate([jnp.full((w,), s, jnp.float32)
                                   for w, s in zip(widths, slices)])
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            constant = init_recurrence_constant(n, k, shape, dt_range,
                                                a_range)
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif constant is not None:
                out[n] = constant
            elif n.endswith("_ssm_in_weight"):
                out[n] = (jax.random.normal(k, shape, jnp.float32)
                          * in_cols).astype(dtype)
            else:
                s = next(v for suffix, v in dev.items()
                         if n.endswith(suffix))
                out[n] = (s * jax.random.normal(k, shape, jnp.float32)
                          ).astype(jnp.float32 if "_moe_router_" in n
                                   else dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
