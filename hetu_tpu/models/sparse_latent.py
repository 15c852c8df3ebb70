"""Latent attention as an operator BY LAYER for the mixed ragged wave:
a decoder (the ``dots3_note`` / DeepSeek-V3.2 / ``glm_moe_dsa`` family's
``config.json`` keys) whose layers are each one of

  full_attention      multi-head latent attention whose every query row
                      reads the ``index_topk`` cached rows a learned
                      indexer chose (``gpt_decode.IndexSpec``): operator
                      "latent_attention", latent rows in the pool and an
                      index key a position beside them
  sliding_attention   latent attention of its OWN ranks, head sizes and
                      head count (the ``swa_*`` keys) over the last
                      ``sliding_window_size`` positions: operator
                      "window_latent_attention", latent rows in a ring

each with a head-wise sigmoid gate on its output and the low-rank norms'
outputs rescaled by ``sqrt(hidden / rank)`` where the configuration asks
for them; the FFN is ``LatentMoEConfig``'s (leading dense gated-SiLU
layers, then the sigmoid router with a selection bias over ALL the
experts and one shared expert), an expert layer holding all experts or a
contiguous SHARE of them (``held_experts``), the vocabulary all rows or a
slice (``vocab_rows``): one chip's part of an expert-parallel deployment.

``SparseLatentConfig`` yields the jit-static ``BlockSpec``; what the wave
traces for a layer with an indexer is ``index_decode``'s.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_OPS = {"full_attention": "latent_attention",
             "sliding_attention": "window_latent_attention"}

# what each weight product's output is, in units of its input's RMS, at
# the seeded weights: a weight's deviation is ``gain / sqrt(fan_in)``.
# With the rescale a query's and a key's columns have RMS ``gain *
# sqrt(hidden / rank)``, so the scores' deviation is several units and
# the softmax is PEAKED: which rows a layer read moves its output.
DEFAULT_GAINS = {
    "embedding": 1.0, "attn_q_a": 1.0, "attn_q_b": 1.0, "attn_kv_a": 1.0,
    "attn_kv_b": 1.0, "attn_out": 0.25, "attn_gate": 1.0, "index_q": 1.0,
    "index_k": 1.0, "index_w": 1.0, "router": 1.0, "router_bias": 0.1,
    "ffn_up": 1.0, "ffn_down": 0.5, "experts_up": 1.0, "experts_down": 0.5,
    "shared_up": 1.0, "shared_down": 0.5, "lm_head": 1.0}


class SparseLatentConfig:
    """Built from the source's own ``config.json`` keys (``from_hf``).
    Keys read: the sizes (``hidden_size``, ``num_hidden_layers``,
    ``layer_types``, ``num_attention_heads``, the five latent sizes and
    their ``swa_*`` twins, ``swa_num_attention_heads``,
    ``sliding_window_size``, ``index_n_heads`` / ``index_head_dim`` /
    ``index_topk``, ``rope_theta`` / ``swa_rope_theta``, the FFN's and
    the router's), ``apply_mla_qkv_lora_rescale``,
    ``attention_gate_type`` / ``swa_attention_gate_type`` (None or
    "headwise").  Values it cannot run raise: a layer type other than
    the two or no full layer at all, a gate type other than "headwise",
    group-limited selection, ``rope_scaling``, a ``topk_method`` other
    than ``noaux_tc``, a scoring other than sigmoid, ``moe_layer_freq``
    other than 1, biases, an activation other than SiLU, a tied head,
    K/V head counts that are not the query's.  Keys it does not know
    (the towers', the multi-token module's) are ignored: they take no
    part in the next-token logits of text."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 layer_types, num_attention_heads, q_lora_rank,
                 kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, intermediate_size, moe_intermediate_size,
                 n_routed_experts, num_experts_per_tok,
                 swa_num_attention_heads=None, swa_q_lora_rank=None,
                 swa_kv_lora_rank=None, swa_qk_nope_head_dim=None,
                 swa_qk_rope_head_dim=None, swa_v_head_dim=None,
                 swa_rope_theta=None, sliding_window_size=0,
                 index_n_heads=0, index_head_dim=0, index_topk=0,
                 apply_mla_qkv_lora_rescale=False,
                 attention_gate_type=None, swa_attention_gate_type=None,
                 n_shared_experts=0, routed_scaling_factor=1.0,
                 norm_topk_prob=True, first_k_dense_replace=0,
                 rope_theta=10000.0, rms_norm_eps=1e-6,
                 max_position_embeddings=4096, num_key_value_heads=None,
                 swa_num_key_value_heads=None, n_group=1, topk_group=1,
                 rope_scaling=None, topk_method="noaux_tc",
                 scoring_func="sigmoid", moe_layer_freq=1,
                 attention_bias=False, hidden_act="silu",
                 tie_word_embeddings=False, held_experts=None,
                 vocab_rows=None, **ignored):
        bad = [f"{k}={v!r}" for k, v, want in (
            ("n_group", n_group, 1), ("topk_group", topk_group, 1),
            ("rope_scaling", rope_scaling, None),
            ("topk_method", topk_method, "noaux_tc"),
            ("scoring_func", scoring_func, "sigmoid"),
            ("moe_layer_freq", moe_layer_freq, 1),
            ("attention_bias", attention_bias, False),
            ("hidden_act", hidden_act, "silu"),
            ("tie_word_embeddings", tie_word_embeddings, False),
            ("num_key_value_heads", num_key_value_heads or
             num_attention_heads, num_attention_heads),
            ("swa_num_key_value_heads", swa_num_key_value_heads or
             swa_num_attention_heads, swa_num_attention_heads))
            if v != want]
        bad += [f"{k}={v!r}" for k, v in (
            ("attention_gate_type", attention_gate_type),
            ("swa_attention_gate_type", swa_attention_gate_type))
            if v not in (None, "headwise")]
        types = tuple(layer_types)
        bad += [f"layer type {t!r}" for t in sorted(set(types))
                if t not in LAYER_OPS]
        if "full_attention" not in types:
            # (the window layers' ring rides the full pool's tables)
            bad.append("layer_types without a full_attention layer")
        if bad:
            raise ValueError(f"SparseLatentConfig cannot run {bad}")
        sliding = "sliding_attention" in types
        first, held = held_experts or (0, n_routed_experts)
        row0, rows = vocab_rows or (0, vocab_size)
        if len(types) != num_hidden_layers \
                or qk_rope_head_dim % 2 \
                or not 1 <= num_experts_per_tok <= n_routed_experts \
                or not 0 <= first_k_dense_replace <= num_hidden_layers \
                or not (0 <= first and 1 <= held
                        and first + held <= n_routed_experts) \
                or not (0 <= row0 and 1 <= rows
                        and row0 + rows <= vocab_size) \
                or (sliding and (sliding_window_size < 1 or None in (
                    swa_num_attention_heads, swa_q_lora_rank,
                    swa_kv_lora_rank, swa_qk_nope_head_dim,
                    swa_qk_rope_head_dim, swa_v_head_dim,
                    swa_rope_theta))) \
                or (index_topk and (
                    index_n_heads < 1 or index_head_dim < qk_rope_head_dim)):
            raise ValueError(
                f"SparseLatentConfig: sizes do not fit: {len(types)} layer "
                f"types for {num_hidden_layers} layers, a rotary width of "
                f"{qk_rope_head_dim}, {num_experts_per_tok} of "
                f"{n_routed_experts} experts, {first_k_dense_replace} "
                f"dense layers, experts held {first, held}, vocabulary "
                f"rows held {row0, rows} of {vocab_size}, a window of "
                f"{sliding_window_size} (sliding layers need every swa_* "
                f"key), an indexer of {index_n_heads} x {index_head_dim}")
        from .gpt_decode import IndexSpec, LatentSpec
        self.published_vocab_size = int(vocab_size)
        self.vocab_rows = (int(row0), int(rows))
        self.vocab_size = int(rows)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.layer_types = types
        self.num_attention_heads = int(num_attention_heads)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.swa_rope_theta = float(swa_rope_theta or rope_theta)
        self.window = int(sliding_window_size) if sliding else 0
        rescale = bool(apply_mla_qkv_lora_rescale)
        index = IndexSpec(int(index_n_heads), int(index_head_dim),
                          int(index_topk), int(qk_rope_head_dim)) \
            if index_topk else None
        self.full = LatentSpec(
            int(q_lora_rank), int(kv_lora_rank), int(qk_nope_head_dim),
            int(qk_rope_head_dim), int(v_head_dim),
            gate=attention_gate_type == "headwise", rescale=rescale,
            index=index)
        self.sliding = LatentSpec(
            int(swa_q_lora_rank), int(swa_kv_lora_rank),
            int(swa_qk_nope_head_dim), int(swa_qk_rope_head_dim),
            int(swa_v_head_dim), heads=int(swa_num_attention_heads),
            gate=swa_attention_gate_type == "headwise",
            rescale=rescale) if sliding else None
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.held_experts = (int(first), int(held))

    @classmethod
    def from_hf(cls, config, held_experts=None, vocab_rows=None):
        """From a ``config.json`` dict.  ``held_experts`` (first, count):
        the experts every expert layer holds; ``vocab_rows`` (first,
        count): the rows of the embedding table and the columns of the
        head that are held (all, by default; the engine then sees a
        vocabulary of ``count`` ids)."""
        return cls(**dict(config, held_experts=held_experts,
                          vocab_rows=vocab_rows))

    def latent_of(self, i):
        return self.full if self.layer_types[i] == "full_attention" \
            else self.sliding

    def heads_of(self, i):
        return self.latent_of(i).heads or self.num_attention_heads

    def routed_spec(self):
        from .moe_decode import RoutedSpec
        first, held = self.held_experts
        return RoutedSpec(
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            scale=self.routed_scaling_factor,
            norm_topk=self.norm_topk_prob, n_shared=self.n_shared_experts,
            held_first=first,
            held=0 if held == self.n_routed_experts else held)

    def block_spec(self):
        from .gpt_decode import BlockSpec, rope_frequencies
        ops = tuple(LAYER_OPS[t] for t in self.layer_types)
        all_dense = self.first_k_dense_replace >= self.num_hidden_layers
        ropes = tuple(
            (op,) + rope_frequencies(la.qk_rope_head_dim, rope_theta=theta)
            for op, la, theta in (
                ("latent_attention", self.full, self.rope_theta),
                ("window_latent_attention", self.sliding,
                 self.swa_rope_theta)) if op in ops)
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="latent",
            latent=self.full, ops=ops, window=self.window,
            latent_by_op=(("window_latent_attention", self.sliding),)
            if self.sliding is not None else None,
            rope_by_op=ropes,
            ffn="swiglu" if all_dense else "routed",
            leading_dense=0 if all_dense else self.first_k_dense_replace,
            routed=None if all_dense else self.routed_spec(),
            head="untied")

    def param_shapes(self, name="d3n"):
        """{leaf: shape} of the serving parameter dict."""
        d = self.hidden_size
        f, fe = self.intermediate_size, self.moe_intermediate_size
        E, held = self.n_routed_experts, self.held_experts[1]
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,),
                  f"{name}_lm_head_weight": (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            us, la, H = f"{name}_h{i}", self.latent_of(i), self.heads_of(i)
            dn, dr, dv = (la.qk_nope_head_dim, la.qk_rope_head_dim,
                          la.v_head_dim)
            dc, dq = la.kv_lora_rank, la.q_lora_rank
            shapes.update({
                f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,),
                f"{us}_attn_q_a_weight": (d, dq),
                f"{us}_attn_q_a_norm_scale": (dq,),
                f"{us}_attn_q_b_weight": (dq, H * (dn + dr)),
                f"{us}_attn_kv_a_weight": (d, dc + dr),
                f"{us}_attn_kv_a_norm_scale": (dc,),
                f"{us}_attn_kv_b_weight": (dc, H * (dn + dv)),
                f"{us}_attn_proj_weight": (H * dv, d)})
            if la.gate:
                shapes[f"{us}_attn_gate_weight"] = (d, H)
            if la.index is not None:
                J, D = la.index.n_heads, la.index.head_dim
                shapes.update({
                    f"{us}_attn_index_q_weight": (dq, J * D),
                    f"{us}_attn_index_k_weight": (d, D),
                    f"{us}_attn_index_k_norm_scale": (D,),
                    f"{us}_attn_index_k_norm_bias": (D,),
                    f"{us}_attn_index_w_weight": (d, J)})
            if i < self.first_k_dense_replace:
                shapes.update({f"{us}_ffn_gate_weight": (d, f),
                               f"{us}_ffn_up_weight": (d, f),
                               f"{us}_ffn_down_weight": (f, d)})
                continue
            fs = fe * self.n_shared_experts
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


# leaf suffix -> the gain its deviation ``gain / sqrt(fan_in)`` takes
_GAIN_OF = {
    "_attn_q_a_weight": "attn_q_a", "_attn_q_b_weight": "attn_q_b",
    "_attn_kv_a_weight": "attn_kv_a", "_attn_kv_b_weight": "attn_kv_b",
    "_attn_proj_weight": "attn_out", "_attn_gate_weight": "attn_gate",
    "_attn_index_q_weight": "index_q", "_attn_index_k_weight": "index_k",
    "_attn_index_w_weight": "index_w", "_ffn_gate_weight": "ffn_up",
    "_ffn_up_weight": "ffn_up", "_ffn_down_weight": "ffn_down",
    "_moe_router_weight": "router", "_moe_experts_gate": "experts_up",
    "_moe_experts_up": "experts_up", "_moe_experts_down": "experts_down",
    "_moe_shared_gate_weight": "shared_up",
    "_moe_shared_up_weight": "shared_up",
    "_moe_shared_down_weight": "shared_down", "_lm_head_weight": "lm_head"}


def init_sparse_latent_params(config, name="d3n", seed=0, gains=None,
                              dtype=jnp.float32):
    """Seeded random serving params for a ``SparseLatentConfig``, made on
    the device in one jitted call.  Every weight matrix is ``normal(gain
    / sqrt(fan_in))`` (``DEFAULT_GAINS``; ``gains`` overrides entries),
    the embedding ``normal(embedding)``, norm scales 1 and the index
    key's norm bias 0, the selection bias ``normal(router_bias)`` so
    that choosing by ``s + b`` and weighting by ``s`` differ.  The
    router's weight and bias are float32 whatever ``dtype`` is."""
    g = dict(DEFAULT_GAINS, **(gains or {}))
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif n.endswith("_norm_bias"):
                out[n] = jnp.zeros(shape, dtype)
            elif n.endswith("_moe_router_bias"):
                out[n] = g["router_bias"] * jax.random.normal(
                    k, shape, jnp.float32)
            elif n.endswith("_wte_table"):
                out[n] = (g["embedding"] * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
            else:
                gain = g[next(v for s, v in _GAIN_OF.items()
                              if n.endswith(s))]
                out[n] = (gain / math.sqrt(shape[-2]) * jax.random.normal(
                    k, shape, jnp.float32)).astype(
                        jnp.float32 if "_moe_router_" in n else dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
