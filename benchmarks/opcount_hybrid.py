"""Operations and bytes the grouped-query attention kernel needs over a
window's waves, from the engine's own counters and the configuration's
sizes alone (``opcount.py``'s rules: what the mathematics requires, a
multiply-add is two operations).  ``readers/kernel_roofline_hybrid.py``
sets them against the kernel's traced time; the routed experts' are
``opcount_latent_moe.routed_ffn`` (the same grouped matmuls, read by the
accepted ``moe_experts_roofline.serve``).

The counters are sums over the waves (``opcount_latent_moe``'s
docstring): ``attn_ctx_tokens`` (every live slot's filled length after
the wave's writes, once a wave), ``attn_score_pairs`` (the positions
every live row sees) and ``moe_assignments`` (valid rows x top_k x routed
layers), each counted once a wave: only the ATTENTION layers
(``layer_types`` entries "full_attention") multiply them here, the conv
layers read no pool.
"""

from __future__ import annotations

BF16 = 2


def attention_layers(config):
    return sum(1 for t in config["layer_types"][:config["num_hidden_layers"]]
               if t == "full_attention")


def gqa_attention(counters, config):
    """(operations, bytes) of grouped-query attention over the window's
    waves.  Bytes: each live slot's cached K rows and V rows
    (``num_key_value_heads`` heads of ``head``) read ONCE an attention
    layer a wave (a K/V head's query heads share them), plus every live
    row's queries in and outputs out (``num_attention_heads`` heads).
    Operations: a score pair costs, a QUERY head, the score over
    ``head`` columns and the value sum over ``head``."""
    layers = attention_layers(config)
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["hidden_size"] // hq
    k = config["num_experts_per_tok"]
    routed = config["num_hidden_layers"] - config["num_dense_layers"]
    rows = counters["moe_assignments"] // (k * routed)
    ops = counters["attn_score_pairs"] * layers * hq * 2 * dh * 2
    nbytes = BF16 * layers * (
        counters["attn_ctx_tokens"] * 2 * hkv * dh + rows * 2 * hq * dh)
    return ops, nbytes
