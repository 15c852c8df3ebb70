"""Operations and bytes the latent-attention kernel and the routed
experts need, from the engine's own counters and the configuration's
sizes alone (``opcount.py``'s rules: what the mathematics requires, a
multiply-add is two operations).  ``kernel_roofline`` sets them against
the kernels' traced time.

The counters are sums over the waves of a window (``ServingMetrics``
``snapshot(since=mark)``): ``attn_ctx_tokens`` (every live slot's filled
length after the wave's writes, once a wave), ``attn_score_pairs`` (the
positions every live row sees), ``moe_assignments`` (valid rows x top_k
x routed layers) and ``moe_experts_touched`` (experts with load > 0,
summed over the routed layers).  Sums of what each wave needs, against
summed kernel time: a sum of maxima is no less than the maximum of sums,
so a share computed this way errs low.
"""

from __future__ import annotations

BF16 = 2


def mla_attention(counters, config):
    """(operations, bytes) of the absorbed latent attention over the
    window's waves.  Bytes: each live slot's cached rows ``[c_kv | k_r]``
    read ONCE a layer a wave (all heads share them), plus the absorbed
    query (``kv_lora_rank + rope`` wide) read and the latent output
    (``kv_lora_rank`` wide) written for every (row, head).  Operations:
    a score pair costs, a head, the score over ``kv_lora_rank + rope``
    columns and the value sum over ``kv_lora_rank``."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    dc, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    k = config["num_experts_per_tok"]
    routed = layers - config["first_k_dense_replace"]
    rows = counters["moe_assignments"] // (k * routed)
    ops = counters["attn_score_pairs"] * layers * heads * ((dc + dr) + dc) * 2
    nbytes = BF16 * layers * (
        counters["attn_ctx_tokens"] * (dc + dr)
        + rows * heads * ((dc + dr) + dc))
    return ops, nbytes


def routed_ffn(counters, config):
    """(operations, bytes) of the routed experts' grouped matmuls over
    the window's waves.  Bytes: the three matrices of every expert
    TOUCHED (once a layer a wave), plus every assignment's row in
    (hidden, twice: gate and up share it in the mathematics, so once),
    its two intermediate rows out and one back in, and its row out.
    Operations: three products of ``hidden x expert width`` an
    assignment."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    a = counters["moe_assignments"]
    ops = a * 3 * 2 * d * f
    nbytes = BF16 * (counters["moe_experts_touched"] * 3 * d * f
                     + a * (2 * d + 3 * f))
    return ops, nbytes
