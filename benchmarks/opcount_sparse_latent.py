"""Operations and bytes the two latent attentions of a model with latent
operators BY LAYER need over a window's waves, from the engine's own
counters and the configuration's sizes alone (``opcount.py``'s rules:
what the mathematics requires, a multiply-add is two operations, in the
ABSORBED form the system keeps: one cached row ``[c_kv | k_r]`` is key
and, in its first ``kv_lora_rank`` columns, value for every head).
``readers/kernel_roofline_sparse_latent.py`` sets them against the
traced time of what implements them.

The counters are sums over the waves, each already summed over the
layers it concerns (``ServingMetrics.record_sparse``,
``record_attention``): ``sparse_keys_read`` (every live row's ``min(in
sight, index_topk)``, times the full layers), ``sparse_keys_needed`` (a
slot's rows read or, where fewer, its positions in sight once, times the
full layers), ``sparse_rows`` (live rows times the full layers),
``attn_window_score_pairs`` / ``attn_window_ctx_tokens`` (what ONE window
layer scores and has in sight) and ``wave_rows_live``.  Sums of what each
wave needs against summed time: a share computed this way errs low.
"""

from __future__ import annotations

BF16 = 2


def layers_of(config, kind):
    return sum(1 for t in config["layer_types"][:config["num_hidden_layers"]]
               if t == kind)


def sparse_latent_attention(counters, config):
    """(operations, bytes) of the full layers' attention over the rows
    their indexer CHOSE.  Operations: a (row, chosen position) pair
    costs, a head, the score over ``kv_lora_rank + qk_rope_head_dim``
    columns and the value sum over ``kv_lora_rank``.  Bytes: the chosen
    cached rows, each ``kv_lora_rank + qk_rope_head_dim`` wide (the pad
    to the lane tile is the implementation's), read once a row or, where
    fewer, a slot's rows in sight once a wave; every live row's absorbed
    queries in and latent outputs out."""
    H = config["num_attention_heads"]
    dc, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    ops = counters["sparse_keys_read"] * H * (2 * dc + dr) * 2
    nbytes = BF16 * (counters["sparse_keys_needed"] * (dc + dr)
                     + counters["sparse_rows"] * H * (2 * dc + dr))
    return ops, nbytes


def window_latent_attention(counters, config):
    """(operations, bytes) of the sliding layers' attention
    (``opcount_window_moe.window_attention``'s rule at the latent
    widths): a score pair INSIDE THE BAND costs, a head of
    ``swa_num_attention_heads``, ``swa_kv_lora_rank +
    swa_qk_rope_head_dim`` columns of score and ``swa_kv_lora_rank`` of
    value sum; bytes the cached rows a live slot's q-block has in sight,
    once a window layer a wave, plus every live row's queries in and
    outputs out."""
    layers = layers_of(config, "sliding_attention")
    H = config["swa_num_attention_heads"]
    dc, dr = config["swa_kv_lora_rank"], config["swa_qk_rope_head_dim"]
    ops = counters["attn_window_score_pairs"] * layers * H \
        * (2 * dc + dr) * 2
    nbytes = BF16 * layers * (
        counters["attn_window_ctx_tokens"] * (dc + dr)
        + counters["wave_rows_live"] * H * (2 * dc + dr))
    return ops, nbytes
