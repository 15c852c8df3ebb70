"""Operations and bytes the WINDOW attention kernel needs over a
window's waves, from the engine's own counters and the configuration's
sizes alone (``opcount.py``'s rules: what the mathematics requires, a
multiply-add is two operations).  ``readers/kernel_roofline_window.py``
sets them against the traced time of the operations named
``ragged_paged_window``, whatever implements them; the routed experts'
are ``opcount_latent_moe.routed_ffn`` (read by the accepted
``moe_experts_roofline.serve``).

The counters are sums over the waves, each counted ONCE a wave
(``ServingMetrics.record_attention``): ``attn_window_ctx_tokens`` (the
positions a window layer had in sight: a live slot's ``min(filled,
window + q_len - 1)``), ``attn_window_score_pairs`` (every live row's
``min(position + 1, window)``) and ``moe_assignments`` (valid rows x
top_k x routed layers; every layer is routed).  Only the WINDOW layers
(``layer_types`` entries "sliding_attention") multiply them here: the
full layers' kernel is ``ragged_paged_mixed``, read by
``ragged_kernel_share.serve``.
"""

from __future__ import annotations

BF16 = 2


def window_layers(config):
    return sum(1 for t in config["layer_types"][:config["num_hidden_layers"]]
               if t == "sliding_attention")


def live_rows(counters, config):
    """The window's live rows, from the router's assignments (every
    layer routes ``num_experts_per_tok`` a row)."""
    return counters["moe_assignments"] // (
        config["num_experts_per_tok"] * config["num_hidden_layers"])


def window_attention(counters, config):
    """(operations, bytes) of the sliding-window attention over the
    window's waves.  Bytes: the K rows and V rows a live slot's q-block
    has IN SIGHT (``num_key_value_heads`` heads of ``head_dim``) read
    once a window layer a wave, plus every live row's queries in and
    outputs out (``num_attention_heads`` heads).  Operations: a score
    pair INSIDE THE BAND costs, a query head, the score over
    ``head_dim`` columns and the value sum over ``head_dim``."""
    layers = window_layers(config)
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    ops = counters["attn_window_score_pairs"] * layers * hq * 2 * dh * 2
    nbytes = BF16 * layers * (
        counters["attn_window_ctx_tokens"] * 2 * hkv * dh
        + live_rows(counters, config) * 2 * hq * dh)
    return ops, nbytes
