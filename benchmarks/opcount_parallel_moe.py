"""Operations and bytes the ``cohere2_moe`` cell's three kernels need over
a window's waves, from the engine's own counters and the configuration's
sizes alone (``opcount.py``'s rules: what the mathematics requires, a
multiply-add is two operations).  ``readers/kernel_roofline_parallel.py``
sets them against the traced time of the operations named
``ragged_paged_window`` (the sliding layers), ``ragged_paged_mixed`` (the
full layers) and of the work under the scope ``moe_experts`` (the held
experts' products), whatever implements them.

The counters are sums over the waves, each counted ONCE a wave
(``ServingMetrics``): ``wave_rows_live`` (the wave's live rows: NOT taken
from ``moe_assignments``, which on a held share counts an eighth of
them), ``attn_ctx_tokens`` / ``attn_score_pairs`` (a live slot's filled
length after the wave's writes; the positions every live row sees),
``attn_window_ctx_tokens`` / ``attn_window_score_pairs`` (the same inside
the band: ``min(filled, window + q_len - 1)`` and ``min(position + 1,
window)``), ``moe_assignments`` (the assignments that LANDED on held
experts, summed over the routed layers) and ``moe_experts_touched`` (held
experts with load > 0, summed over them).  The head size is the
configuration's own key (``head_dim`` 128, not ``hidden_size / heads`` =
32).  Sums of what each wave needs against summed time: a share computed
this way errs low (``opcount_latent_moe``).
"""

from __future__ import annotations

BF16 = 2


def layers_of(config, kind):
    return sum(1 for t in config["layer_types"][:config["num_hidden_layers"]]
               if t == kind)


def _attention(config, layers, ctx_tokens, score_pairs, rows):
    """(operations, bytes) of grouped-query attention in ``layers``
    layers.  Bytes: the K rows and V rows a live slot's q-block has in
    sight (``num_key_value_heads`` heads of ``head_dim``) read ONCE a
    layer a wave (a K/V head's 16 query heads share them), plus every
    live row's queries in and outputs out (``num_attention_heads``
    heads).  Operations: a score pair costs, a QUERY head, the score
    over ``head_dim`` columns and the value sum over ``head_dim``."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    ops = score_pairs * layers * hq * 2 * dh * 2
    nbytes = BF16 * layers * (ctx_tokens * 2 * hkv * dh + rows * 2 * hq * dh)
    return ops, nbytes


def wide_window_attention(counters, config):
    """The sliding layers' kernel: what lies inside the band."""
    return _attention(config, layers_of(config, "sliding_attention"),
                      counters["attn_window_ctx_tokens"],
                      counters["attn_window_score_pairs"],
                      counters["wave_rows_live"])


def wide_full_attention(counters, config):
    """The full layers' kernel: everything before the query."""
    return _attention(config, layers_of(config, "full_attention"),
                      counters["attn_ctx_tokens"],
                      counters["attn_score_pairs"],
                      counters["wave_rows_live"])


def held_experts(counters, config):
    """(operations, bytes) of the held experts' products.  An expert is
    THREE matrices ``hidden_size x intermediate_size`` (gate, up, down).
    Bytes: the three matrices of every held expert TOUCHED, once a layer
    a wave; every landed assignment's row in and its row out (the
    intermediate rows between the products are the implementation's, not
    the mathematics').  Operations: three products of ``hidden x width``
    an assignment."""
    d, f = config["hidden_size"], config["intermediate_size"]
    a = counters["moe_assignments"]
    ops = a * 3 * 2 * d * f
    nbytes = BF16 * (counters["moe_experts_touched"] * 3 * d * f + a * 2 * d)
    return ops, nbytes
