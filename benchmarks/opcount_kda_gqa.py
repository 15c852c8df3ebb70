"""Operations and bytes of the two mixers of the delta-rule / grouped-query
decoder (the ``solar_open2`` family) over a window's waves, from the
engine's own counters and the configuration's sizes alone (``opcount.py``'s
rules: what the mathematics requires, a multiply-add is two operations).
``readers/kernel_roofline_kda_gqa.py`` sets them against the traced time of
the WORK, so that a share reads the same whatever implements it.

``kda_free_scan``: the scan of the delta rule whose decay has no lower
bound (scopes ``kda_scan`` and ``state_write``: the kernel
``kda_chunk_scan``, the one-row step and the state's store).  The counters
are sums over the waves, each already times the KDA layers
(``ServingMetrics.record_kda``): ``kda_slot_steps`` (slots with ONE live
row: a step of the recurrence, the slot's state read and written once a
layer) and ``kda_chunk_rows`` (the live rows of the q-blocks wider than one
row, which take the chunked form).  The count is ``opcount_kda_latent``'s,
at this configuration's heads: what the chunked form NEEDS inside a chunk
is two lower-triangular score matrices, a forward substitution and three
products with the state.  How an implementation keeps ``exp(G_i - G_j)``
inside float32 for a decay free of any bound is its own cost and is NOT
counted: the program's level-by-level pairing spends, a chunk of 64 rows a
head, twelve ``[64, 128]`` blocks of exponentials and six more ``[128,
128] x [128, 64]`` products than the four sub-block rows of the bounded
gate's form, and one ``[832, 64] x [64, 128]`` float32 product for the
levels' sums of ``g``; a pairwise form (``[16, 16, 128]`` exponentials a
sub-block) would spend more.  Either lowers the share and neither raises
the count.

``kda_gqa_attention``: the one grouped-query layer in four
(``ragged_paged_mixed`` at 64 query heads over 8 K/V heads of 128), counted
ONCE a wave (``ServingMetrics``): ``attn_ctx_tokens`` (a live slot's filled
length after the wave's writes), ``attn_score_pairs`` (the positions every
live row sees), ``wave_rows_live``.
"""

from __future__ import annotations

BF16 = 2
F32 = 4
# the rows a state update of the chunked form spans (``kda_decode.CHUNK``)
CHUNK = 64


def heads(config):
    """(KDA heads, their width) from ``linear_attn_config``."""
    la = config["linear_attn_config"]
    return la["num_heads"], la["head_dim"]


def state_bytes(config):
    """A slot's matrix state a layer: ``S`` [H, D, D] float32 (the dtype
    the configuration states for it): 4,194,304 B at 64 heads of 128."""
    H, D = heads(config)
    return H * D * D * F32


def kda_free_scan(counters, config):
    """(operations, bytes) of the scan over the window's waves.

    Bytes: a live slot's ``S`` read ONCE and written ONCE a layer a wave,
    however many rows the slot has in the wave: every one-row step's, and
    of the wider q-blocks AT LEAST one a ``prefill_chunk`` rows (the
    widest q-block a wave carries: the count errs low, and so does the
    share); every live row's q, k, v (bfloat16), decay (float32 a
    channel) and beta in and its output (bfloat16) out.
    Operations, a head: a one-row step costs the decay (``D^2``), the
    read ``S'^T k``, the rank-one correction and the read-out (``2 D^2``
    each); a row of the chunked form costs its part of the three
    products with the state (``W S``, ``(q e^G) S`` and the update: ``2
    D^2`` each) and, inside its chunk of ``CHUNK`` rows, of the two
    lower-triangular score matrices (``CHUNK x D`` each), of the forward
    substitution over ``2 D`` columns (``2 x CHUNK x D``) and of the
    scores' product with the corrected values (``CHUNK x D``)."""
    H, D = heads(config)
    steps = counters.get("kda_slot_steps") or 0
    rows = counters.get("kda_chunk_rows") or 0
    widest = int(config["runner_args"]["prefill_chunk"])
    ops = steps * H * 7 * D * D + rows * H * (6 * D * D + 5 * CHUNK * D)
    nbytes = (steps + rows // widest) * 2 * state_bytes(config) \
        + (steps + rows) * H * (BF16 * 4 * D + F32 * (D + 1))
    return ops, nbytes


def kda_gqa_attention(counters, config):
    """(operations, bytes) of the grouped-query layers' kernel.  Bytes:
    each live slot's cached K rows and V rows (``num_key_value_heads``
    heads of ``head_dim``) read ONCE an attention layer a wave (a K/V
    head's query heads share them), plus every live row's queries in and
    outputs out (``num_attention_heads`` heads).  Operations: a score
    pair costs, a QUERY head, the score over ``head_dim`` columns and the
    value sum over ``head_dim``."""
    layers = len(config["gqa_layers"])
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    ops = counters["attn_score_pairs"] * layers * hq * 2 * dh * 2
    nbytes = BF16 * layers * (
        counters["attn_ctx_tokens"] * 2 * hkv * dh
        + (counters.get("wave_rows_live") or 0) * 2 * hq * dh)
    return ops, nbytes
