"""Operations and bytes the power-retention scan needs over a window's
waves, from the engine's own counters and the configuration's sizes alone
(``opcount.py``'s rules: what the mathematics requires, a multiply-add is
two operations).  ``readers/kernel_roofline_retention.py`` sets them
against the traced time of the WORK (scopes ``ret_expand``, ``ret_scan``
and ``state_write`` and whatever kernel later runs under them), so that
the share reads the same whatever implements the scan.

The counters are sums over the waves, each already times the retention
layers (``ServingMetrics.record_state_scan``): ``ret_slot_steps`` (live
slots: a slot's state moves once a layer a wave), ``ret_rows`` (live
rows) and ``ret_chunk_pairs`` (row pairs ``j <= i`` inside the chunks of
the q-blocks wider than one row).
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def state_bytes(config):
    """A slot's state a layer: ``S`` [g, D, d] and the normaliser ``z``
    [g, D], ``D = d (d + 1) / 2``, float32 (the dtype the configuration
    states for them): 34,080,768 B at 8 K/V heads of 128."""
    g, d = config["num_key_value_heads"], config["head_dim"]
    D = d * (d + 1) // 2
    return g * D * (d + 1) * F32


def retention_scan(counters, config):
    """(operations, bytes) of the scan over the window's waves.

    Bytes: a live slot's ``S`` and ``z`` read ONCE and written ONCE a
    layer a wave, however many rows the slot has in the wave (a dead
    slot's state counts as unmoved); every live row's q, k, v (bfloat16)
    and gate (float32) in and y (bfloat16) out.
    Operations: a live row costs, a K/V head, the state's update and, a
    query head, its read-out (``2 D (d + 1)`` each: the normaliser is the
    ``d + 1``-th column), and ``phi`` of its q and k (``D`` products a
    head); a row pair inside a chunk costs, a query head, the score (``2
    d``) and the weighted sums of v and of ones (``2 (d + 1)``)."""
    n, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    D = d * (d + 1) // 2
    rows = counters["ret_rows"]
    ops = rows * (g + n) * 2 * D * (d + 1) + rows * (g + n) * D \
        + counters.get("ret_chunk_pairs", 0) * n * (2 * d + 2 * (d + 1))
    nbytes = counters["ret_slot_steps"] * 2 * state_bytes(config) \
        + rows * (BF16 * d * (2 * n + 2 * g) + F32 * g)
    return ops, nbytes
