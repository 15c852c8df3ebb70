"""Operations and bytes the state-space scan needs over a window's waves,
from the engine's own counters and the configuration's sizes alone
(``opcount.py``'s rules: what the mathematics requires, a multiply-add is
two operations).  ``readers/kernel_roofline_ssm.py`` sets them against
the traced time of the WORK (scopes ``ssm_scan`` and ``state_write`` and
whatever kernel later runs under them), so that the share reads the same
whatever implements the scan.

The counters are sums over the waves, each already times the state-space
layers (``ServingMetrics.record_ssm``): ``ssm_slot_steps`` (live slots: a
slot's matrix state moves once a layer a wave), ``ssm_rows`` (live rows)
and ``ssm_chunk_pairs`` (row pairs ``j <= i`` inside the chunks of the
q-blocks wider than one row).
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def ssm_scan(counters, config):
    """(operations, bytes) of the scan over the window's waves.

    Bytes: a live slot's matrix state (``mamba_n_heads`` x
    ``mamba_d_head`` x ``mamba_d_state`` float32, the dtype the
    configuration states for it) read ONCE and written ONCE a layer a
    wave, however many rows the slot has in the wave; every live row's
    x, B, C (bfloat16) and dt (float32) in and y (bfloat16) out.
    Operations: a live row costs, a head, the state's update
    (``2 P N``: decay and rank-one increment) and its read-out
    (``2 P N``); a row pair inside a chunk costs ``2 N`` a group (``C_i
    B_j``) and ``2 P`` a head (the weighted sum of x) besides."""
    H, P, N = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    G = config["mamba_n_groups"]
    ops = counters["ssm_rows"] * H * 4 * P * N \
        + counters.get("ssm_chunk_pairs", 0) * (G * 2 * N + H * 2 * P)
    nbytes = counters["ssm_slot_steps"] * 2 * H * P * N * F32 \
        + counters["ssm_rows"] * (BF16 * (2 * H * P + 2 * G * N) + F32 * H)
    return ops, nbytes
