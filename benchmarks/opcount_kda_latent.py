"""Operations and bytes the gated delta rule's scan needs over a window's
waves, from the engine's own counters and the configuration's sizes alone
(``opcount.py``'s rules: what the mathematics requires, a multiply-add is
two operations).  ``readers/kernel_roofline_kda.py`` sets them against the
traced time of the WORK (scopes ``kda_scan`` and ``state_write`` and
whatever kernel later runs under them: a Pallas kernel for the scan would
be named ``kda_chunk_scan`` and called under ``kda_scan``), so that the
share reads
the same whatever implements the scan.

The counters are sums over the waves, each already times the KDA layers
(``ServingMetrics.record_kda``): ``kda_slot_steps`` (slots with ONE live
row: a step of the recurrence, the slot's state read and written once a
layer) and ``kda_chunk_rows`` (the live rows of the q-blocks wider than
one row, which take the chunked form).
"""

from __future__ import annotations

BF16 = 2
F32 = 4
# the rows a state update of the chunked form spans (``kda_decode.CHUNK``)
CHUNK = 64


def state_bytes(config):
    """A slot's matrix state a layer: ``S`` [H, D, D] float32 (the dtype
    the configuration states for it): 2,097,152 B at 32 heads of 128."""
    H, D = config["num_attention_heads"], config["head_dim"]
    return H * D * D * F32


def kda_scan(counters, config):
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
    H, D = config["num_attention_heads"], config["head_dim"]
    steps = counters.get("kda_slot_steps") or 0
    rows = counters.get("kda_chunk_rows") or 0
    widest = int(config["runner_args"]["prefill_chunk"])
    ops = steps * H * 7 * D * D + rows * H * (6 * D * D + 5 * CHUNK * D)
    nbytes = (steps + rows // widest) * 2 * state_bytes(config) \
        + (steps + rows) * H * (BF16 * 4 * D + F32 * (D + 1))
    return ops, nbytes
