"""Operations and bytes the held latent experts' two products need over a
window's waves, from the engine's own counters and the configuration's
sizes alone (``opcount.py``'s rules: what the mathematics requires, a
multiply-add is two operations).  ``readers/kernel_roofline_nemotron_h``
sets them against the traced time of the WORK (the scope ``moe_experts``
and the compiler's ``ragged-dot`` operations), so that the share reads
the same whatever implements the products.

The counters are sums over the waves (``ServingMetrics.record_routed``):
``moe_assignments`` (the assignments that LANDED on experts this chip
holds, summed over the routed layers: what the products run over; not
``moe_assignments_routed``, which counts the other chips' too) and
``moe_experts_touched`` (held experts with load > 0, summed over the
routed layers).  Sums of what each wave needs against summed time: a
share computed this way errs low (``opcount_latent_moe``).
"""

from __future__ import annotations

BF16 = 2


def latent_experts(counters, config):
    """(operations, bytes) of the held experts' products.  An expert is
    TWO matrices at the latent width, ``moe_latent_size x
    moe_intermediate_size`` up and back (squared ReLU between, no gate).
    Bytes: both matrices of every held expert TOUCHED, once a layer a
    wave; every landed assignment's latent row in and its latent row
    out (the intermediate row between the two products is the
    implementation's, not the mathematics').  Operations: two products
    of ``latent x width`` an assignment."""
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    a = counters["moe_assignments"]
    ops = a * 2 * 2 * lat * f
    nbytes = BF16 * (counters["moe_experts_touched"] * 2 * lat * f
                     + a * 2 * lat)
    return ops, nbytes
