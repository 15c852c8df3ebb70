"""The row layout of the paged K/V pool: the one contract between whoever
holds the pool (``serving/kv_manager.py``), whoever writes it
(``models/gpt_decode.py``) and the kernel that reads it in place
(``kernels/ragged_attention.py``).

A pooled position is ONE row a layer for K and one for V: head ``h`` in
lanes ``[h * Dh, (h + 1) * Dh)``, the row padded with zeros to a whole
number of the TPU's 128-lane tiles (GPT-2 XL: 25 x 64 = 1600 -> 1664,
4 % of padding; 768 and 1024 need none).  The device pads a last
dimension to the lanes anyway; stated in the shape, the pad keeps the
pool's default layout row-major, which is the layout a hand-copied page
has, so a donated pool is read where it lies (``[.., 25, 64]`` in bf16
tiles to (32, 128), 2.56 x its bytes, and the compiler then picks another
layout and copies the whole pool to the kernel's and back every wave:
ledger, PR 30, ``copy`` 1.995 s of a 6 s trace).  Everyone but the mixed
ragged wave sees ``[.., H, Dh]`` through :func:`kv_heads`, and the wire
(handoff, tiers) stays ``[.., H, Dh]`` with the pad stripped.
"""

import jax.numpy as jnp

LANES = 128


def kv_row_width(heads, head_dim):
    """Lanes of one pooled K (or V) row: ``heads * head_dim`` rounded up
    to a whole number of lane tiles (as ``LatentSpec.row_width``)."""
    return -(-(heads * head_dim) // LANES) * LANES


def kv_rows(x, width):
    """``[..., H, Dh]`` K/V slabs as pool rows ``[..., width]``: heads
    side by side, zeros in the pad columns."""
    flat = x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
    pad = width - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return flat


def kv_heads(rows, heads, head_dim):
    """Pool rows ``[..., W]`` seen as ``[..., H, Dh]``: the first
    ``H * Dh`` columns reshaped, the pad dropped (numpy or jax)."""
    return rows[..., :heads * head_dim].reshape(
        rows.shape[:-1] + (heads, head_dim))
