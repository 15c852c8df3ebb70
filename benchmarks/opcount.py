"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that a PR that claims a gain cannot change
the yardstick.  Every function counts what the mathematics requires, not
what an implementation happens to recompute; a multiply-add is two
operations.
"""

from __future__ import annotations


def matmul_params(cfg):
    """Weights of a GPT-2 block stack that sit in a matrix product per
    token: q, k, v, proj (4 d^2) and the two FFN matrices (8 d^2) per
    layer, plus the tied head (V d).  Embedding lookups, position rows,
    biases and LayerNorm are not matrix products."""
    d = cfg["n_embd"]
    return 12 * cfg["n_layer"] * d * d + cfg["vocab_size"] * d


def causal_attention_flops(batch, heads, seq, head_dim):
    """Forward QK^T and PV of one causal attention call: 2 products of
    2*S*S*Dh operations per head, halved because only the lower triangle
    is needed."""
    return batch * heads * 4 * seq * seq * head_dim // 2


def train_step_flops(cfg, batch, seq):
    """The 6PT step: forward 2 and backward 4 operations per matmul
    weight per token, plus causal attention forward and twice that
    backward.  Recomputed operations are not counted."""
    heads, dh = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    attn = 3 * cfg["n_layer"] * causal_attention_flops(batch, heads, seq, dh)
    return 6 * matmul_params(cfg) * batch * seq + attn
