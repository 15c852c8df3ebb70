"""Pallas TPU kernels for the hot ops: fused flash attention (training
and prefill), the phase-split paged decode/verify kernels, and the
mixed-mode ragged kernel the serving engine's TPU default runs."""

from . import flash_attention  # noqa: F401
from . import decode_attention  # noqa: F401
from . import ragged_attention  # noqa: F401

__all__ = ["flash_attention", "decode_attention", "ragged_attention"]
