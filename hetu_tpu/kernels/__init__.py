"""Pallas TPU kernels for the hot ops: fused flash attention (training
and prefill), the ragged kernel that scores the serving engine's wave
on a TPU, the page write of a wide q-block's K/V rows
(``paged_kv_write``), and the grouped matmul of a chunk wave's routed
experts."""

from . import flash_attention  # noqa: F401
from . import ragged_attention  # noqa: F401
from . import grouped_matmul  # noqa: F401

__all__ = ["flash_attention", "ragged_attention", "grouped_matmul"]
