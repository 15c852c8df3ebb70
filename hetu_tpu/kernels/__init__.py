"""Pallas TPU kernels for the hot ops: fused flash attention (training
and prefill), the ragged kernel that scores the serving engine's wave
on a TPU, the page write of a wide q-block's K/V rows
(``paged_kv_write``), the grouped matmul of a wave's routed experts, and
the state operators' scans on the manager's state where it lies: the
power-retention layer's one-row step and chunked form (two kernels in
``retention_scan``), the Mamba-2
mixer's one-row step (``ssm_step``) and the gated delta rule's chunked
form (``kda_scan``)."""

from . import flash_attention  # noqa: F401
from . import ragged_attention  # noqa: F401
from . import grouped_matmul  # noqa: F401

__all__ = ["flash_attention", "ragged_attention", "grouped_matmul"]
