"""hetu_tpu: a TPU-native distributed deep-learning framework.

Brand-new implementation of the capabilities of Hetu (Hankpipi/Hetu,
PKU DAIR Lab) on JAX/XLA/Pallas/pjit: dataflow-graph training API with
autodiff and named subgraphs, compiled to single jitted XLA step programs;
data/tensor/pipeline/expert/context parallelism as mesh shardings; host-side
parameter server with HET-style embedding cache; MoE; auto-parallel planner.

Public surface mirrors the reference package exports
(python/hetu/__init__.py:1-13 + gpu_ops/__init__.py; SURVEY.md Appendix A)
so code written against `import hetu as ht` works with
`import hetu_tpu as ht`.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()

__version__ = "0.1.0"

from .context import (
    DLContext, DeviceGroup, DistConfig, context, get_current_context,
    cpu, gpu, tpu, rcpu, rgpu, rtpu, is_gpu_ctx, check_worker,
)
from .ndarray import (
    NDArray, array, empty, sparse_array, IndexedSlices, ND_Sparse_Array,
)
from .graph import *  # noqa: F401,F403 — the op-factory surface
from .graph import Op, PlaceholderOp, Variable, placeholder_op
from .graph.autodiff import gradients
from .executor import Executor, HetuConfig, SubExecutor
from .dataloader import Dataloader, DataloaderOp, dataloader_op, GNNDataLoaderOp
from .gpu_ops import scheduler_init, scheduler_finish, worker_init, \
    worker_finish, server_init, server_finish, get_worker_communicate, \
    wrapped_mpi_nccl_init, new_group_comm

from . import optimizer as optim
from . import initializers as init
from . import lr_scheduler as lr
from . import data
from . import layers
from . import metrics
from . import parallel
from .parallel import distributed_strategies as dist
from .profiler import HetuProfiler, NCCLProfiler, TPUProfiler
from .cache import CacheSparseTable, EmbeddingCache
from . import tokenizers
from . import planner
from . import onnx
from . import graphboard
from . import hf
from . import launcher
from . import serving
from . import envvars
from . import analysis

# MoE / communication op surface
from .graph.ops_moe import (
    layout_transform_op, reverse_layout_transform_op,
    reverse_layout_transform_no_gate_op, alltoall_op, halltoall_op,
    balance_assignment_op, group_topk_idx_op, sam_group_sum_op, sam_max_op,
    dispatch,
)
from .graph.ops_attention import flash_attention_op, ring_attention_op
from .graph.ops_comm import (
    allreduceCommunicate_op, allreduceCommunicatep2p_op,
    groupallreduceCommunicate_op, allgatherCommunicate_op,
    reducescatterCommunicate_op, broadcastCommunicate_op,
    reduceCommunicate_op, pipeline_send_op, pipeline_receive_op,
    parameterServerCommunicate_op, parameterServerSparsePull_op,
    datah2d_op, datad2h_op, quantized_allreduce_op,
)

from . import telemetry

# what a process pays before its first line of work, as the program sees
# it (the interpreter's own start and ``import jax`` by a caller that
# came first are outside)
telemetry.set_gauge("process.import_ms",
                    (_time.perf_counter() - _IMPORT_T0) * 1e3)
