"""Build-time wiring: Executor / ServingEngine run the static checks.

``HETU_VALIDATE=1`` (default-on under pytest, tests/conftest.py) makes
every executor build and every new feed-shape compile run
:func:`~.verify.verify_graph` + :func:`~.shard_check.check_parallelism`
BEFORE jax traces anything, and every serving-engine build validate its
params against its config.  Each validation appends JSONL records in
the launcher's failure-log shape (:mod:`.report`) to
``$HETU_VALIDATE_LOG`` when set.

Two passes per subgraph, because feed shapes arrive late:

- **build** (``Executor.__init__``): everything derivable from the
  graph alone — cycles, duplicate names, comm axes, sharding
  divisibility, pipeline stage plans, plus shape/dtype propagation
  through every node whose inputs are fully shaped (variables have
  declared shapes; only fed placeholders are UNKNOWN).
- **feeds** (``SubExecutor.run``, once per new feed signature, just
  before the compile that would otherwise produce the XLA stack dump):
  the same walk with the concrete feed shapes, so feed-dependent
  mismatches also fail named-node-first.
"""

from __future__ import annotations

import numpy as np

from .. import envvars
from .report import emit_records, make_record
from .shard_check import ShardCheckError, check_parallelism
from .verify import GraphVerifyError, verify_graph


def validation_enabled() -> bool:
    return envvars.get_bool("HETU_VALIDATE")


def _coerce(dt):
    # mirror gather_feeds' host-side dtype coercion (x64 stays off)
    s = str(dt)
    if s == "float64":
        return np.float32
    if s == "int64":
        return np.int32
    return dt


def _feed_sig_maps(feeds):
    shapes, dtypes = {}, {}
    for k, v in (feeds or {}).items():
        shape = getattr(v, "shape", None)
        if shape is None:
            shape = np.shape(v)
        shapes[k] = tuple(shape)
        dt = getattr(v, "dtype", None)
        if dt is not None:
            dtypes[k] = _coerce(dt)
    return shapes, dtypes


def _validate_sub(ex, sub, phase, feeds=None):
    feed_shapes, feed_dtypes = _feed_sig_maps(feeds)
    # pipeline subgraphs bake the MICROBATCH shape: the executor splits
    # each fed global batch into M chunks along dim 0 before tracing
    # (pipeline_executor._split_microbatches), so validation must model
    # the per-microbatch shapes.  Non-divisible feeds are left out —
    # the executor raises its own (already named) error for those.
    if feeds is None:
        # build phase: dataloader batch shapes are known pre-feed from
        # THIS subgraph's wired loaders
        for dl in getattr(sub, "dataloader_ops", ()):
            loader = getattr(dl, "dataloaders", {}).get(sub.name)
            if loader is not None and getattr(loader, "shape", None):
                feed_shapes.setdefault(dl.name, tuple(loader.shape))
                data = getattr(loader, "data", None)
                if getattr(data, "dtype", None) is not None:
                    feed_dtypes.setdefault(dl.name, _coerce(data.dtype))
    M = getattr(sub, "num_microbatches", None)
    if M and M > 1 and feed_shapes:
        skip = getattr(sub, "non_batch_feeds", frozenset())
        split = {}
        for k, shape in feed_shapes.items():
            if k in skip:
                split[k] = shape
            elif shape and shape[0] % M == 0:
                split[k] = (shape[0] // M,) + tuple(shape[1:])
        feed_shapes = split
    cfg = ex.config
    records = []
    try:
        rep = verify_graph(
            sub.eval_nodes,
            feed_shapes=feed_shapes, feed_dtypes=feed_dtypes,
            rng_available=True,
            mixed_precision=cfg.mixed_precision,
            config=cfg, mesh=ex.mesh,
            skip_ids=frozenset(getattr(sub, "skip_dense", ())))
        findings = check_parallelism(
            sub.eval_nodes, ex.mesh, config=cfg,
            feed_shapes={k: v for k, v in feed_shapes.items()
                         if not k.startswith("__ps")})
        records.append(make_record(
            "graph_verified", subgraph=sub.name, phase=phase,
            nodes=len(rep.table), verified=rep.verified_count(),
            findings=rep.findings + findings))
    except (GraphVerifyError, ShardCheckError) as e:
        records.append(make_record(
            "graph_verify_error", subgraph=sub.name, phase=phase,
            kind=getattr(e, "kind", "unknown"),
            node=getattr(getattr(e, "node", None), "name", None),
            error=str(e)))
        emit_records(records)
        raise
    emit_records(records)
    return records


def validate_executor_build(executor):
    """Executor.__init__ hook: verify every named subgraph with the
    shapes known pre-feed.  Raises GraphVerifyError/ShardCheckError on
    the first defect (no jit traceback, no chip allocation)."""
    if not validation_enabled():
        return None
    out = []
    for sub in executor.subexecutor.values():
        out += _validate_sub(executor, sub, phase="build")
    return out


def validate_subgraph_feeds(executor, sub, feeds):
    """SubExecutor.run hook, once per NEW feed signature (the call
    sites gate on compile-cache misses): re-verify with concrete feed
    shapes so feed-dependent mismatches fail before the trace."""
    if not validation_enabled():
        return None
    return _validate_sub(executor, sub, phase="feeds", feeds=feeds)


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #

def validate_serving(params, config, name, mesh=None):
    """ServingEngine build hook: params/config consistency before any
    cache allocation or compile.  Uses the same error/record contract
    as the graph path."""
    if not validation_enabled():
        return None
    records = []
    try:
        H = int(config.hidden_size)
        heads = int(config.num_attention_heads)
        from ..models.gpt_decode import block_spec_of
        # latent attention has its own head sizes: hidden / heads is
        # not one of them
        if H % heads != 0 \
                and block_spec_of(config).attention != "latent":
            raise ShardCheckError(
                f"serving config: hidden_size {H} is not divisible by "
                f"num_attention_heads {heads}", kind="divisibility")
        wte = params.get(f"{name}_wte_table")
        if wte is None:
            raise GraphVerifyError(
                f"serving params: missing {name}_wte_table (model "
                f"prefix {name!r}; params hold "
                f"{sorted(params)[:8]}...)", kind="shape")
        if tuple(wte.shape)[1] != H:
            raise GraphVerifyError(
                f"serving params: {name}_wte_table has embed dim "
                f"{tuple(wte.shape)[1]}, config.hidden_size is {H}",
                kind="shape")
        wpe = params.get(f"{name}_wpe")
        if wpe is not None:
            if tuple(wpe.shape)[1] != H:
                raise GraphVerifyError(
                    f"serving params: {name}_wpe embed dim "
                    f"{tuple(wpe.shape)[1]} != hidden_size {H}",
                    kind="shape")
            if tuple(wpe.shape)[0] < int(config.max_position_embeddings):
                raise GraphVerifyError(
                    f"serving params: {name}_wpe covers "
                    f"{tuple(wpe.shape)[0]} positions, config asks "
                    f"{int(config.max_position_embeddings)}",
                    kind="shape")
        # MoE serving configs (models/moe_decode.py): every MoE block
        # must carry the gate + stacked expert weights with the expert
        # count the config declares — a per-expert leaf with the wrong
        # leading dim is exactly the corrupt rolling-swap payload the
        # PR 15 shape validation exists to catch, so catch it at build
        # too
        from ..models.moe_decode import moe_spec_of
        spec = moe_spec_of(config)
        if spec is not None:
            E = spec.num_experts
            for i in range(int(config.num_hidden_layers)):
                if not spec.is_moe_layer(i):
                    continue
                us = f"{name}_h{i}"
                gate = params.get(f"{us}_moe_gate_weight")
                w1 = params.get(f"{us}_moe_expert_stack_w1")
                w2 = params.get(f"{us}_moe_expert_stack_w2")
                for leaf, v in (("moe_gate_weight", gate),
                                ("moe_expert_stack_w1", w1),
                                ("moe_expert_stack_w2", w2)):
                    if v is None:
                        raise GraphVerifyError(
                            f"serving params: MoE layer {i} is missing "
                            f"{us}_{leaf} (config routes every "
                            f"{spec.moe_every}th block through "
                            f"{E} experts)", kind="shape")
                if tuple(gate.shape) != (H, E):
                    raise GraphVerifyError(
                        f"serving params: {us}_moe_gate_weight has "
                        f"shape {tuple(gate.shape)}, config wants "
                        f"({H}, {E})", kind="shape")
                for leaf, v, dim, want in (
                        ("moe_expert_stack_w1", w1, 0, E),
                        ("moe_expert_stack_w2", w2, 0, E),
                        ("moe_expert_stack_w1", w1, 1, H),
                        ("moe_expert_stack_w2", w2, 2, H)):
                    if tuple(v.shape)[dim] != want:
                        raise GraphVerifyError(
                            f"serving params: {us}_{leaf} dim {dim} is "
                            f"{tuple(v.shape)[dim]}, config wants "
                            f"{want} (shape {tuple(v.shape)})",
                            kind="shape")
        dtypes = sorted({str(v.dtype) for v in params.values()
                         if hasattr(v, "dtype")})
        records.append(make_record(
            "serving_verified", model=name, params=len(params),
            hidden=H, heads=heads, dtypes=dtypes,
            moe=(None if spec is None else
                 {"experts": spec.num_experts, "top_k": spec.top_k,
                  "moe_every": spec.moe_every})))
    except (GraphVerifyError, ShardCheckError) as e:
        records.append(make_record(
            "graph_verify_error", model=name, phase="serving",
            kind=getattr(e, "kind", "unknown"), error=str(e)))
        emit_records(records)
        raise
    emit_records(records)
    return records
