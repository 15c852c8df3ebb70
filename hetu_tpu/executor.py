"""Executor: named subgraphs compiled to jitted XLA step functions.

API-parity with the reference Executor/HetuConfig
(gpu_ops/executor.py:134,365,570): ``Executor({'train': [loss, train_op],
'validate': [...]})`` then ``run(name, feed_dict)``.

Architectural divergence (SURVEY.md §1): the reference walks a topo-sorted
op list per step, launching one CUDA kernel per op over five streams with
event-based ordering (executor.py:1005-1061) and a static memory-reuse plan
(memory_pool.py).  Here each named subgraph is traced ONCE per feed-shape
into a single XLA program: fusion replaces per-op dispatch, buffer donation
replaces the memory planner, XLA async collectives replace stream overlap.

Distribution: a `jax.sharding.Mesh` + per-leaf NamedShardings on params and
feeds replace the reference's graph-rewriting (AllReduce op splicing,
optimizer.py:145-164).  Gradient reduction is inserted by XLA from the
shardings alone.
"""

from __future__ import annotations

import os
import pickle
import re
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .graph.node import Op, TraceContext
from .graph.autodiff import find_topo_sort
from .graph.ops_misc import Backward, PlaceholderOp
from .graph.ops_embed import IndexedSlicesOp
from .optimizer import OptimizerOp
from . import telemetry


class _ParamView:
    """Node-keyed view over the name-keyed param dict used inside traces."""

    def __init__(self, d):
        self._d = d

    def __getitem__(self, node):
        return self._d[node.name]

    def __contains__(self, node):
        return node.name in self._d


class _ExtraOutputs(dict):
    """Node-keyed writes, name-keyed storage."""

    def __setitem__(self, node, value):
        super().__setitem__(node.name if isinstance(node, Op) else node, value)


class HetuConfig:
    """Runtime config (reference executor.py:134-211 slot list).

    Knob semantics here:
      comm_mode      None/'AllReduce' = pure jit path (gradient reduction
                     comes from shardings); 'Hybrid' = embedding tables
                     live on the PS (with the HET cache when
                     cstable_policy is set) while dense grads stay on
                     device; 'PS' = dense params also round-trip the PS
                     with server-side optimizers.
      cstable_policy 'LRU'/'LFU'/'LFUOpt' — cache-enabled embedding path.
      cache_bound    cache capacity in rows per embedding table.
      bsp            -1 async, 0 per-step barrier, >0 SSP staleness bound
                     (multi-worker PS training).
      prefetch       overlap next batch's PS embedding lookup with the
                     current step (dataloader-fed ids only).
      async_push     opt-in: drain phase B (grad D2H + PS/cache push)
                     on a background worker; the next step's lookups
                     join it first, so read-your-writes semantics (and
                     the staleness-0 trajectory) are unchanged.  Pays
                     off only when the training loop has host work to
                     overlap (data augmentation, metrics, multi-table
                     steps); in a tight run() loop the join lands
                     immediately and the thread handoff is pure
                     overhead (measured 27->41 ms/step on the CTR
                     shape), so the default stays synchronous.
      use_sparse_pull sparse row pull vs full-table pull in PS mode.
      enable_lazy / overlap / use_nccl_collectives — no-ops by design:
                     everything is lazily traced into one jitted program,
                     XLA overlaps collectives, and collectives are always
                     XLA's (documented, accepted for API parity).
      pipeline       'gpipe'/'1f1b'/'pipedream'/'hetpipe' — training
                     subgraphs run through the pipeline partitioner +
                     microbatch schedules (pipeline_executor.py); with a
                     'pp' mesh axis and a uniform repeated body the SPMD
                     scan pipeline is used.  num_stages/num_microbatches/
                     sync_every parameterize it.
      use_preduce — raises; drive parallel.preduce.PartialReduce directly.
    """

    def __init__(self, eval_node_list=None, train_name=None, val_name=None,
                 comm_mode=None, use_sparse_pull=True, cstable_policy=None,
                 bsp=-1, prefetch=True, async_push=False, enable_lazy=False,
                 cache_bound=100,
                 log_path=None, my_eval_nodes=None, dist_strategy=None,
                 pipeline=None, overlap=True, use_preduce=False,
                 use_nccl_collectives=True, seed=0, mesh=None,
                 num_microbatches=None, num_stages=None, sync_every=None,
                 non_batch_feeds=(), dtype=jnp.float32,
                 mixed_precision=None, ps_comm=None,
                 shard_pipeline_ends=True):
        if comm_mode not in (None, "AllReduce", "PS", "Hybrid"):
            raise ValueError(f"comm_mode must be None/'AllReduce'/'PS'/"
                             f"'Hybrid', got {comm_mode!r}")
        self.comm_mode = comm_mode
        self.use_sparse_pull = use_sparse_pull
        if cstable_policy is not None and comm_mode not in ("PS", "Hybrid"):
            raise ValueError("cstable_policy requires comm_mode='PS' or "
                             "'Hybrid' (the cache fronts the PS)")
        self.cstable_policy = cstable_policy
        self.bsp = bsp
        self.prefetch = prefetch
        self.async_push = async_push
        self.enable_lazy = enable_lazy
        self.cache_bound = cache_bound
        self.log_path = log_path
        self.dist_strategy = dist_strategy
        if pipeline not in (None, "gpipe", "1f1b", "pipedream", "hetpipe"):
            raise ValueError(f"unknown pipeline mode {pipeline!r}")
        self.pipeline = pipeline
        self.num_stages = num_stages
        self.sync_every = sync_every
        # pipeline mode: feed names that are per-step constants (e.g. an
        # [S, S] attention mask), passed whole to every microbatch rather
        # than split along dim 0
        self.non_batch_feeds = tuple(non_batch_feeds)
        self.overlap = overlap
        if use_preduce:
            raise NotImplementedError(
                "use_preduce: drive parallel.preduce.PartialReduce "
                "directly (host-coordinated subgroup mean over the PS)")
        self.use_preduce = use_preduce
        self.use_nccl_collectives = use_nccl_collectives
        self.seed = seed
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self.dtype = dtype
        # compute dtype policy: None = full precision; "bf16"/jnp.bfloat16
        # casts params+float feeds at graph entry, keeps fp32 master
        # weights in the optimizer (MXU wants bf16 matmuls)
        if mixed_precision in ("bf16", "bfloat16"):
            mixed_precision = jnp.bfloat16
        elif mixed_precision in ("fp16", "float16"):
            mixed_precision = jnp.float16
        self.mixed_precision = mixed_precision
        self.ps_comm = ps_comm
        # pipeline mode: place big pre/post ("end") tensors 1/S-sharded
        # over the 'pp' axis instead of replicated per stage (see
        # Executor._shard_end_params_over_pp)
        self.shard_pipeline_ends = shard_pipeline_ends


# below this per-batch size the background device_put costs more (thread
# contention on dispatch) than the H2D it hides: small batches run
# fastest with host-only ring assembly (threshold not re-measured on
# today's host link)
_RING_DEVICE_PUT_MIN_BYTES = 4 << 20

# most training steps of push traffic the direct (cache-less) hybrid
# path buffers through a PS outage before the run fails
_PS_BACKLOG_STEPS = 32


def _wire_prefetch(sub):
    """Wire this subgraph's dataloaders: multi-host batch sharding, then
    background prefetch rings (config.prefetch; reference 3-deep ring,
    dataloader.py:30-100).

    Multi-host (VERDICT r2 item 5): each process's loader is told to
    produce only the batch rows its addressable devices hold under the
    feed sharding — host RAM traffic and feed work per process stay
    constant as processes are added, instead of every process
    materializing the identical global batch (the reference's per-worker
    dp-sharded loaders, dataloader.py:22-28).

    Loaders feeding PS embedding lookups stay host-side AND unsharded —
    phase A needs the raw global ids as numpy.  Large batches
    additionally device_put (with the feed sharding) inside the ring so
    the H2D transfer leaves the critical path; small batches stay
    host-only (the put is cheaper than the thread contention it
    causes)."""
    ex = sub.executor
    ps_srcs = {id(lk.inputs[1]) for lk in getattr(sub, "ps_lookups", [])}
    for dl_op in sub.dataloader_ops:
        loaders = getattr(dl_op, "dataloaders", None)
        loader = loaders.get(sub.name) if loaders else None
        if loader is None or loader._ring is not None:
            continue
        is_ps = id(dl_op) in ps_srcs
        if not is_ps:
            loader.init_states()
            # drop_last only: a partial global tail would be
            # indistinguishable from a local shard by row count
            if ex.multiprocess and loader._shard is None \
                    and loader.drop_last:
                rows = ex.process_batch_rows(dl_op.name,
                                             tuple(loader.shape))
                if rows is not None:
                    loader.set_batch_shard(*rows)
                    # keyed by local row count: one DataloaderOp name can
                    # front loaders with different batch sizes
                    ex._proc_shard.setdefault(dl_op.name, {})[
                        rows[1] - rows[0]] = (
                        rows[0], rows[1], loader.shape[0])
        if not ex.config.prefetch:
            continue
        transform = None
        if not is_ps:
            local_rows = loader.shape[0] if loader._shard is None \
                else loader._shard[1] - loader._shard[0]
            nbytes = local_rows * int(np.prod(loader.shape[1:])) * \
                loader.data.dtype.itemsize
            if nbytes >= _RING_DEVICE_PUT_MIN_BYTES:
                def transform(arr, _n=dl_op.name):
                    arr = np.asarray(arr)
                    if arr.dtype == np.float64:
                        arr = arr.astype(np.float32)
                    if arr.dtype == np.int64:
                        arr = arr.astype(np.int32)
                    return ex.device_put_feed(_n, arr)
        loader.start_prefetch(transform=transform)


def _bucket_len(n):
    """Next power of two >= n (min 64): pads the variable unique-row
    count to a handful of shapes so the shape-keyed compile cache stays
    small while the host link still ships ~n rows."""
    b = 64
    while b < n:
        b <<= 1
    return b


def stable_rng_ids(sub):
    """node.id -> topo position: a build-invariant RNG stream index
    (two builds of the same graph give every node the same position,
    while raw ids shift with the global counter).  Cached on the
    subexecutor; shared by the plain and pipeline executors so their
    dropout/rand streams follow one contract."""
    ids = getattr(sub, "_rng_ids", None)
    if ids is None:
        ids = sub._rng_ids = {n.id: i for i, n in enumerate(sub.topo)}
    return ids


def gather_feeds(sub, feed_dict, peek=False):
    """Collect dataloader + fed values into a name-keyed dict, coercing
    dtypes host-side.  Device-resident jax.Arrays pass through untouched
    (np.asarray on them would force a blocking D2H).  ``peek`` reads the
    dataloaders WITHOUT consuming a batch — analysis paths (profiler
    lower/compile) must not advance the training data position."""
    if not getattr(sub, "_prefetch_wired", False):
        sub._prefetch_wired = True
        _wire_prefetch(sub)
    feeds = {}
    for dl in sub.dataloader_ops:
        feeds[dl.name] = dl.peek_arr(sub.name) if peek \
            else dl.get_arr(sub.name)
    for node, value in feed_dict.items():
        name = node.name if isinstance(node, Op) else node
        feeds[name] = value
    for name in list(feeds):
        v = feeds[name]
        if isinstance(v, jax.Array) and v.dtype not in (
                jnp.float64, jnp.int64):
            continue
        arr = np.asarray(v)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        if arr.dtype == np.int64:
            arr = arr.astype(np.int32)
        feeds[name] = arr
    return feeds


_SERIAL = re.compile(r"_(in)?\d+")


def scope_name(node):
    """The ``jax.named_scope`` a node's compute is traced under: its
    name without the serial numbers (``Linear_45`` -> ``Linear``,
    ``grad_Linear_45_in1_213`` -> ``grad_Linear``), so that the device
    trace tells the flash op, the tied-head loss and the optimizer from
    the block matmuls under names that survive a rebuild."""
    if isinstance(node, OptimizerOp):
        return "optimizer"
    return _SERIAL.sub("", node.name)


class SubExecutor:
    """One named subgraph compiled to a jitted step function, cached per
    feed-shape signature (reference SubExecutor at executor.py:570, but the
    whole compute loop collapses into XLA)."""

    def __init__(self, name, eval_nodes, executor):
        self.name = name
        self.eval_nodes = eval_nodes
        self.executor = executor
        self.topo = find_topo_sort(eval_nodes)
        self.optimizer_ops = [n for n in self.topo if isinstance(n, OptimizerOp)]
        self.training = len(self.optimizer_ops) > 0
        self.feeds = [n for n in self.topo
                      if isinstance(n, PlaceholderOp) and not n.is_variable]
        from .dataloader import DataloaderOp
        self.dataloader_ops = [n for n in self.topo
                               if isinstance(n, DataloaderOp)]
        # IndexedSlices nodes consumed only sparsely are never densified
        consumers = {}
        for n in self.topo:
            for i in n.inputs:
                consumers.setdefault(id(i), []).append(n)
        self.skip_dense = set()
        for n in self.topo:
            if isinstance(n, IndexedSlicesOp):
                cons = consumers.get(id(n), [])
                if cons and all(isinstance(c, OptimizerOp) for c in cons):
                    self.skip_dense.add(id(n))
        # PS-managed embedding lookups: their rows are gathered host-side
        # (from the PS / HET cache) before the jitted step and fed in; the
        # table itself never materializes on device
        from .graph.ops_embed import EmbeddingLookupOp
        self.ps_lookups = []     # EmbeddingLookupOp nodes on PS tables
        self.ps_var_names = frozenset(executor.ps_sparse_vars) \
            | frozenset(executor.ps_dense_vars)
        if executor.ps_sparse_vars:
            for n in self.topo:
                if isinstance(n, EmbeddingLookupOp) and \
                        n.inputs[0].name in executor.ps_sparse_vars:
                    src = n.inputs[1]
                    from .dataloader import DataloaderOp
                    if not (isinstance(src, DataloaderOp) or
                            (isinstance(src, PlaceholderOp)
                             and not src.is_variable)):
                        raise NotImplementedError(
                            f"PS embedding lookup ids must come straight "
                            f"from a feed or dataloader (got "
                            f"{type(src).__name__} feeding {n.name}); the "
                            f"host gather needs concrete ids pre-step")
                    self.ps_lookups.append(n)
        self._ps_lookup_ids = set(id(n) for n in self.ps_lookups)
        self._prefetched = {}    # lookup node name -> (ids, Future)
        self._compiled = {}
        self._runs = 0           # run() calls: the spans' step= tag
        # async phase B: one worker drains the grad D2H + PS/cache push
        # off the critical path (reference overlaps push with the next
        # batch via CSEvent streams, stream.py:90-105); ordering with
        # the next lookup is enforced by _join_phase_b
        self._phase_b_pool = None
        if self.training and self.ps_var_names \
                and executor.config.async_push:
            from concurrent.futures import ThreadPoolExecutor
            self._phase_b_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"psb-{name}")

    # ------------------------------------------------------------------ #

    def _stable_rng_ids(self):
        return stable_rng_ids(self)

    def _trace(self, params, opt_states, step, rng, feeds):
        tc = TraceContext(params=_ParamView(params), rng=rng,
                          training=self.training, mesh=self.executor.mesh,
                          config=self.executor.config, step=step)
        tc.rng_ids = self._stable_rng_ids()
        tc.extra_outputs = _ExtraOutputs()
        # the whole subgraph is ONE jax trace: its gradient nodes share
        # each forward's pullback and each backward's call
        tc.backward = Backward(self.topo)
        vals = {}
        new_opt_states = dict(opt_states)
        side_outputs = {}
        mp = self.executor.config.mixed_precision

        def _cast_in(v):
            # graph entry: float params/feeds compute in the policy dtype;
            # masters stay fp32 in `params` (optimizer reads those)
            if mp is not None and hasattr(v, "dtype") \
                    and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(mp)
            return v

        from .dataloader import DataloaderOp
        for node in self.topo:
            if id(node) in self._ps_lookup_ids:
                # PS-managed embedding: UNIQUE rows pre-gathered
                # host-side; the in-trace gather re-expands them
                # (device-side dedup — the host link carries U unique
                # rows, not B*T positions; reference dedups on GPU via
                # IndexedSlices, src/ops/IndexedSlices.cu).  Unique rows
                # are keyed per TABLE (several lookups share one fetch);
                # the expansion map is per lookup.
                uniq = _cast_in(feeds["__psuniq__" + node.inputs[0].name])
                inv = feeds["__psinv__" + node.name]
                vals[id(node)] = jnp.take(uniq, inv, axis=0)
            elif isinstance(node, DataloaderOp):
                vals[id(node)] = _cast_in(feeds[node.name])
            elif isinstance(node, PlaceholderOp):
                if node.name in self.executor.ps_sparse_vars:
                    vals[id(node)] = None  # table lives on the PS
                elif node.name in params:
                    vals[id(node)] = _cast_in(params[node.name])
                else:
                    vals[id(node)] = _cast_in(feeds[node.name])
            elif isinstance(node, OptimizerOp):
                grad_vals = []
                for i, g in enumerate(node.inputs):
                    if i in node.sparse_inputs:
                        grad_vals.append((vals[id(g.ids_node)],
                                          vals[id(g.values_node)]))
                    else:
                        grad_vals.append(vals[id(g)])
                with jax.named_scope(scope_name(node)):
                    new_opt_states[node.name] = node.apply(
                        grad_vals, tc, opt_states[node.name],
                        ps_vars=self.ps_var_names,
                        side_outputs=side_outputs)
                vals[id(node)] = None
            elif id(node) in self.skip_dense:
                vals[id(node)] = None
            else:
                ins = [vals[id(i)] for i in node.inputs]
                with jax.named_scope(scope_name(node)):
                    # node.compute, under jax.vjp where a VJPOp of this
                    # subgraph will want the pullback
                    vals[id(node)] = tc.backward.compute(node, ins, tc)
        # dedup the embedding grads on DEVICE: segment-sum per-position
        # rows into the unique-row slots so phase B ships U rows back,
        # mirroring the forward's unique-row feed.  The adjoint carries
        # vocab ids (possibly concatenated across several lookups into
        # the table); searchsorted against the sorted unique-id feed maps
        # them to slots.
        for var in {lk.inputs[0].name for lk in self.ps_lookups}:
            if var in side_outputs and var in self.executor.ps_sparse_vars:
                ids, rows = side_outputs[var]
                uniq_ids = feeds["__psuniqids__" + var]
                slot = jnp.searchsorted(uniq_ids,
                                        ids.astype(uniq_ids.dtype))
                g_uniq = jnp.zeros(
                    (uniq_ids.shape[0], rows.shape[-1]),
                    rows.dtype).at[slot].add(rows)
                if mp is not None:
                    # grads were computed in the policy dtype; shipping
                    # them D2H at that width halves the host-link bytes
                    # (the PS applies the update in fp32 regardless)
                    g_uniq = g_uniq.astype(mp)
                side_outputs[var] = g_uniq
        outputs = [vals[id(n)] for n in self.eval_nodes]
        if mp is not None:
            # report losses/metrics in fp32
            outputs = [o.astype(jnp.float32) if hasattr(o, "dtype")
                       and jnp.issubdtype(o.dtype, jnp.floating) else o
                       for o in outputs]
        new_params = dict(params)
        for k, v in tc.extra_outputs.items():
            if k in params and hasattr(v, "dtype") \
                    and v.dtype != params[k].dtype:
                # state written from a bf16 trace (e.g. BN running stats)
                # must not narrow the fp32 master copy
                v = v.astype(params[k].dtype)
            new_params[k] = v
        return new_params, new_opt_states, outputs, side_outputs

    def _compile(self, feed_sig):
        ex = self.executor

        def step_fn(params, opt_states, step, rng, feeds):
            # rng splits INSIDE the jitted program (an eager per-step
            # split is a full host<->device round trip)
            new_rng, sub = jax.random.split(rng)
            new_params, new_opt, outputs, side = self._trace(
                params, opt_states, step, sub, feeds)
            # only optimizer steps advance the counter — eval passes must
            # not skew Adam bias correction / LR schedules
            new_step = step + 1 if self.training else step
            return new_params, new_opt, new_step, new_rng, outputs, side

        jit_kwargs = dict(donate_argnums=(0, 1))
        if ex.mesh is not None:
            param_sh = {k: ex.param_sharding(k) for k in ex.var_values}
            feed_sh = {name: ex.feed_sharding(name, shape)
                       for name, shape, _ in feed_sig}
            rep = NamedSharding(ex.mesh, P())
            opt_sh = _opt_sharding_like(ex, ex.opt_states)
            jit_kwargs["in_shardings"] = (
                param_sh, opt_sh, rep, rep, feed_sh)
            # pin updated params/opt states to their input shardings —
            # otherwise GSPMD may pick a different output layout and the
            # next step's in_shardings check fails
            jit_kwargs["out_shardings"] = (param_sh, opt_sh, rep, rep,
                                           None, None)
        return jax.jit(step_fn, **jit_kwargs)

    @property
    def batch_num(self):
        nums = [dl.get_batch_num(self.name) for dl in self.dataloader_ops]
        nums = [n for n in nums if n is not None]
        return min(nums) if nums else None

    def run(self, feed_dict, convert_to_numpy_ret_vals=False):
        """One step of this subgraph.

        Spans, a fixed number a step, all tagged ``step=`` (this
        subgraph's run count): ``exec.step`` (root, the whole of
        ``run``) holding ``exec.feed`` (gathering the feeds and joining
        the previous step's PS push; under a mesh a second one,
        ``part="place"``, places them), ``exec.phase_a`` (PS lookups),
        ``exec.compile`` (a new feed signature only), ``exec.dispatch``,
        ``exec.phase_b`` (PS subgraphs only) and ``exec.fetch`` (only
        with ``convert_to_numpy_ret_vals``: the host waits for the
        device there).  ``exec.dispatch`` closes when the asynchronous call
        returns: it is ENQUEUE time, not step time; the step's device
        time is in the profiler's trace, under the same span names with
        the ``hetu.`` prefix."""
        self._runs += 1
        with telemetry.span("exec.step", subgraph=self.name,
                            step=self._runs):
            return self._run(feed_dict, convert_to_numpy_ret_vals,
                             self._runs)

    def _run(self, feed_dict, convert_to_numpy_ret_vals, step):
        ex = self.executor
        with telemetry.span("exec.feed", subgraph=self.name, step=step):
            feeds = gather_feeds(self, feed_dict)
            # read-your-writes: the previous step's async push must
            # land in the cache/PS before this step's lookups
            ex.join_ps_push()
        with telemetry.span("exec.phase_a", subgraph=self.name, step=step):
            ps_ids = self._ps_phase_a(feeds)
        feed_sig = tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in feeds.items()))
        compiled_now = feed_sig not in self._compiled
        if compiled_now:
            # pre-trace validation with the concrete feed shapes: a
            # miswired graph fails HERE with the node named, not as an
            # XLA stack dump out of the compile below (HETU_VALIDATE=1)
            telemetry.inc("exec.compile_cache_miss")
            with telemetry.span("exec.compile", subgraph=self.name,
                                step=step):
                from .analysis import validate_subgraph_feeds
                validate_subgraph_feeds(ex, self, feeds)
                self._compiled[feed_sig] = self._compile(feed_sig)
        fn = self._compiled[feed_sig]
        if ex.mesh is not None:
            # placement follows phase A (its rows are feeds too) and the
            # signature (taken from the host shapes)
            with telemetry.span("exec.feed", subgraph=self.name,
                                step=step, part="place"):
                feeds = {k: ex.device_put_feed(k, v)
                         for k, v in feeds.items()}
        # dispatch covers trace+compile on a cache-miss step (jax.jit is
        # lazy — the first call lowers); `compiled` marks those spans so
        # the trace attributes the fat step correctly
        with telemetry.span("exec.dispatch", subgraph=self.name,
                            step=step, compiled=compiled_now):
            ex.var_values, ex.opt_states, ex.step, ex.rng, outputs, side \
                = fn(ex.var_values, ex.opt_states, ex.step, ex.rng, feeds)
        telemetry.inc("exec.steps")
        if self.ps_var_names and self.training:
            if self._phase_b_pool is not None:
                # the worker blocks on the grads' D2H, pushes, THEN
                # prefetches (so the prefetched rows see the update);
                # the main thread returns to the training loop
                def _push():
                    with telemetry.span("exec.phase_b", step=step,
                                        subgraph=self.name, mode="async"):
                        self._ps_phase_b(side, ps_ids)
                    self._ps_prefetch()
                ex._ps_push_future = self._phase_b_pool.submit(_push)
            else:
                with telemetry.span("exec.phase_b", subgraph=self.name,
                                    step=step, mode="sync"):
                    self._ps_phase_b(side, ps_ids)
                self._ps_prefetch()
        else:
            self._ps_prefetch()
        if not convert_to_numpy_ret_vals:
            return list(outputs)
        with telemetry.span("exec.fetch", subgraph=self.name, step=step):
            return [None if o is None else np.asarray(o) for o in outputs]

    # ------------------------------------------------------------------ #
    # Hybrid/PS host phases (reference ParameterServerCommunicate.py:38-57
    # push-pull compute, :193-204 prefetch; executor.py:253-258 cache
    # wiring).  Phase A gathers embedding rows for the batch from the PS /
    # HET cache; phase B pushes the step's grads back; prefetch overlaps
    # the NEXT batch's lookup with everything after dispatch.
    # ------------------------------------------------------------------ #

    def _ps_phase_a(self, feeds):
        """Gather UNIQUE rows for every PS-managed lookup; returns
        {var: unique ids}.  The host link carries U unique rows, padded
        to power-of-two
        buckets so the jitted step compiles a handful of shapes, not one
        per batch; the in-trace gather re-expands to B*T positions."""
        ex = self.executor
        ps_ids = {}
        by_var = {}
        for lk in self.ps_lookups:
            by_var.setdefault(lk.inputs[0].name, []).append(lk)
        for var_name, lks in by_var.items():
            id_arrays = [np.asarray(feeds[lk.inputs[1].name])
                         for lk in lks]
            all_flat = np.concatenate(
                [a.reshape(-1).astype(np.int64) for a in id_arrays])
            pre = self._prefetched.pop(var_name, None)
            if pre is not None and np.array_equal(pre[0], all_flat):
                _, uniq, fut = pre
                rows = fut.result()
            else:
                uniq = np.unique(all_flat)
                rows = ex.ps_lookup(var_name, uniq)
            rows = np.asarray(rows, np.float32).reshape(len(uniq), -1)
            mp = ex.config.mixed_precision
            if mp is not None:
                # the trace casts float feeds to the policy dtype anyway;
                # casting host-side halves the H2D bytes for the rows
                rows = rows.astype(mp)
            upad = _bucket_len(len(uniq))
            if upad > len(uniq):
                rows = np.concatenate(
                    [rows, np.zeros((upad - len(uniq), rows.shape[1]),
                                    rows.dtype)])
            # sorted unique ids, padded with a +inf-like sentinel so the
            # device searchsorted stays within a sorted array.  int32:
            # jax (x64 off) would silently demote an int64 feed and
            # overflow the sentinel into the middle of the "sorted" array
            uniq_pad = np.full(upad, np.iinfo(np.int32).max, np.int32)
            uniq_pad[:len(uniq)] = uniq
            feeds["__psuniq__" + var_name] = rows
            feeds["__psuniqids__" + var_name] = uniq_pad
            for lk, ids in zip(lks, id_arrays):
                inv = np.searchsorted(uniq, ids.reshape(-1))
                feeds["__psinv__" + lk.name] = \
                    inv.reshape(ids.shape).astype(np.int32)
            ps_ids[var_name] = uniq
        # dense-PS params ('PS' mode): refresh from the server so other
        # workers' pushes are visible (BSP/SSP pacing via config.bsp)
        for name in ex.ps_dense_vars:
            if ex.ps_dense_dirty.pop(name, False):
                val = ex.ps_comm.pull(name)
                if ex.mesh is not None:
                    arr = ex.place_value(np.asarray(val),
                                         ex.param_sharding(name))
                else:
                    arr = jnp.asarray(val)
                ex.var_values[name] = arr
        return ps_ids

    def _ps_phase_b(self, side, ps_ids):
        """Push grads: sparse rows -> cache/PS, dense grads -> PS.
        Sparse rows arrive already segment-summed into unique-row slots
        (device-side dedup), so the push is duplicate-free."""
        ex = self.executor
        for var_name, g in side.items():
            g = np.asarray(g, np.float32)
            if var_name in ex.ps_sparse_vars:
                uniq = ps_ids[var_name]
                ex.ps_update(var_name, uniq, g[:len(uniq)])
            else:
                ex._ps_push_guarded("dense", var_name, None, g)
                ex.ps_dense_dirty[var_name] = True
        ex.ps_step_sync()

    def _ps_prefetch(self):
        """Overlap the next batch's embedding lookup (dataloader ids only:
        the next feed is peekable without advancing the loader)."""
        ex = self.executor
        if not ex.config.prefetch or not self.ps_lookups:
            return
        from .dataloader import DataloaderOp
        by_var = {}
        for lk in self.ps_lookups:
            by_var.setdefault(lk.inputs[0].name, []).append(lk)
        for var_name, lks in by_var.items():
            srcs = [lk.inputs[1] for lk in lks]
            if not all(isinstance(s, DataloaderOp) for s in srcs):
                continue
            try:
                id_arrays = [np.asarray(s.peek_arr(self.name))
                             for s in srcs]
            except Exception:
                continue
            all_flat = np.concatenate(
                [a.reshape(-1).astype(np.int64) for a in id_arrays])
            uniq = np.unique(all_flat)
            fut = ex.ps_lookup_async(var_name, uniq)
            if fut is not None:
                self._prefetched[var_name] = (all_flat, uniq, fut)


def _opt_sharding_like(ex, opt_states):
    """Optimizer slot states inherit their parameter's sharding (they are
    created with zeros_like(param)), so declare whatever each leaf
    actually has; replicated otherwise."""
    rep = NamedSharding(ex.mesh, P())
    return jax.tree_util.tree_map(
        lambda x: x.sharding if isinstance(x, jax.Array)
        and hasattr(x, "sharding") else rep, opt_states)


class Executor:
    """Multi-subgraph driver (reference executor.py:365-541)."""

    @telemetry.spanned("exec.build")
    def __init__(self, eval_node_dict, config=None, **kargs):
        if isinstance(eval_node_dict, list):
            eval_node_dict = {"default": eval_node_dict}
        self.eval_node_dict = eval_node_dict
        self.config = config if config is not None else HetuConfig(**kargs)
        self.mesh = self.config.mesh
        self.rng = jax.random.PRNGKey(self.config.seed)
        self.step = jnp.zeros((), jnp.int32)

        all_nodes = find_topo_sort(
            [n for nodes in eval_node_dict.values() for n in nodes])
        # hidden state vars (e.g. batch-norm running stats)
        for node in list(all_nodes):
            for sv in getattr(node, "state_vars", []):
                all_nodes.append(sv)
        self.variables = {}
        seen_names = set()
        for n in all_nodes:
            if isinstance(n, PlaceholderOp) and n.is_variable:
                assert n.name not in seen_names, f"duplicate variable name {n.name}"
                seen_names.add(n.name)
                self.variables[n.name] = n

        # strategy hook: assigns mesh + sharding specs before init
        if self.config.dist_strategy is not None:
            self.config.dist_strategy.configure(self)
            self.mesh = self.config.mesh

        # pipeline the ends (VERDICT r2 item 3): big embedding/head
        # tensors get 'pp'-sharded BEFORE placement so neither their
        # storage nor their optimizer slots are replicated per stage
        if (self.mesh is not None and "pp" in self.mesh.axis_names
                and self.config.pipeline in ("gpipe", "1f1b")
                and self.config.shard_pipeline_ends):
            self._shard_end_params_over_pp(eval_node_dict)

        # Hybrid/PS comm modes: embedding tables move to the PS (with the
        # HET cache when cstable_policy is set); in 'PS' mode dense params
        # are server-optimized too.  Must run before device init so the
        # big tables never materialize in HBM.
        self.ps_comm = None
        self.ps_sparse_vars = {}
        self.ps_dense_vars = {}
        self.ps_dense_dirty = {}
        self.cstables = {}
        self.ps_var_opt = {}
        self._ps_opt_specs = {}
        self._ssp_inited = False
        self._ps_push_future = None   # pending async phase B (one step)
        # outage handling for the direct (cache-less) hybrid path:
        # pushes that cannot reach the PS buffer here and replay on the
        # next successful contact, bounded by _PS_BACKLOG_STEPS
        self._ps_push_backlog = []
        if self.config.comm_mode in ("PS", "Hybrid"):
            self._setup_ps(all_nodes)

        self.var_values = {name: n.init_value(self.config.seed)
                           for name, n in self.variables.items()
                           if name not in self.ps_sparse_vars}
        if self.mesh is not None:
            self.var_values = {
                k: self.place_value(v, self.param_sharding(k))
                for k, v in self.var_values.items()}

        # feed name -> (lo, hi, global_batch): dataloader feeds this
        # process produces only the local rows of (multi-host sharding)
        self._proc_shard = {}
        self.subexecutor = {}
        self.opt_states = {}
        self._opt_ops = {}
        for name, nodes in eval_node_dict.items():
            has_opt = any(isinstance(n, OptimizerOp) for n in nodes)
            if self.config.pipeline is not None and has_opt:
                from .pipeline_executor import PipelineSubExecutor
                sub = PipelineSubExecutor(name, nodes, self)
            else:
                sub = SubExecutor(name, nodes, self)
            self.subexecutor[name] = sub
            for opt_op in sub.optimizer_ops:
                prev = self._opt_ops.get(opt_op.name)
                if prev is not None and prev is not opt_op:
                    raise ValueError(
                        f"two distinct optimizers cover the same variable "
                        f"set (stable name {opt_op.name!r}); their slot "
                        f"states would collide — give them disjoint "
                        f"var_lists")
                self._opt_ops[opt_op.name] = opt_op
                if opt_op.name not in self.opt_states:
                    self.opt_states[opt_op.name] = opt_op.init_state(
                        _ParamView(self.var_values),
                        skip=sub.ps_var_names)

        # static checks (HETU_VALIDATE=1): verify every subgraph's
        # shapes/dtypes and the mesh/plan BEFORE any trace or chip work;
        # a defect raises GraphVerifyError/ShardCheckError naming the
        # node (analysis/integration.py; no-op when validation is off)
        from .analysis import validate_executor_build
        validate_executor_build(self)

    # ------------------------------------------------------------------ #
    # Hybrid/PS setup + host-side embedding API
    # (reference executor.py:253-258 cache wiring, optimizer.py:145-164
    # comm-mode routing, ParameterServerCommunicate.py push-pull)
    # ------------------------------------------------------------------ #

    def _setup_ps(self, all_nodes):
        from .ps.client import PSClient
        from .graph.ops_embed import EmbeddingLookupOp, IndexedSlicesOp
        from .optimizer import SGDOptimizer

        cfg = self.config
        self.ps_comm = cfg.ps_comm or PSClient.get()
        cfg.ps_comm = self.ps_comm

        consumers = {}
        for n in all_nodes:
            for i in n.inputs:
                consumers.setdefault(id(i), []).append(n)
        for op in all_nodes:
            if isinstance(op, OptimizerOp):
                for v in op.var_list:
                    self.ps_var_opt[v.name] = op.optimizer

        for name, node in self.variables.items():
            if not node.trainable:
                continue
            cons = consumers.get(id(node), [])
            # a table can live on the PS iff its device value is only ever
            # needed row-wise: lookups and sparse adjoints.  ANY number of
            # lookups composes — autodiff keeps multi-lookup adjoints
            # sparse (merge_indexed_slices concat) and phase A fetches the
            # union of their ids once per table.
            n_lookups = sum(1 for c in cons
                            if isinstance(c, EmbeddingLookupOp)
                            and c.inputs[0] is node)
            sparse_ok = getattr(node, "is_embed", False) and \
                n_lookups >= 1 and all(
                (isinstance(c, (EmbeddingLookupOp, IndexedSlicesOp))
                 and c.inputs[0] is node) or isinstance(c, OptimizerOp)
                for c in cons)
            if sparse_ok:
                self.ps_sparse_vars[name] = node
            elif cfg.comm_mode == "PS":
                self.ps_dense_vars[name] = node

        def _spec_for(name, opt):
            if opt is None:
                return None
            if getattr(opt, "l2reg", 0.0):
                raise NotImplementedError(
                    f"l2reg on PS-managed var '{name}': the server applies "
                    f"the update and has no l2 term")
            spec = opt.server_opt_spec()
            if spec is None:
                raise NotImplementedError(
                    f"{type(opt).__name__} (or an LR schedule) has no PS "
                    f"server-side counterpart for var '{name}'; use the "
                    f"cache path (cstable_policy) or SGD/Momentum/"
                    f"AdaGrad/Adam with a scalar LR")
            return spec

        for name, node in self.ps_sparse_vars.items():
            val = np.asarray(node.init_value(cfg.seed), np.float32)
            opt = self.ps_var_opt.get(name)
            if cfg.cstable_policy:
                # HET cache: the worker applies SGD scaling locally and the
                # server raw-accumulates the pushed deltas (hetu_cache
                # write-back semantics) — other optimizers would need their
                # slot state inside every cache line.  LR SCHEDULES are
                # fine: each push scales by the pushing step's lr_value
                # (ps_update reads the step index), so scheduled-SGD
                # deltas accumulate exactly like the dense path.
                if opt is not None and (type(opt) is not SGDOptimizer
                                        or opt.l2reg):
                    raise NotImplementedError(
                        "the HET cache path accumulates -lr*grad deltas; "
                        "only SGD (fixed or scheduled LR, no l2) is "
                        "supported on cached embeddings (reference "
                        "hetu_cache ditto)")
                # the HET cache's versioned sync protocol needs the whole
                # table on ONE server; with a sharded client the table
                # lives whole on its home server of the group
                cache_comm = self.ps_comm._home(name) \
                    if hasattr(self.ps_comm, "_home") else self.ps_comm
                cache_comm.param_set(name, val)
                self._ps_opt_specs[name] = None
                from .cache.cstable import CacheSparseTable
                self.cstables[name] = CacheSparseTable(
                    cfg.cache_bound, val.shape[0], val.shape[1], key=name,
                    comm=cache_comm, policy=cfg.cstable_policy)
            else:
                spec = _spec_for(name, opt)
                self._ps_opt_specs[name] = spec
                self.ps_comm.param_set(
                    name, val, opt=spec and spec[0],
                    opt_args=spec and spec[1])

        for name, node in self.ps_dense_vars.items():
            val = np.asarray(node.init_value(cfg.seed), np.float32)
            spec = _spec_for(name, self.ps_var_opt.get(name))
            self._ps_opt_specs[name] = spec
            self.ps_comm.param_set(name, val, opt=spec and spec[0],
                                   opt_args=spec and spec[1])

    def ps_lookup(self, name, ids):
        """Rows for `ids` from the HET cache or the PS (phase A)."""
        ids = np.asarray(ids)
        ct = self.cstables.get(name)
        if ct is not None:
            return ct.embedding_lookup(ids)
        if self._ps_push_backlog:
            # recovery-ordering: buffered pushes must land before the
            # next read observes the table (replay failure just means
            # the PS is still down — the read below reports that)
            try:
                self._ps_replay_backlog()
            except ConnectionError:
                pass
        if self.config.use_sparse_pull:
            flat = ids.reshape(-1).astype(np.int64)
            uniq, inv = np.unique(flat, return_inverse=True)
            rows = np.asarray(self.ps_comm.sparse_pull(name, uniq),
                              np.float32)
            return rows[inv].reshape(*ids.shape, rows.shape[-1])
        table = np.asarray(self.ps_comm.pull(name), np.float32)
        return table[ids.reshape(-1)].reshape(*ids.shape, table.shape[-1])

    def ps_lookup_async(self, name, ids):
        ct = self.cstables.get(name)
        if ct is not None:
            return ct.embedding_lookup_async(ids)
        pool = getattr(self.ps_comm, "_pool", None)
        if pool is None:
            return None
        return pool.submit(self.ps_lookup, name, ids)

    def ps_update(self, name, ids, rows):
        """Push one step's embedding grads (phase B).  Cache path: the
        worker scales to -lr*grad deltas (write-back accumulate); direct
        path: raw grads, the server optimizer applies the update."""
        rows = np.asarray(rows, np.float32)
        rows = rows.reshape(-1, rows.shape[-1])
        flat = np.asarray(ids).reshape(-1).astype(np.int64)
        ct = self.cstables.get(name)
        if ct is not None:
            opt = self.ps_var_opt[name]
            # the device step already advanced self.step; the update being
            # pushed used the pre-increment step's LR
            lr = float(np.asarray(opt.lr_value(
                jnp.asarray(max(int(self.step) - 1, 0), jnp.int32))))
            # phase B hands us the device-side segment-summed UNIQUE rows
            # (_ps_phase_b passes phase A's sorted-unique ids) — skip the
            # cache's host re-dedup pass
            ct.embedding_update(flat, -lr * rows, assume_unique=True)
        else:
            self._ps_push_guarded("sparse", name, flat, rows)

    def _ps_replay_backlog(self):
        """Drain pushes buffered during a PS outage (FIFO)."""
        while self._ps_push_backlog:
            kind, name, ids, rows = self._ps_push_backlog[0]
            if kind == "sparse":
                self.ps_comm.sparse_push(name, ids, rows)
            else:
                self.ps_comm.push(name, rows)
            self._ps_push_backlog.pop(0)

    def _ps_push_guarded(self, kind, name, ids, rows):
        """Direct-path push with outage buffering: a PS that cannot be
        reached costs a bounded backlog entry, not the training run.
        The (client_id, seq) wire dedup makes the eventual replay safe
        against the retries that preceded the buffering."""
        from .ps.client import PSConnectionError
        try:
            self._ps_replay_backlog()
            if kind == "sparse":
                self.ps_comm.sparse_push(name, ids, rows)
            else:
                self.ps_comm.push(name, rows)
        except ConnectionError as e:
            self._ps_push_backlog.append((kind, name, ids, rows))
            if len(self._ps_push_backlog) > _PS_BACKLOG_STEPS:
                raise PSConnectionError(
                    f"PS outage: push backlog exceeded "
                    f"{_PS_BACKLOG_STEPS} buffered steps "
                    f"(last failure: {e})") from e

    def ps_step_sync(self):
        """BSP/SSP pacing after each training step (config.bsp)."""
        bsp = self.config.bsp
        if self.ps_comm is None or bsp is None or bsp < 0:
            return
        if bsp == 0:
            self.ps_comm.BarrierWorker()
        else:
            if not self._ssp_inited:
                self.ps_comm.ssp_init(0, bsp)
                self._ssp_inited = True
            self.ps_comm.ssp_sync(0)

    def join_ps_push(self):
        """Wait for (and surface errors from) the pending async phase-B
        push.  Called before any PS/cache read and before flush/save."""
        fut = self._ps_push_future
        if fut is not None:
            self._ps_push_future = None
            fut.result()

    def ps_perf_summary(self):
        """Cache counters per table (reference cstable perf counters)."""
        self.join_ps_push()
        return {name: ct.perf_summary() for name, ct in self.cstables.items()}

    # ------------------------------------------------------------------ #
    # sharding helpers
    # ------------------------------------------------------------------ #

    @property
    def multiprocess(self):
        """True when the mesh spans jax processes (multi-host SPMD over
        DCN/ICI via jax.distributed; reference's multi-node NCCL/MPI
        role, SURVEY §5.8).  Every process must build the identical graph
        and run the identical steps.  Cached: the mesh is fixed at
        construction and this sits on the per-feed hot path."""
        mpv = getattr(self, "_multiprocess", None)
        if mpv is None:
            if self.mesh is None:
                mpv = False
            else:
                pid = jax.process_index()
                mpv = any(d.process_index != pid
                          for d in self.mesh.devices.flat)
            self._multiprocess = mpv
        return mpv

    def place_value(self, value, sharding):
        """Place a host (or replicated-device) value with `sharding`.
        Single-process: plain device_put.  Multi-process: device_put of a
        cross-process sharding is illegal, so each process supplies its
        addressable shards from the (identical) host value.  Values that
        already carry the target sharding (e.g. ring-prefetched feeds)
        pass through untouched."""
        if sharding is None:
            return jnp.asarray(value)
        if isinstance(value, jax.Array) and \
                value.sharding.is_equivalent_to(sharding, value.ndim):
            return value
        if not self.multiprocess:
            return jax.device_put(value, sharding)
        value = np.asarray(value)
        return jax.make_array_from_callback(
            value.shape, sharding, lambda idx: value[idx])

    def _shard_end_params_over_pp(self, eval_node_dict):
        """Pipeline the non-uniform ends, the TPU way (reference:
        pipeline_subexecutor.py:29-81 folds embedding into stage 0 and
        head+loss into the last stage so each lives on one stage's
        devices).

        A scan pipeline wants uniform stages, and on TPU the same memory
        goal has a more direct expression: every big pre/post ("end")
        tensor is SHARDED over the otherwise-idle 'pp' mesh axis, so each
        stage holds 1/S of the embedding and head (and of their optimizer
        slots) instead of a full replica — the same total footprint as
        the reference's one-stage residency, better balanced, and it
        needs no schedule surgery for tied embedding/LM-head weights
        (both use sites read the same sharded array; GSPMD inserts the
        batched collectives and sums the grads).  Runs before parameter
        placement; fills only specs the user left unset."""
        from .parallel.partition import partition
        S = self.mesh.shape["pp"]
        min_elems = 1 << 18          # don't bother with biases/LN params
        for name, nodes in eval_node_dict.items():
            if not any(isinstance(n, OptimizerOp) for n in nodes):
                continue
            losses = [n for n in nodes if not isinstance(n, OptimizerOp)]
            if len(losses) != 1:
                continue
            topo = find_topo_sort(losses)
            if any(getattr(n, "state_vars", []) for n in topo):
                continue          # such graphs take the microbatch-scan path
            plan = partition(losses[0], S)
            if not plan.uniform:
                continue
            ends = {id(v): v for v in plan.pre_params + plan.post_params}
            for var in ends.values():
                if getattr(var, "sharding_spec", None) is not None:
                    continue      # user spec wins
                shape = tuple(var.shape or ())
                if not shape or int(np.prod(shape)) < min_elems:
                    continue
                divisible = [i for i, s in enumerate(shape) if s % S == 0]
                if not divisible:
                    continue
                dim = max(divisible, key=lambda i: shape[i])
                spec = [None] * len(shape)
                spec[dim] = "pp"
                var.sharding_spec = P(*spec)

    def param_sharding(self, name):
        node = self.variables[name]
        spec = getattr(node, "sharding_spec", None)
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec if spec is not None else P())

    def feed_sharding(self, name, shape):
        """Feeds shard along the batch dim over the 'dp' axis if present;
        on a pure expert-parallel mesh tokens are data-parallel over the
        expert group (reference MoE: DP and EP share the same devices)."""
        if self.mesh is None:
            return None
        from .parallel.mesh import batch_axis
        ax = batch_axis(self.mesh, shape[0]) if len(shape) >= 1 else None
        return NamedSharding(self.mesh, P(ax) if ax else P())

    def process_batch_rows(self, name, global_shape):
        """Rows [lo, hi) of the dim-0-sharded feed ``name`` that THIS
        process's addressable devices hold, or None when the feed is not
        cleanly dim-0-sharded / the process's rows are not one contiguous
        range / the whole batch is addressable anyway."""
        sharding = self.feed_sharding(name, global_shape)
        if sharding is None or not self.multiprocess:
            return None
        spec = tuple(sharding.spec)
        if not spec or spec[0] is None \
                or any(s is not None for s in spec[1:]):
            return None
        try:
            imap = sharding.devices_indices_map(tuple(global_shape))
        except Exception:
            return None
        pid = jax.process_index()
        spans = sorted(
            {( (idx[0].start or 0),
               (idx[0].stop if idx[0].stop is not None
                else global_shape[0]) )
             for d, idx in imap.items() if d.process_index == pid})
        if not spans:
            return None
        lo, hi = spans[0]
        for s, e in spans[1:]:
            if s > hi:
                return None        # holes: keep the global convention
            hi = max(hi, e)
        if (lo, hi) == (0, int(global_shape[0])):
            return None
        return lo, hi

    def device_put_feed(self, name, value):
        """Feed placement.  Dataloader feeds wired by _wire_prefetch
        arrive as this process's LOCAL batch shard (rows [lo, hi) of the
        global batch) and are assembled into the global array without
        any process ever materializing the whole batch.  Everything else
        keeps the legacy convention: every process feeds the identical
        GLOBAL batch and each keeps only its addressable shards."""
        info = self._proc_shard.get(name, {}).get(value.shape[0]) \
            if self._proc_shard else None
        if info is not None:
            lo, hi, gb = info
            if value.shape[0] == hi - lo:
                v = np.asarray(value)
                gshape = (gb,) + tuple(v.shape[1:])
                sharding = self.feed_sharding(name, gshape)

                def local_rows(idx):
                    sl = idx[0]
                    s = (sl.start or 0) - lo
                    e = (sl.stop if sl.stop is not None else gb) - lo
                    return v[(slice(s, e),) + tuple(idx[1:])]

                return jax.make_array_from_callback(gshape, sharding,
                                                    local_rows)
        return self.place_value(value,
                                self.feed_sharding(name, value.shape))

    # ------------------------------------------------------------------ #

    def run(self, name="default", eval_node_list=None, feed_dict=None,
            convert_to_numpy_ret_vals=False, **kwargs):
        if isinstance(name, dict) and feed_dict is None:
            # positional style: executor.run(feed_dict)
            feed_dict, name = name, "default"
        feed_dict = feed_dict or {}
        return self.subexecutor[name].run(feed_dict, convert_to_numpy_ret_vals)

    # ------------------------------------------------------------------ #
    # checkpointing (reference executor.py:461-541; strictly better — we
    # save optimizer slot state, step, and rng as well, SURVEY.md §5.4)
    # ------------------------------------------------------------------ #

    def save(self, path, file=None, varlist=None, sharded=False,
             async_=False):
        """Checkpoint params + optimizer slots + step + rng (reference
        executor.py:461-485 saves params only; SURVEY §5.4 'strictly
        better').  ``sharded=True`` writes an orbax checkpoint: each
        device stores only its shard (no host gather of the full state —
        required once params exceed one host's RAM), ``async_=True``
        returns immediately and flushes in the background
        (``wait_for_checkpoint()`` joins it)."""
        self.join_ps_push()
        if sharded or async_:
            return self._save_orbax(path, async_=async_)
        if self.multiprocess:
            raise ValueError(
                "pickle save cannot gather shards held by other "
                "processes; use save(path, sharded=True) — orbax writes "
                "each process's shards collectively")
        os.makedirs(path, exist_ok=True)
        fname = os.path.join(path, file or "checkpoint.pkl")
        # copy=True: np.asarray over jax CPU arrays is zero-copy and the
        # buffers are donated to the next step — a view would rot
        params = {k: np.array(v, copy=True)
                  for k, v in self.var_values.items()
                  if varlist is None or k in varlist}
        # PS-managed vars: the server (after a cache flush) is the source
        # of truth, not the device copy
        for name in list(self.ps_sparse_vars) + list(self.ps_dense_vars):
            if varlist is not None and name not in varlist:
                continue
            ct = self.cstables.get(name)
            if ct is not None:
                ct.flush()
            params[name] = np.asarray(self.ps_comm.pull(name))
        opt = jax.tree_util.tree_map(lambda x: np.asarray(x), self.opt_states)
        with open(fname, "wb") as f:
            pickle.dump({"params": params, "opt_states": opt,
                         "step": int(self.step),
                         "rng": np.asarray(self.rng),
                         "dataloaders": self._loader_states()}, f)

    def _loaders(self):
        # keys must be stable across BUILDS (auto node names embed the
        # global id counter): subgraph name + topo position + loader name
        seen = {}
        for sub_name in sorted(self.subexecutor):
            sub = self.subexecutor[sub_name]
            for i, dl_op in enumerate(getattr(sub, "dataloader_ops", [])):
                for key, loader in getattr(dl_op, "dataloaders",
                                           {}).items():
                    seen[f"{sub_name}:{i}:{key}"] = loader
        return seen

    def _loader_states(self):
        """Exact mid-epoch resume state (reference loses the iterator
        position on restart; SURVEY §5.4 'strictly better')."""
        return {k: ld.state_dict() for k, ld in self._loaders().items()}

    def _restore_loaders(self, states):
        loaders = self._loaders()
        missing = []
        for k, st in (states or {}).items():
            if k in loaders:
                loaders[k].load_state_dict(st)
            else:
                missing.append(k)
        if missing:
            import warnings
            warnings.warn(
                f"checkpoint dataloader state {missing} has no match in "
                f"this build (graph structure changed?); those data "
                f"streams restart from batch 0 while params resume at "
                f"step {int(self.step)}", stacklevel=2)

    # ---- orbax path: sharded + async ---- #

    def _orbax_state(self):
        self.join_ps_push()
        state = {"params": dict(self.var_values),
                 "opt_states": self.opt_states,
                 "step": self.step, "rng": self.rng}
        for name in list(self.ps_sparse_vars) + list(self.ps_dense_vars):
            ct = self.cstables.get(name)
            if ct is not None:
                ct.flush()
            state["params"][name] = jnp.asarray(
                np.asarray(self.ps_comm.pull(name)))
        return state

    def _save_orbax(self, path, async_=False):
        import json
        import orbax.checkpoint as ocp
        loaders_file = os.path.join(os.path.abspath(path), "loaders.json")
        path = os.path.abspath(os.path.join(path, "orbax"))
        self.wait_for_checkpoint()
        # dataloader positions are a handful of host-side scalars; a JSON
        # sidecar keeps them out of the sharded tree so per-loader schema
        # changes can never make the orbax restore structure-mismatch.
        # The payload is stamped with the step and published (atomic
        # rename) only AFTER the orbax tree is durable, so a crash at any
        # point leaves either a matching pair or a stamp mismatch the
        # restore detects — never a silent position/params divergence.
        payload = json.dumps({"step": int(self.step),
                              "loaders": self._loader_states()},
                             default=int)

        def publish():
            os.makedirs(os.path.dirname(loaders_file), exist_ok=True)
            tmp = loaders_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(payload)
            os.replace(tmp, loaders_file)

        if async_:
            # one AsyncCheckpointer per executor, reused across saves —
            # a fresh instance per save would churn its thread pool and
            # leak resources over a long run if any close were missed
            ck = getattr(self, "_async_ckptr", None)
            if ck is None:
                ck = self._async_ckptr = ocp.AsyncCheckpointer(
                    ocp.StandardCheckpointHandler())
            ck.save(path, args=ocp.args.StandardSave(
                self._orbax_state()), force=True)

            def wait_then_publish():
                ck.wait_until_finished()
                publish()

            self._sidecar_thread = threading.Thread(
                target=wait_then_publish, daemon=True)
            self._sidecar_thread.start()
        else:
            with ocp.StandardCheckpointer() as ckptr:
                ckptr.save(path, self._orbax_state(), force=True)
            publish()

    def close(self):
        """Release executor-held host resources (the async-checkpoint
        thread pool).  Safe to call more than once; subsequent saves
        re-create what they need."""
        self.wait_for_checkpoint(close=True)

    def __del__(self):
        # best-effort backstop for executors discarded without close():
        # an un-closed AsyncCheckpointer keeps its thread pool (and can
        # keep the interpreter alive at exit)
        try:
            if getattr(self, "_async_ckptr", None) is not None:
                self.close()
        except Exception:
            pass

    def wait_for_checkpoint(self, close=False):
        """Join any in-flight async save.  The checkpointer instance is
        kept for reuse by later saves; pass ``close=True`` (teardown) to
        release its thread pool."""
        t = getattr(self, "_sidecar_thread", None)
        if t is not None:
            t.join()
            self._sidecar_thread = None
        ck = getattr(self, "_async_ckptr", None)
        if ck is not None:
            ck.wait_until_finished()
            if close:
                ck.close()
                self._async_ckptr = None

    def _restore_superset(self, ocp, path, target):
        """Restore a checkpoint whose tree holds keys the current build no
        longer has (forward compat): target = current abstract leaves where
        keys overlap, on-disk shape/dtype for the rest.  Returns the
        restored state (extras included — callers filter) or None."""
        try:
            with ocp.StandardCheckpointer() as ckptr:
                meta = ckptr.metadata(path)
            # StepMetadata -> TreeMetadata -> nested {key: ArrayMetadata}
            tree = getattr(getattr(meta, "item_metadata", meta),
                           "tree", None)
            if tree is None and isinstance(meta, dict):
                # older orbax returns the nested metadata tree directly
                tree = meta
            if tree is None:
                return None
            tree = dict(tree)

            # the on-disk tree must COVER the target: a checkpoint missing
            # current keys is a real mismatch (renamed param, wrong model)
            # that must surface as the original error, not silently
            # restore partial state
            def covered(t, m):
                if isinstance(t, dict):
                    return isinstance(m, dict) and all(
                        k in m and covered(v, m[k]) for k, v in t.items())
                return not isinstance(m, dict)

            if not covered(target, tree):
                return None

            t2 = dict(target)
            # legacy in-tree dataloader scalars ride along (cheap); every
            # OTHER extra (e.g. materialized causal masks — potentially
            # hundreds of MB) is skipped outright by the partial restore,
            # never read or materialized
            if "dataloaders" in tree and "dataloaders" not in t2:
                t2["dataloaders"] = jax.tree_util.tree_map(
                    lambda m: jax.ShapeDtypeStruct(
                        tuple(m.shape), np.dtype(m.dtype)),
                    tree["dataloaders"])
            try:
                with ocp.Checkpointer(
                        ocp.PyTreeCheckpointHandler()) as ckptr:
                    return ckptr.restore(path, args=ocp.args.PyTreeRestore(
                        item=t2,
                        restore_args=ocp.checkpoint_utils
                        .construct_restore_args(t2),
                        partial_restore=True))
            except Exception:
                # older orbax has no working partial restore (the
                # restore_args must cover every on-disk key): widen the
                # target to the FULL on-disk tree — extras are read and
                # materialized (the cost partial restore avoids), then
                # discarded by the callers' key filtering
                def merge(t, m):
                    if isinstance(m, dict):
                        t = t if isinstance(t, dict) else {}
                        return {k: merge(t.get(k), mv)
                                for k, mv in m.items()}
                    if t is not None:
                        return t
                    return jax.ShapeDtypeStruct(tuple(m.shape),
                                                np.dtype(m.dtype))

                t3 = merge(t2, tree)
                with ocp.Checkpointer(
                        ocp.PyTreeCheckpointHandler()) as ckptr:
                    return ckptr.restore(path, args=ocp.args.PyTreeRestore(
                        item=t3,
                        restore_args=ocp.checkpoint_utils
                        .construct_restore_args(t3)))
        except Exception:
            return None

    def load_sharded(self, path):
        """Restore an orbax checkpoint, placing each leaf directly with
        THIS executor's shardings (resharding across different meshes /
        layouts happens inside orbax — a tp2-saved checkpoint restores
        onto an fsdp8 executor without a full-state host bounce)."""
        import json
        import orbax.checkpoint as ocp
        # join any in-flight async save first: its sidecar publishes only
        # after the orbax finalize, and restoring inside that window would
        # silently drop the dataloader positions
        self.wait_for_checkpoint()
        loaders_file = os.path.join(os.path.abspath(path), "loaders.json")
        path = os.path.abspath(os.path.join(path, "orbax"))
        cur = self._orbax_state()

        def abstract(x):
            x = jnp.asarray(x) if not hasattr(x, "dtype") else x
            sharding = getattr(x, "sharding", None)
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        target = jax.tree_util.tree_map(abstract, cur)
        loader_states, sidecar_step = None, None
        if os.path.exists(loaders_file):
            with open(loaders_file) as f:
                sidecar = json.load(f)
            loader_states = sidecar.get("loaders", sidecar)
            sidecar_step = sidecar.get("step")
        try:
            with ocp.StandardCheckpointer() as ckptr:
                state = ckptr.restore(path, target)
        except Exception as core_err:
            # Orbax needs an exact tree match, so a checkpoint whose tree
            # is a SUPERSET of the current state fails the target above —
            # e.g. non-trainable Variables an older build stored that this
            # build computes in-trace (causal masks), or in-tree dataloader
            # state.  Rebuild the target from the checkpoint's own
            # metadata (current abstract leaf where keys overlap, on-disk
            # shape/dtype for the extras), restore, and discard extras.
            state = self._restore_superset(ocp, path, target)
            if state is not None:
                loader_states = state.pop("dataloaders", loader_states)
            # checkpoints from builds that stored dataloader state INSIDE
            # the orbax tree: retry with that subtree mirrored from each
            # schema those builds ever wrote.  If nothing matches, surface
            # the original error — don't let the compat chain mask a real
            # shape/dtype problem.
            def loader_target(keys):
                # np dtypes: orbax stored the in-tree python scalars as
                # int64/bool_, not jax's int32 default
                return {
                    name: {k: jax.ShapeDtypeStruct(
                        (), np.asarray(v).dtype)
                        for k, v in st.items() if k in keys}
                    for name, st in self._loader_states().items()}

            if state is None:
                for keys in (("consumed", "seed", "shuffle"),
                             ("consumed", "seed")):
                    t2 = dict(target)
                    t2["dataloaders"] = loader_target(keys)
                    try:
                        with ocp.StandardCheckpointer() as ckptr:
                            state = ckptr.restore(path, t2)
                        loader_states = state.pop("dataloaders", None)
                        break
                    except Exception:
                        state = None
            if state is None:
                raise core_err
        params = state["params"]
        for name in list(self.ps_sparse_vars) + list(self.ps_dense_vars):
            if name in params:
                self.load_dict({name: np.asarray(params.pop(name))})
        self.var_values = {k: v for k, v in params.items()
                           if k in self.variables
                           and k not in self.ps_sparse_vars}
        self.opt_states = state["opt_states"]
        self.step = jnp.asarray(state["step"], jnp.int32)
        self.rng = jnp.asarray(state["rng"], jnp.uint32)
        if loader_states and sidecar_step is not None \
                and sidecar_step != int(self.step):
            # crash window between the orbax finalize and the sidecar
            # publish (or vice versa): positions belong to another save
            import warnings
            warnings.warn(
                f"dataloader sidecar is stamped step {sidecar_step} but "
                f"the checkpoint restored step {int(self.step)}; "
                f"ignoring it — data streams restart from batch 0",
                stacklevel=2)
            loader_states = None
        if loader_states:
            self._restore_loaders(loader_states)

    def load(self, path, file=None, consider_splits=False):
        if os.path.isdir(os.path.join(path, "orbax")) and not os.path.exists(
                os.path.join(path, file or "checkpoint.pkl")):
            return self.load_sharded(path)
        fname = os.path.join(path, file or "checkpoint.pkl")
        with open(fname, "rb") as f:
            ckpt = pickle.load(f)
        self.load_dict(ckpt["params"])
        if ckpt.get("opt_states"):
            loaded = ckpt["opt_states"]        # raw checkpoint leaves

            def _placed(cur_state, new_state):
                """Restore leaves directly onto the placement their
                freshly-initialized counterparts already have — a bare
                jnp.asarray would pin everything to device 0 and the
                next jitted step would reject the mixed placements."""
                if self.mesh is None:
                    return jax.tree_util.tree_map(jnp.asarray, new_state)
                try:
                    return jax.tree_util.tree_map(
                        lambda c, n: self.place_value(np.asarray(n),
                                                      c.sharding)
                        if hasattr(c, "sharding") else jnp.asarray(n),
                        cur_state, new_state)
                except ValueError:         # structure changed; keep raw
                    return jax.tree_util.tree_map(jnp.asarray, new_state)

            # optimizer names are checkpoint-stable (hash of the var set),
            # so direct lookup works; the key-set match remains only as a
            # fallback for checkpoints written before stable naming
            remapped = {}
            used = set()
            for cur_key, cur_state in self.opt_states.items():
                if cur_key in loaded:
                    used.add(cur_key)
                    remapped[cur_key] = _placed(cur_state, loaded[cur_key])
                    continue
                match = None
                for old_key, old_state in loaded.items():
                    if old_key not in used and \
                            set(old_state) == set(cur_state):
                        match = old_key
                        break
                if match is not None:
                    used.add(match)
                    remapped[cur_key] = _placed(cur_state, loaded[match])
                else:
                    remapped[cur_key] = cur_state
            self.opt_states = remapped
        if "step" in ckpt:
            self.step = jnp.asarray(ckpt["step"], jnp.int32)
        if "rng" in ckpt:
            self.rng = jnp.asarray(ckpt["rng"], jnp.uint32)
        if ckpt.get("dataloaders"):
            self._restore_loaders(ckpt["dataloaders"])

    def load_dict(self, state_dict):
        self.join_ps_push()
        from .cache.cstable import CacheSparseTable
        for k, v in state_dict.items():
            if k in self.ps_sparse_vars or k in self.ps_dense_vars:
                spec = self._ps_opt_specs.get(k)
                comm = self.ps_comm
                if k in self.cstables and hasattr(comm, "_home"):
                    comm = comm._home(k)   # cache tables live whole
                comm.param_set(k, np.asarray(v, np.float32),
                               opt=spec and spec[0],
                               opt_args=spec and spec[1])
                ct = self.cstables.get(k)
                if ct is not None:
                    # drop cached lines; they refer to pre-load values.
                    # comm stays the HOME server (sharded groups don't
                    # speak the cache's versioned sync protocol)
                    self.cstables[k] = CacheSparseTable(
                        ct.cache.limit if hasattr(ct.cache, "limit")
                        else self.config.cache_bound,
                        ct.vocab, ct.width, key=k, comm=comm,
                        policy=self.config.cstable_policy,
                        pull_bound=ct.pull_bound, push_bound=ct.push_bound)
                if k in self.ps_dense_vars:
                    if self.mesh is not None:
                        arr = self.place_value(np.asarray(v),
                                               self.param_sharding(k))
                    else:
                        arr = jnp.asarray(v)
                    self.var_values[k] = arr
                    self.ps_dense_dirty.pop(k, None)
                continue
            if k in self.var_values:
                if self.mesh is not None:
                    arr = self.place_value(np.asarray(v),
                                           self.param_sharding(k))
                else:
                    arr = jnp.asarray(v)
                self.var_values[k] = arr

    def load_seeds(self, seed):
        self.rng = jax.random.PRNGKey(seed)

    def return_tensor_values(self):
        self.join_ps_push()
        # copies, not views: the underlying buffers are donated next step
        out = {k: np.array(v, copy=True)
               for k, v in self.var_values.items()}
        # PS-managed vars: the server (post cache-flush) is authoritative;
        # the device copy of a dense-PS var lags by one step
        for name in list(self.ps_sparse_vars) + list(self.ps_dense_vars):
            ct = self.cstables.get(name)
            if ct is not None:
                ct.flush()
            out[name] = np.asarray(self.ps_comm.pull(name))
        return out

    def profile(self, feed_shapes=None, log_file=None, profiler="gpu"):
        from .profiler import HetuProfiler
        return HetuProfiler(self, feed_shapes, log_file)

    def recordLoads(self):
        pass

    @property
    def batch_num(self):
        # dataloader integration supplies this; see dataloader.py
        subs = list(self.subexecutor.values())
        return subs[0].batch_num if subs and hasattr(subs[0], "batch_num") else None


def gradients(output_node, node_list, insert_grad=None, return_all=False):
    from .graph.autodiff import gradients as _g
    return _g(output_node, node_list, insert_grad, return_all)
