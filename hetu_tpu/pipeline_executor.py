"""Pipeline-as-an-executor-mode: ``Executor(pipeline='gpipe'|'1f1b'|...)``.

Reference behavior being matched: ``Executor(..., pipeline='gpipe')``
partitions the built graph at recv/send boundaries and drives microbatch
schedules over the partitions (gpipe_subexecutor.py:33-111,
pipedream_subexecutor.py:51-372, partition logic
pipeline_subexecutor.py:29-81).  The reference choreographs per-op sends
and receives over NCCL from the host; on TPU the whole schedule lives
inside ONE jitted XLA program.

Two lowerings, chosen automatically from the partitioner's plan
(parallel/partition.py):

1. **SPMD scan pipeline** — mesh has a 'pp' axis, the graph has a uniform
   repeated body (e.g. N identical transformer blocks), and the mode is a
   synchronous schedule ('gpipe'/'1f1b').  Body-block params are stacked
   ``[S, R/S, ...]`` and sharded over 'pp'; microbatches flow through
   the scan+ppermute pipeline; the non-uniform ends — embedding in
   front, head+loss behind — run OUTSIDE the pipeline loop, vmapped over
   microbatches (the reference folds them into first/last stage; here
   their big tensors are instead SHARDED over the otherwise-idle 'pp'
   axis, see ``_shard_end_params_over_pp``, so neither their params nor
   their optimizer state are replicated per stage).  Two schedules:

   * 'gpipe' (``spmd_pipeline``): differentiate through the forward
     scan; activation high-water O(M + S) saved boundary carries.
   * '1f1b' (``spmd_pipeline_1f1b``): custom-VJP staggered
     one-forward-one-backward schedule; activation high-water O(S)
     in-flight boundary slots per device — the real PipeDream/1F1B
     memory property (pipedream_subexecutor.py:25-48), proven by
     ``profiler.memory_analysis`` in test_pipeline_executor.

2. **Microbatch scan** — no 'pp' mesh axis or no uniform body.  The step
   jits a ``lax.scan`` over microbatches: 'gpipe'/'1f1b' accumulate grads
   and update once (their loss trajectory is IDENTICAL to the
   non-pipelined step, which is what the reference's tier-2 equivalence
   suite asserts; with no 'pp' axis there are no stages, so '1f1b' has
   no schedule to stagger and is gpipe by construction); 'pipedream'
   applies per-microbatch updates in the scan
   carry (reference per-in-flight-microbatch weight semantics collapse to
   sequential per-microbatch SGD when the program is a single SPMD step);
   'hetpipe' is 'pipedream' plus a host-side PS delta-sync every
   ``sync_every`` batches (pipedream_subexecutor.py:317-328).

Parameter storage stays name-keyed and unstacked (per-layer masters);
the SPMD path stacks in-trace under a 'pp' sharding constraint.  That
keeps checkpointing, load_dict, and eval subgraphs untouched; the cost is
replicated masters (a stacked-storage optimization can come later without
changing this interface).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .graph.node import Op, TraceContext
from .graph.autodiff import find_topo_sort
from .graph.ops_misc import PlaceholderOp
from .optimizer import OptimizerOp
from .parallel.partition import partition
from .parallel.pipeline import spmd_pipeline, spmd_pipeline_1f1b


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class PipelineSubExecutor:
    """Training subgraph driven through a pipeline schedule."""

    def __init__(self, name, eval_nodes, executor):
        self.name = name
        self.eval_nodes = eval_nodes
        self.executor = executor
        cfg = executor.config
        self.mode = cfg.pipeline

        if cfg.comm_mode in ("PS", "Hybrid"):
            raise NotImplementedError(
                "pipeline mode with comm_mode='PS'/'Hybrid' is not wired; "
                "'hetpipe' provides the PS-synced pipeline path")

        opts = [n for n in eval_nodes if isinstance(n, OptimizerOp)]
        if len(opts) != 1:
            raise NotImplementedError(
                f"Executor(pipeline=...) drives exactly one optimizer per "
                f"training subgraph (got {len(opts)} in '{name}')")
        self.opt_op = opts[0]
        losses = [n for n in eval_nodes if not isinstance(n, OptimizerOp)]
        if len(losses) != 1:
            raise NotImplementedError(
                "pipeline-mode eval nodes must be [loss, train_op]")
        self.loss_node = losses[0]
        self.optimizer_ops = [self.opt_op]
        self.training = True
        self.ps_var_names = frozenset()

        self.topo = find_topo_sort([self.loss_node])
        self.non_batch_feeds = frozenset(cfg.non_batch_feeds or ())
        # stateful layers (BN running stats): their updates must chain
        # microbatch-to-microbatch through the scan carry
        self.state_var_names = sorted({
            sv.name for n in self.topo
            for sv in getattr(n, "state_vars", [])})
        from .dataloader import DataloaderOp
        self.dataloader_ops = [n for n in self.topo
                               if isinstance(n, DataloaderOp)]
        self.feeds = [n for n in self.topo
                      if isinstance(n, PlaceholderOp) and not n.is_variable]

        mesh = executor.mesh
        if mesh is not None and "pp" in mesh.axis_names:
            self.num_stages = mesh.shape["pp"]
            if cfg.num_stages not in (None, self.num_stages):
                raise ValueError(
                    f"num_stages={cfg.num_stages} != mesh pp axis "
                    f"{mesh.shape['pp']}")
        else:
            self.num_stages = cfg.num_stages or 2
        self.num_microbatches = cfg.num_microbatches or self.num_stages

        self.plan = partition(self.loss_node, self.num_stages)
        # stateful ops (BN running stats) thread extra_outputs, which the
        # SPMD lowering drops — those graphs take the microbatch-scan path
        has_state = any(getattr(n, "state_vars", []) for n in self.topo)
        self.spmd = (mesh is not None and "pp" in mesh.axis_names
                     and self.plan.uniform and not has_state
                     and self.mode in ("gpipe", "1f1b"))

        # hetpipe: host-side PS delta sync every sync_every batches
        self._batches_seen = 0
        self._ps_snapshot = None
        if self.mode == "hetpipe":
            if cfg.ps_comm is None:
                from .ps.client import PSClient
                cfg.ps_comm = PSClient.get()
            self.sync_every = getattr(cfg, "sync_every", None) \
                or self.num_stages
        self._compiled = {}

    # ------------------------------------------------------------------ #
    # graph segment tracing
    # ------------------------------------------------------------------ #

    def _trace_nodes(self, nodes, params, feeds, tc, seed_vals=None):
        """Evaluate a topo slice; returns the vals map."""
        vals = dict(seed_vals or {})
        from .dataloader import DataloaderOp
        mp = self.executor.config.mixed_precision

        def cast(v):
            if mp is not None and hasattr(v, "dtype") \
                    and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(mp)
            return v

        def bind(node):
            if isinstance(node, DataloaderOp):
                return cast(feeds[node.name])
            src = params if node.is_variable else feeds
            return cast(src[node.name])

        for node in nodes:
            if id(node) in vals:
                continue
            if isinstance(node, (PlaceholderOp, DataloaderOp)):
                vals[id(node)] = bind(node)
            else:
                ins = []
                for i in node.inputs:
                    if id(i) not in vals:
                        # a placeholder that topologically lives in another
                        # segment (e.g. embedding weights tied into the
                        # post-body LM head) — globally available, bind here
                        if isinstance(i, (PlaceholderOp, DataloaderOp)):
                            vals[id(i)] = bind(i)
                        else:
                            raise KeyError(
                                f"pipeline segment references value "
                                f"{i.name} produced outside the segment "
                                f"(input of {node.name}); the partitioner "
                                f"should have prevented this cut")
                    ins.append(vals[id(i)])
                vals[id(node)] = node.compute(ins, tc)
        return vals

    def _stable_rng_ids(self):
        from .executor import stable_rng_ids
        return stable_rng_ids(self)

    def _forward_loss(self, params, feeds, rng, step):
        """Full-graph forward for one microbatch -> (loss, extra_outputs)."""
        from .executor import _ParamView
        tc = TraceContext(params=_ParamView(params), rng=rng, training=True,
                          mesh=self.executor.mesh,
                          config=self.executor.config, step=step)
        tc.rng_ids = self._stable_rng_ids()
        tc.extra_outputs = {}
        vals = self._trace_nodes(self.topo, params, feeds, tc)
        loss = vals[id(self.loss_node)]
        extras = {k.name if isinstance(k, Op) else k: v
                  for k, v in tc.extra_outputs.items()}
        return loss.astype(jnp.float32), extras

    def _apply_template_block(self, param_vals, x, tc):
        """Apply body block 0's structure with another block's params —
        positional binding is sound because the partitioner only admits
        blocks with identical signatures (op types+attrs, param shapes)."""
        tmpl = self.plan.body_blocks[0]
        vals = {id(self.plan.body_entry): x}
        for ph, v in zip(tmpl.params, param_vals):
            vals[id(ph)] = v
        for node in tmpl.nodes:
            if isinstance(node, PlaceholderOp):
                continue
            vals[id(node)] = node.compute(
                [vals[id(i)] for i in node.inputs], tc)
        return vals[id(tmpl.boundary_out)]

    # ------------------------------------------------------------------ #
    # optimizer
    # ------------------------------------------------------------------ #

    def _apply_opt(self, params, grads, opt_state, step):
        opt = self.opt_op.optimizer
        lr = opt.lr_value(step)
        new_params = dict(params)
        new_state = dict(opt_state)
        for var in self.opt_op.var_list:
            p = params[var.name]
            g = grads[var.name]
            new_p, ns = opt.update_one(p, g.astype(p.dtype),
                                       opt_state.get(var.name), lr, step)
            new_params[var.name] = new_p
            new_state[var.name] = ns
        return new_params, new_state

    # ------------------------------------------------------------------ #
    # step compilation
    # ------------------------------------------------------------------ #

    def _split_microbatches(self, feeds):
        """Batched feeds -> [M, mb, ...]; feeds named in
        config.non_batch_feeds (per-step constants like attention masks)
        are NOT split — each microbatch sees them whole."""
        M = self.num_microbatches
        skip = self.non_batch_feeds
        split, whole = {}, {}
        for k, v in feeds.items():
            if k in skip:
                whole[k] = v
            elif v.ndim == 0 or v.shape[0] % M:
                raise ValueError(
                    f"feed '{k}' batch dim {v.shape} not divisible by "
                    f"num_microbatches={M}; if it is a per-step constant "
                    f"rather than a batch, list it in "
                    f"HetuConfig(non_batch_feeds=...)")
            else:
                split[k] = v.reshape(M, v.shape[0] // M, *v.shape[1:])
        return split, whole

    def _make_step_fn(self):
        ex = self.executor
        M = self.num_microbatches
        train_names = [v.name for v in self.opt_op.var_list]
        opt_name = self.opt_op.name

        def split_params(params):
            tp = {k: params[k] for k in train_names}
            frozen = {k: v for k, v in params.items()
                      if k not in train_names}
            return tp, frozen

        if self.spmd:
            loss_of = self._spmd_loss_fn()
        else:
            loss_of = None

        def step_fn(params, opt_states, step, rng, feeds):
            mb, whole = self._split_microbatches(feeds)
            rngs = jax.random.split(rng, M)
            tp, frozen = split_params(params)
            ostate = opt_states[opt_name]

            state0 = {k: params[k] for k in self.state_var_names}

            def advance_state(st, extras):
                # BN updates chain sequentially microbatch-to-microbatch
                # (the reference's per-microbatch compute does the same)
                return {k: extras[k].astype(st[k].dtype)
                        if k in extras else st[k] for k in st}

            if self.mode in ("gpipe", "1f1b"):
                if loss_of is not None:
                    def total_loss(tp_):
                        return loss_of({**frozen, **tp_}, mb, whole,
                                       rngs, step)
                    loss, grads = jax.value_and_grad(total_loss)(tp)
                    state_fin = state0
                else:
                    def body(carry, xs):
                        acc, st = carry
                        fmb, r = xs

                        def mb_loss(tp_):
                            return self._forward_loss(
                                {**frozen, **st, **tp_},
                                {**fmb, **whole}, r, step)
                        (l, ex_), g = jax.value_and_grad(
                            mb_loss, has_aux=True)(tp)
                        return (_tree_add(acc, g),
                                advance_state(st, ex_)), l
                    zeros = jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), tp)
                    (grads, state_fin), losses = jax.lax.scan(
                        body, (zeros, state0), (mb, rngs))
                    grads = jax.tree_util.tree_map(lambda g: g / M, grads)
                    loss = losses.mean()
                new_tp, new_ostate = self._apply_opt(tp, grads, ostate, step)
                new_params = {**frozen, **state_fin, **new_tp}
            else:   # pipedream / hetpipe: per-microbatch updates
                def body(carry, xs):
                    tp_c, ostate_c, st = carry
                    fmb, r = xs

                    def mb_loss(tp_):
                        return self._forward_loss(
                            {**frozen, **st, **tp_},
                            {**fmb, **whole}, r, step)
                    (l, ex_), g = jax.value_and_grad(
                        mb_loss, has_aux=True)(tp_c)
                    tp_n, ostate_n = self._apply_opt(tp_c, g, ostate_c, step)
                    return (tp_n, ostate_n, advance_state(st, ex_)), l
                (new_tp, new_ostate, state_fin), losses = jax.lax.scan(
                    body, (tp, ostate, state0), (mb, rngs))
                loss = losses.mean()
                new_params = {**frozen, **state_fin, **new_tp}
            new_opt = dict(opt_states)
            new_opt[opt_name] = new_ostate
            return new_params, new_opt, step + 1, loss

        return step_fn

    def _spmd_loss_fn(self):
        """Loss over all microbatches via the SPMD scan pipeline."""
        ex = self.executor
        mesh = ex.mesh
        plan = self.plan
        S = self.num_stages
        R = plan.num_body_blocks()
        rps = R // S
        n_pos = len(plan.body_blocks[0].params)

        def loss_of(params, mb, whole, rngs, step):
            cfg = ex.config

            def pre_one(fmb, r):
                tc = TraceContext(params={}, rng=r, training=True,
                                  mesh=mesh, config=cfg, step=step)
                tc.rng_ids = self._stable_rng_ids()
                vals = self._trace_nodes(plan.pre_nodes, params,
                                         {**fmb, **whole}, tc)
                return vals[id(plan.body_entry)]

            xs = jax.vmap(pre_one)(mb, rngs)     # [M, mb, ...]

            # stack body params [R, ...] -> [S, R/S, ...], 'pp'-sharded;
            # mixed precision casts at graph entry (masters stay fp32)
            mp = cfg.mixed_precision

            def entry_cast(v):
                if mp is not None and jnp.issubdtype(v.dtype,
                                                    jnp.floating):
                    return v.astype(mp)
                return v

            stacked = []
            for pos in range(n_pos):
                tmpl = plan.body_params[0][pos]
                # the stacked constraint can express only ONE spec per
                # position: require per-layer specs to be uniform, or the
                # template's would silently override the others.
                # (normalize: P('tp') == P('tp', None))
                def _norm(spec):
                    t = tuple(spec) if spec is not None else ()
                    while t and t[-1] is None:
                        t = t[:-1]
                    return t
                specs = {_norm(getattr(plan.body_params[r][pos],
                                       "sharding_spec", None))
                         for r in range(R)}
                if len(specs) > 1:
                    raise ValueError(
                        f"pipeline body param position {pos} "
                        f"({tmpl.name}-like) has non-uniform sharding "
                        f"specs across layers ({sorted(map(str, specs))}); "
                        f"give "
                        f"every body layer the same spec")
                leaves = [entry_cast(params[plan.body_params[r][pos].name])
                          for r in range(R)]
                st = jnp.stack(leaves).reshape(S, rps, *leaves[0].shape)
                # shard_map is manual over 'pp' ONLY; the per-layer tp/dp
                # specs carry into the stacked dims and GSPMD partitions
                # the in-stage matmuls (true pp x tp composition)
                var_spec = getattr(tmpl, "sharding_spec", None)
                tail = tuple(var_spec) if var_spec is not None \
                    else (None,) * (st.ndim - 2)
                st = jax.lax.with_sharding_constraint(
                    st, NamedSharding(mesh, P("pp", None, *tail)))
                stacked.append(st)
            stacked = tuple(stacked)

            base_rng = jax.random.fold_in(rngs[0], 7)

            def stage_fn(plist, x, m):
                # plist leaves [rps, ...].  RNG decorrelates over stage,
                # microbatch index, and block index — without this every
                # block/microbatch would reuse the template nodes'
                # dropout masks.  Keyed by MICROBATCH (not tick) so the
                # 1F1B backward's recompute reproduces the forward's
                # randomness exactly.
                r = jax.random.fold_in(base_rng, jax.lax.axis_index("pp"))
                r = jax.random.fold_in(r, m)

                def blk(h, pr_bi):
                    pr, bi = pr_bi
                    tc = TraceContext(params={},
                                      rng=jax.random.fold_in(r, bi),
                                      training=True, mesh=mesh, config=cfg,
                                      step=step, axis_env=mesh.axis_names)
                    tc.rng_ids = self._stable_rng_ids()
                    return self._apply_template_block(list(pr), h, tc), None
                h, _ = jax.lax.scan(blk, x, (plist, jnp.arange(rps)))
                return h

            if self.mode == "1f1b":
                # real staggered 1F1B: O(S) activation high-water via the
                # custom-VJP schedule (vs gpipe's O(M+S) saved carries)
                ys = spmd_pipeline_1f1b(stage_fn, stacked, xs, mesh=mesh,
                                        axis="pp",
                                        mb_spec=P(*([None] * (xs.ndim))),
                                        manual_axes={"pp"})
            else:
                ys = spmd_pipeline(stage_fn, stacked, xs, mesh=mesh,
                                   axis="pp",
                                   mb_spec=P(*([None] * (xs.ndim))),
                                   stage_takes_index=True,
                                   manual_axes={"pp"})

            def post_one(y, fmb, r):
                tc = TraceContext(params={}, rng=jax.random.fold_in(r, 13),
                                  training=True, mesh=mesh, config=cfg,
                                  step=step)
                tc.rng_ids = self._stable_rng_ids()
                seed = {id(plan.body_blocks[-1].boundary_out): y}
                vals = self._trace_nodes(plan.post_nodes, params,
                                         {**fmb, **whole}, tc,
                                         seed_vals=seed)
                return vals[id(self.loss_node)].astype(jnp.float32)

            losses = jax.vmap(post_one)(ys, mb, rngs)
            return losses.mean()

        return loss_of

    def _compile(self, feed_sig):
        ex = self.executor
        inner = self._make_step_fn()

        def step_fn(params, opt_states, step, rng, feeds):
            # rng splits INSIDE the jitted program (an eager per-step
            # split is a full host<->device round trip)
            new_rng, sub = jax.random.split(rng)
            p, o, s, loss = inner(params, opt_states, step, sub, feeds)
            return p, o, s, new_rng, loss

        jit_kwargs = dict(donate_argnums=(0, 1))
        if ex.mesh is not None:
            from .executor import _opt_sharding_like
            param_sh = {k: ex.param_sharding(k) for k in ex.var_values}
            feed_sh = {name: ex.feed_sharding(name, shape)
                       for name, shape, _ in feed_sig}
            rep = NamedSharding(ex.mesh, P())
            opt_sh = _opt_sharding_like(ex, ex.opt_states)
            jit_kwargs["in_shardings"] = (
                param_sh, opt_sh, rep, rep, feed_sh)
            jit_kwargs["out_shardings"] = (param_sh, opt_sh, rep, rep, None)
        return jax.jit(step_fn, **jit_kwargs)

    # ------------------------------------------------------------------ #

    @property
    def batch_num(self):
        nums = [dl.get_batch_num(self.name) for dl in self.dataloader_ops]
        nums = [n for n in nums if n is not None]
        return min(nums) if nums else None

    def run(self, feed_dict, convert_to_numpy_ret_vals=False):
        from .executor import gather_feeds
        ex = self.executor
        feeds = gather_feeds(self, feed_dict)
        feed_sig = tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in feeds.items()))
        if feed_sig not in self._compiled:
            # same pre-trace gate as SubExecutor.run: fail with the node
            # named before the pipeline trace (HETU_VALIDATE=1)
            from .analysis import validate_subgraph_feeds
            validate_subgraph_feeds(ex, self, feeds)
            self._compiled[feed_sig] = self._compile(feed_sig)
        fn = self._compiled[feed_sig]
        if ex.mesh is not None:
            feeds = {k: ex.device_put_feed(k, v) for k, v in feeds.items()}
        ex.var_values, ex.opt_states, ex.step, ex.rng, loss = fn(
            ex.var_values, ex.opt_states, ex.step, ex.rng, feeds)
        self._batches_seen += 1
        if self.mode == "hetpipe" and \
                self._batches_seen % self.sync_every == 0:
            self._hetpipe_sync()
        results = []
        for n in self.eval_nodes:
            if isinstance(n, OptimizerOp):
                results.append(None)
            elif convert_to_numpy_ret_vals:
                results.append(np.asarray(loss))
            else:
                results.append(loss)
        return results

    # ------------------------------------------------------------------ #
    # HetPipe PS delta-sync (reference pipedream_subexecutor.py:317-328:
    # local updates between syncs, push accumulated delta to the PS every
    # pp_nrank batches; the server accumulates pushes into the param)
    # ------------------------------------------------------------------ #

    def _hetpipe_sync(self):
        from .parallel.pipeline import ps_delta_sync
        ex = self.executor
        cur = {v.name: np.array(ex.var_values[v.name], copy=True)
               for v in self.opt_op.var_list}
        merged, self._ps_snapshot = ps_delta_sync(
            ex.config.ps_comm, cur, self._ps_snapshot)
        for k, v in merged.items():
            ex.var_values[k] = self._replace(k, v)

    def _replace(self, name, value):
        arr = jnp.asarray(value)
        if self.executor.mesh is not None:
            arr = jax.device_put(arr, self.executor.param_sharding(name))
        return arr
