"""Runner ``serve_sparse_latent``: a decoder whose layers are latent
attention BY LAYER (the ``dots3_note`` / DeepSeek-V3.2 family): full
layers that read the rows a learned indexer chose, window layers over a
latent ring of their own width and head count, the expert layers holding
this chip's SHARE of the experts and the head this chip's rows of the
vocabulary, served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys, cut
to one chip's share of a deployment (``deployment``: which experts and
which vocabulary rows are held); the program's ``SparseLatentConfig`` is
built from them with the ROUTER's width and the vocabulary as published
and carries the block spec the mixed wave reads.  The weights are made on
the device in one jitted call, the engine is built with NO path argument
(fast path, mixed ragged wave, paged block 16 on the TPU; the latent
pool, the index keys' pool and the window layers' latent ring live in
the engine's own manager), every (bucket, ``has_fresh``) program is
warmed, and the loop is ``runners/serve.py``'s own (``drive``, ``Load``,
``reduce_rows``, ``chunk_buckets``, ``warm_up``) with
``runners/serve_latent_moe.py``'s ``Marks`` around the harness,
``runners/serve_hybrid_moe.py``'s ``sample``,
``runners/serve_window_moe.py``'s ``window_view`` and
``runners/serve_retention.py``'s ``AfterARetirement`` (the profiler is
switched between waves: with a wave in flight at its start
``wave_trace`` joined modules to dispatch spans one wave off in four
traced runs of six, and every wave metric read nothing), all loaded by
name: there is no copy of them here.

What this runner adds is the comparison that decides ``correct``, against
``reference_dots3_note``'s float32 forward over prompt + answer of a
seeded sample of finished requests, given the same held experts and the
same held vocabulary rows:

* logits, not tokens, by the routed cells' rule: the engine is greedy, so
  a served token's float32 reference logit should lie within
  ``logit_margin`` of its row's largest.  bf16 scores flip the last
  chosen expert of a row whose ``s + b`` nearly tie at the 8th place, and
  the last chosen position of a row whose index scores nearly tie at the
  2,048th, so rows of either kind are counted APART: a row is HELD when
  its smallest routing margin over the layers is at least ``tie_margin``
  and its smallest index tie margin over the full layers at least
  ``index_tie_margin``; of the held rows at most ``held_over_share_max``
  lie over the margin, there are at least ``held_rows_min`` of them, the
  near ties' share stays under ``tie_share_max``; of ALL answer rows at
  most ``over_margin_share_max`` lie over it;
* at least ``selecting_rows_min`` answer rows past ``index_topk``
  positions (rows whose indexer had to choose), a prompt of
  ``long_prompt_chunks`` chunks or more in the sample, and every answer
  its exact length.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_dots3_note
from benchmarks.run import load_module

NAME = "d3n"
COUNTER_KEYS = ("moe_assignments", "moe_assignments_routed",
                "moe_experts_touched", "moe_kernel_waves", "moe_load",
                "moe_load_imbalance", "attn_ctx_tokens", "attn_score_pairs",
                "attn_window_ctx_tokens", "attn_window_score_pairs",
                "window_blocks_recycled", "sparse_rows",
                "sparse_rows_selecting", "sparse_keys_in_sight",
                "sparse_keys_read", "sparse_keys_needed", "index_ctx_tokens",
                "wave_rows_live", "wave_rows_computed", "chunks_deferred",
                "steps")
# the keys the file states for its own use: the rest is the source's
OWN_KEYS = ("source", "published", "reduced", "reduced_why", "deployment",
            "assumed", "runner", "dtype", "runner_args", "memory_analysis")
GAP_STEPS = (0.03, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.5, 1.0)


def published_source(config):
    """(the source's keys as the program takes them: the router's width
    and the vocabulary as published, the depth and the layer types as
    served; the experts held; the vocabulary rows held)."""
    dep = config["deployment"]
    first, held = dep["experts_held"]
    row0, rows = dep["vocab_rows_held"]
    if held != config["n_routed_experts"] or rows != config["vocab_size"]:
        raise SystemExit("benchmark: deployment and n_routed_experts / "
                         "vocab_size disagree on what is held")
    pub = config["published"]
    source = {k: v for k, v in config.items() if k not in OWN_KEYS}
    source.update(n_routed_experts=pub["n_routed_experts"],
                  vocab_size=pub["vocab_size"],
                  max_position_embeddings=config["max_position_embeddings"])
    return source, (int(first), int(held)), (int(row0), int(rows))


def model_config(config):
    try:
        from hetu_tpu.models.sparse_latent import SparseLatentConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no SparseLatentConfig "
                         "(latent attention by layer, a learned indexer, a "
                         "latent ring); it cannot run the configuration. "
                         "Nothing was run.")
    source, held, rows = published_source(config)
    return SparseLatentConfig.from_hf(source, held_experts=held,
                                      vocab_rows=rows)


def agree(h, params, ref_config, held, done, args, control=None):
    """Outside the window: the parts of the module's docstring over
    ``serve_hybrid_moe.sample``'s requests.  ``control`` asks the
    reference for one of
    ``reference_dots3_note.CONTROLS`` (the probe and the tests; the run
    never passes it): the comparison has to call each not correct.
    Returns (ok, record)."""
    t0 = time.perf_counter()
    # a seeded choice of ``check_requests`` with one long prompt in it
    picks, longest = load_module("runners", "serve_hybrid_moe").sample(
        h, done, args)
    margin_of = float(args["logit_margin"])
    topk = int(ref_config.get("index_topk") or 0)
    worst = worst_tie = gap_sum = 0.0
    rows_all = rows_tie = rows_index_tie = rows_route_tie = 0
    rows_over = held_over = 0
    selecting = 0
    by_gap = {g: [0, 0] for g in GAP_STEPS}
    stats = {}
    for at, i in enumerate(picks):
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        rows = np.arange(r.prompt_len - 1, n)
        lg, margin, index_tie = reference_dots3_note.forward(
            params, ref_config, seq[:-1], rows, name=NAME, held=held,
            control=control, stats=stats if at == 0 else None)
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        near_route = margin[rows] < float(args["tie_margin"])
        near_index = index_tie[rows] < float(args["index_tie_margin"])
        tie = near_route | near_index
        over = gap > margin_of
        rows_all += len(rows)
        rows_tie += int(tie.sum())
        rows_index_tie += int(near_index.sum())
        rows_route_tie += int(near_route.sum())
        rows_over += int(over.sum())
        held_over += int(over[~tie].sum())
        selecting += int((rows >= topk).sum()) if topk else 0
        gap_sum += float(gap.sum())
        worst = max(worst, float(gap[~tie].max(initial=0.0)))
        worst_tie = max(worst_tie, float(gap[tie].max(initial=0.0)))
        for g, cell in by_gap.items():
            cell[0] += int((gap > g).sum())
            cell[1] += int((gap[~tie] > g).sum())
    held_rows = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over_share = rows_over / max(rows_all, 1)
    held_share = held_over / max(held_rows, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held_rows >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over_share <= float(args["over_margin_share_max"])
          and selecting >= int(args["selecting_rows_min"])
          and longest >= int(args["long_prompt_chunks"]))
    record = {"requests_checked": len(picks), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "mean_logit_gap": gap_sum / max(rows_all, 1),
              "held_rows": held_rows, "held_rows_min": args["held_rows_min"],
              "held_rows_over_margin": held_over,
              "held_over_share": held_share,
              "held_over_share_max": args["held_over_share_max"],
              "near_tie_rows": rows_tie, "near_tie_share": share,
              "near_index_tie_rows": rows_index_tie,
              "near_routing_tie_rows": rows_route_tie,
              "tie_margin": args["tie_margin"],
              "index_tie_margin": args["index_tie_margin"],
              "tie_share_max": args["tie_share_max"],
              "rows_over_margin": rows_over, "over_margin_share": over_share,
              "over_margin_share_max": args["over_margin_share_max"],
              "widest_gap_on_near_tie_rows": worst_tie,
              "selecting_rows": selecting,
              "selecting_rows_min": args["selecting_rows_min"],
              "longest_checked_prompt_chunks": longest,
              # [all rows, held rows] whose gap is over each step: what
              # another logit_margin would have seen
              "over_by_gap": {str(g): v for g, v in by_gap.items()},
              # of the first request checked, a layer: its kind, the RMS
              # of the residual and of the layer's two parts
              "rms": stats.get("layers"), "logit_std": stats.get("logits"),
              "seconds": time.perf_counter() - t0}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_window(h):
    """Build, warm, ramp, window, drain: everything but the comparison
    (the CPU rehearsal in the tests narrows the configuration's own keys
    and nothing else).  Returns what ``run`` and the probe
    (``probe_dots3_check.py``) read; the pools are given back to the
    device before it returns, so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    # first: a program that cannot run the configuration stops here
    cfg = model_config(config)
    import jax.numpy as jnp
    from hetu_tpu.models.sparse_latent import init_sparse_latent_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    window = load_module("runners", "serve_window_moe")
    source, held, _ = published_source(config)
    t_start = time.perf_counter()
    params = init_sparse_latent_params(
        cfg, name=NAME, seed=h.seed, gains=args["init_gain"],
        dtype=jnp.dtype(config["dtype"]))
    eng = ServingEngine(params, cfg, slots=args["slots"],
                        queue_limit=args["queue_limit"],
                        max_seq_len=args["max_seq_len"],
                        pool_blocks=args["pool_blocks"],
                        prefill_chunk=args["prefill_chunk"])
    buckets = serve.chunk_buckets(mix, args["prefill_chunk"])
    t_built = time.perf_counter()
    serve.warm_up(eng, buckets, cfg.vocab_size)
    h.log(line="setup", build_s=t_built - t_start,
          warmup_s=time.perf_counter() - t_built,
          weight_bytes=int(sum(v.nbytes for v in params.values())),
          pool_bytes=int(eng.kv.cache_bytes),
          index_bytes=int(eng.kv.index_bytes),
          window_bytes=int(eng.kv.window_bytes), ring=int(eng.kv.ring),
          experts_held=list(held), router_experts=cfg.n_routed_experts,
          vocab_rows_held=list(cfg.vocab_rows))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    load.no_token_yet = load_module(
        "runners", "serve_retention").AfterARetirement(load.rows)
    marks = latent.Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    counters = {part: {k: snap.get(k) for k in COUNTER_KEYS}
                for part, snap in marks.counters.items()}
    engine = {"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
              "paged": bool(eng.paged), "slots": args["slots"],
              "pool_blocks": args["pool_blocks"],
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets, "window": view,
              "drained": not eng.pending}
    for buffer in (eng.kv.cache_k, eng.kv.cache_v, eng.kv.win_k):
        if buffer is not None:
            buffer.delete()
    return {"params": params, "ref_config": source, "held": held,
            "engine": engine, "buckets": buckets, "load": load,
            "view": window.window_view(view, counters), "stats": stats,
            "untraced_until": untraced_until, "out": out,
            "counters": counters}


def run(h):
    return report(h, serve_window(h))


def report(h, w):
    """The comparison and the result of one served window ``w``."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    load, out, counters = w["load"], w["out"], w["counters"]
    ok, record = agree(h, w["params"], w["ref_config"], w["held"],
                       out["done"], args) if out["done"] else (False, {})
    p95 = lambda xs: loadgen.percentile(xs, 95)             # noqa: E731
    finished = sum(1 for r in out["done"] if r["done"] <= h.seconds)
    h.log(line="serve", loop=mix["loop"], attempted=out["attempted"],
          failed=out["failed"], finished_in_window=finished,
          ttft_samples=len(out["ttft_ms"]),
          tpot_samples=len(out["tpot_ms"]),
          ttft_ms={f"p{q}": loadgen.percentile(out["ttft_ms"], q)
                   for q in (50, 80, 90, 95)},
          tpot_ms={f"p{q}": loadgen.percentile(out["tpot_ms"], q)
                   for q in (50, 80, 90, 95)},
          tokens_in_window=load.tokens_in_window,
          tokens_per_s=out["tokens_per_s"],
          requests_issued=load.issued,
          gen_lag_p95_ms=p95(out["gen_lag_ms"]),
          untraced_until_s=w["untraced_until"],
          traced_window_s=None if w["untraced_until"] is None
          else h.seconds - w["untraced_until"],
          engine=w["engine"],
          counters={part: {k: v for k, v in c.items() if k != "moe_load"}
                    for part, c in counters.items()},
          exact_lengths=out["exact_lengths"], tokens_agree=ok)
    compared = [
        {"name": key, "value": record[key], "limit": float(args[limit]),
         "within": record[key] <= float(args[limit])}
        for key, limit in (("held_over_share", "held_over_share_max"),
                           ("over_margin_share", "over_margin_share_max"),
                           ("near_tie_share", "tie_share_max"))
        if key in record]
    compared += [
        {"name": key, "value": record[key], "limit": int(args[limit]),
         "within": record[key] >= int(args[limit])}
        for key, limit in (("held_rows", "held_rows_min"),
                           ("selecting_rows", "selecting_rows_min"),
                           ("longest_checked_prompt_chunks",
                            "long_prompt_chunks"))
        if key in record]
    compared.append({"name": "exact_lengths", "value": out["exact_lengths"],
                     "limit": True, "within": out["exact_lengths"]})
    return {
        "correct": ok and out["exact_lengths"] and bool(out["done"]),
        "attempted": out["attempted"], "failed": out["failed"],
        "memory_peak_bytes": w["stats"].get("peak_bytes_in_use", 0),
        "end_to_end": {"serve_tokens_per_s": out["tokens_per_s"],
                       "ttft_p95_ms": p95(out["ttft_ms"]),
                       "tpot_p95_ms": p95(out["tpot_ms"])},
        "data": {"snapshot": w["view"], "samples": out["untraced"],
                 "counters": counters},
        "notes": {"slots": args["slots"], "buckets": w["buckets"],
                  "finished_in_window": finished,
                  **{k: v for k, v in record.items() if k != "rms"}},
        "compared": compared,
    }
