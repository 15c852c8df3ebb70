"""Runner ``serve_parallel_moe``: the ``cohere2_moe`` family (a PARALLEL
block on one bias-free LayerNorm, sliding layers rotated over a K/V ring,
full layers that rotate nothing, a bias-free sigmoid router beside
averaged shared experts, a tied head), the layers holding this chip's
SHARE of the experts and the table its share of the rows, served by
``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys, cut
to one chip's share of a deployment (``deployment``: which experts and
which vocabulary rows are held); the program's ``ParallelMoEConfig`` is
built from them with the ROUTER's width and the table's height as
published and carries the block spec the mixed wave reads.  The weights
are made on the device in one jitted call in the PUBLISHED layout and the
configuration object permutes the rotating layers' ``W_q`` / ``W_k``
once (rotate-half on the chip is then the published interleaved
rotation); the engine is built with NO path argument (fast path, mixed
ragged wave, paged block 16 on the TPU; the sliding layers' ring pool
lives in the engine's own manager), every (bucket, ``has_fresh``) program
is warmed, and the loop is ``runners/serve.py``'s own (``drive``,
``Load``, ``reduce_rows``, ``chunk_buckets``, ``warm_up``) with
``runners/serve_latent_moe.py``'s ``Marks``, ``runners/
serve_hybrid_moe.py``'s ``NoOneWaits`` and ``runners/
serve_window_moe.py``'s ``sample`` and ``window_view``, all loaded by
name: there is no copy of any here.

What this runner adds is the comparison that decides ``correct``:
``reference_command_a_plus``'s float32 forward over prompt + answer of a
seeded sample of finished requests, on the published layout (the
permutation undone), given the same held experts and the same held rows,
against what the timed engine produced through chunked prefill and decode
over the two pools: logits, not tokens, by SHARES as the code cell does
(``runners/serve_window_moe.py``).  The engine is greedy, so a served
token's float32 reference logit should lie within ``logit_margin`` of its
row's largest; bf16 activations flip the last chosen expert of a row
whose sigmoid scores nearly tie at the 8th place of 128, so rows whose
smallest selection margin over the layers is under ``tie_margin`` are
counted apart.  Held rows: at most ``held_over_share_max`` of them over
``logit_margin``, at least ``held_rows_min`` of them, the near ties'
share under ``tie_share_max``; ALL answer rows: at most
``over_margin_share_max`` over it.  The sample must hold a prompt of
``long_prompt_positions`` or more (the ring has turned, pages were
recycled) and one under ``short_prompt_positions`` (the band never
binds).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_command_a_plus
from benchmarks.run import load_module

NAME = "cmd"
REFERENCE_KEYS = (
    "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_size", "intermediate_size",
    "layer_norm_eps", "rope_theta", "sliding_window", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "norm_topk_prob",
    "logit_scale")
COUNTER_KEYS = ("moe_assignments", "moe_assignments_routed",
                "moe_experts_touched", "moe_kernel_waves", "moe_load",
                "moe_load_imbalance", "attn_ctx_tokens", "attn_score_pairs",
                "attn_window_ctx_tokens", "attn_window_score_pairs",
                "attn_window_bound_rows", "window_blocks_recycled",
                "wave_rows_live", "wave_rows_computed", "chunks_deferred",
                "steps")
# the reference's selection margin is a gap between two sigmoid scores
# of 128 (each of order 0.5, the 8th and the 9th largest)
MARGIN_STEPS = (0.0, 1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3)
# logit gaps the record counts rows over, beside ``logit_margin``'s own
GAP_STEPS = (0.03, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.5)


def published(config):
    """The source's keys as the program takes them: the router's width
    and the table's height as published (the file's ``num_experts`` and
    ``vocab_size`` are what this chip HOLDS), and which experts and rows
    those are."""
    dep = config["deployment"]
    first, held = dep["experts_held"]
    row0, rows = dep["vocab_rows_held"]
    if held != config["num_experts"] or rows != config["vocab_size"]:
        raise SystemExit("benchmark: deployment and the held num_experts / "
                         "vocab_size disagree")
    pub = config["published"]
    source = dict(config, num_experts=pub["num_experts"],
                  vocab_size=pub["vocab_size"])
    return source, (int(first), int(held)), (int(row0), int(rows))


def model_config(config):
    """The program's configuration object, or a clean exit at once where
    the program cannot run the family (the parent of the PR that brought
    it): before anything is built."""
    try:
        from hetu_tpu.models.parallel_moe import ParallelMoEConfig
    except ImportError:
        raise SystemExit(
            "benchmark: this program has no ParallelMoEConfig (a parallel "
            "block on one bias-free LayerNorm, rotation by operator with "
            "one operator unrotated, averaged shared experts beside a held "
            "share); it cannot run the configuration. Nothing was run.")
    source, held, rows = published(config)
    return ParallelMoEConfig.from_hf(source, held_experts=held,
                                     vocab_rows=rows)


def agree(h, params, ref_config, held, done, args, control=None):
    """Outside the window: the module's docstring over
    ``serve_window_moe.sample``'s requests.  ``params`` is the PUBLISHED
    layout.  ``control`` asks the reference for one of
    ``reference_command_a_plus.CONTROLS`` (the probe and the tests; the
    run never passes it): the comparison has to call each not correct.
    Returns (ok, record)."""
    t0 = time.perf_counter()
    window = load_module("runners", "serve_window_moe")
    picks, longest, shortest = window.sample(h, done, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    margin_of = float(args["logit_margin"])
    worst = worst_tie = gap_sum = 0.0
    rows_all = rows_tie = rows_over = held_over = 0
    by_margin = {m: [0, 0.0, 0] for m in MARGIN_STEPS}
    by_gap = {g: [0, 0] for g in GAP_STEPS}
    by_request = []
    stats = {}
    for at, i in enumerate(picks):
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = seq[:-1]
        rows = np.arange(r.prompt_len - 1, n)
        want = np.full(-(-len(rows) // row_pad) * row_pad, rows[-1])
        want[:len(rows)] = rows
        lg, margin = reference_command_a_plus.forward(
            params, ref_config, padded, want, name=NAME, held=held,
            control=control, stats=stats if at == 0 else None)
        lg = lg[:len(rows)]
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        tie = margin[rows] < float(args["tie_margin"])
        over = gap > margin_of
        rows_all += len(rows)
        rows_tie += int(tie.sum())
        rows_over += int(over.sum())
        held_over += int(over[~tie].sum())
        gap_sum += float(gap.sum())
        worst = max(worst, float(gap[~tie].max(initial=0.0)))
        worst_tie = max(worst_tie, float(gap[tie].max(initial=0.0)))
        by_request.append([int(r.prompt_len), len(rows), int(over.sum()),
                           float(gap.max(initial=0.0))])
        for g, cell in by_gap.items():
            cell[0] += int((gap > g).sum())
            cell[1] += int((gap[~tie] > g).sum())
        for m, cell in by_margin.items():
            keep = margin[rows] >= m
            cell[0] += int(keep.sum())
            cell[1] = max(cell[1], float(gap[keep].max(initial=0.0)))
            cell[2] += int((gap[keep] > margin_of).sum())
    held_rows = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over_share = rows_over / max(rows_all, 1)
    held_share = held_over / max(held_rows, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held_rows >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over_share <= float(args["over_margin_share_max"])
          and longest >= int(args["long_prompt_positions"])
          and shortest < int(args["short_prompt_positions"]))
    record = {"requests_checked": len(picks), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "mean_logit_gap": gap_sum / max(rows_all, 1),
              "held_rows": held_rows, "held_rows_min": args["held_rows_min"],
              "held_rows_over_margin": held_over,
              "held_over_share": held_share,
              "held_over_share_max": args["held_over_share_max"],
              "near_tie_rows": rows_tie, "near_tie_share": share,
              "tie_margin": args["tie_margin"],
              "tie_share_max": args["tie_share_max"],
              "rows_over_margin": rows_over, "over_margin_share": over_share,
              "over_margin_share_max": args["over_margin_share_max"],
              "widest_gap_on_near_tie_rows": worst_tie,
              "longest_checked_prompt": longest,
              "shortest_checked_prompt": shortest,
              # [rows, widest gap, rows over logit_margin] among the
              # rows whose margin is at least each step: what another
              # tie_margin would have seen
              "by_margin": {str(m): v for m, v in by_margin.items()},
              # [all rows, held rows] whose gap is over each step: what
              # another logit_margin would have seen
              "over_by_gap": {str(g): v for g, v in by_gap.items()},
              # a request checked: its prompt's length, its answer rows,
              # those over logit_margin, its widest gap
              "by_request": by_request,
              # of the first request checked, a layer: its kind, the RMS
              # of the residual, of the attention's and of the FFN's part
              "rms": stats.get("layers"), "logit_std": stats.get("logits"),
              "seconds": time.perf_counter() - t0}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_window(h, cfg=None):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else.  Returns what ``run`` and the probe
    (``probe_command_a_plus_check.py``) read; the pools are given back to
    the device before it returns and ``params`` is the published layout
    again, so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    narrowed = cfg is not None
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config)
    import jax.numpy as jnp
    from hetu_tpu.models.parallel_moe import init_parallel_moe_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    hybrid = load_module("runners", "serve_hybrid_moe")
    window = load_module("runners", "serve_window_moe")
    source, held, _ = published(config)
    ref_config = {k: source[k] for k in REFERENCE_KEYS}
    if narrowed:
        held = cfg.held_experts
        ref_config.update(
            num_hidden_layers=cfg.num_hidden_layers,
            layer_types=list(cfg.layer_types),
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            sliding_window=cfg.sliding_window,
            num_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            num_shared_experts=cfg.num_shared_experts)
    t_start = time.perf_counter()
    params = cfg.permute_rotary(init_parallel_moe_params(
        cfg, name=NAME, seed=h.seed, gains=args["init_gain"],
        dtype=jnp.dtype(config["dtype"])), NAME)
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
          full_pool_bytes=int(eng.kv.full_bytes),
          window_pool_bytes=int(eng.kv.window_bytes),
          window_ring=int(eng.kv.ring), experts_held=list(held),
          router_experts=cfg.n_routed_experts,
          vocab_rows_held=list(cfg.vocab_rows))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    load.no_token_yet = hybrid.NoOneWaits()
    marks = latent.Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    counters = {part: {k: snap.get(k) for k in COUNTER_KEYS}
                for part, snap in marks.counters.items()}
    engine = {"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
              "paged": bool(eng.paged),
              "window_layers": int(eng.kv.window_layers),
              "window_ring": int(eng.kv.ring),
              "window_blocks_recycled": int(eng.kv.window_blocks_recycled),
              "slots": args["slots"], "pool_blocks": args["pool_blocks"],
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets,
              "window": window.window_view(view, counters),
              "drained": not eng.pending}
    # the pools' device memory goes to the reference, which reads the
    # published layout: the permutation undone
    for buffer in (eng.kv.cache_k, eng.kv.cache_v, eng.kv.win_k,
                   eng.kv.win_v):
        buffer.delete()
    params = cfg.permute_rotary(params, NAME, inverse=True)
    return {"params": params, "ref_config": ref_config, "held": held,
            "engine": engine, "buckets": buckets, "load": load,
            "view": engine["window"], "stats": stats,
            "untraced_until": untraced_until, "out": out,
            "counters": counters}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    w = serve_window(h, cfg)
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
          requests_issued=load.issued, requests_finished=len(out["done"]),
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
                           ("longest_checked_prompt",
                            "long_prompt_positions"))
        if key in record]
    if "shortest_checked_prompt" in record:
        compared.append({
            "name": "shortest_checked_prompt",
            "value": record["shortest_checked_prompt"],
            "limit": int(args["short_prompt_positions"]),
            "within": record["shortest_checked_prompt"]
            < int(args["short_prompt_positions"])})
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
