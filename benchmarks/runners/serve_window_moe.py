"""Runner ``serve_window_moe``: a decoder whose layers attend over a
sliding window or over everything, each over a softmax-routed FFN (the
``mellum`` family) served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys; the
program's ``HybridMoEConfig`` is built from them (``model_type``
"mellum") and carries the block spec the mixed wave reads: the operator
of every layer, the window, the rotary frequencies by layer kind.  The
weights are made on the device in one jitted call, the engine is built
with NO path argument (fast path, mixed ragged wave, paged block 16 on
the TPU; the window layers' ring pool lives in the engine's own
manager), every (bucket, ``has_fresh``) program is warmed, and the loop
is ``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` and ``runners/serve_hybrid_moe.py``'s ``NoOneWaits`` around
the harness, all loaded by name: there is no copy of any here.

What this runner adds: the window layers' counters beside the full
layers' (what the window saved: ``window_ctx_share``), the two pools'
bytes apart, and the comparison that decides ``correct``:
``reference_mellum2``'s full forward over prompt + answer against what
the timed engine produced through chunked prefill and decode over the
two pools, logits not tokens, by the RAG cell's rule (``agree``), on a
sample that holds a prompt long enough for the ring to have wrapped and
one the window never binds on (``sample``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_mellum2
from benchmarks.run import load_module

NAME = "mel"
REFERENCE_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "layer_types", "rms_norm_eps",
    "rope_parameters", "sliding_window", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "tie_word_embeddings")
# the reference's selection margin is a gap between two softmax scores
# over 64 experts, each about 1/64: steps in those units
MARGIN_STEPS = (0.0, 2e-5, 5e-5, 1e-4, 2e-4, 3e-4, 5e-4, 1e-3)
# logit gaps the record counts rows over, beside ``logit_margin``'s own
GAP_STEPS = (0.03, 0.05, 0.075, 0.1, 0.125, 0.15, 0.2)
WINDOW_KEYS = ("attn_window_ctx_tokens", "attn_window_score_pairs",
               "window_blocks_recycled", "wave_rows_live",
               "wave_rows_computed")


def model_config(config):
    """The program's configuration object, or a clean exit at once where
    the program cannot run the family (the parent of the PR that brought
    it): before anything is built."""
    try:
        from hetu_tpu.models.moe_decode import HybridMoEConfig
        known = getattr(HybridMoEConfig, "FAMILIES", {})
    except ImportError:
        known = {}
    if config["model_type"] not in known:
        raise SystemExit(
            f"benchmark: this program's HybridMoEConfig does not run "
            f"model_type {config['model_type']!r} (sliding-window layers, "
            f"rotary parameters by layer kind, a softmax router); it "
            f"cannot run the configuration. Nothing was run.")
    return HybridMoEConfig.from_hf(config)   # keys it does not know pass


def sample(h, done, args):
    """The finished requests the reference is run over: a seeded choice
    of ``check_requests``, of which at least one has a prompt of
    ``long_prompt_positions`` or more (the ring has wrapped, pages were
    recycled, the kernel's first group is not 0) and one a prompt under
    ``short_prompt_positions`` (the window never binds).  Whichever the
    seeded choice lacks takes the last pick's place (the last but one's
    where the last is the only one of the other kind): the first such
    request in the seed's order.  Returns (picks, the longest and
    the shortest picked prompt)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    order = [int(i) for i in rng.permutation(len(done))]
    picks = order[:int(args["check_requests"])]
    plen = lambda i: int(done[i]["result"].prompt_len)     # noqa: E731
    long_at, short_at = (int(args["long_prompt_positions"]),
                         int(args["short_prompt_positions"]))
    kinds = (lambda i: plen(i) >= long_at, lambda i: plen(i) < short_at)
    for need, other in (kinds, kinds[::-1]):
        found = next((i for i in order if need(i)), None)
        if found is None or any(map(need, picks)):
            continue
        # in place of the last pick that is not the only one of the
        # other kind
        spare = [k for k, i in enumerate(picks)
                 if not (other(i) and sum(map(other, picks)) == 1)]
        if spare:
            picks[spare[-1]] = found
    lens = [plen(i) for i in picks]
    return picks, max(lens, default=0), min(lens, default=0)


def agree(h, params, ref_config, done, args, margin_steps, lower=False):
    """Outside the window: for the sampled finished requests (``sample``)
    the reference's full forward over prompt + answer, at the widths
    served, against what the timed engine produced.  The engine is
    greedy, so for every answer row the token it chose should have a
    float32 reference logit within ``logit_margin`` of the row's
    largest.  bf16 scores flip the last chosen expert of a row whose
    softmax scores nearly tie at the 8th place, and with 12 routed
    layers of top-8 of 64 nearly every row is such a row somewhere, so
    both kinds of row are bounded by a SHARE (the RAG cell's rule,
    ``runners/serve_hybrid_moe.py``).  Held rows (smallest selection
    margin over the layers at least ``tie_margin``): at most
    ``held_over_share_max`` of them over ``logit_margin``, there must be
    ``held_rows_min`` of them, and the near ties' share stays under
    ``tie_share_max``.  ALL answer rows, near ties included: at most
    ``over_margin_share_max`` over it.  The sample must hold a prompt
    of ``long_prompt_positions`` or more and one under
    ``short_prompt_positions``.  ``lower`` asks the reference for the
    precision below the one served (the tests and PERF.md's second
    reading; the run never passes it).  Returns (ok, record)."""
    t0 = time.perf_counter()
    picks, longest, shortest = sample(h, done, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    worst = worst_tie = 0.0
    rows_all = rows_tie = rows_over = held_over = 0
    stds = []
    by_margin = {m: [0, 0.0, 0] for m in margin_steps}
    by_gap = {g: [0, 0] for g in GAP_STEPS}
    for i in picks:
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = seq[:-1]
        rows = np.arange(r.prompt_len - 1, n)
        want = np.full(-(-len(rows) // row_pad) * row_pad, rows[-1])
        want[:len(rows)] = rows
        lg, margin = reference_mellum2.forward(
            params, ref_config, padded, want, name=NAME, lower=lower)
        lg = lg[:len(rows)]
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        tie = margin[rows] < float(args["tie_margin"])
        rows_all += len(rows)
        rows_tie += int(tie.sum())
        over = gap > float(args["logit_margin"])
        rows_over += int(over.sum())
        held_over += int(over[~tie].sum())
        worst = max(worst, float(gap[~tie].max(initial=0.0)))
        worst_tie = max(worst_tie, float(gap[tie].max(initial=0.0)))
        stds.append(float(lg.std()))
        for g, cell in by_gap.items():
            cell[0] += int((gap > g).sum())
            cell[1] += int((gap[~tie] > g).sum())
        for m, cell in by_margin.items():
            keep = margin[rows] >= m
            cell[0] += int(keep.sum())
            cell[1] = max(cell[1], float(gap[keep].max(initial=0.0)))
            cell[2] += int((gap[keep] > float(args["logit_margin"])).sum())
    held = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over = rows_over / max(rows_all, 1)
    held_share = held_over / max(held, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over <= float(args["over_margin_share_max"])
          and longest >= int(args["long_prompt_positions"])
          and shortest < int(args["short_prompt_positions"]))
    record = {"requests_checked": len(picks), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "held_rows": held, "held_rows_min": args["held_rows_min"],
              "held_rows_over_margin": held_over,
              "held_over_share": held_share,
              "held_over_share_max": args["held_over_share_max"],
              "near_tie_rows": rows_tie, "near_tie_share": share,
              "tie_margin": args["tie_margin"],
              "tie_share_max": args["tie_share_max"],
              "rows_over_margin": rows_over, "over_margin_share": over,
              "over_margin_share_max": args["over_margin_share_max"],
              "widest_gap_on_near_tie_rows": worst_tie,
              "longest_checked_prompt": longest,
              "shortest_checked_prompt": shortest,
              "logit_std": float(np.mean(stds)) if stds else None,
              # [rows, widest gap, rows over logit_margin] among the
              # rows whose margin is at least each step: what another
              # tie_margin would have seen
              "by_margin": {str(m): v for m, v in by_margin.items()},
              # [all rows, held rows] whose gap is over each step: what
              # another logit_margin would have seen
              "over_by_gap": {str(g): v for g, v in by_gap.items()},
              "seconds": time.perf_counter() - t0}
    h.log(line="reference", lower=lower, **record)
    return ok, record


def window_view(view, counters):
    """The window's snapshot with what the window layers saved beside
    it: ``window_ctx_share``, the positions they had in sight over what
    the same layers would have read unwindowed, over the untraced part
    of the window (None where the program counts neither)."""
    c = counters.get("untraced") or {}
    seen, full = c.get("attn_window_ctx_tokens"), c.get("attn_ctx_tokens")
    return dict(view, window_ctx_share=seen / full
                if seen is not None and full else None)


def serve_window(h, cfg=None):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else.  Returns what ``run`` and the probe
    (``probe_mellum2_check.py``) read."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    narrowed = cfg is not None
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config)
    import jax.numpy as jnp
    from hetu_tpu.models.moe_decode import init_hybrid_moe_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    hybrid = load_module("runners", "serve_hybrid_moe")
    # the sizes the reference reads: the file's, or the narrowed
    # object's own in the CPU rehearsal
    ref_config = {k: config[k] for k in REFERENCE_KEYS}
    if narrowed:
        ref_config.update(
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, hidden_size=cfg.hidden_size,
            layer_types=list(cfg.layer_types),
            rope_parameters=cfg.rope_parameters,
            sliding_window=cfg.sliding_window,
            num_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok)
    t_start = time.perf_counter()
    params = init_hybrid_moe_params(
        cfg, name=NAME, seed=h.seed, scale=float(args["init_scale"]),
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
          full_pool_bytes=int(eng.kv.full_bytes),
          window_pool_bytes=int(eng.kv.window_bytes),
          window_ring=int(eng.kv.ring))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    load.no_token_yet = hybrid.NoOneWaits()
    marks = latent.Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    keys = latent.COUNTER_KEYS + WINDOW_KEYS
    counters = {part: {k: snap.get(k) for k in keys}
                for part, snap in marks.counters.items()}
    return {"params": params, "ref_config": ref_config, "eng": eng,
            "buckets": buckets, "load": load,
            "view": window_view(view, counters),
            "untraced_until": untraced_until, "stats": stats, "out": out,
            "counters": counters, "margin_steps": MARGIN_STEPS}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    w = serve_window(h, cfg)
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    eng, load, out, counters = w["eng"], w["load"], w["out"], w["counters"]
    view, stats, buckets = w["view"], w["stats"], w["buckets"]
    ok, record = agree(h, w["params"], w["ref_config"], out["done"], args,
                       w["margin_steps"]) \
        if out["done"] else (False, {})
    p95 = lambda xs: loadgen.percentile(xs, 95)             # noqa: E731
    h.log(line="serve", loop=mix["loop"], attempted=out["attempted"],
          failed=out["failed"], ttft_samples=len(out["ttft_ms"]),
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
          # the window's seconds under the profiler (``NoOneWaits``): a
          # trace cut short shows here
          traced_window_s=None if w["untraced_until"] is None
          else h.seconds - w["untraced_until"],
          engine={"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
                  "paged": bool(eng.paged),
                  "window_layers": int(eng.kv.window_layers),
                  "window_ring": int(eng.kv.ring),
                  "window_blocks_recycled":
                      int(eng.kv.window_blocks_recycled),
                  "slots": args["slots"], "pool_blocks": args["pool_blocks"],
                  "prefill_chunk": args["prefill_chunk"],
                  "warmed_buckets": buckets, "window": view},
          counters={part: {k: v for k, v in c.items() if k != "moe_load"}
                    for part, c in counters.items()},
          exact_lengths=out["exact_lengths"], tokens_agree=ok)
    compared = [
        {"name": key, "value": record[key], "limit": float(args[limit]),
         "within": record[key] <= float(args[limit])}
        for key, limit in (("held_over_share", "held_over_share_max"),
                           ("near_tie_share", "tie_share_max"),
                           ("over_margin_share", "over_margin_share_max"))
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
        "memory_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "end_to_end": {"serve_tokens_per_s": out["tokens_per_s"],
                       "ttft_p95_ms": p95(out["ttft_ms"]),
                       "tpot_p95_ms": p95(out["tpot_ms"])},
        "data": {"snapshot": view, "samples": out["untraced"],
                 "counters": counters},
        "notes": {"slots": args["slots"], "buckets": buckets, **record},
        "compared": compared,
    }
