"""Runner ``serve_hybrid_moe``: a decoder of gated short convolutions and
grouped-query attention over a routed FFN (the ``lfm2_moe`` family)
served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys; the
program's ``HybridMoEConfig`` is built from them and carries the block
spec the mixed wave reads, the operator of every layer in it.  The
weights are made on the device in one jitted call, the engine is built
with NO path argument (fast path, mixed ragged wave, paged block 16 on
the TPU; the conv state lives in the engine's own manager), every
(bucket, ``has_fresh``) program is warmed, and the loop is
``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` around the harness, both loaded by name: there is no copy of
either here.

What this runner adds: the rows a wave COMPUTES beside the rows that are
live (``assemble_mixed_wave`` pads every slot to the widest q-block, and
only the routed experts skip dead rows), from the engine's own counters;
and the comparison that decides ``correct``: ``reference_lfm2``'s full
forward over prompt + answer against what the timed engine produced
through chunked prefill and decode over the K/V pool and the conv state,
logits not tokens (see ``agree``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_lfm2
from benchmarks.run import load_module

NAME = "lfm"
REFERENCE_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "hidden_size", "layer_types", "norm_eps", "rope_theta", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "num_dense_layers")


class NoOneWaits(set):
    """``Load.no_token_yet`` for this cell: always empty.  ``drive``
    starts the profiler only while that set is empty (a stall mid-prefill
    once tripped an assertion the engine no longer makes: it counts
    ``serve.lifecycle_residue`` and goes on).  Here a prompt takes
    seconds to prefill and a client sends its next the moment its last
    completes, so some request nearly always waits for its first token:
    the trace would start late (1.4 of its 6 s: my chip run, PR 34) or
    never, and a traced run with no trace fails.  Host-clock samples
    are read from before the profiler's start either way."""

    def add(self, _request_id):
        pass


def model_config(config):
    try:
        from hetu_tpu.models.moe_decode import HybridMoEConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no HybridMoEConfig; "
                         "it cannot run the configuration. Nothing was run.")
    return HybridMoEConfig.from_hf(config)   # keys it does not know pass


def sample(h, done, args):
    """The finished requests the reference is run over: a seeded choice
    of ``check_requests``, of which at least one has a prompt of
    ``long_prompt_chunks`` chunks or more (the conv state is then
    carried across that many q-blocks before the first token; a sample
    without one would not check the carry).  The long one, if the
    seeded choice holds none, takes the last pick's place: the first in
    the seed's order.  Returns (picks, the longest picked prompt in
    chunks)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    order = rng.permutation(len(done))
    picks = [int(i) for i in order[:int(args["check_requests"])]]
    chunk = int(args["prefill_chunk"])
    chunks = lambda i: -(-done[i]["result"].prompt_len // chunk)  # noqa: E731
    want = int(args["long_prompt_chunks"])
    if picks and max(chunks(i) for i in picks) < want:
        long = [int(i) for i in order if chunks(int(i)) >= want]
        if long:
            picks[-1] = long[0]
    return picks, max((chunks(i) for i in picks), default=0)


def agree(h, params, ref_config, done, args, margin_steps, lower=False):
    """Outside the window: for the sampled finished requests (``sample``)
    the reference's full forward over prompt + answer, at the widths
    served, against what the timed engine produced.  The engine is
    greedy, so for every answer row the token it chose should have a
    float32 reference logit within ``logit_margin`` of the row's
    largest.  bf16 scores flip the last chosen expert of a row whose
    ``s + b`` nearly tie, and with 12 routed layers nearly every row is
    such a row somewhere, so both kinds of row are bounded by a SHARE.
    Held rows (smallest selection margin over the routed layers at least
    ``tie_margin``): at most ``held_over_share_max`` of them over
    ``logit_margin``, there must be ``held_rows_min`` of them, and the
    near ties' share stays under ``tie_share_max``.  ALL answer rows,
    near ties included: at most ``over_margin_share_max`` over it.  Not
    the widest gap: flips thin out with the margin but do not stop at
    it (3 of 1,306 rows at 0.0075-0.01, none of 832 over it, widest
    0.111), and a largest value over 80 rows a run would fail one sound
    run in twenty-five; float8 operands put a third of the rows of
    EITHER kind over (PERF.md section 6, PR 34).
    ``lower`` asks the reference for the precision below the one served
    (the tests and PERF.md's second reading; the run never passes it).
    Returns (ok, record)."""
    picks, longest = sample(h, done, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    worst = worst_tie = 0.0
    rows_all = rows_tie = rows_over = held_over = 0
    stds = []
    by_margin = {m: [0, 0.0, 0] for m in margin_steps}
    for i in picks:
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = seq[:-1]
        rows = np.arange(r.prompt_len - 1, n)
        want = np.full(-(-len(rows) // row_pad) * row_pad, rows[-1])
        want[:len(rows)] = rows
        lg, margin = reference_lfm2.forward(
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
          and longest >= int(args["long_prompt_chunks"]))
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
              "longest_checked_prompt_chunks": longest,
              "logit_std": float(np.mean(stds)) if stds else None,
              # [rows, widest gap, rows over logit_margin] among the
              # rows whose margin is at least each step: what another
              # tie_margin would have seen
              "by_margin": {str(m): v for m, v in by_margin.items()}}
    h.log(line="reference", lower=lower, **record)
    return ok, record


def serve_window(h, cfg=None):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else.  Returns what ``run`` and the probe
    (``probe_lfm2_check.py``) read."""
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
    # the sizes the reference reads: the file's, or the narrowed
    # object's own in the CPU rehearsal
    ref_config = {k: config[k] for k in REFERENCE_KEYS}
    if narrowed:
        ref_config.update(
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            hidden_size=cfg.hidden_size, layer_types=list(cfg.layer_types),
            num_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            num_dense_layers=cfg.num_dense_layers)
    t_start = time.perf_counter()
    params = init_hybrid_moe_params(
        cfg, name=NAME, seed=h.seed, scale=float(args["init_scale"]),
        bias_scale=float(args["init_bias_scale"]),
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
          state_bytes=int(eng.kv.state.nbytes))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    load.no_token_yet = NoOneWaits()
    marks = latent.Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    keys = latent.COUNTER_KEYS + ("wave_rows_live", "wave_rows_computed")
    counters = {part: {k: snap.get(k) for k in keys}
                for part, snap in marks.counters.items()}
    return {"params": params, "ref_config": ref_config, "eng": eng,
            "buckets": buckets, "load": load, "view": view,
            "untraced_until": untraced_until, "stats": stats, "out": out,
            "counters": counters, "margin_steps": latent.MARGIN_STEPS}


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
          samples_beyond_p95=len(out["ttft_ms"]) // 20,
          ttft_ms={f"p{q}": loadgen.percentile(out["ttft_ms"], q)
                   for q in (50, 80, 90, 95)},
          tpot_ms={f"p{q}": loadgen.percentile(out["tpot_ms"], q)
                   for q in (50, 80, 90, 95)},
          tokens_in_window=load.tokens_in_window,
          tokens_per_s=out["tokens_per_s"],
          requests_issued=load.issued,
          gen_lag_p95_ms=p95(out["gen_lag_ms"]),
          untraced_until_s=w["untraced_until"],
          # the window's seconds under the profiler (``NoOneWaits``): a
          # trace cut short shows here
          traced_window_s=None if w["untraced_until"] is None
          else h.seconds - w["untraced_until"],
          engine={"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
                  "paged": bool(eng.paged), "stateful": bool(eng.kv.stateful),
                  "state_resets": eng.kv.state_resets,
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
                           ("longest_checked_prompt_chunks",
                            "long_prompt_chunks"))
        if key in record]
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
