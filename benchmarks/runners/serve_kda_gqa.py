"""Runner ``serve_kda_gqa``: a decoder whose layers are the gated delta
rule as published (KDA: a decay with no lower bound, low-rank projections,
beta in (0, 2)) and, where ``gqa_layers`` says, gated position-free
grouped-query attention, in ONE block (the ``solar_open2`` family), every
layer's FFN holding this chip's SHARE of the experts behind a plain top-k
sigmoid router and the head this chip's rows of the vocabulary, served by
``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys, cut
to one chip's share of a deployment (``deployment``: which experts and
which vocabulary rows are held); the program's ``KDAGQAConfig`` is built
from them with the ROUTER's width and the vocabulary as published and
carries the block spec the mixed wave reads.  The weights are made on the
device in one jitted call, the engine is built with NO path argument
(fast path, mixed ragged wave, paged block 16 on the TPU; the K/V pool of
the GQA layers AND the slot states of the KDA layers live in the engine's
ONE manager), every (bucket, ``has_fresh``) program is warmed, and the
loop is ``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` around the harness, ``runners/serve_kda_latent.py``'s
``probe_queries``, ``read_states`` and ``state_error``, and ``runners/serve_retention.py``'s
``AfterARetirement``, all loaded by name: there is no copy of them here.

What this runner adds is the comparison that decides ``correct``, in two
parts, both against ``reference_solar_open2``'s float32 forward over
prompt + answer of a seeded sample of finished requests, given the same
held experts and the same held vocabulary rows:

* logits, not tokens, by the routed cells' rule: the engine is greedy, so
  a served token's float32 reference logit should lie within
  ``logit_margin`` of its row's largest.  bf16 scores flip the last chosen
  expert of a row whose 320 scores nearly tie there, so such rows are
  counted APART: a row is HELD when its smallest routing margin over the
  four layers is at least ``tie_margin``; of the held rows at most
  ``held_over_share_max`` lie over the margin, there are at least
  ``held_rows_min`` of them, the near ties' share stays under
  ``tie_share_max``; of ALL answer rows at most ``over_margin_share_max``
  lie over it; the sample holds a prompt of ``long_prompt_chunks`` chunks
  or more and one under ``short_prompt_tokens``, every answer its exact
  length;
* the state's own check (a state kept in fewer bits moves no logit): the
  requests still in flight when the window closes finish in the drain and
  nothing is admitted after them, so each is the LAST on its slot and the
  slot keeps ``S`` as that request left it.  EVERY checked request is
  such a request where the drain left enough (``sample``), at least
  ``state_requests`` of them.  Seeded probe queries ``r`` read every
  slot's state (``S^T r``, computed on the device before the states are
  given back) and the reference answers the same probes from its own
  step-by-step state; a request's reading is the relative error ``|got -
  ref| / |ref|`` a head, its MEAN over the ``state_slow_heads`` heads
  that decay slowest (the smallest ``exp(A_log)``: where a rounding at
  every write adds up longest) of the FIRST ``state_lead_layers`` KDA
  layers (those with the fewest routed layers before them).  EVERY layer of this model routes, so no KDA layer's
  input is untouched by a bf16 router's flipped experts: a flip that
  involves a held expert in a request's last few dozen rows doubles THAT
  request's reading (one request in four), while a state kept in fewer
  bits raises EVERY request's.  So the SMALLEST of the requests' readings
  stays under ``state_margin`` (what is systematic), and the WIDEST head
  over all KDA layers and requests under ``deep_state_margin`` (what is
  gross: an index into the state set, a layer's order).
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks import loadgen, reference_solar_open2
from benchmarks.run import load_module

NAME = "slr"
COUNTER_KEYS = ("moe_assignments", "moe_assignments_routed",
                "moe_experts_touched", "moe_kernel_waves", "moe_load",
                "moe_load_imbalance", "attn_ctx_tokens", "attn_score_pairs",
                "kda_slot_steps", "kda_chunk_rows", "wave_rows_live",
                "wave_rows_computed", "chunks_deferred", "steps")
# the keys the file states for its own use: the rest is the source's
OWN_KEYS = ("source", "published", "reduced", "reduced_why", "deployment",
            "assumed", "not_served", "runner", "dtype", "runner_args",
            "memory_analysis")
GAP_STEPS = (0.03, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.5, 1.0)


def published_source(config):
    """(the source's keys as the program takes them: the router's width
    and the vocabulary as published, the depth as served; the experts
    held; the vocabulary rows held)."""
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


def model_config(config, **over):
    try:
        from hetu_tpu.models.kda_gqa import KDAGQAConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no KDAGQAConfig "
                         "(the published delta rule beside gated "
                         "grouped-query attention in one block); it cannot "
                         "run the configuration. Nothing was run.")
    source, held, rows = published_source(config)
    return KDAGQAConfig.from_hf(source, held_experts=held,
                                vocab_rows=rows, **over)


@functools.lru_cache(maxsize=None)
def kda_latent():
    """``runners/serve_kda_latent.py``: ``probe_queries``,
    ``read_states`` and ``state_error`` are its own."""
    return load_module("runners", "serve_kda_latent")


def sample(h, done, seconds, args):
    """The finished requests the reference is run over: ``check_requests``
    of them in the seed's order, those that finished in the DRAIN first
    (each the last on its slot, so that its state can be read: a run
    leaves 48), with a prompt of ``long_prompt_chunks`` chunks or more and
    one under ``short_prompt_tokens`` among them: where the choice holds
    none, the first such in the seed's order (a drained one first) takes
    the last place, or the one before it.  Returns (picks, which of them
    finished in the drain, the longest picked prompt in chunks, the
    shortest in tokens)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    order = [int(i) for i in rng.permutation(len(done))]
    drained = [i for i in order if done[i].get("done") is not None
               and done[i]["done"] > seconds]
    order = drained + [i for i in order if i not in drained]
    picks = order[:int(args["check_requests"])]
    length = lambda i: int(done[i]["result"].prompt_len)    # noqa: E731
    long = int(args["long_prompt_chunks"]) * int(args["prefill_chunk"]) \
        - int(args["prefill_chunk"]) + 1
    short = int(args["short_prompt_tokens"])
    for place, fits in ((-1, lambda n: n >= long), (-2, lambda n: n < short)):
        if len(picks) >= -place and not any(fits(length(i)) for i in picks):
            other = next((i for i in order
                          if fits(length(i)) and i not in picks), None)
            if other is not None:
                picks[place] = other
    chunk = int(args["prefill_chunk"])
    return picks, set(drained) & set(picks), max(
        (-(-length(i) // chunk) for i in picks), default=0), min(
        (length(i) for i in picks), default=0)


def agree(h, params, ref_config, held, done, args, seconds, read=None,
          probes=None, control=None):
    """Outside the window: the two parts of the module's docstring over
    ``sample``'s requests.  ``control`` asks the reference for one of
    ``reference_solar_open2.CONTROLS`` (the probe and the tests; the run
    never passes it): the comparison has to call each not correct.
    ``read`` is what ``probes`` read in the slots' states
    (``read_states``), None where they could not be kept (the state's
    check is then not made and the run not correct).  Returns (ok,
    record)."""
    t0 = time.perf_counter()
    picks, drained, longest, shortest = sample(h, done, seconds, args)
    margin_of = float(args["logit_margin"])
    worst = worst_tie = gap_sum = state_worst = lead_worst = 0.0
    lead_mean = []
    by_layer = []
    # the first KDA layers: the fewest routed layers before them; of
    # their heads the ``state_slow_heads`` that decay slowest (the
    # smallest ``exp(A_log)``): a state that remembers longest is the one
    # in which a rounding at every write adds up
    lead = int(args["state_lead_layers"])
    kda = [i for i in range(ref_config["num_hidden_layers"])
           if reference_solar_open2.is_kda(ref_config, i)]
    rates = [np.exp(np.asarray(params[f"{NAME}_h{i}_kda_A_log"], np.float64))
             for i in kda[:lead]]
    slow = [np.argsort(a, kind="stable")[:int(args["state_slow_heads"])]
            for a in rates]
    by_head = []
    rows_all = rows_tie = rows_over = held_over = state_checked = 0
    by_gap = {g: [0, 0] for g in GAP_STEPS}
    stats = {}
    for at, i in enumerate(picks):
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        rows = np.arange(r.prompt_len - 1, n)
        ask = probes if i in drained and read is not None else None
        lg, margin, answered = reference_solar_open2.forward(
            params, ref_config, seq[:-1], rows, name=NAME, held=held,
            control=control, probes=ask, stats=stats if at == 0 else None)
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
        for g, cell in by_gap.items():
            cell[0] += int((gap > g).sum())
            cell[1] += int((gap[~tie] > g).sum())
        if answered is not None:
            rel, _ = kda_latent().state_error(read, answered)
            state_worst = max(state_worst, float(rel.max()))
            lead_worst = max(lead_worst, float(rel[:lead].max()))
            lead_mean.append(float(np.mean(
                [rel[j, slow[j]].mean() for j in range(lead)])))
            by_head.append([[float(v) for v in rel[j]]
                            for j in range(lead)])
            by_layer.append([[float(v) for v in rel.mean(axis=1)],
                             [float(v) for v in rel.max(axis=1)]])
            state_checked += 1
    # what is systematic raises every request's reading: the smallest
    lead_least = min(lead_mean, default=0.0)
    held_rows = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over_share = rows_over / max(rows_all, 1)
    held_share = held_over / max(held_rows, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held_rows >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over_share <= float(args["over_margin_share_max"])
          and lead_least <= float(args["state_margin"])
          and state_worst <= float(args["deep_state_margin"])
          and state_checked >= int(args["state_requests"])
          and longest >= int(args["long_prompt_chunks"])
          and 0 < shortest < int(args["short_prompt_tokens"]))
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
              "state_requests_checked": state_checked,
              "state_requests": args["state_requests"],
              "leading_state_error": lead_least,
              "leading_state_errors": lead_mean,
              "state_margin": args["state_margin"],
              "widest_state_error": state_worst,
              "deep_state_margin": args["deep_state_margin"],
              # the widest over the leading layers' heads, and a
              # request's [mean, widest] over the heads by layer
              "widest_leading_state_error": lead_worst,
              "state_error_by_layer": by_layer,
              # a request's reading by head of the leading layers, and
              # those layers' decay constants ``exp(A_log)``
              "state_error_by_head": by_head,
              "decay_constants": [[float(v) for v in a] for a in rates],
              "longest_checked_prompt_chunks": longest,
              "shortest_checked_prompt_tokens": shortest,
              # [all rows, held rows] whose gap is over each step: what
              # another logit_margin would have seen
              "over_by_gap": {str(g): v for g, v in by_gap.items()},
              # of the first request checked, a layer: its kind, the RMS
              # of the residual and of the layer's two parts
              "rms": stats.get("layers"), "logit_std": stats.get("logits"),
              "seconds": time.perf_counter() - t0}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_window(h, cfg=None, params=None, **over):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; ``over`` lays keys over the configuration (the probe's
    ``state_dtype="bfloat16"`` control) and ``params`` hands in weights
    already made.  Returns what ``run`` and the probe
    (``probe_solar_open2_check.py``) read; the pool and the states are given
    back to the device before it returns (what the probes read in the
    states is kept), so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config, **over)
    import jax.numpy as jnp
    from hetu_tpu.models.kda_gqa import init_kda_gqa_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    source, held, _ = published_source(config)
    t_start = time.perf_counter()
    if params is None:
        params = init_kda_gqa_params(
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
          state_bytes=int(eng.kv.state_bytes), slots=int(eng.kv.n_slots),
          state_dtypes=sorted({str(s.dtype) for s in eng.kv.states}),
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
              "paged": bool(eng.paged), "stateful": bool(eng.kv.stateful),
              "kv_pool_layers": int(eng.kv.pool_layers),
              "state_resets": eng.kv.state_resets,
              "slots": int(eng.kv.n_slots),
              "pool_blocks": args["pool_blocks"],
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets, "window": view,
              "drained": not eng.pending}
    # what the probes read in the states as the drain left them; then the
    # pool's and the states' device memory goes to the reference
    probes = kda_latent().probe_queries(
        h.seed, int(args["state_probes"]), cfg.num_attention_heads,
        cfg.head_dim)
    read = kda_latent().read_states(eng.kv.states, probes) \
        if not eng.pending else None
    for buffer in (eng.kv.cache_k, eng.kv.cache_v) + tuple(eng.kv.states):
        buffer.delete()
    return {"params": params, "ref_config": source, "held": held,
            "engine": engine, "buckets": buckets, "load": load,
            "view": view, "stats": stats, "untraced_until": untraced_until,
            "out": out, "read": read, "probes": probes,
            "counters": counters}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    return report(h, serve_window(h, cfg))


def report(h, w):
    """The comparison and the result of one served window ``w``."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    load, out, counters = w["load"], w["out"], w["counters"]
    ok, record = agree(h, w["params"], w["ref_config"], w["held"],
                       out["done"], args, h.seconds, read=w["read"],
                       probes=w["probes"]) if out["done"] else (False, {})
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
                           ("near_tie_share", "tie_share_max"),
                           ("leading_state_error", "state_margin"),
                           ("widest_state_error", "deep_state_margin"))
        if key in record]
    compared += [
        {"name": key, "value": record[key], "limit": int(args[limit]),
         "within": record[key] >= int(args[limit])}
        for key, limit in (("held_rows", "held_rows_min"),
                           ("state_requests_checked", "state_requests"),
                           ("longest_checked_prompt_chunks",
                            "long_prompt_chunks"))
        if key in record]
    if "shortest_checked_prompt_tokens" in record:
        compared.append({
            "name": "shortest_checked_prompt_tokens",
            "value": record["shortest_checked_prompt_tokens"],
            "limit": int(args["short_prompt_tokens"]),
            "within": 0 < record["shortest_checked_prompt_tokens"]
            < int(args["short_prompt_tokens"])})
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
        "notes": {"slots": w["engine"]["slots"], "buckets": w["buckets"],
                  "finished_in_window": finished,
                  **{k: v for k, v in record.items()
                     if k not in ("rms", "state_error_by_head",
                                  "decay_constants")}},
        "compared": compared,
    }
