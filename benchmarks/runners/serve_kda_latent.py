"""Runner ``serve_kda_latent``: a decoder whose layers are the gated delta
rule (KDA) and, one in every ``layer_group_size``, latent attention, in
ONE block (the ``Ling-3.0-flash`` family), the expert layers holding this
chip's SHARE of the experts behind a group-limited router and the head
this chip's rows of the vocabulary, served by ``ServingEngine`` on its
normal path.

The configuration file holds the source's own ``config.json`` keys, cut
to one chip's share of a deployment (``deployment``: which experts and
which vocabulary rows are held); the program's ``KDALatentConfig`` is
built from them with the ROUTER's width and the vocabulary as published
and carries the block spec the mixed wave reads.  The weights are made on
the device in one jitted call, the engine is built with NO path argument
(fast path, mixed ragged wave, paged block 16 on the TPU; the latent pool
of the MLA layers AND the slot states of the KDA layers live in the
engine's ONE manager), every (bucket, ``has_fresh``) program is warmed,
and the loop is ``runners/serve.py``'s own (``drive``, ``Load``,
``reduce_rows``, ``chunk_buckets``, ``warm_up``) with
``runners/serve_latent_moe.py``'s ``Marks`` around the harness,
``runners/serve_ssm_hybrid.py``'s ``sample`` and
``runners/serve_retention.py``'s ``AfterARetirement``, all loaded by name:
there is no copy of them here.

What this runner adds is the comparison that decides ``correct``, in two
parts, both against ``reference_ling3_flash``'s float32 forward over
prompt + answer of a seeded sample of finished requests, given the same
held experts and the same held vocabulary rows:

* logits, not tokens, by the routed cells' rule: the engine is greedy, so
  a served token's float32 reference logit should lie within
  ``logit_margin`` of its row's largest.  bf16 scores flip the last kept
  group or the last chosen expert of a row whose scores nearly tie there,
  so such rows are counted APART: a row is HELD when its smallest routing
  margin over the routed layers is at least ``tie_margin``; of the held
  rows at most ``held_over_share_max`` lie over the margin, there are at
  least ``held_rows_min`` of them, the near ties' share stays under
  ``tie_share_max``; of ALL answer rows at most ``over_margin_share_max``
  lie over it; the sample holds a prompt of ``long_prompt_chunks`` chunks
  or more and one under ``short_prompt_tokens``, every answer its exact
  length;
* the state's own check (a state kept in fewer bits moves no logit): the
  requests still in flight when the window closes finish in the drain and
  nothing is admitted after them, so each is the LAST on its slot and the
  slot keeps ``S`` as that request left it.  ``state_requests`` of the
  sample are such requests.  Seeded probe queries ``r`` read every slot's
  state (``S^T r``, computed on the device before the states are given
  back) and the reference answers the same probes from its own step-by-
  step state.  The relative error ``|got - ref| / |ref|`` a head, its
  MEAN over the heads of the LEADING KDA layers (those whose input no
  routed FFN has touched: layers ``i <= first_k_dense_replace``), the
  larger of the requests', stays under ``state_margin``: behind a routed
  layer a bf16 router's flipped experts move the mixer's INPUT on a few
  rows in a hundred, which reads as five times the error a state kept in
  bfloat16 adds, and a mean over 96 heads is steadier than their widest;
  the WIDEST over all KDA layers' heads stays under
  ``deep_state_margin``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_ling3_flash
from benchmarks.run import load_module

NAME = "lng"
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
    if held != config["num_experts"] or rows != config["vocab_size"]:
        raise SystemExit("benchmark: deployment and num_experts / "
                         "vocab_size disagree on what is held")
    pub = config["published"]
    source = {k: v for k, v in config.items() if k not in OWN_KEYS}
    source.update(num_experts=pub["num_experts"],
                  vocab_size=pub["vocab_size"],
                  max_position_embeddings=config["max_position_embeddings"])
    return source, (int(first), int(held)), (int(row0), int(rows))


def model_config(config, **over):
    try:
        from hetu_tpu.models.kda_latent import KDALatentConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no KDALatentConfig "
                         "(delta-rule layers beside latent attention in "
                         "one block, a group-limited router); it cannot "
                         "run the configuration. Nothing was run.")
    source, held, rows = published_source(config)
    return KDALatentConfig.from_hf(source, held_experts=held,
                                   vocab_rows=rows, **over)


def sample(h, done, seconds, args):
    """``serve_ssm_hybrid.sample``'s choice (``state_requests`` that
    finished in the drain first, a prompt of ``long_prompt_chunks`` chunks
    or more among them) with a prompt under ``short_prompt_tokens`` too:
    the first such in the seed's order takes the place before the last
    where the choice holds none.  Returns (picks, which of them finished
    in the drain, the longest picked prompt in chunks, the shortest in
    tokens)."""
    picks, drained, longest = load_module(
        "runners", "serve_ssm_hybrid").sample(h, done, seconds, args)
    length = lambda i: int(done[i]["result"].prompt_len)    # noqa: E731
    short = int(args["short_prompt_tokens"])
    if len(picks) >= 2 and min(length(i) for i in picks) >= short:
        rng = np.random.default_rng([h.seed % (2 ** 63), 7])
        order = [int(i) for i in rng.permutation(len(done))]
        under = [i for i in order if length(i) < short and i not in picks]
        at = next((j for j in range(len(picks) - 2, -1, -1)
                   if picks[j] not in drained), None)
        if under and at is not None:
            picks[at] = under[0]
    return picks, drained, longest, min(
        (length(i) for i in picks), default=0)


def probe_queries(seed, count, heads, dim):
    """``count`` seeded probe queries a head, each of a real query's
    scale (L2-normalised)."""
    rng = np.random.default_rng([seed % (2 ** 63), 13])
    r = rng.normal(size=(count, heads, dim)).astype(np.float32)
    return r / np.linalg.norm(r, axis=-1, keepdims=True)


def read_states(states, probes):
    """What ``probes`` [M, H, D] read in every slot of the manager's set
    ``states`` (every KDA layer's conv tails, then every layer's ``S`` [1,
    slots, H, D, D]): ``S^T r`` [layers, slots, M, H, D] on the host,
    float32 at precision ``highest``."""
    import jax
    import jax.numpy as jnp
    layers = len(states) // 2

    @jax.jit
    def read(S, r):
        return jnp.einsum("mhk,shkv->smhv", r, S[0].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    return np.stack([np.asarray(read(states[layers + i],
                                     jnp.asarray(probes)))
                     for i in range(layers)])


def state_error(served, want):
    """``served`` [layers, slots, M, H, D] (what the probes read in every
    slot) against ``want`` [layers, M, H, D] (the reference's for one
    request): the slot is the one whose layer-0 reading is nearest;
    returns (the relative errors ``|got - ref| / |ref|`` over the probes
    and the columns in that slot, [layers, H]; the slot)."""
    got, ref = np.asarray(served, np.float64), np.asarray(want, np.float64)
    near = np.linalg.norm((got[0] - ref[0][None]).reshape(got.shape[1], -1),
                          axis=-1)
    slot = int(np.argmin(near))
    rel = np.linalg.norm(got[:, slot] - ref, axis=(1, 3)) \
        / (np.linalg.norm(ref, axis=(1, 3)) + 1e-30)
    return rel, slot


def agree(h, params, ref_config, held, done, args, seconds, read=None,
          probes=None, control=None):
    """Outside the window: the two parts of the module's docstring over
    ``sample``'s requests.  ``control`` asks the reference for one of
    ``reference_ling3_flash.CONTROLS`` (the probe and the tests; the run
    never passes it): the comparison has to call each not correct.
    ``read`` is what ``probes`` read in the slots' states
    (``read_states``), None where they could not be kept (the state's
    check is then not made and the run not correct).  Returns (ok,
    record)."""
    t0 = time.perf_counter()
    picks, drained, longest, shortest = sample(h, done, seconds, args)
    margin_of = float(args["logit_margin"])
    worst = worst_tie = gap_sum = state_worst = lead_worst = lead_mean = 0.0
    by_layer = []
    # the KDA layers whose input no routed FFN has touched
    lead = sum(1 for i in range(ref_config["num_hidden_layers"])
               if reference_ling3_flash.is_kda(ref_config, i)
               and i <= ref_config.get("first_k_dense_replace", 0))
    rows_all = rows_tie = rows_over = held_over = state_checked = 0
    by_gap = {g: [0, 0] for g in GAP_STEPS}
    stats = {}
    for at, i in enumerate(picks):
        r = done[i]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        rows = np.arange(r.prompt_len - 1, n)
        ask = probes if i in drained and read is not None else None
        lg, margin, answered = reference_ling3_flash.forward(
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
            rel, _ = state_error(read, answered)
            state_worst = max(state_worst, float(rel.max()))
            lead_worst = max(lead_worst, float(rel[:lead].max()))
            lead_mean = max(lead_mean, float(rel[:lead].mean()))
            by_layer.append([float(v) for v in rel.max(axis=1)])
            state_checked += 1
    held_rows = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over_share = rows_over / max(rows_all, 1)
    held_share = held_over / max(held_rows, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held_rows >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over_share <= float(args["over_margin_share_max"])
          and lead_mean <= float(args["state_margin"])
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
              "leading_state_error": lead_mean,
              "state_margin": args["state_margin"],
              "widest_state_error": state_worst,
              "deep_state_margin": args["deep_state_margin"],
              # the widest over the leading layers' heads, and a
              # request's widest by layer
              "widest_leading_state_error": lead_worst,
              "state_error_by_layer": by_layer,
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
    (``probe_ling3_check.py``) read; the pool and the states are given
    back to the device before it returns (what the probes read in the
    states is kept), so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config, **over)
    import jax.numpy as jnp
    from hetu_tpu.models.kda_latent import init_kda_latent_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    source, held, _ = published_source(config)
    t_start = time.perf_counter()
    if params is None:
        params = init_kda_latent_params(
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
              "latent_pool": bool(eng.kv.latent),
              "state_resets": eng.kv.state_resets,
              "slots": int(eng.kv.n_slots),
              "pool_blocks": args["pool_blocks"],
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets, "window": view,
              "drained": not eng.pending}
    # what the probes read in the states as the drain left them; then the
    # pool's and the states' device memory goes to the reference
    probes = probe_queries(h.seed, int(args["state_probes"]),
                           cfg.num_attention_heads, cfg.head_dim)
    read = read_states(eng.kv.states, probes) if not eng.pending else None
    for buffer in (eng.kv.cache_k,) + tuple(eng.kv.states):
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
                  **{k: v for k, v in record.items() if k != "rms"}},
        "compared": compared,
    }
