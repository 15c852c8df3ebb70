"""Runner ``serve_nemotron_h``: a decoder whose layers are EACH a Mamba-2
mixer, an attention or a latent routed FFN alone (the ``nemotron_h``
family), the expert layers holding this chip's SHARE of the experts,
served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys, cut
to one chip's share of a deployment (``deployment``: which experts and
which vocabulary rows are held); the program's ``NemotronHConfig`` is
built from them with the ROUTER's width as published and carries the
block spec the mixed wave reads.  The weights are made on the device in
one jitted call, the engine is built with NO path argument (fast path,
mixed ragged wave, paged block 16 on the TPU; the conv tails and the
float32 matrix states live in the engine's own manager beside the pool),
every (bucket, ``has_fresh``) program is warmed, and the loop is
``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` around the harness, ``runners/serve_hybrid_moe.py``'s
``NoOneWaits`` and ``runners/serve_ssm_hybrid.py``'s ``sample`` and
``state_error``, all loaded by name: there is no copy of them here.

What this runner adds is the comparison that decides ``correct``, against
``reference_nemotron_h``'s float32 forward over prompt + answer of a
seeded sample of finished requests, given the same held experts and the
same held vocabulary rows:

* logits, not tokens, by the routed cells' rule (``runners/
  serve_hybrid_moe.py``): the engine is greedy, so a served token's
  float32 reference logit should lie within ``logit_margin`` of its
  row's largest; bf16 scores flip the last chosen expert of a row whose
  ``s + b`` nearly tie at the 22nd place, and with 5 routed layers of 22
  of 512 nearly every row is such a row somewhere, so both kinds of row
  are bounded by a SHARE: of the held rows (smallest selection margin
  over the layers at least ``tie_margin``) at most
  ``held_over_share_max`` over the margin, at least ``held_rows_min`` of
  them, the near ties' share under ``tie_share_max``; of ALL answer
  rows at most ``over_margin_share_max`` over it;
* the state's own check (``runners/serve_ssm_hybrid.py``): the matrix
  states the drain left in the slots of ``state_requests`` sampled
  requests against the reference's.  The FIRST mixer lies before every
  expert layer, so its state differs from the reference's by the
  precision of its own arithmetic alone and is held to ``state_margin``
  (a state kept in bfloat16 fails it); a later mixer's input has been
  through expert layers whose last chosen expert a bfloat16 score may
  flip, so all five together are held to the wider
  ``state_margin_all``;
* a prompt of ``long_prompt_chunks`` chunks or more in the sample (the
  carry crossed that many waves), and every answer its exact length.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_nemotron_h
from benchmarks.run import load_module

NAME = "nmh"
REFERENCE_KEYS = (
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "layer_norm_epsilon", "mamba_num_heads",
    "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
    "n_routed_experts")
COUNTER_KEYS = ("moe_assignments", "moe_assignments_routed",
                "moe_experts_touched", "moe_kernel_waves", "moe_load",
                "moe_load_imbalance", "ssm_slot_steps", "ssm_rows",
                "ssm_chunk_pairs", "attn_ctx_tokens", "attn_score_pairs",
                "wave_rows_live", "wave_rows_computed", "chunks_deferred",
                "steps")
# the reference's selection margin is a gap between two values of
# ``s + b`` (sigmoid scores of order 0.5 and a bias of deviation 0.1)
MARGIN_STEPS = (0.0, 1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3)
# logit gaps the record counts rows over, beside ``logit_margin``'s own
GAP_STEPS = (0.03, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3)


def published_router(config):
    """The source's keys as the program takes them: the router's width
    as published (the file's ``n_routed_experts`` is what this chip
    HOLDS), and which experts those are."""
    first, held = config["deployment"]["experts_held"]
    if held != config["n_routed_experts"]:
        raise SystemExit("benchmark: deployment.experts_held and "
                         "n_routed_experts disagree on the experts held")
    return dict(config, n_routed_experts=config["published"][
        "n_routed_experts"]), (int(first), int(held))


def model_config(config):
    try:
        from hetu_tpu.models.nemotron_h import NemotronHConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no NemotronHConfig "
                         "(one-part layers, an expert layer told which "
                         "experts it holds); it cannot run the "
                         "configuration. Nothing was run.")
    source, held = published_router(config)
    return NemotronHConfig.from_hf(source, held_experts=held)


def agree(h, params, ref_config, held, done, args, seconds, states=None,
          control=None):
    """Outside the window: the parts of the module's docstring over
    ``serve_ssm_hybrid.sample``'s requests.  ``control`` asks the
    reference for one of ``reference_nemotron_h.CONTROLS`` (the probe and
    the tests; the run never passes it): the comparison has to call each
    not correct.  ``states`` is the manager's matrix states on the host,
    None where they could not be kept (the state's check is then not
    made and the run not correct).  Returns (ok, record)."""
    t0 = time.perf_counter()
    ssm = load_module("runners", "serve_ssm_hybrid")
    picks, drained, longest = ssm.sample(h, done, seconds, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    chunk = int(args["prefill_chunk"])
    margin_of = float(args["logit_margin"])
    worst = worst_tie = gap_sum = 0.0
    rows_all = rows_tie = rows_over = held_over = 0
    state_worst = ratio_worst = first_worst = 0.0
    state_checked = 0
    by_margin = {m: [0, 0.0, 0] for m in MARGIN_STEPS}
    by_gap = {g: [0, 0] for g in GAP_STEPS}
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
        lg, ref_states, margin = reference_nemotron_h.forward(
            params, ref_config, padded, want, n=n, name=NAME, held=held,
            control=control, carry_at=chunk,
            stats=stats if at == 0 else None)
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
        for g, cell in by_gap.items():
            cell[0] += int((gap > g).sum())
            cell[1] += int((gap[~tie] > g).sum())
        for m, cell in by_margin.items():
            keep = margin[rows] >= m
            cell[0] += int(keep.sum())
            cell[1] = max(cell[1], float(gap[keep].max(initial=0.0)))
            cell[2] += int((gap[keep] > margin_of).sum())
        if i in drained and states is not None:
            rel, ratio, _ = ssm.state_error(states, ref_states)
            state_worst, ratio_worst = max(state_worst, rel), \
                max(ratio_worst, ratio)
            first_worst = max(first_worst, ssm.state_error(
                states[:1], ref_states[:1])[0])
            state_checked += 1
    held_rows = rows_all - rows_tie
    share = rows_tie / max(rows_all, 1)
    over_share = rows_over / max(rows_all, 1)
    held_share = held_over / max(held_rows, 1)
    ok = (held_share <= float(args["held_over_share_max"])
          and held_rows >= int(args["held_rows_min"])
          and share <= float(args["tie_share_max"])
          and over_share <= float(args["over_margin_share_max"])
          and first_worst <= float(args["state_margin"])
          and state_worst <= float(args["state_margin_all"])
          and state_checked >= int(args["state_requests"])
          and longest >= int(args["long_prompt_chunks"]))
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
              "first_mixer_state_error": first_worst,
              "state_margin": args["state_margin"],
              "widest_state_error": state_worst,
              "state_margin_all": args["state_margin_all"],
              "widest_state_norm_error": ratio_worst,
              "longest_checked_prompt_chunks": longest,
              # [rows, widest gap, rows over logit_margin] among the
              # rows whose margin is at least each step: what another
              # tie_margin would have seen
              "by_margin": {str(m): v for m, v in by_margin.items()},
              # [all rows, held rows] whose gap is over each step: what
              # another logit_margin would have seen
              "over_by_gap": {str(g): v for g, v in by_gap.items()},
              # of the first request checked, a layer: its letter, the
              # RMS of the residual and of the layer's part
              "rms": stats.get("layers"), "logit_std": stats.get("logits"),
              "seconds": time.perf_counter() - t0}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_window(h, cfg=None):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else.  Returns what ``run`` and the probe
    (``probe_nemotron_h_check.py``) read; the pool and the states are
    given back to the device before it returns (the matrix states are
    kept on the host), so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    narrowed = cfg is not None
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config)
    import jax.numpy as jnp
    from hetu_tpu.models.nemotron_h import init_nemotron_h_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    hybrid = load_module("runners", "serve_hybrid_moe")
    source, held = published_router(config)
    ref_config = {k: source[k] for k in REFERENCE_KEYS}
    if narrowed:
        sp = cfg.ssm
        held = cfg.held_experts
        ref_config.update(
            hybrid_override_pattern=cfg.pattern,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, hidden_size=cfg.hidden_size,
            mamba_num_heads=sp.heads, mamba_head_dim=sp.head_dim,
            ssm_state_size=sp.state, n_groups=sp.groups,
            conv_kernel=sp.conv_kernel,
            num_experts_per_tok=cfg.num_experts_per_tok,
            n_routed_experts=cfg.n_routed_experts)
    t_start = time.perf_counter()
    params = init_nemotron_h_params(
        cfg, name=NAME, seed=h.seed, gains=args["init_gain"],
        dtype=jnp.dtype(config["dtype"]), dt_range=args["init_dt_range"],
        a_range=args["init_a_range"])
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
          state_bytes=int(eng.kv.state_bytes),
          state_dtypes=[str(s.dtype) for s in eng.kv.states],
          experts_held=list(held), router_experts=cfg.n_routed_experts)

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
              "paged": bool(eng.paged), "stateful": bool(eng.kv.stateful),
              "state_resets": eng.kv.state_resets, "slots": args["slots"],
              "pool_blocks": args["pool_blocks"],
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets, "window": view,
              "drained": not eng.pending}
    # the matrix states as the drain left them, on the host; then the
    # pool's and the states' device memory goes to the reference
    # (the set is every mixer's conv tail, then every mixer's matrix
    # state ``[1, slots, H, P, N]``)
    mats = eng.kv.states[len(eng.kv.states) // 2:]
    states = np.concatenate([np.asarray(s) for s in mats]) \
        if not eng.pending else None
    for buffer in (eng.kv.cache_k, eng.kv.cache_v) + tuple(eng.kv.states):
        buffer.delete()
    return {"params": params, "ref_config": ref_config, "held": held,
            "engine": engine, "buckets": buckets, "load": load,
            "view": view, "stats": stats, "untraced_until": untraced_until,
            "out": out, "states": states, "counters": counters}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    w = serve_window(h, cfg)
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    load, out, counters = w["load"], w["out"], w["counters"]
    ok, record = agree(h, w["params"], w["ref_config"], w["held"],
                       out["done"], args, h.seconds, states=w["states"]) \
        if out["done"] else (False, {})
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
                           ("first_mixer_state_error", "state_margin"),
                           ("widest_state_error", "state_margin_all"))
        if key in record]
    compared += [
        {"name": key, "value": record[key], "limit": int(args[limit]),
         "within": record[key] >= int(args[limit])}
        for key, limit in (("held_rows", "held_rows_min"),
                           ("state_requests_checked", "state_requests"),
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
