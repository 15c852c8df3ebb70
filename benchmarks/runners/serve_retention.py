"""Runner ``serve_retention``: a decoder of power-retention layers alone
(the ``brumby`` family: no attention, no K/V page, a gated degree-2 state
a K/V head a slot a layer) served by ``ServingEngine`` on its normal
path.

The configuration file holds the source's own ``config.json`` keys; the
program's ``RetentionConfig`` is built from them and carries the block
spec the mixed wave reads.  The weights are made on the device in one
jitted call, the engine is built with NO path argument (its manager then
holds no pool layer: the float32 states are all there is), every
(bucket, ``has_fresh``) program is warmed, and the loop is
``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` around the harness and ``runners/serve_ssm_hybrid.py``'s
``sample``, all loaded by name: there is no copy of them here.

What this runner adds:

* ``long_prompts`` requests of ``long_prompt_tokens`` tokens each, served
  by the TIMED engine (its slots, its two programs) after the warm-up and
  before the ramp, counted as set-up: a state that has taken in twelve
  thousand increments, past the length at which K/V would be the
  smaller form.  They join the comparison's sample whatever it draws.
* the comparison that decides ``correct``, in two parts, both against
  ``reference_brumby``'s float32 ATTENTION form over prompt + answer
  (``sample`` + the long prompts):
  logits, not tokens: the engine is greedy, so every served token's
  float32 reference logit lies within ``logit_margin`` of its row's
  largest;
  the state's own check: the requests still in flight when the window
  closes finish in the drain and nothing is admitted after them, so each
  is the LAST on its slot and the slot keeps ``S`` and ``z`` as that
  request left them.  ``state_requests`` of the sample are such
  requests.  Seeded probe queries ``r`` read the slot's state through
  the program's own ``phi`` (``phi(r)^T S``, ``phi(r) . z``: computed on
  the device before the states are given back) and the reference
  answers the same probes in the attention form (``sum_j a_j v_j``,
  ``sum_j a_j`` over the whole sequence): the widest relative error of
  the numerators over layers and K/V heads stays under ``state_margin``
  and that of the denominators under ``normaliser_margin``.  A token's
  logit moves little when the state is kept in fewer bits; what the
  state reads moves a lot.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_brumby
from benchmarks.run import load_module

NAME = "bru"
REFERENCE_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "rms_norm_eps", "rope_theta")
COUNTER_KEYS = ("ret_slot_steps", "ret_rows", "ret_chunk_pairs",
                "attn_ctx_tokens", "attn_score_pairs", "wave_rows_live",
                "wave_rows_computed", "chunks_deferred", "steps")


class AfterARetirement(set):
    """``Load.no_token_yet`` for this cell.  ``drive`` switches the
    profiler only while that set is EMPTY, asked once an iteration of
    its loop, before the engine's next step.  This one is empty exactly
    at the first look after a step that landed some request's LAST token
    (``Load`` calls ``add`` at a request's submission and ``discard``
    for every token that lands, so the count is kept here, against the
    request's ``max_new_tokens`` in ``rows``).  A request that ends by
    count is always landed in order, and the engine hands it back at
    once without launching (``ServingEngine.step``): at that look NO
    wave is in flight.  So the trace begins, and ends, between waves:
    ``wave_trace.waves`` joins the k-th wave module of a trace to its
    k-th dispatch span, and a 106 ms wave dispatched just before the
    profiler's start would otherwise be the first module of a trace that
    holds the NEXT wave's dispatch first (my chip run, PR 44, call 1: 35
    of 60 waves disagreed and four accepted metrics read nothing; with a
    retirement every half second the trace starts within a second of its
    time).  As ``runners/serve_hybrid_moe.NoOneWaits``, it does not wait
    for first tokens: here some request nearly always waits for one."""

    def __init__(self, rows):
        super().__init__()
        self.rows, self.landed, self.retired = rows, {}, False

    def add(self, request_id):
        self.landed[request_id] = 0

    def discard(self, request_id):
        if request_id in self.landed:
            self.landed[request_id] += 1
            if self.landed[request_id] >= \
                    self.rows[request_id]["request"].max_new_tokens:
                del self.landed[request_id]
                self.retired = True

    def __bool__(self):
        """Not empty, unless a request was retired since the last
        look."""
        retired, self.retired = self.retired, False
        return not retired


def model_config(config, **over):
    try:
        from hetu_tpu.models.retention_decode import RetentionConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no RetentionConfig; "
                         "it cannot run the configuration. Nothing was run.")
    # (keys it does not know pass)
    return RetentionConfig.from_hf(dict(config, **over))


def probe_queries(seed, count, heads, dim):
    """``count`` seeded probe queries a K/V head, each of a real query's
    scale (a per-head RMSNorm's output has mean square 1)."""
    rng = np.random.default_rng([seed % (2 ** 63), 11])
    return rng.normal(size=(count, heads, dim)).astype(np.float32)


def read_states(states, probes):
    """What ``probes`` [M, g, d] read in every slot of the manager's set
    ``states`` (every layer's ``S`` [1, slots, g, D, d], then every
    layer's ``z`` [1, slots, g, D]), through the program's ``phi``:
    (numerators [layers, slots, M, g, d], denominators [layers, slots,
    M, g]) on the host, float32 at precision ``highest``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.retention_decode import sympow2
    layers = len(states) // 2

    @jax.jit
    def read(S, z, r):
        pr = sympow2(r)                                    # [M, g, D]
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        return (jnp.einsum("mgD,sgDd->smgd", pr, S[0].astype(f32),
                           precision=hi),
                jnp.einsum("mgD,sgD->smg", pr, z[0].astype(f32),
                           precision=hi))

    out = [read(states[i], states[layers + i], jnp.asarray(probes))
           for i in range(layers)]
    return (np.stack([np.asarray(o[0]) for o in out]),
            np.stack([np.asarray(o[1]) for o in out]))


def state_error(served, want):
    """``served`` (numerators [layers, slots, M, g, d], denominators
    [layers, slots, M, g]: what the probes read in every slot) against
    ``want`` (the reference's [layers, M, g, d] and [layers, M, g] for
    one request): the slot is the one whose layer-0 numerators are
    nearest; returns (the widest relative error of the numerators over
    layers and K/V heads in that slot, that of the denominators, the
    slot)."""
    num, den = (np.asarray(a, np.float64) for a in served)
    wn, wd = (np.asarray(a, np.float64) for a in want)
    near = np.linalg.norm((num[0] - wn[0][None]).reshape(num.shape[1], -1),
                          axis=-1)
    slot = int(np.argmin(near))
    # over the probes (and the columns): [layers, g]
    rel_n = np.linalg.norm(num[:, slot] - wn, axis=(1, 3)) \
        / (np.linalg.norm(wn, axis=(1, 3)) + 1e-30)
    rel_d = np.linalg.norm(den[:, slot] - wd, axis=1) \
        / (np.linalg.norm(wd, axis=1) + 1e-30)
    return float(rel_n.max()), float(rel_d.max()), slot


def agree(h, params, ref_config, done, args, seconds, read=None,
          probes=None, long_done=(), control=None):
    """Outside the window: the two parts of the module's docstring over
    ``sample``'s requests and the long prompts' (``long_done``).
    ``control`` asks the reference for one of
    ``reference_brumby.CONTROLS`` (the probe and the tests; the run never
    passes it): the comparison has to call each not correct.  ``read`` is
    what ``probes`` read in the slots' states (``read_states``), None
    where they could not be kept (the state's check is then not made and
    the run not correct).  Returns (ok, record)."""
    sample = load_module("runners", "serve_ssm_hybrid").sample
    picks, drained, longest = sample(h, done, seconds, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    checked = [(done[i]["result"], i in drained) for i in picks] \
        + [(r, False) for r in long_done]
    worst = gap_sum = 0.0
    rows_all = rows_over = 0
    state_worst = norm_worst = 0.0
    state_checked = 0
    longest_tokens = 0
    stats = {}
    for at, (r, last_on_slot) in enumerate(checked):
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = seq[:-1]
        rows = np.arange(r.prompt_len - 1, n)
        want = np.full(-(-len(rows) // row_pad) * row_pad, rows[-1])
        want[:len(rows)] = rows
        ask = probes if last_on_slot and read is not None else None
        lg, answered = reference_brumby.forward(
            params, ref_config, padded, want, n=n, name=NAME,
            control=control, probes=ask, stats=stats if at == 0 else None)
        lg = lg[:len(rows)]
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        rows_all += len(rows)
        rows_over += int((gap > float(args["logit_margin"])).sum())
        gap_sum += float(gap.sum())
        worst = max(worst, float(gap.max(initial=0.0)))
        longest_tokens = max(longest_tokens, int(r.prompt_len))
        if answered is not None:
            rel_n, rel_d, _ = state_error(read, answered)
            state_worst = max(state_worst, rel_n)
            norm_worst = max(norm_worst, rel_d)
            state_checked += 1
    ok = (worst <= float(args["logit_margin"])
          and state_worst <= float(args["state_margin"])
          and norm_worst <= float(args["normaliser_margin"])
          and state_checked >= int(args["state_requests"])
          and longest_tokens >= int(args["long_prompt_tokens"]))
    record = {"requests_checked": len(checked), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "rows_over_margin": rows_over,
              "mean_logit_gap": gap_sum / max(rows_all, 1),
              "state_requests_checked": state_checked,
              "state_requests": args["state_requests"],
              "widest_state_error": state_worst,
              "state_margin": args["state_margin"],
              "widest_normaliser_error": norm_worst,
              "normaliser_margin": args["normaliser_margin"],
              "longest_checked_prompt_tokens": longest_tokens,
              "longest_sampled_prompt_chunks": longest,
              # of the first request checked, a layer: the RMS of the
              # residual and of each branch's contribution to it; and the
              # logits' standard deviation
              "rms": stats.get("layers"), "logit_std": stats.get("logits")}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_long(eng, seed, vocab, args):
    """The long prompts through the timed engine, to their end; returns
    their Results."""
    from hetu_tpu.serving import Request
    reqs = [Request(loadgen.prompt_tokens(seed, 10 ** 6 + i,
                                          int(args["long_prompt_tokens"]),
                                          vocab),
                    int(args["long_prompt_answer"]), request_id=f"long{i}")
            for i in range(int(args["long_prompts"]))]
    out = eng.run(reqs)
    return [out[r.request_id] for r in reqs]


def serve_window(h, cfg=None, params=None, **over):
    """Build, warm, the long prompts, ramp, window, drain: everything but
    the comparison.  ``cfg`` narrows the model for the CPU rehearsal in
    the tests and nothing else; ``over`` lays keys over the configuration
    (the probe's ``state_dtype="bfloat16"`` control) and ``params`` hands
    in weights already made.  Returns what ``run`` and the probe
    (``probe_brumby_check.py``) read; the states are given back to the
    device before it returns (what the probes read in them is kept), so
    that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    narrowed = cfg is not None
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config, **over)
    import jax.numpy as jnp
    from hetu_tpu.models.retention_decode import init_retention_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    ref_config = {k: config[k] for k in REFERENCE_KEYS}
    if narrowed:
        ref_config.update(
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, hidden_size=cfg.hidden_size)
    t_start = time.perf_counter()
    if params is None:
        params = init_retention_params(
            cfg, name=NAME, seed=h.seed, gains=args["init_gain"],
            dtype=jnp.dtype(config["dtype"]),
            memory_range=args["init_memory_range"])
    eng = ServingEngine(params, cfg, slots=args["slots"],
                        queue_limit=args["queue_limit"],
                        max_seq_len=args["max_seq_len"],
                        prefill_chunk=args["prefill_chunk"])
    buckets = serve.chunk_buckets(mix, args["prefill_chunk"])
    t_built = time.perf_counter()
    serve.warm_up(eng, buckets, cfg.vocab_size)
    t_warm = time.perf_counter()
    long_done = serve_long(eng, h.seed, cfg.vocab_size, args)
    h.log(line="setup", build_s=t_built - t_start, warmup_s=t_warm - t_built,
          long_prompts_s=time.perf_counter() - t_warm,
          weight_bytes=int(sum(v.nbytes for v in params.values())),
          pool_bytes=int(eng.kv.cache_bytes),
          state_bytes=int(eng.kv.state_bytes), slots=int(eng.kv.n_slots),
          state_dtypes=sorted({str(s.dtype) for s in eng.kv.states}))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    load.no_token_yet = AfterARetirement(load.rows)
    marks = latent.Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    counters = {part: {k: snap.get(k) for k in COUNTER_KEYS}
                for part, snap in marks.counters.items()}
    engine = {"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
              "paged": bool(eng.paged), "stateful": bool(eng.kv.stateful),
              "pool": eng.kv.cache_k is not None,
              "state_resets": eng.kv.state_resets,
              "slots": int(eng.kv.n_slots),
              "prefill_chunk": args["prefill_chunk"],
              "warmed_buckets": buckets, "window": view,
              "drained": not eng.pending}
    # what the probes read in the states as the drain left them; then the
    # states' device memory goes to the reference
    probes = probe_queries(h.seed, int(args["state_probes"]),
                           cfg.num_key_value_heads, cfg.head_dim)
    read = read_states(eng.kv.states, probes) if not eng.pending else None
    for buffer in eng.kv.states:
        buffer.delete()
    return {"params": params, "ref_config": ref_config, "engine": engine,
            "buckets": buckets, "load": load, "view": view, "stats": stats,
            "untraced_until": untraced_until, "out": out, "read": read,
            "probes": probes, "long_done": long_done, "counters": counters}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    w = serve_window(h, cfg)
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    load, out, counters = w["load"], w["out"], w["counters"]
    ok, record = agree(h, w["params"], w["ref_config"], out["done"], args,
                       h.seconds, read=w["read"], probes=w["probes"],
                       long_done=w["long_done"]) \
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
          engine=w["engine"], counters=counters,
          exact_lengths=out["exact_lengths"], tokens_agree=ok)
    compared = [
        {"name": key, "value": record[key], "limit": float(args[limit]),
         "within": record[key] <= float(args[limit])}
        for key, limit in (("widest_logit_gap", "logit_margin"),
                           ("widest_state_error", "state_margin"),
                           ("widest_normaliser_error", "normaliser_margin"))
        if key in record]
    compared += [
        {"name": key, "value": record[key], "limit": int(args[limit]),
         "within": record[key] >= int(args[limit])}
        for key, limit in (("state_requests_checked", "state_requests"),
                           ("longest_checked_prompt_tokens",
                            "long_prompt_tokens"))
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
        "notes": {"slots": w["engine"]["slots"], "buckets": w["buckets"],
                  "finished_in_window": finished,
                  **{k: v for k, v in record.items() if k != "rms"}},
        "compared": compared,
    }
