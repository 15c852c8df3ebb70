"""Runner ``serve_ssm_hybrid``: a decoder whose every layer runs a Mamba-2
mixer and grouped-query attention side by side on one norm (the
``falcon_h1`` family) served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys; the
program's ``SSMHybridConfig`` is built from them and carries the block
spec the mixed wave reads.  The weights are made on the device in one
jitted call, the engine is built with NO path argument (fast path, mixed
ragged wave, paged block 16 on the TPU; the conv tails and the float32
matrix states live in the engine's own manager beside the pool), every
(bucket, ``has_fresh``) program is warmed, and the loop is
``runners/serve.py``'s own (``drive``, ``Load``, ``reduce_rows``,
``chunk_buckets``, ``warm_up``) with ``runners/serve_latent_moe.py``'s
``Marks`` around the harness and ``runners/serve_hybrid_moe.py``'s
``NoOneWaits``, all loaded by name: there is no copy of them here.

What this runner adds is the comparison that decides ``correct``, in two
parts, both against ``reference_falcon_h1``'s float32 forward over
prompt + answer of a seeded sample of finished requests (``sample``):

* logits, not tokens: the engine is greedy, so every served token's
  float32 reference logit lies within ``logit_margin`` of its row's
  largest; the sample holds a prompt of ``long_prompt_chunks`` chunks or
  more (the carry crossed that many waves before the first token);
* the state's own check: the requests still in flight when the window
  closes finish in the drain and nothing is admitted after them, so each
  is the LAST on its slot and the slot keeps its matrix states as that
  request left them.  ``state_requests`` of the sample are such
  requests; the reference's states after their last consumed token are
  set against the slot that holds them (found by its nearest state,
  layer 0), head by head: the widest relative error
  ``|S_served - S_ref| / |S_ref|`` over layers and heads stays under
  ``state_margin``.  A token's logit moves little when a long-memory
  head's state is kept in fewer bits; the state itself moves a lot.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_falcon_h1
from benchmarks.run import load_module

NAME = "fh1"
REFERENCE_KEYS = (
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_size", "rms_norm_eps", "rope_theta",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "embedding_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
    "lm_head_multiplier")
COUNTER_KEYS = ("ssm_slot_steps", "ssm_rows", "ssm_chunk_pairs",
                "attn_ctx_tokens", "attn_score_pairs", "wave_rows_live",
                "wave_rows_computed", "chunks_deferred", "steps")


def model_config(config):
    try:
        from hetu_tpu.models.ssm_decode import SSMHybridConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no SSMHybridConfig; "
                         "it cannot run the configuration. Nothing was run.")
    return SSMHybridConfig.from_hf(config)   # keys it does not know pass


def sample(h, done, seconds, args):
    """The finished requests the reference is run over: a seeded choice
    of ``check_requests``, of which the first ``state_requests`` finished
    in the DRAIN (each the last on its slot: the state's own check) and
    at least one has a prompt of ``long_prompt_chunks`` chunks or more
    (the long one, if the choice holds none, takes the last pick's
    place: the first in the seed's order).  Returns (picks, which of
    them finished in the drain, the longest picked prompt in chunks)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    order = [int(i) for i in rng.permutation(len(done))]
    drained = [i for i in order
               if done[i].get("done") is not None
               and done[i]["done"] > seconds][:int(args["state_requests"])]
    rest = [i for i in order if i not in drained]
    picks = drained + rest[:max(int(args["check_requests"]) - len(drained),
                                0)]
    chunk = int(args["prefill_chunk"])
    chunks = lambda i: -(-done[i]["result"].prompt_len // chunk)  # noqa: E731
    want = int(args["long_prompt_chunks"])
    if picks and max(chunks(i) for i in picks) < want:
        long = [i for i in order if chunks(i) >= want]
        if long and long[0] not in picks:
            picks[-1] = long[0]
    return picks, set(drained), max((chunks(i) for i in picks), default=0)


def state_error(served, want):
    """``served`` [layers, slots, H, P, N] (the manager's matrix states
    as the drain left them) against ``want`` [layers, H, P, N] (the
    reference's after one request's last consumed token): the slot is
    the one whose layer-0 state is nearest; returns (the widest relative
    error ``|S_served - S_ref| / |S_ref|`` over layers and heads in that
    slot, the widest ``| |S_served| / |S_ref| - 1 |``, the slot)."""
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))        # noqa: E731
    near = np.linalg.norm(flat(served[0] - want[0][None]), axis=(-1, -2))
    slot = int(np.argmin(near))
    got = flat(served[:, slot]).astype(np.float64)
    ref = flat(want).astype(np.float64)
    norm = np.linalg.norm(ref, axis=-1) + 1e-30             # [layers, H]
    rel = np.linalg.norm(got - ref, axis=-1) / norm
    ratio = np.abs(np.linalg.norm(got, axis=-1) / norm - 1.0)
    return float(rel.max()), float(ratio.max()), slot


def agree(h, params, ref_config, done, args, seconds, states=None,
          control=None):
    """Outside the window: the two parts of the module's docstring over
    ``sample``'s requests.  ``control`` asks the reference for one of
    ``reference_falcon_h1.CONTROLS`` (the probe and the tests; the run
    never passes it): the comparison has to call each not correct.
    ``states`` is the manager's matrix states on the host, None where
    they could not be kept (the state's check is then not made and the
    run not correct).  Returns (ok, record)."""
    picks, drained, longest = sample(h, done, seconds, args)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    chunk = int(args["prefill_chunk"])
    worst = gap_sum = 0.0
    rows_all = rows_over = 0
    state_worst = ratio_worst = 0.0
    state_checked = 0
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
        lg, ref_states = reference_falcon_h1.forward(
            params, ref_config, padded, want, n=n, name=NAME,
            control=control, carry_at=chunk,
            stats=stats if at == 0 else None)
        lg = lg[:len(rows)]
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        rows_all += len(rows)
        rows_over += int((gap > float(args["logit_margin"])).sum())
        gap_sum += float(gap.sum())
        worst = max(worst, float(gap.max(initial=0.0)))
        if i in drained and states is not None:
            rel, ratio, _ = state_error(states, ref_states)
            state_worst, ratio_worst = max(state_worst, rel), \
                max(ratio_worst, ratio)
            state_checked += 1
    ok = (worst <= float(args["logit_margin"])
          and state_worst <= float(args["state_margin"])
          and state_checked >= int(args["state_requests"])
          and longest >= int(args["long_prompt_chunks"]))
    record = {"requests_checked": len(picks), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "rows_over_margin": rows_over,
              "mean_logit_gap": gap_sum / max(rows_all, 1),
              "state_requests_checked": state_checked,
              "state_requests": args["state_requests"],
              "widest_state_error": state_worst,
              "state_margin": args["state_margin"],
              "widest_state_norm_error": ratio_worst,
              "longest_checked_prompt_chunks": longest,
              # of the first request checked, a layer: the RMS of the
              # residual, of each branch's contribution to it and of the
              # scores; and the logits' standard deviation
              "rms": stats.get("layers"), "logit_std": stats.get("logits")}
    h.log(line="reference", control=control, **record)
    return ok, record


def serve_window(h, cfg=None):
    """Build, warm, ramp, window, drain: everything but the comparison.
    ``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else.  Returns what ``run`` and the probe
    (``probe_falcon_h1_check.py``) read; the pool and the states are
    given back to the device before it returns (the matrix states are
    kept on the host), so that the reference has their room."""
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    narrowed = cfg is not None
    # first: a program that cannot run the configuration stops here
    cfg = cfg or model_config(config)
    import jax.numpy as jnp
    from hetu_tpu.models.ssm_decode import init_ssm_hybrid_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    latent = load_module("runners", "serve_latent_moe")
    hybrid = load_module("runners", "serve_hybrid_moe")
    ref_config = {k: config[k] for k in REFERENCE_KEYS}
    if narrowed:
        sp = cfg.ssm
        ref_config.update(
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, hidden_size=cfg.hidden_size,
            mamba_n_heads=sp.heads, mamba_d_head=sp.head_dim,
            mamba_d_state=sp.state, mamba_n_groups=sp.groups,
            mamba_d_conv=sp.conv_kernel)
    t_start = time.perf_counter()
    params = init_ssm_hybrid_params(
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
          state_dtypes=[str(s.dtype) for s in eng.kv.states])

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
    # (the set is every layer's conv tail, then every layer's matrix
    # state ``[1, slots, H, P, N]``)
    mats = eng.kv.states[len(eng.kv.states) // 2:]
    states = np.concatenate([np.asarray(s) for s in mats]) \
        if not eng.pending else None
    for buffer in (eng.kv.cache_k, eng.kv.cache_v) + tuple(eng.kv.states):
        buffer.delete()
    return {"params": params, "ref_config": ref_config, "engine": engine,
            "buckets": buckets, "load": load, "view": view, "stats": stats,
            "untraced_until": untraced_until, "out": out, "states": states,
            "counters": counters}


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    w = serve_window(h, cfg)
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    load, out, counters = w["load"], w["out"], w["counters"]
    ok, record = agree(h, w["params"], w["ref_config"], out["done"], args,
                       h.seconds, states=w["states"]) \
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
                           ("widest_state_error", "state_margin"))
        if key in record]
    compared += [
        {"name": key, "value": record[key], "limit": int(args[limit]),
         "within": record[key] >= int(args[limit])}
        for key, limit in (("state_requests_checked", "state_requests"),
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
