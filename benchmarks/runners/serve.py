"""Runner ``serve``: a decoder-only LM served by ``ServingEngine``.

The engine is built as ``chip_smoke.py`` builds it (PR 22): weights
handed over, NO path argument, so the TPU defaults apply (fast path,
mixed ragged wave, paged KV block 16).  The configuration file gives the
sizes and the engine's slots, pool and prefill chunk (read from the
compiler's memory analysis, see PERF.md); the traffic file the loop
kind, the length distributions and the rate or the client count.

One thread drives both the load and the engine: arrivals that fell due
during a wave are submitted before the next one, and how late each was
is reported (``gen_lag_ms``).  A request is timed from when it was DUE.

Of the engine the runner uses what a user of it calls and nothing
underscored: ``ServingEngine(...)``, ``submit``, ``step``, ``run``,
``pending``, ``metrics.snapshot()``, a ``Request``'s ``stream_cb`` and a
``Result``'s fields.  What it must know besides (which requests still
wait for their first token) it keeps itself, from the stream callback.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference


def gpt_config(config):
    from hetu_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_hidden_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        seq_len=config["n_positions"], dropout_rate=0.0)


def init_params(cfg, seed, dtype, name="gpt"):
    """GPT-2's initialisation (normal 0.02, LayerNorm 1/0, biases 0),
    made ON THE DEVICE in one jitted call, in the type it is served in."""
    import jax
    import jax.numpy as jnp
    d, f = cfg.hidden_size, cfg.ffn_size
    shapes = {f"{name}_wte_table": (cfg.vocab_size, d),
              f"{name}_wpe": (cfg.max_position_embeddings, d),
              f"{name}_ln_f_scale": (d,), f"{name}_ln_f_bias": (d,)}
    for i in range(cfg.num_hidden_layers):
        us = f"{name}_h{i}"
        for ln in ("ln1", "ln2"):
            shapes[f"{us}_{ln}_scale"] = (d,)
            shapes[f"{us}_{ln}_bias"] = (d,)
        for nm in ("q", "k", "v", "proj"):
            shapes[f"{us}_attn_{nm}_weight"] = (d, d)
            shapes[f"{us}_attn_{nm}_bias"] = (d,)
        shapes[f"{us}_ffn_wi_weight"] = (d, f)
        shapes[f"{us}_ffn_wi_bias"] = (f,)
        shapes[f"{us}_ffn_wo_weight"] = (f, d)
        shapes[f"{us}_ffn_wo_bias"] = (d,)

    def make(key):
        out = {}
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif n.endswith("_bias"):
                out[n] = jnp.zeros(shape, dtype)
            else:
                out[n] = (0.02 * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def chunk_buckets(mix, chunk):
    """The q-block buckets a wave of this traffic can have besides the
    decode wave's 1: the powers of two that a prompt's last chunk, a
    multiple of ``round_to`` of at most ``chunk`` tokens, rounds up to."""
    step = int(mix["prompt_len"].get("round_to", 1))
    top = min(chunk, int(mix["prompt_len"]["hi"]))
    sizes = {1 << (n - 1).bit_length() for n in range(step, top + 1, step)}
    return sorted(sizes)


def warm_up(eng, buckets, vocab):
    """One request per bucket, alone in the engine, two tokens each: a
    chunk wave of that bucket, then a decode wave.  Each prompt starts
    with another token: the engine shares prefixes by default, and a
    prompt that continued the last one would prefill only its tail, in a
    smaller bucket."""
    from hetu_tpu.serving import Request
    for n in buckets:
        eng.submit(Request((np.arange(n) + n) % vocab, 2,
                           request_id=f"warm{n}"))
        eng.run()


def request_count(mix, seconds):
    """How many sizes the mix's fixed set holds.  Open loop: exactly the
    arrivals of ramp + window, so every seed serves the same requests in
    another order.  Closed loop: ``request_pool``, about as many as one
    run completes (a run takes them in the seed's order and starts over
    if it gets through them)."""
    if mix["loop"] == "closed":
        return int(mix["request_pool"])
    return len(loadgen.poisson_arrivals(mix, 0, -float(mix["ramp_seconds"]),
                                        seconds))


class Load:
    """The cell's requests, by index, and what became of each."""

    def __init__(self, mix, seed, vocab, count):
        self.sizes = loadgen.request_sizes(mix, seed, count)
        self.seed, self.vocab = seed, vocab
        self.issued = 0
        self.rows = {}          # request_id -> record
        self.emitted = 0        # tokens landed so far (the stream callback)
        self.tokens_in_window = 0
        self.no_token_yet = set()   # submitted, first token not seen

    def on_token(self, request, _token):
        self.emitted += 1
        self.no_token_yet.discard(request.request_id)

    def next_request(self, due, now):
        from hetu_tpu.serving import Request
        i = self.issued
        self.issued += 1
        p, n = self.sizes[i % len(self.sizes)]
        req = Request(loadgen.prompt_tokens(self.seed, i, p, self.vocab), n,
                      request_id=f"q{i}", stream_cb=self.on_token)
        self.rows[req.request_id] = {"due": due, "submitted": now,
                                     "request": req, "result": None,
                                     "done": None}
        self.no_token_yet.add(req.request_id)
        return req

    def rejected(self, req):
        """Its row keeps no result: a failure."""
        self.no_token_yet.discard(req.request_id)


def window_view(at_open, now):
    """The engine's ``snapshot()`` narrowed to the window as far as its
    public keys allow.  Means weighted by ``steps`` are taken as the
    difference of the two snapshots, so the warm-up's one-slot waves
    stay out of ``mean_batch_occupancy``.  The medians cover the
    engine's life (a median cannot be subtracted): the warm-up adds two
    or three waves a bucket to some hundreds and moves neither."""
    view = {k: now.get(k) for k in ("decode_ms_p50", "prefill_ms_p50")}
    steps = (now.get("steps") or 0) - (at_open.get("steps") or 0)
    view["steps"] = steps
    if steps > 0 and now.get("mean_batch_occupancy") is not None:
        view["mean_batch_occupancy"] = (
            now["mean_batch_occupancy"] * now["steps"]
            - (at_open.get("mean_batch_occupancy") or 0.0)
            * (at_open.get("steps") or 0)) / steps
    return view


def drive(h, eng, load, mix, seconds):
    """Ramp, window, drain.  Time is on the window's clock: 0 where the
    window opens, negative during the ramp.  Returns what the engine
    reported over the UNTRACED part of the window, the window time at
    which the profiler was started (None in an untraced run) and
    the device's memory statistics at the window's close.

    A traced run traces the window's last ``trace_seconds``.  Starting
    the profiler stalls the host for seconds, so whatever is read from
    the host's clock or the engine's counters is read from the part of
    the window before it: the snapshot is taken then, and the runner
    keeps only the requests that had finished by then."""
    import jax
    from hetu_tpu.serving import QueueFull
    ramp = float(mix["ramp_seconds"])
    closed = mix["loop"] == "closed"
    t_ramp = time.perf_counter()
    clock = lambda: time.perf_counter() - t_ramp - ramp    # noqa: E731
    if closed:
        pending_due = [-ramp] * int(mix["clients"])
    else:
        pending_due = loadgen.poisson_arrivals(mix, h.seed, -ramp, seconds)
    pending_due.reverse()                                   # pop() the next
    opened = False
    at_open = view = stats = None
    untraced_until = None
    programs_at_ramp = h.compiles["programs"]
    drain_until = seconds + float(mix["drain_limit_seconds"])

    def submit_due(now):
        while pending_due and pending_due[-1] <= now:
            due = pending_due.pop()
            with h.span("submit"):
                req = load.next_request(due, now)
                try:
                    eng.submit(req)
                except QueueFull:
                    load.rejected(req)

    while True:
        now = clock()
        if not opened and now >= 0:
            if h.compiles["programs"] != programs_at_ramp:
                raise SystemExit("benchmark: the ramp built or loaded a "
                                 "program: the warm-up missed a shape")
            at_open = eng.metrics.snapshot()
            h.open_window()
            opened = True
        if stats is None and now >= seconds:
            stats = jax.devices()[0].memory_stats() or {}
            if view is None:
                view = window_view(at_open, eng.metrics.snapshot())
            h.mute_spans()
        # The profiler is switched only while no request waits for its
        # first token: the engine asserts that a claimed request never
        # waits long outside a wave (PERF.md section 6), and the stall
        # would trip it.  It is stopped after the window, where the
        # stall delays no arrival.
        if h.trace and not load.no_token_yet:
            if stats is not None:
                h.trace_stop()
            elif now >= seconds - float(mix["trace_seconds"]) \
                    and not h.tracing:
                view = window_view(at_open, eng.metrics.snapshot())
                untraced_until = now
                h.trace_start()
        submit_due(now)
        if eng.pending:
            landed = load.emitted
            with h.span("engine_step"):
                done = eng.step()
            t = clock()
            if 0 <= t <= seconds:
                load.tokens_in_window += load.emitted - landed
            for r in done:
                row = load.rows[r.request_id]
                row["result"], row["done"] = r, t
                if closed and t < seconds:
                    pending_due.append(t)      # this client's next request
        elif pending_due and pending_due[-1] < seconds:
            with h.span("wait_for_arrival"):
                time.sleep(max(min(pending_due[-1] - clock(), 0.05), 0))
        elif now < seconds:
            time.sleep(0.001)
        left = pending_due and pending_due[-1] < seconds
        if clock() >= seconds and not left and (
                not eng.pending or clock() > drain_until):
            break
    h.trace_stop()
    h.close_window()
    if view is None:
        view = window_view(at_open or {}, eng.metrics.snapshot())
    return view, untraced_until, stats or {}


def pace_ms(result):
    """A finished request's mean time per output token after the first."""
    return (result.latency_s - result.ttft_s) / (result.n_generated - 1) * 1e3


def reduce_rows(load, seconds, untraced_until=None):
    """End-to-end numbers and samples from the per-request records.  The
    per-layer samples (``untraced``) keep only the requests that had
    finished before the profiler was started."""
    rows = [r for r in load.rows.values() if 0 <= r["due"] < seconds]
    done = [r for r in rows if r["result"] is not None]
    clean = [r for r in done
             if untraced_until is None or r["done"] <= untraced_until]
    ttft = [(r["submitted"] - r["due"] + r["result"].ttft_s) * 1e3
            for r in done]
    tpot = [pace_ms(r["result"]) for r in done
            if r["result"].n_generated > 1]
    return {
        "attempted": len(rows), "failed": len(rows) - len(done),
        "ttft_ms": ttft, "tpot_ms": tpot,
        "gen_lag_ms": [(r["submitted"] - r["due"]) * 1e3 for r in rows],
        "untraced": {
            "tpot_ms": [pace_ms(r["result"]) for r in clean
                        if r["result"].n_generated > 1],
            "gen_lag_ms": [(r["submitted"] - r["due"]) * 1e3 for r in clean],
        },
        "tokens_per_s": load.tokens_in_window / seconds,
        "exact_lengths": all(r["result"].n_generated
                             == r["request"].max_new_tokens for r in done),
        "done": done,
    }


def tokens_agree(h, params, config, done, margin, sample):
    """Outside the window: for a seeded sample of finished requests, the
    plain float32 reference over prompt + answer must give every token
    the engine chose a logit within ``margin`` of its row's largest.
    Returns (ok, the widest gap seen)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    picks = rng.choice(len(done), min(sample, len(done)), replace=False)
    worst = 0.0
    for i in picks:
        r = done[int(i)]["result"]
        seq = np.asarray(r.tokens, np.int32)
        # one padded length, one compiled reference; causal, so the
        # padding changes no row that is read
        padded = np.zeros(config["n_positions"], np.int32)
        padded[:len(seq) - 1] = seq[:-1]
        lg = np.asarray(reference.logits(params, config, padded))
        rows = lg[r.prompt_len - 1:len(seq) - 1]
        chosen = rows[np.arange(len(rows)), seq[r.prompt_len:]]
        worst = max(worst, float((rows.max(-1) - chosen).max()))
    h.log(line="reference", requests_checked=len(picks),
          widest_logit_gap=worst, margin=margin)
    return worst <= margin, worst


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    import jax.numpy as jnp
    from hetu_tpu.serving import ServingEngine

    config, mix = h.config, h.traffic
    args = config["runner_args"]
    cfg = cfg or gpt_config(config)
    t_start = time.perf_counter()
    params = init_params(cfg, h.seed, jnp.dtype(config["dtype"]))
    eng = ServingEngine(params, cfg, slots=args["slots"],
                        queue_limit=args["queue_limit"],
                        pool_blocks=args["pool_blocks"],
                        prefill_chunk=args["prefill_chunk"])
    buckets = chunk_buckets(mix, args["prefill_chunk"])
    t_built = time.perf_counter()
    warm_up(eng, buckets, cfg.vocab_size)
    h.log(line="setup", build_s=t_built - t_start,
          warmup_s=time.perf_counter() - t_built)

    load = Load(mix, h.seed, cfg.vocab_size, request_count(mix, h.seconds))
    view, untraced_until, stats = drive(h, eng, load, mix, h.seconds)
    out = reduce_rows(load, h.seconds, untraced_until)
    ok, worst = tokens_agree(h, params, config, out["done"],
                             float(args["logit_margin"]),
                             int(args["check_requests"]))
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
          gen_lag_p95_ms=p95(out["gen_lag_ms"]),
          untraced_until_s=untraced_until,
          untraced_tpot_samples=len(out["untraced"]["tpot_ms"]),
          engine={"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
                  "paged": bool(eng.paged), "slots": args["slots"],
                  "pool_blocks": args["pool_blocks"],
                  "prefill_chunk": args["prefill_chunk"],
                  "warmed_buckets": buckets, "window": view},
          exact_lengths=out["exact_lengths"], tokens_agree=ok)
    return {
        "correct": ok and out["exact_lengths"] and bool(out["done"]),
        "attempted": out["attempted"], "failed": out["failed"],
        "memory_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "end_to_end": {"serve_tokens_per_s": out["tokens_per_s"],
                       "ttft_p95_ms": p95(out["ttft_ms"]),
                       "tpot_p95_ms": p95(out["tpot_ms"])},
        # what the per-layer readers see: host-clock samples and engine
        # counters of the untraced part of the window only
        "data": {"snapshot": view, "samples": out["untraced"]},
        "notes": {"slots": args["slots"], "buckets": buckets,
                  "widest_logit_gap": worst},
        "compared": [
            {"name": "widest_logit_gap", "value": worst,
             "limit": float(args["logit_margin"]), "within": ok},
            {"name": "exact_lengths", "value": out["exact_lengths"],
             "limit": True, "within": out["exact_lengths"]}],
    }
