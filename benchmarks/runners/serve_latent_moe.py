"""Runner ``serve_latent_moe``: a latent-attention (MLA), routed-FFN
decoder served by ``ServingEngine`` on its normal path.

The configuration file holds the source's own ``config.json`` keys; the
program's ``LatentMoEConfig`` is built from them and carries the block
spec the mixed wave reads.  The weights are made on the device in one
jitted call, the engine is built with NO path argument (fast path, mixed
ragged wave, paged block 16 on the TPU), every (bucket, ``has_fresh``)
program is warmed, and the loop is ``runners/serve.py``'s own (``drive``,
``Load``, ``reduce_rows``, ``chunk_buckets``, ``warm_up``), loaded by
name: there is no copy of it here.

What this runner adds is what the new metrics need: the engine's routed
and attention counters over exactly the untraced and the traced part of
the window (``Marks`` takes ``metrics.mark()`` where ``drive`` opens the
window and switches the profiler), and the comparison that decides
``correct``: ``reference_glm47flash``'s full forward over prompt +
answer against what the timed engine produced, logits not tokens, with
the rows whose routing nearly tied counted apart (see ``agree``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, reference_glm47flash
from benchmarks.run import load_module

NAME = "glm"


def model_config(config):
    try:
        from hetu_tpu.models.moe_decode import LatentMoEConfig
    except ImportError:
        # the parent of the PR that brought the configuration: fail at
        # once and cleanly, before anything is built
        raise SystemExit("benchmark: this program has no LatentMoEConfig; "
                         "it cannot run the configuration. Nothing was run.")
    return LatentMoEConfig.from_hf(config)   # keys it does not know pass


def reference_config(cfg):
    """The sizes the reference reads, from the config OBJECT, so that the
    CPU rehearsal's narrow model is compared at its own sizes."""
    keys = ("num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "rms_norm_eps", "rope_theta", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_k_dense_replace",
            "n_shared_experts", "tie_word_embeddings")
    return {k: getattr(cfg, k) for k in keys}


class Marks:
    """The harness as ``drive`` sees it, plus the engine's counters over
    the two parts of the window: ``untraced`` (from the window's opening
    to the profiler's start, or to its close in an untraced run) and
    ``traced`` (from the profiler's start to the window's close: the
    seconds ``xplane.window_of`` bounds by the first and last ``bench.*``
    span, since ``mute_spans`` ends them)."""

    def __init__(self, h, metrics):
        self._h, self._metrics = h, metrics
        self._open = self._trace = None
        self.counters = {}

    def __getattr__(self, name):
        return getattr(self._h, name)

    def open_window(self):
        self._open = self._metrics.mark()
        return self._h.open_window()

    def trace_start(self):
        if self._h.trace and not self._h.tracing:
            self.counters["untraced"] = self._metrics.snapshot(
                since=self._open)
            self._trace = self._metrics.mark()
        self._h.trace_start()

    def mute_spans(self):
        if self._trace is not None:
            self.counters.setdefault(
                "traced", self._metrics.snapshot(since=self._trace))
        else:
            self.counters.setdefault(
                "untraced", self._metrics.snapshot(since=self._open))
        self._h.mute_spans()


MARGIN_STEPS = (0.0, 0.002, 0.005, 0.0075, 0.01, 0.0125, 0.015, 0.02)
COUNTER_KEYS = ("moe_assignments", "moe_experts_touched", "moe_load",
                "moe_load_imbalance", "attn_ctx_tokens", "attn_score_pairs",
                "steps")


def agree(h, params, ref_config, done, args, lower=False):
    """Outside the window: for a seeded sample of finished requests the
    reference's full forward over prompt + answer, at the widths served,
    against what the timed engine produced through chunked prefill and
    decode over the latent cache.  The engine is greedy, so for every
    answer row the token it chose must have a float32 reference logit
    within ``logit_margin`` of the row's largest.

    Routing ties: bf16 activations against the float32 reference flip
    the last chosen and the first not chosen expert of a row whose
    ``s + b`` values nearly tie, and the result jumps.  The reference
    reports each row's smallest selection margin over the routed layers;
    rows under ``tie_margin`` are counted apart, their share must stay
    under ``tie_share_max``, and the logit bound holds on all the others.
    ``lower`` asks the reference for the precision below the one served
    (the tests and PERF.md's second reading; the run never passes it).
    Returns (ok, record)."""
    rng = np.random.default_rng([h.seed % (2 ** 63), 7])
    picks = rng.choice(len(done), min(int(args["check_requests"]), len(done)),
                       replace=False)
    pad_to, row_pad = int(args["reference_pad"]), int(args["reference_rows"])
    worst = worst_tie = 0.0
    rows_all = rows_tie = 0
    stds = []
    by_margin = {m: [0, 0.0, 0] for m in MARGIN_STEPS}
    for i in picks:
        r = done[int(i)]["result"]
        seq = np.asarray(r.tokens, np.int32)
        n = len(seq) - 1                       # inputs: all but the last
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = seq[:-1]
        rows = np.arange(r.prompt_len - 1, n)
        want = np.full(-(-len(rows) // row_pad) * row_pad, rows[-1])
        want[:len(rows)] = rows
        lg, margin = reference_glm47flash.forward(
            params, ref_config, padded, want, name=NAME, lower=lower)
        lg = lg[:len(rows)]
        gap = lg.max(-1) - lg[np.arange(len(rows)), seq[r.prompt_len:]]
        tie = margin[rows] < float(args["tie_margin"])
        rows_all += len(rows)
        rows_tie += int(tie.sum())
        worst = max(worst, float(gap[~tie].max(initial=0.0)))
        worst_tie = max(worst_tie, float(gap[tie].max(initial=0.0)))
        stds.append(float(lg.std()))
        for m, cell in by_margin.items():
            keep = margin[rows] >= m
            cell[0] += int(keep.sum())
            cell[1] = max(cell[1], float(gap[keep].max(initial=0.0)))
            cell[2] += int((gap[keep] > float(args["logit_margin"])).sum())
    share = rows_tie / max(rows_all, 1)
    ok = (worst <= float(args["logit_margin"])
          and share <= float(args["tie_share_max"]))
    record = {"requests_checked": len(picks), "rows_checked": rows_all,
              "widest_logit_gap": worst, "logit_margin": args["logit_margin"],
              "near_tie_rows": rows_tie, "near_tie_share": share,
              "tie_margin": args["tie_margin"],
              "tie_share_max": args["tie_share_max"],
              "widest_gap_on_near_tie_rows": worst_tie,
              "logit_std": float(np.mean(stds)) if stds else None,
              # [rows, widest gap, rows over logit_margin] among the
              # rows whose margin is at least each step: what another
              # tie_margin would have seen
              "by_margin": {str(m): v for m, v in by_margin.items()}}
    h.log(line="reference", lower=lower, **record)
    return ok, record


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    import jax.numpy as jnp
    from hetu_tpu.models.moe_decode import init_latent_moe_params
    from hetu_tpu.serving import ServingEngine

    serve = load_module("runners", "serve")
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    cfg = cfg or model_config(config)
    t_start = time.perf_counter()
    params = init_latent_moe_params(
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
          pool_bytes=int(eng.kv.cache_bytes))

    load = serve.Load(mix, h.seed, cfg.vocab_size,
                      serve.request_count(mix, h.seconds))
    marks = Marks(h, eng.metrics)
    view, untraced_until, stats = serve.drive(marks, eng, load, mix,
                                              h.seconds)
    out = serve.reduce_rows(load, h.seconds, untraced_until)
    counters = {part: {k: snap.get(k) for k in COUNTER_KEYS}
                for part, snap in marks.counters.items()}
    ok, record = agree(h, params, reference_config(cfg), out["done"], args) \
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
          gen_lag_p95_ms=p95(out["gen_lag_ms"]),
          untraced_until_s=untraced_until,
          engine={"fast_path": bool(eng.fast_path), "ragged": bool(eng.ragged),
                  "paged": bool(eng.paged), "latent": bool(eng.kv.latent),
                  "slots": args["slots"], "pool_blocks": args["pool_blocks"],
                  "prefill_chunk": args["prefill_chunk"],
                  "warmed_buckets": buckets, "window": view},
          counters={part: {k: v for k, v in c.items() if k != "moe_load"}
                    for part, c in counters.items()},
          exact_lengths=out["exact_lengths"], tokens_agree=ok)
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
        "compared": [
            {"name": key, "value": record[key], "limit": float(args[limit]),
             "within": record[key] <= float(args[limit])}
            for key, limit in (("widest_logit_gap", "logit_margin"),
                               ("near_tie_share", "tie_share_max"))
            if key in record] + [
            {"name": "exact_lengths", "value": out["exact_lengths"],
             "limit": True, "within": out["exact_lengths"]}],
    }
