"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the published width of GPT-2 small (12 layers, hidden 768, 12 heads,
vocab 50257, 1024 positions; weights random from ``--seed``):

  trainer  ``ht.Executor`` built as examples/nlp/train_gpt.py builds it
           (bf16 compute, seq 1024, dropout 0 so attention is the Pallas
           flash kernel, AdamW, synthetic next-token task): a few steps,
           loss finite and falling, flash kernel present in the lowered
           step.
  server   the trained ``ex.var_values`` handed to ``ServingEngine`` with
           NO path arguments, so the TPU defaults apply (fast path, mixed
           ragged wave, paged KV block 16): requests with prompts of
           8..900 tokens, greedy tokens equal to ``generate_fast``'s,
           ragged kernel present in the lowered mixed step.

``--chips 4`` runs only the across-chips path and what it is compared
with: the same train step under ``ht.dist`` on a dp2 x tp2 mesh of the
four real devices against the same weights and batches on one of them,
then ``__graft_entry__.dryrun_multichip(4)``.

One process, JAX touched only here, no child needs the chip.  Without a
TPU the script exits non-zero before it trains or serves anything.
Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
A phase that fails raises, and the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

_KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"')

# prompt lengths for the server phase: short to near-full context, two
# beyond 512 tokens; 32 new tokens keep every request inside 1024 positions
PROMPT_LENS = (8, 100, 120, 400, 500, 600, 800, 900)
NEW_TOKENS = 32
# the trainer's batch and step count, on one chip and on four
BATCH = 8
STEPS = 6


def pallas_kernels(lowered_text):
    """Names of the Pallas kernels a lowered program calls on the chip
    (``tpu_custom_call``); an interpreted kernel leaves none."""
    if "tpu_custom_call" not in lowered_text:
        return []
    return sorted(set(_KERNEL_NAME.findall(lowered_text)))


def require_tpu():
    """The device as JAX reports it — or exit, before any work, when it
    is not a TPU whose peaks the repo knows."""
    import jax
    from hetu_tpu.planner.chip_calibration import spec_peak_tflops

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform={d.platform!r} "
            f"({d.device_kind}). Nothing was run.")
    spec_peak_tflops(d.device_kind)     # unknown kind raises
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def gpt2_small(batch_size, **kw):
    """GPT-2 small at its published width; ``kw`` narrows it for the CPU
    rehearsal (tests/test_chip_smoke.py) and nothing else."""
    from hetu_tpu.models import GPTConfig
    kw.setdefault("seq_len", 1024)
    kw.setdefault("max_position_embeddings", kw["seq_len"])
    return GPTConfig.small(batch_size=batch_size, dropout_rate=0.0, **kw)


def build_trainer(cfg, name="gpt", **executor_kw):
    """The train subgraph exactly as examples/nlp/train_gpt.py builds it."""
    import hetu_tpu as ht
    from hetu_tpu.models import GPTForCausalLM

    model = GPTForCausalLM(cfg, name=name)
    ids = ht.placeholder_op(f"{name}_input_ids")
    labels = ht.placeholder_op(f"{name}_labels")
    loss, _logits = model(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4, weight_decay=0.01)
    opt.clip_grad_norm = 1.0
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     mixed_precision="bf16", **executor_kw)
    return ex, ids, labels


# ids the synthetic task draws from.  The model keeps its full 50257-wide
# vocabulary; the DATA uses a corner of it, because six steps on fresh
# batches of 8192 tokens drawn from all 50257 ids teach nothing (measured
# on the chip, PR 22: loss 10.840 -> 10.845), while which 512 ids occur
# at all is learnt at once, so a working optimizer shows a falling loss.
DATA_IDS = 512


def synthetic_batches(cfg, seed, n):
    """``n`` batches of the example's synthetic next-token task
    (next = 3 * token + 7, modulo the ids in use), from ``seed``."""
    rng = np.random.RandomState(seed)
    ids = min(DATA_IDS, cfg.vocab_size)
    out = []
    for _ in range(n):
        x = rng.randint(0, ids,
                        (cfg.batch_size, cfg.seq_len)).astype(np.int32)
        out.append((x, ((3 * x + 7) % ids).astype(np.int32)))
    return out


def lowered_train_step(ex, feed_dict):
    """StableHLO text of the compiled train step, lowered again from the
    executor's live state and one batch."""
    from hetu_tpu.executor import gather_feeds
    sub = ex.subexecutor["train"]
    fn = next(iter(sub._compiled.values()))
    return fn.lower(ex.var_values, ex.opt_states, ex.step, ex.rng,
                    gather_feeds(sub, feed_dict, peek=True)).as_text()


def run_steps(ex, ids, labels, batches):
    """(losses, seconds per step), each step waited for on the host."""
    losses, secs = [], []
    for x, y in batches:
        t0 = time.perf_counter()
        out = ex.run("train", feed_dict={ids: x, labels: y})
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        secs.append(time.perf_counter() - t0)
    return losses, secs


def train_phase(cfg, steps, seed):
    """Build, take ``steps`` steps, check the loss.  Returns the executor
    (its ``var_values`` are the server's weights) and the phase record."""
    t0 = time.perf_counter()
    ex, ids, labels = build_trainer(cfg, seed=seed)
    build_s = time.perf_counter() - t0
    batches = synthetic_batches(cfg, seed, steps)
    losses, secs = run_steps(ex, ids, labels, batches)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"train: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train: loss did not fall: {losses}")
    x, y = batches[0]
    kernels = pallas_kernels(lowered_train_step(ex, {ids: x, labels: y}))
    warm = sorted(secs[1:])
    return ex, {
        "phase": "train", "model": "gpt2-small",
        "layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
        "heads": cfg.num_attention_heads, "vocab": cfg.vocab_size,
        "batch": cfg.batch_size, "seq": cfg.seq_len, "compute": "bf16",
        "steps": steps, "losses": [round(v, 4) for v in losses],
        "build_s": round(build_s, 2),
        "first_step_s": round(secs[0], 2),        # trace + compile + run
        "step_ms_median_after_warmup": round(warm[len(warm) // 2] * 1e3, 2),
        "tokens_per_step": cfg.batch_size * cfg.seq_len,
        "kernels": kernels,
    }


def lowered_mixed_step(eng):
    """StableHLO text of the engine's mixed step for a decode-only wave,
    lowered from the engine's own state (nothing is executed)."""
    from hetu_tpu.serving.kv_manager import assemble_mixed_wave
    wave = assemble_mixed_wave(eng.kv.n_slots, {})
    args = [eng.params, eng.cfg_tuple, eng.kv.cache_k, eng.kv.cache_v,
            eng.kv.tables.copy(), wave["pos"], wave["tokens"],
            wave["q_len"], wave["first_row"], wave["self_fresh"],
            eng._temp, eng._topk, eng._keys]
    return eng._mixed.func.lower(*args, **eng._mixed.keywords).as_text()


def serve_phase(params, cfg, prompt_lens, new_tokens, seed):
    """Serve one greedy request per prompt length through a default
    ``ServingEngine`` and hold every token to ``generate_fast``."""
    from hetu_tpu.models.gpt_decode import generate_fast
    from hetu_tpu.serving import Request, ServingEngine

    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(0, min(DATA_IDS, cfg.vocab_size), n)
               .astype(np.int32).tolist() for n in prompt_lens]

    t0 = time.perf_counter()
    eng = ServingEngine(params, cfg, slots=len(prompts))
    for i, p in enumerate(prompts):
        eng.submit(Request(p, new_tokens, request_id=f"r{i}"))
    done = eng.run()
    serve_s = time.perf_counter() - t0
    if sorted(done) != sorted(f"r{i}" for i in range(len(prompts))):
        raise RuntimeError(f"serve: finished {sorted(done)} of "
                           f"{len(prompts)} requests")

    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        want = generate_fast(params, cfg, [p], new_tokens)[0, len(p):]
        got = np.asarray(done[f"r{i}"].generated, np.int32)
        if got.shape != want.shape or not np.array_equal(got, want):
            raise RuntimeError(
                f"serve: request r{i} (prompt {len(p)} tokens) differs "
                f"from generate_fast:\n engine   {got.tolist()}\n "
                f"reference {want.tolist()}")
    reference_s = time.perf_counter() - t0

    kernels = pallas_kernels(lowered_mixed_step(eng))
    snap = eng.metrics.snapshot()
    return {
        "phase": "serve", "requests": len(prompts),
        "prompt_lens": list(prompt_lens), "new_tokens": new_tokens,
        "tokens_out": sum(r.n_generated for r in done.values()),
        "engine": {"fast_path": bool(eng.fast_path),
                   "paged": bool(eng.paged),
                   "kv_block": getattr(eng.kv, "block", 0),
                   "kv_dtype": str(eng.kv.quant or eng.params[
                       f"{eng._name}_wte_table"].dtype),
                   "steps": eng.steps},
        # the engine's own host-clock medians over its synced steps
        "engine_metrics": {k: snap.get(k) for k in (
            "prefill_ms_p50", "decode_ms_p50", "ttft_p50_s")},
        # wall time of submit..drain including the engine's compiles
        "serve_s_with_compile": round(serve_s, 2),
        "reference_s_with_compile": round(reference_s, 2),
        "matches_generate_fast": True,
        "kernels": kernels,
    }


# ------------------------------------------------------------------ #
# --chips 4: the across-chips path and what it is compared with
# ------------------------------------------------------------------ #

def tp_specs(cfg, name="gpt"):
    """Megatron column/row split of every block's attention and FFN
    matmuls over 'tp' (the plan __graft_entry__'s GPT block uses)."""
    from jax.sharding import PartitionSpec as P
    specs = {}
    for i in range(cfg.num_hidden_layers):
        us = f"{name}_h{i}"
        for nm in ("q", "k", "v"):
            specs[f"{us}_attn_{nm}_weight"] = P(None, "tp")
        specs[f"{us}_attn_proj_weight"] = P("tp", None)
        specs[f"{us}_ffn_wi_weight"] = P(None, "tp")
        specs[f"{us}_ffn_wo_weight"] = P("tp", None)
    return specs


def shard_devices(array):
    return {s.device for s in array.addressable_shards}


def multichip_phase(cfg, steps, seed, devices):
    """The GPT train step on a dp2 x tp2 mesh over ``devices`` against
    the same weights and batches on ``devices[0]`` alone."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.parallel.mesh import make_mesh

    if len(devices) != 4:
        raise RuntimeError(f"multichip: needs 4 devices, got {len(devices)}")
    batches = synthetic_batches(cfg, seed, steps)

    # one device (the default one, devices[0]); its initial weights are
    # what the mesh run starts from
    ex1, ids1, labels1 = build_trainer(cfg, seed=seed)
    w0 = ex1.return_tensor_values()
    t0 = time.perf_counter()
    one, _ = run_steps(ex1, ids1, labels1, batches)
    one_s = time.perf_counter() - t0
    w1 = {k: np.asarray(v) for k, v in ex1.var_values.items()}
    del ex1

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    ex4, ids4, labels4 = build_trainer(
        cfg, seed=seed, mesh=mesh,
        dist_strategy=ht.dist.ShardingPlan(tp_specs(cfg)))
    ex4.load_dict(w0)
    t0 = time.perf_counter()
    four, _ = run_steps(ex4, ids4, labels4, batches)
    four_s = time.perf_counter() - t0

    # bf16 compute: the two trajectories agree to bf16 round-off
    if not np.allclose(one, four, rtol=2e-2, atol=2e-2):
        raise RuntimeError(f"multichip: dp2 x tp2 losses {four} differ "
                           f"from one-device losses {one}")
    if not four[-1] < four[0]:
        raise RuntimeError(f"multichip: loss did not fall: {four}")

    # shards really sit on four distinct devices, not all on the first
    w = ex4.var_values["gpt_h0_ffn_wi_weight"]
    x = jax.device_put(batches[0][0],
                       ex4.feed_sharding(ids4.name, batches[0][0].shape))
    for what, arr in (("parameter", w), ("batch", x)):
        if shard_devices(arr) != set(devices):
            raise RuntimeError(
                f"multichip: {what} shards sit on "
                f"{sorted(map(str, shard_devices(arr)))}, not on "
                f"{sorted(map(str, devices))}")
    if w.addressable_shards[0].data.shape[1] * 2 != w.shape[1]:
        raise RuntimeError("multichip: ffn_wi is not split over 'tp'")
    if x.addressable_shards[0].data.shape[0] * 2 != x.shape[0]:
        raise RuntimeError("multichip: the batch is not split over 'dp'")
    # the sharded run trained the same weights the single device did
    drift = max(float(np.max(np.abs(np.asarray(v) - w1[k])))
                for k, v in ex4.var_values.items())
    return {
        "phase": "multichip", "mesh": {"dp": 2, "tp": 2}, "steps": steps,
        "layers": cfg.num_hidden_layers, "batch": cfg.batch_size,
        "seq": cfg.seq_len,
        "losses_one_device": [round(v, 4) for v in one],
        "losses_dp2_tp2": [round(v, 4) for v in four],
        "max_weight_diff_after_steps": drift,
        "shard_devices": sorted(str(d) for d in shard_devices(w)),
        "one_device_s_with_compile": round(one_s, 2),
        "dp2_tp2_s_with_compile": round(four_s, 2),
    }


def emit(record):
    print(json.dumps(record), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the dp2 x tp2 path and its one-device "
                         "comparison (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_tpu()
    leaked = sorted(k for k in os.environ
                    if k.startswith(("HETU_SERVE_", "HETU_KV_")))
    if leaked:
        raise SystemExit(f"chip_smoke: unset {leaked}: the smoke drives "
                         f"the engine's own defaults")
    if device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX "
                         f"reports {device['count']} device(s)")

    import jax
    import hetu_tpu.native
    from hetu_tpu import compile_cache
    cache_dir = compile_cache.enable_compile_cache()
    t_start = time.perf_counter()

    cfg = gpt2_small(BATCH)
    if args.chips == 4:
        emit(multichip_phase(cfg, STEPS, args.seed, jax.devices()))
        from __graft_entry__ import dryrun_multichip
        dryrun_multichip(4)
        emit({"phase": "dryrun_multichip", "devices": 4, "ok": True})
    else:
        ex, rec = train_phase(cfg, STEPS, args.seed)
        if not any("fwd" in k for k in rec["kernels"]) or \
                not any("bwd" in k for k in rec["kernels"]):
            raise RuntimeError(f"train: no flash kernel in the lowered "
                               f"step (found {rec['kernels']})")
        emit(rec)
        rec = serve_phase(ex.var_values, cfg, PROMPT_LENS, NEW_TOKENS,
                          args.seed)
        want = {"fast_path": True, "paged": True, "kv_block": 16}
        got = {k: rec["engine"][k] for k in want}
        if got != want:
            raise RuntimeError(f"serve: engine defaults on TPU are "
                               f"{got}, expected {want}")
        if not any("ragged" in k for k in rec["kernels"]):
            raise RuntimeError(f"serve: no ragged kernel in the lowered "
                               f"mixed step (found {rec['kernels']})")
        emit(rec)

    cache = compile_cache.summary()
    emit({"phase": "process",
          "seconds": round(time.perf_counter() - t_start, 1),
          "compile_cache_dir": cache_dir,
          "compile_cache_hits": cache["cache_hits"],
          "compile_cache_misses": cache["cache_misses"],
          "native_libraries": hetu_tpu.native.loaded,
          "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
              "peak_bytes_in_use")})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
