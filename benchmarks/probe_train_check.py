"""Where the training cell's limits come from: the check of
``runners/train.py`` read on many seeds and states in one process.

    python3 benchmarks/probe_train_check.py --seeds 77,2148718214 \
        --steps 170,214,275 [--controls 1] [--deep 1] --out chiprun_out/x.jsonl

Not part of a run of the benchmark (``run.py`` never imports it); it is
the tool PERF.md's table of readings was made with, kept so that a later
PR can read the limit again.  For every seed it builds the cell's trainer
as the runner does, takes the runner's two warm-up steps and then
``max(steps)`` steps over the same pool in the same order.  At the first
step it reads what the runner's first-step check reads (the loss and the
gradient's norms leaf by leaf, system against reference), and beside it
the reference at the stated precision in the system's place.  At every
count in ``--steps`` (214 is where a 51 s window ends on a v5e) it reads
what the runner's trained-state check reads: the float32 reference on the
weights as they stand, then the system's loss of one more step on the
check batch.  That step is a training step, as in
the runner, so a later count sees a state one step further than a run of
that length would.  ``--controls 1`` adds, on the reference's side, the
five faults the limits have to catch, at both places.  ``--deep 1`` reads, at the first
count: the system's forward loss from a second, forward-only subgraph on
other batches of the pool and row by row, and where the hidden states of
system and reference part, block by block.  One JSON object a line.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import loadgen, reference  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import train  # noqa: E402


def faults(params, config, labels, name="gpt"):
    """{fault: (params, labels, how to ask the reference)}: the five
    faults the limits have to catch, each made on the weights or the
    labels handed to the reference, or by its ``mask_shift`` /
    ``operands``."""
    import jax.numpy as jnp
    mid = config["n_layer"] // 2
    skipped = dict(params)
    for k in ("attn_proj_weight", "attn_proj_bias", "ffn_wo_weight",
              "ffn_wo_bias"):                  # the block adds nothing
        key = f"{name}_h{mid}_{k}"
        skipped[key] = jnp.zeros_like(params[key])
    shifted = dict(params)
    shifted[f"{name}_wpe"] = jnp.roll(params[f"{name}_wpe"], 1, 0)
    return {
        "mask_off_by_one": (params, labels, {"mask_shift": 1}),
        "block_skipped": (skipped, labels, {}),
        "positions_shifted": (shifted, labels, {}),
        # head row v holds what row v - 1 should: the loss of label - 1
        "head_rolled": (params, (labels - 1) % config["vocab_size"], {}),
        "float8_products": (params, labels,
                            {"operands": "float8_e4m3fn"}),
    }


def controls(params, config, batch):
    """{fault: the reference's loss of ``batch`` with that fault}."""
    x, y = batch
    return {k: reference.mean_loss(p, config, x, y2, **how)
            for k, (p, y2, how) in faults(params, config, y).items()}


def first_step_controls(params, config, batch, want, args):
    """{fault: what the first-step check reads with the faulted
    reference in the system's place}."""
    x, y = batch
    out = {}
    for k, (p, y2, how) in faults(params, config, y).items():
        found = train.judge_first_step(
            train.reference_first_step(p, config, (x, y2), **how), want,
            args)
        out[k] = {key: found[key] for key in (
            "first_loss_gap", "first_gradient_gap",
            "first_gradient_worst_leaf")}
    return out


def build_deep(cfg, seed, name="gpt"):
    """The runner's trainer with two more subgraphs over the same
    variables: the loss alone, and every block's output."""
    import hetu_tpu as ht
    from hetu_tpu.graph import (array_reshape_op, embedding_lookup_op,
                                slice_op)
    from hetu_tpu.models import GPTForCausalLM
    model = GPTForCausalLM(cfg, name=name)
    ids = ht.placeholder_op(f"{name}_input_ids")
    labels = ht.placeholder_op(f"{name}_labels")
    loss, _ = model(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4, weight_decay=0.01)
    opt.clip_grad_norm = 1.0
    t = model.transformer
    pos = t.wpe if cfg.max_position_embeddings == cfg.seq_len else \
        slice_op(t.wpe, [0, 0], [cfg.seq_len, -1])
    hcur = array_reshape_op(
        embedding_lookup_op(t.wte.embedding_table, ids) + pos,
        [-1, cfg.hidden_size])
    taps = [hcur]
    for blk in t.blocks:
        hcur = blk(hcur)
        taps.append(hcur)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)], "eval": [loss],
                      "taps": taps}, mixed_precision="bf16", seed=seed)
    return ex, ids, labels


def deep(ex, ids, labels, config, batches, log):
    import jax
    import jax.numpy as jnp
    def sys_loss(x, y):
        out = ex.run("eval", feed_dict={ids: x, labels: y})
        return float(np.asarray(out[0]).reshape(-1)[0])
    # every run of a subgraph donates the weights and hands them back:
    # ``ex.var_values`` is read anew after each

    for i in sorted({len(batches) - 1, 0, 1, 7, 31, 62} & set(range(len(batches)))):
        x, y = batches[i]
        log(line="batch", batch=i, system_forward=sys_loss(x, y),
            reference=reference.mean_loss(ex.var_values, config, x, y),
            stated=reference.mean_loss(ex.var_values, config, x, y,
                                       operands="bfloat16"))
    x, y = batches[-1]
    rows = []
    for b in range(x.shape[0]):
        only = np.full_like(y, -1)
        only[b] = y[b]
        rows.append(sys_loss(x, only))
    log(line="rows", batch=len(batches) - 1, system_forward=rows,
        reference=reference.row_losses(ex.var_values, config, x, y),
        stated=reference.row_losses(ex.var_values, config, x, y,
                                    operands="bfloat16"))
    got = [np.asarray(t, np.float32) for t in
           ex.run("taps", feed_dict={ids: x})]
    params = ex.var_values
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    parts = {}
    for tag, operands in (("reference", None), ("stated", "bfloat16")):
        with jax.default_matmul_precision("highest"):
            h = reference._embed(jnp.asarray(x, jnp.int32),
                                 params["gpt_wte_table"], params["gpt_wpe"],
                                 operands=operands)
            hs = [h]
            for i in range(config["n_layer"]):
                w = {k: params[f"gpt_h{i}_{k}"]
                     for k in reference._LAYER_KEYS}
                h = reference._block(h, w, heads=config["n_head"], eps=eps,
                                     operands=operands)
                hs.append(h)
        parts[tag] = [np.asarray(h).reshape(-1, h.shape[-1]) for h in hs]
    ref = parts["reference"]

    def whole(a, b):
        return [float(np.linalg.norm(x - r) / np.linalg.norm(r))
                for x, r in zip(a, b)]

    def by_row(a, b):
        """Per block: the rows' relative errors at their median, 99th
        and 99.9th percentile and largest, with the largest's position
        in its sequence and its norm over the median row's."""
        out = []
        for x, r in zip(a, b):
            size = np.linalg.norm(r, axis=1)
            err = np.linalg.norm(x - r, axis=1) / size
            at = int(err.argmax())
            out.append([float(v) for v in np.percentile(err, [50, 99, 99.9])]
                       + [float(err[at]), at % seq,
                          float(size[at] / np.median(size))])
        return out

    seq = x.shape[1]
    log(line="taps", batch=len(batches) - 1,
        system_vs_reference=whole(got, ref),
        stated_vs_reference=whole(parts["stated"], ref),
        system_vs_stated=whole(got, parts["stated"]),
        rows_system_vs_reference=by_row(got, ref),
        rows_stated_vs_reference=by_row(parts["stated"], ref))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", default="214")
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--deep", type=int, default=0)
    ap.add_argument("--workload", default="train-gpt2-medium-s1024")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    counts = sorted(int(s) for s in args.steps.split(","))

    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    resolved = bench_run.resolve_cell(bench, args.workload)
    config, mix = resolved["config"], resolved["traffic"]
    bench_run.enable_compile_cache()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sink = open(args.out, "a")

    def log(**record):
        text = json.dumps(record, default=float)
        print(text, flush=True)
        sink.write(text + "\n")
        sink.flush()

    class Quiet:                       # what ``one_step`` asks of a harness
        @staticmethod
        def span(_name):
            return contextlib.nullcontext()

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cfg = train.gpt_config(config, mix["batch"], mix["seq"])
        build = build_deep if args.deep else train.build_trainer
        ex, ids, labels = build(cfg, seed % (2 ** 31 - 1))
        batches = loadgen.train_batches(mix, seed, cfg.vocab_size)

        def step(batch):
            return train.one_step(Quiet, ex, ids, labels, batch)

        # the seeded weights stand until the first step donates them
        rargs = config["runner_args"]
        want = train.reference_first_step(ex.var_values, config, batches[0])
        lower = first_step_controls(ex.var_values, config, batches[0], want,
                                    rargs) if args.controls else {}
        stated = train.judge_first_step(train.reference_first_step(
            ex.var_values, config, batches[0], operands="bfloat16"),
            want, rargs)
        rows = reference.row_losses(ex.var_values, config, *batches[0])
        losses = [step(batches[0])]
        found = train.judge_first_step(
            (losses[0], train.first_gradient_norms(
                train.first_gradient_squares(ex))), want, rargs)
        log(line="first_step", seed=seed, **found,
            # what the loss would read with half the batch left out
            half_batch_moves=abs(sum(rows[:len(rows) // 2])
                                 / (len(rows) // 2) - sum(rows) / len(rows)),
            reference_at_stated_precision={
                k: stated[k] for k in ("first_loss_gap",
                                       "first_gradient_gap",
                                       "first_gradient_worst_leaf")},
            with_fault=lower)
        losses += [step(batches[i])
                   for i in range(1, int(mix["warmup_steps"]))]
        n_warm = len(losses)
        for count in counts:
            while len(losses) - n_warm < count:
                i = len(losses) - n_warm
                losses.append(step(batches[i % len(batches)]))
            if args.deep and count == counts[0]:
                deep(ex, ids, labels, config, batches,
                     lambda **r: log(seed=seed, steps=count, **r))
            # the controls first: the check's step moves the weights on
            faults = controls(ex.var_values, config, batches[-1]) \
                if args.controls else {}
            t1 = time.perf_counter()
            found = train.read_trained_state(ex, step, config, batches[-1])
            log(line="check", seed=seed, steps=count,
                loss_last=losses[-1], check_s=time.perf_counter() - t1,
                **found)
            if faults:
                log(line="controls", seed=seed, steps=count,
                    loss_system=found["loss_system"], loss_with_fault=faults)
        log(line="seed", seed=seed, seconds=time.perf_counter() - t0,
            loss_first=losses[n_warm], losses=losses)
        del ex, ids, labels, step
        gc.collect()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
