"""Runner ``train``: a decoder-only LM trained through ``ht.Executor``.

``build_trainer`` and the step loop are copies of ``chip_smoke.py``'s
(PR 22 proved them on the chip): the train subgraph exactly as
``examples/nlp/train_gpt.py`` builds it, bf16 compute over f32 masters,
AdamW, dropout 0 so that attention is the Pallas flash kernel.  The
configuration file gives the sizes, the traffic file the batch, the
sequence length and the synthetic task.

``correct`` is decided in two places (PERF.md section 2 has the limits'
evidence).  At the seeded weights, where every layer's gradient depends
on every layer before and after it: the first warm-up step, the very
object and call the window then drives, against ``reference.py``'s
float32 loss and gradient of the same batch (``first_step_*``).  And on
the weights the window ended on: the loss of one more step against the
reference's (``trained_state_*``), a coarse check, because a model that
sits on the task's plateau answers the same whatever its blocks do.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

from benchmarks import loadgen, opcount, reference

# the optimizer as ``build_trainer`` sets it: the first-step check works
# the gradient out of Adam's first moment and clips the reference's as
# the trainer clips its own
BETA1 = 0.9
CLIP_NORM = 1.0


def gpt_config(config, batch, seq):
    from hetu_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_hidden_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        dropout_rate=0.0, batch_size=batch, seq_len=seq)


def build_trainer(cfg, seed, name="gpt"):
    """The train subgraph exactly as examples/nlp/train_gpt.py builds it."""
    import hetu_tpu as ht
    from hetu_tpu.models import GPTForCausalLM

    model = GPTForCausalLM(cfg, name=name)
    ids = ht.placeholder_op(f"{name}_input_ids")
    labels = ht.placeholder_op(f"{name}_labels")
    loss, _logits = model(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4, beta1=BETA1,
                                  weight_decay=0.01)
    opt.clip_grad_norm = CLIP_NORM
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     mixed_precision="bf16", seed=seed)
    return ex, ids, labels


def one_step(h, ex, ids, labels, batch):
    """One optimizer step, closed by fetching the loss to the host."""
    x, y = batch
    with h.span("train_step"):
        out = ex.run("train", feed_dict={ids: x, labels: y})
        return float(np.asarray(out[0]).reshape(-1)[0])


def measure(h, step, batches, seconds, trace_seconds):
    """Steps until ``seconds`` have passed; the last ``trace_seconds``
    are traced when the run asks for a trace.  The profiler stalls the
    host when it starts and when it stops: it is stopped after the
    window, and the step times a reader sees are those before it was
    started.  Returns (losses, step seconds, elapsed seconds at the last
    completed step, the number of steps before the profiler)."""
    losses, secs = [], []
    untraced = None
    t0 = h.open_window()
    now = t0
    while now - t0 < seconds:
        if h.trace and untraced is None \
                and now - t0 >= seconds - trace_seconds:
            untraced = len(secs)
            h.trace_start()
            now = time.perf_counter()      # the stall is no step's time
        with h.span("next_batch"):
            batch = batches[len(losses) % len(batches)]
        losses.append(step(batch))
        t = time.perf_counter()
        secs.append(t - now)
        now = t
    h.mute_spans()
    h.trace_stop()
    h.close_window()
    return (losses, secs, now - t0,
            len(secs) if untraced is None else untraced)


def first_gradient_squares(ex):
    """{leaf: the sum of squares of Adam's first moment}, left on the
    device: a reduction a leaf (a dozen shapes, so a dozen small
    programs) enqueued behind the step, nothing fetched, so that set-up
    does not wait.  ``first_gradient_norms`` reads them after the
    window."""
    import jax
    import jax.numpy as jnp
    squares = jax.jit(lambda m: jnp.sum(jnp.square(m.astype(jnp.float32))))
    (state,) = ex.opt_states.values()
    return {k: squares(s["m"]) for k, s in state.items()}


def first_gradient_norms(squares):
    """{leaf: norm of the first gradient as the optimizer got it}, worked
    out from its state after ONE step: Adam's first moment is then
    (1 - beta1) x that gradient, clipped."""
    return {k: math.sqrt(float(v)) / (1.0 - BETA1)
            for k, v in squares.items()}


def reference_first_step(params, config, batch, **how):
    """The reference's float32 loss of ``batch`` at ``params`` and its
    gradient's norms leaf by leaf, clipped to ``CLIP_NORM`` over all
    leaves as the trainer clips.  ``how`` is handed to the reference (the
    controls' lower precision or moved mask)."""
    loss, norms = reference.gradient_norms(params, config, *batch, **how)
    whole = math.sqrt(sum(n * n for n in norms.values()))
    factor = min(1.0, CLIP_NORM / (whole + 1e-6))
    return loss, {k: n * factor for k, n in norms.items()}


def judge_first_step(system, want, args):
    """``system`` and ``want`` are (loss, {leaf: gradient norm}).  Two
    numbers, each with its limit: the gap of the losses, and the widest
    gap of a leaf's gradient norm, the system's against the reference's,
    as a share of the reference's norm of that leaf or of its median
    leaf, whichever is larger (a key bias's gradient is nothing but
    rounding: the softmax does not see it)."""
    floor = statistics.median(want[1].values())
    gaps = {k: abs(system[1][k] - n) / max(n, floor)
            for k, n in want[1].items()}
    leaf = max(gaps, key=gaps.get)
    loss_gap = abs(system[0] - want[0])
    limits = (float(args["first_loss_gap_max"]),
              float(args["first_gradient_gap_max"]))
    return {"first_loss_system": system[0], "first_loss_reference": want[0],
            "first_loss_gap": loss_gap, "first_loss_gap_max": limits[0],
            "first_gradient_gap": gaps[leaf],
            "first_gradient_gap_max": limits[1],
            "first_gradient_worst_leaf": leaf,
            "first_step_agrees": bool(loss_gap <= limits[0]
                                      and gaps[leaf] <= limits[1])}


def read_trained_state(ex, step, config, batch):
    """Outside the window, on the weights as the window left them: the
    plain float32 reference's loss of ``batch``, then the system's loss
    of one more step on it.  The reference comes first: the step donates
    the weights it is handed."""
    want = reference.mean_loss(ex.var_values, config, *batch)
    return {"loss_system": step(batch), "loss_reference": want}


def at_most(name, value, limit):
    """One entry of a result's ``compared``."""
    return {"name": name, "value": value, "limit": limit,
            "within": bool(value <= limit)}


def judge_trained_state(found, args):
    limit = float(args["trained_loss_gap_max"])
    reading = abs(found["loss_system"] - found["loss_reference"])
    return dict(found, reading=reading, limit=limit,
                agrees_share=reading / limit, agrees=bool(reading <= limit))


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    import jax
    config, mix = h.config, h.traffic
    args = config["runner_args"]
    cfg = cfg or gpt_config(config, mix["batch"], mix["seq"])
    seed = h.seed % (2 ** 31 - 1)
    t_start = time.perf_counter()
    ex, ids, labels = build_trainer(cfg, seed)
    batches = loadgen.train_batches(mix, h.seed, cfg.vocab_size)
    t_built = time.perf_counter()

    def step(batch):
        return one_step(h, ex, ids, labels, batch)

    # the object the window drives takes its first steps here, from the
    # seed; the first is the one the reference follows
    marks = [time.perf_counter()]
    warm = [step(batches[0])]
    marks.append(time.perf_counter())
    first_squares = first_gradient_squares(ex)
    marks.append(time.perf_counter())
    for i in range(1, int(mix["warmup_steps"])):
        warm.append(step(batches[i]))
        marks.append(time.perf_counter())
    h.log(line="setup", build_s=t_built - t_start,
          warmup_s=marks[-1] - t_built,
          warmup_parts_s=[b - a for a, b in zip(marks, marks[1:])])
    losses, secs, elapsed, untraced = measure(
        h, step, batches, h.seconds, float(mix["trace_seconds"]))
    stats = jax.devices()[0].memory_stats() or {}
    tokens = cfg.batch_size * cfg.seq_len
    rate = len(losses) * tokens / elapsed
    flops = opcount.train_step_flops(config, cfg.batch_size, cfg.seq_len)
    finite = bool(np.all(np.isfinite(warm + losses)))
    # from the window's first loss to the mean of its last quarter: a
    # quarter's mean against a quarter's hides the fall (the loss drops
    # from 10 to the plateau within 20 steps) behind one late spike
    fall = losses[0] - float(np.mean(losses[-max(len(losses) // 4, 1):]))
    fell = fall >= float(args["loss_fall_min"])
    t_check = time.perf_counter()
    trained = judge_trained_state(
        read_trained_state(ex, step, config, batches[-1]), args)
    # the trainer's state goes before the reference's gradient comes;
    # the seeded weights are made again, by the initialisers that made
    # the trainer's
    ex = None
    gc.collect()
    seeded = dict(build_trainer(cfg, seed)[0].var_values)
    start = judge_first_step(
        (warm[0], first_gradient_norms(first_squares)),
        reference_first_step(seeded, config, batches[0]), args)
    del seeded
    check = dict(start, **{f"trained_state_{k}": v
                           for k, v in trained.items()},
                 steps=len(losses), loss_fall=fall,
                 check_s=time.perf_counter() - t_check)
    h.log(line="reference", **check)
    compared = [
        at_most("first_gradient_gap", start["first_gradient_gap"],
                start["first_gradient_gap_max"]),
        at_most("first_loss_gap", start["first_loss_gap"],
                start["first_loss_gap_max"]),
        at_most("trained_state_loss_gap", trained["reading"],
                trained["limit"]),
        {"name": "loss_fall", "value": fall,
         "limit": float(args["loss_fall_min"]), "within": fell},
        {"name": "losses_finite", "value": finite, "limit": True,
         "within": finite}]
    h.log(line="train", steps=len(losses), tokens_per_step=tokens,
          elapsed_s=elapsed, flops_per_step=flops,
          mfu_percent=100 * rate / tokens * flops / h.peak["bf16_flops_per_s"],
          loss_first=losses[0], loss_last=losses[-1], loss_warmup=warm,
          finite=finite, fell=fell, agrees_with_reference=trained["agrees"],
          first_step_agrees=start["first_step_agrees"])
    return {
        "correct": all(c["within"] for c in compared),
        "attempted": len(losses), "failed": 0,
        "memory_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "end_to_end": {"train_tokens_per_s": rate},
        # step times of the untraced part of the window only
        "data": {"samples": {"train_step_ms":
                             [s * 1e3 for s in secs[:untraced]]}},
        "notes": {"batch": cfg.batch_size, "seq": cfg.seq_len, **check},
        "compared": compared,
    }
