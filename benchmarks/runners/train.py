"""Runner ``train``: a decoder-only LM trained through ``ht.Executor``.

``build_trainer`` and the step loop are copies of ``chip_smoke.py``'s
(PR 22 proved them on the chip): the train subgraph exactly as
``examples/nlp/train_gpt.py`` builds it, bf16 compute over f32 masters,
AdamW, dropout 0 so that attention is the Pallas flash kernel.  The
configuration file gives the sizes, the traffic file the batch, the
sequence length and the synthetic task.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import loadgen, opcount, reference


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
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4, weight_decay=0.01)
    opt.clip_grad_norm = 1.0
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


def loss_agrees(h, ex, step, config, batch, tolerance):
    """Outside the window: the executor's loss on one more batch against
    the plain float32 reference on the same weights (read before the step
    updates them) and the same batch.  By then the model has learnt part
    of the task, so a wrong attention or a wrong head shows; at the
    initial weights every model scores log(vocabulary)."""
    x, y = batch
    want = reference.mean_loss(ex.var_values, config, x, y)
    got = step(batch)
    h.log(line="reference", loss_system=got, loss_reference=want,
          relative_tolerance=tolerance)
    return abs(got - want) <= tolerance * max(abs(want), 1.0)


def run(h, cfg=None):
    """``cfg`` narrows the model for the CPU rehearsal in the tests and
    nothing else; the command never passes it."""
    import jax
    config, mix = h.config, h.traffic
    cfg = cfg or gpt_config(config, mix["batch"], mix["seq"])
    t_start = time.perf_counter()
    ex, ids, labels = build_trainer(cfg, h.seed % (2 ** 31 - 1))
    batches = loadgen.train_batches(mix, h.seed, cfg.vocab_size)
    t_built = time.perf_counter()

    def step(batch):
        return one_step(h, ex, ids, labels, batch)

    warm = [step(batches[i]) for i in range(int(mix["warmup_steps"]))]
    h.log(line="setup", build_s=t_built - t_start,
          warmup_s=time.perf_counter() - t_built)
    losses, secs, elapsed, untraced = measure(
        h, step, batches, h.seconds, float(mix["trace_seconds"]))
    stats = jax.devices()[0].memory_stats() or {}
    tokens = cfg.batch_size * cfg.seq_len
    rate = len(losses) * tokens / elapsed
    flops = opcount.train_step_flops(config, cfg.batch_size, cfg.seq_len)
    k = max(len(losses) // 4, 1)
    finite = bool(np.all(np.isfinite(warm + losses)))
    fell = float(np.mean(losses[-k:])) < float(np.mean(losses[:k]))
    agrees = loss_agrees(h, ex, step, config, batches[-1],
                         float(config["runner_args"]["loss_tolerance"]))
    h.log(line="train", steps=len(losses), tokens_per_step=tokens,
          elapsed_s=elapsed, flops_per_step=flops,
          mfu_percent=100 * rate / tokens * flops / h.peak["bf16_flops_per_s"],
          loss_first=losses[0], loss_last=losses[-1], loss_warmup=warm,
          finite=finite, fell=fell, agrees_with_reference=agrees)
    return {
        "correct": finite and fell and agrees,
        "attempted": len(losses), "failed": 0,
        "memory_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "end_to_end": {"train_tokens_per_s": rate},
        # step times of the untraced part of the window only
        "data": {"samples": {"train_step_ms":
                             [s * 1e3 for s in secs[:untraced]]}},
        "notes": {"batch": cfg.batch_size, "seq": cfg.seq_len,
                  "steps": len(losses)},
    }
