"""``kernel_roofline`` for the two latent attentions whose operations and
bytes ``benchmarks/opcount_sparse_latent.py`` counts: the least time the
chip could take for what the mathematics needs (``max(bytes / peak bytes
a second, operations / peak operations a second)``) over the device time
of the TOP-LEVEL operations traced under any of ``scopes`` or named any
of ``ops``, in per cent, over the traced part of the window
(``data["counters"]["traced"]``).  Where the program has no such counter,
scope or kernel, as the parent has not, or the configuration has no
``layer_types``, nothing is returned."""

from benchmarks import opcount_sparse_latent, program_trace

# the counter a model cannot be counted without
NEEDS = {"sparse_latent_attention": "sparse_keys_read",
         "window_latent_attention": "attn_window_score_pairs"}


def read(data, model, scopes=(), ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get(NEEDS[model]) or not harness \
            or "layer_types" not in harness.config \
            or "kv_lora_rank" not in harness.config:
        program_trace.missing(data, "kernel_roofline_sparse_latent",
                              "traced counters")
        return None
    scoped = program_trace.scoped_trace(data)
    if scoped is None:
        program_trace.missing(data, "kernel_roofline_sparse_latent",
                              "name stacks")
        return None
    spent_ns = sum(
        e[2] for e, stack in program_trace.top_level(scoped)
        if program_trace.op_name(e[0]) in ops
        or program_trace.under_scope(stack, scopes))
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_sparse_latent",
                              list(scopes) + list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_sparse_latent, model)(
        counters, harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
