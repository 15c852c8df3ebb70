"""``kernel_roofline`` for the work whose operations and bytes
``benchmarks/opcount_parallel_moe.py`` counts (``readers/kernel_roofline.py``
names ``opcount_latent_moe`` and may not be edited): the least time the
chip could take for what the mathematics needs (``max(bytes / peak bytes
a second, operations / peak operations a second)``) over the device time
of the TOP-LEVEL operations named any of ``ops`` or traced under any of
``scopes``, in per cent, over the traced part of the window
(``data["counters"]["traced"]``).  Where the program has no such counter,
kernel or scope, as the parent has not, or the configuration is not a
parallel block's (no ``use_parallel_block``), nothing is returned."""

from benchmarks import opcount_parallel_moe, program_trace


def read(data, model, ops=(), scopes=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get("wave_rows_live") \
            or counters.get("attn_window_bound_rows") is None \
            or not harness \
            or not harness.config.get("use_parallel_block"):
        program_trace.missing(data, "kernel_roofline_parallel",
                              "traced counters")
        return None
    spent_ns = sum(e[2] for _, e in program_trace.window_ops(data["trace"])
                   if program_trace.op_name(e[0]) in ops)
    scoped = program_trace.scoped_trace(data) if scopes else None
    if scoped is not None:
        spent_ns += sum(
            e[2] for e, stack in program_trace.top_level(scoped)
            if program_trace.op_name(e[0]) not in ops
            and program_trace.under_scope(stack, scopes))
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_parallel",
                              list(ops) + list(scopes))
        return None
    n_ops, n_bytes = getattr(opcount_parallel_moe, model)(
        counters, harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
