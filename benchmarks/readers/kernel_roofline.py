"""A kernel's share of its roofline over the traced part of the window:
the least time the chip could take for what the mathematics needs
(``max(bytes / peak bytes a second, operations / peak operations a
second)``) over the device time of the kernel's operations, in per cent.

The operations are the TOP-LEVEL ones named any of ``ops`` (the
instruction's own name) or traced under any of ``scopes``; operations
and bytes come from ``benchmarks/opcount_latent_moe.py``'s function
``model`` over the engine's counters of exactly those seconds
(``data["counters"]["traced"]``, which the runner takes from where the
profiler starts to where the window closes).  Where the program has no
such counter or kernel, as the parent has not, nothing is returned."""

from benchmarks import opcount_latent_moe, program_trace


def read(data, model, ops=(), scopes=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get("moe_assignments") or not harness:
        program_trace.missing(data, "kernel_roofline", "traced counters")
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
        program_trace.missing(data, "kernel_roofline",
                              list(ops) + list(scopes))
        return None
    n_ops, n_bytes = getattr(opcount_latent_moe, model)(
        counters, harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
