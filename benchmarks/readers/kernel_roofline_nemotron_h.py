"""``kernel_roofline`` for the work whose operations and bytes
``benchmarks/opcount_nemotron_h.py`` counts (``readers/kernel_roofline.py``
names ``opcount_latent_moe`` and may not be edited): the least time the
chip could take for what the mathematics needs (``max(bytes / peak bytes
a second, operations / peak operations a second)``) over the device time
of the TOP-LEVEL operations traced under any of ``scopes`` or named any
of ``ops``, in per cent, over the traced part of the window
(``data["counters"]["traced"]``).  Where the program has no such counter
or scope, as the parent has not, or the configuration's experts do not
work at a latent width (no ``moe_latent_size``), nothing is returned."""

from benchmarks import opcount_nemotron_h, program_trace


def read(data, model, scopes=(), ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get("moe_assignments") or not harness \
            or "moe_latent_size" not in harness.config:
        program_trace.missing(data, "kernel_roofline_nemotron_h",
                              "traced counters")
        return None
    scoped = program_trace.scoped_trace(data)
    if scoped is None:
        program_trace.missing(data, "kernel_roofline_nemotron_h",
                              "name stacks")
        return None
    spent_ns = sum(
        e[2] for e, stack in program_trace.top_level(scoped)
        if program_trace.op_name(e[0]) in ops
        or program_trace.under_scope(stack, scopes))
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_nemotron_h",
                              list(scopes) + list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_nemotron_h, model)(counters,
                                                        harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
