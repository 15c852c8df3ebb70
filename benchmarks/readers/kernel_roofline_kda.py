"""``kernel_roofline`` for the work whose operations and bytes
``benchmarks/opcount_kda_latent.py`` counts (``readers/kernel_roofline.py``
names ``opcount_latent_moe`` and may not be edited): the least time the
chip could take for what the mathematics needs (``max(bytes / peak bytes
a second, operations / peak operations a second)``) over the device time
of the TOP-LEVEL operations traced under any of ``scopes`` or named any
of ``ops``, in per cent, over the traced part of the window
(``data["counters"]["traced"]``).  Where the program has no such counter
or scope, as the parent has not, or the configuration has no delta-rule
layers (no ``kda_lower_bound``), nothing is returned."""

from benchmarks import opcount_kda_latent, program_trace


def read(data, model, scopes=(), ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not harness \
            or not (counters.get("kda_slot_steps")
                    or counters.get("kda_chunk_rows")) \
            or "kda_lower_bound" not in harness.config:
        program_trace.missing(data, "kernel_roofline_kda",
                              "traced counters")
        return None
    scoped = program_trace.scoped_trace(data)
    if scoped is None:
        program_trace.missing(data, "kernel_roofline_kda", "name stacks")
        return None
    spent_ns = sum(
        e[2] for e, stack in program_trace.top_level(scoped)
        if program_trace.op_name(e[0]) in ops
        or program_trace.under_scope(stack, scopes))
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_kda",
                              list(scopes) + list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_kda_latent, model)(counters,
                                                        harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
