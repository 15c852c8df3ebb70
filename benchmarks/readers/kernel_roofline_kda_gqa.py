"""``kernel_roofline`` for the work whose operations and bytes
``benchmarks/opcount_kda_gqa.py`` counts (``readers/kernel_roofline_kda.py``
returns nothing without ``kda_lower_bound`` and may not be edited): the
least time the chip could take for what the mathematics needs
(``max(bytes / peak bytes a second, operations / peak operations a
second)``) over the device time of the TOP-LEVEL operations traced under
any of ``scopes`` or named any of ``ops``, in per cent, over the traced
part of the window (``data["counters"]["traced"]``).  Where the program
has no such counter, scope or kernel, as the parent has not, or the
configuration is not this family's (no ``gqa_layers``), nothing is
returned."""

from benchmarks import opcount_kda_gqa, program_trace

# the counter a model cannot be read without
NEEDS = {"kda_free_scan": ("kda_slot_steps", "kda_chunk_rows"),
         "kda_gqa_attention": ("attn_score_pairs",)}


def read(data, model, scopes=(), ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not harness \
            or not any(counters.get(k) for k in NEEDS[model]) \
            or "gqa_layers" not in harness.config:
        program_trace.missing(data, "kernel_roofline_kda_gqa",
                              "traced counters")
        return None
    scoped = program_trace.scoped_trace(data)
    if scoped is None:
        program_trace.missing(data, "kernel_roofline_kda_gqa", "name stacks")
        return None
    spent_ns = sum(
        e[2] for e, stack in program_trace.top_level(scoped)
        if program_trace.op_name(e[0]) in ops
        or program_trace.under_scope(stack, scopes))
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_kda_gqa",
                              list(scopes) + list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_kda_gqa, model)(counters,
                                                     harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
