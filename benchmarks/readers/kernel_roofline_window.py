"""``kernel_roofline`` for the window attention kernel, whose operations
and bytes ``benchmarks/opcount_window_moe.py`` counts: the least time
the chip could take for what the mathematics needs (``max(bytes / peak
bytes a second, operations / peak operations a second)``) over the
device time of the TOP-LEVEL operations named any of ``ops``, in per
cent, over the traced part of the window
(``data["counters"]["traced"]``).  Where the program has no such counter
or kernel, as the parent has not, or the configuration has no window
layer, nothing is returned."""

from benchmarks import opcount_window_moe, program_trace


def read(data, model, ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get("attn_window_score_pairs") \
            or not harness \
            or "sliding_attention" not in harness.config.get(
                "layer_types", ()):
        program_trace.missing(data, "kernel_roofline_window",
                              "traced counters")
        return None
    spent_ns = sum(e[2] for _, e in program_trace.window_ops(data["trace"])
                   if program_trace.op_name(e[0]) in ops)
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_window", list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_window_moe, model)(
        counters, harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
