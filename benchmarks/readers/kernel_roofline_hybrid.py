"""``kernel_roofline`` for the kernels whose operations and bytes
``benchmarks/opcount_hybrid.py`` counts (``readers/kernel_roofline.py``
names ``opcount_latent_moe`` and may not be edited): the least time the
chip could take for what the mathematics needs (``max(bytes / peak bytes
a second, operations / peak operations a second)``) over the device time
of the TOP-LEVEL operations named any of ``ops``, in per cent, over the
traced part of the window (``data["counters"]["traced"]``).  Where the
program has no such counter or kernel, or the configuration is not a
hybrid one (no ``layer_types``), nothing is returned."""

from benchmarks import opcount_hybrid, program_trace


def read(data, model, ops=()):
    counters = (data.get("counters") or {}).get("traced")
    harness = data.get("harness")
    if not counters or not counters.get("attn_score_pairs") or not harness \
            or "layer_types" not in harness.config:
        program_trace.missing(data, "kernel_roofline_hybrid",
                              "traced counters")
        return None
    spent_ns = sum(e[2] for _, e in program_trace.window_ops(data["trace"])
                   if program_trace.op_name(e[0]) in ops)
    if not spent_ns:
        program_trace.missing(data, "kernel_roofline_hybrid", list(ops))
        return None
    n_ops, n_bytes = getattr(opcount_hybrid, model)(counters, harness.config)
    peak = harness.peak
    by_bytes = n_bytes / peak["hbm_bytes_per_s"]
    by_ops = n_ops / peak["bf16_flops_per_s"]
    harness.log(line="roofline", model=model, operations=n_ops,
                bytes=n_bytes, kernel_s=spent_ns / 1e9,
                least_s=max(by_bytes, by_ops),
                bound="bytes" if by_bytes >= by_ops else "operations")
    return 100.0 * max(by_bytes, by_ops) / (spent_ns / 1e9)
