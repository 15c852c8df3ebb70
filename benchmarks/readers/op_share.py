"""Device time of the operations NAMED any of ``ops`` (the instruction's
own name, as a Pallas kernel's ``name=`` gives it), as a share of the time
the device was busy."""

from benchmarks import program_trace, xplane


def read(data, ops):
    trace = data["trace"]
    busy_s, _ = xplane.busy_seconds(trace)
    found = [e for _, e in program_trace.window_ops(trace)
             if program_trace.op_name(e[0]) in ops]
    if not found or not busy_s:
        program_trace.missing(data, "op_share", ops)
        return None
    return 100.0 * sum(e[2] for e in found) / 1e9 / busy_s
