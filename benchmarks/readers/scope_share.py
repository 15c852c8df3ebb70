"""Device time of the top-level operations traced under any of the
``jax.named_scope`` names ``scopes``, as a share of the time the device
was busy.  Top-level: a ``while`` counts once, its body's operations not
again (and lends the ``while`` its scope, which the profiler leaves
without one); an operation the compiler added outside every scope belongs
to none."""

from benchmarks import program_trace, xplane


def read(data, scopes):
    trace = program_trace.scoped_trace(data)
    busy_s, _ = xplane.busy_seconds(data["trace"])
    if trace is None or not busy_s:
        program_trace.missing(data, "scope_share", "name stacks")
        return None
    under = {stack: program_trace.under_scope(stack, scopes)
             for stack in trace["op_scopes"]["table"]}
    if not any(under.values()):
        program_trace.missing(data, "scope_share", scopes)
        return None
    under_ns = sum(e[2] for e, stack in program_trace.top_level(trace)
                   if under[stack])
    return 100.0 * under_ns / 1e9 / busy_s
