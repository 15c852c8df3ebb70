"""Device time of the top-level operations traced under any of the
``jax.named_scope`` names ``scopes`` OR named any of ``ops``, as a share
of the time the device was busy.  ``scope_share`` with one more way in:
the compiler's own grouped matmul (``ragged-dot-none``, what
``jax.lax.ragged_dot`` becomes on the TPU) carries its own name and no
name stack, so the scope it was written under does not find it."""

from benchmarks import program_trace, xplane


def read(data, scopes, ops=()):
    trace = program_trace.scoped_trace(data)
    busy_s, _ = xplane.busy_seconds(data["trace"])
    if trace is None or not busy_s:
        program_trace.missing(data, "scope_or_op_share", "name stacks")
        return None
    under_ns = sum(
        e[2] for e, stack in program_trace.top_level(trace)
        if program_trace.op_name(e[0]) in ops
        or program_trace.under_scope(stack, scopes))
    if not under_ns:
        program_trace.missing(data, "scope_or_op_share",
                              list(scopes) + list(ops))
        return None
    return 100.0 * under_ns / 1e9 / busy_s
