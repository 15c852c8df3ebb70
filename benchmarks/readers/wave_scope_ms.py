"""Device time, in ms a wave, of the TOP-LEVEL operations traced under
any of the ``jax.named_scope`` names ``scopes`` or named any of ``ops``
(the compiler's ``ragged-dot-none`` carries its own name and no name
stack) INSIDE the module events of the serving waves of ONE ``kind``,
over the number of those waves: what a part of the wave costs in a chunk
wave, whatever share of the window chunk waves are."""

from benchmarks import program_trace, wave_trace


def read(data, kind, scopes, ops=()):
    found = wave_trace.top_level_in(data, kind)
    if found is None:
        return None
    inside, n_waves = found
    under_ns = sum(e[2] for e, stack in inside
                   if program_trace.op_name(e[0]) in ops
                   or program_trace.under_scope(stack, scopes))
    if not under_ns:
        program_trace.missing(data, "wave_scope_ms",
                              list(scopes) + list(ops))
        return None
    return under_ns / n_waves / 1e6
