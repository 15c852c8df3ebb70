"""What the serving tests call wave after wave, as programs.

On the CPU a tiny program's compile costs more than its run, and a
function of ``jax.numpy`` called as it stands is a program an operator (a
3-layer ``_mixed_step`` called eagerly: 360 compiles).  The engine jits
its step; so do the tests that drive the step or a reference's forward
by hand.
"""

import jax

from hetu_tpu.models import gpt_decode as gd

mixed_wave = jax.jit(gd._mixed_step, static_argnums=(1,),
                     static_argnames=("window", "has_fresh"))

_forwards = {}


def reference(forward, params, cfg, tokens, *args, **kw):
    """``forward(params, cfg, tokens, *args, **kw)`` as one program a
    length of ``tokens``, kept for the process: calls of one reference
    with one configuration and the same options share it (the kept
    function holds ``cfg``, so its ``id`` names nothing else)."""
    key = (forward, id(cfg), args, tuple(sorted(kw.items())))
    if key not in _forwards:
        _forwards[key] = jax.jit(
            lambda p, t: forward(p, cfg, t, *args, **kw))
    return _forwards[key](params, tokens)
