"""Recompile sentinel: the "ONE compiled core" claim, asserted.

The serving engine's perf story leans on two compile-count claims that
were, until now, untested as claims: PR 15's rolling weight swap is
"no recompile" (every jitted step takes the param dict as an argument,
so a swap must not grow any jit cache), and PR 18's mixed-mode ragged
dispatch serves a whole mixed trace through ONE compiled kernel per
(bucket, config) signature.  A silent regression — a shape leaking
into a static argument, a dtype flapping between waves — shows up only
as a mysterious slowdown on chip.

This module makes the claim checkable in milliseconds on CPU:

- every ``ServingEngine`` registers its jitted step functions here at
  build when ``HETU_VALIDATE=1`` (the same gate as the graph verifier:
  zero presence in production paths);
- :func:`snapshot` reads each function's jit-cache entry count
  (``jitted._cache_size()``); :func:`assert_no_recompile` diffs two
  snapshots and raises :class:`JitAuditError` naming every function
  whose cache GREW — serving the same traffic twice, or swapping
  weights, must be a no-op diff.

``tests/test_jit_audit.py`` is the regression gate; suite stage 00k
runs the same check before chip time.
"""

from __future__ import annotations

import weakref

from .. import envvars

__all__ = ["JitAuditError", "register_engine", "registered",
           "snapshot", "assert_no_recompile", "reset"]

# the jitted-step attributes an engine may carry (absent/None skipped)
_ENGINE_FNS = ("_mixed", "_propose", "_draft_prefill")

_ENGINES: list = []       # [(label, weakref-to-engine)]
_N_REGISTERED = 0


class JitAuditError(RuntimeError):
    """A jit cache grew where the engine contract says it must not."""


def register_engine(engine, label=None):
    """Track an engine's jitted step functions (weakly — a retired
    replica drops out of the audit with its last reference).  Called by
    ``ServingEngine.__init__`` under ``HETU_VALIDATE=1``."""
    global _N_REGISTERED
    _N_REGISTERED += 1
    if label is None:
        label = f"{getattr(engine, '_name', 'engine')}#{_N_REGISTERED}"
    _ENGINES.append((label, weakref.ref(engine)))
    return label


def registered() -> list:
    """Labels of engines still alive in the audit."""
    return [lbl for lbl, ref in _ENGINES if ref() is not None]


def _cache_size(fn):
    # the engine binds its wave's static arguments with functools.partial
    try:
        return int(getattr(fn, "func", fn)._cache_size())
    except Exception:
        return None


def snapshot() -> dict:
    """{"<label>.<attr>": jit-cache entry count} over every live
    registered engine (functions without a readable cache skipped)."""
    out = {}
    for label, ref in _ENGINES:
        engine = ref()
        if engine is None:
            continue
        for attr in _ENGINE_FNS:
            fn = getattr(engine, attr, None)
            if fn is None:
                continue
            n = _cache_size(fn)
            if n is not None:
                out[f"{label}.{attr}"] = n
    return out


def assert_no_recompile(before, after=None, context=""):
    """Raise :class:`JitAuditError` for every jitted step whose cache
    grew between the two snapshots; returns ``after``.

    New keys in ``after`` (an engine built between snapshots) are not
    recompiles; keys that vanished (engine retired) are ignored."""
    if after is None:
        after = snapshot()
    grew = [(k, before[k], after[k])
            for k in before if k in after and after[k] > before[k]]
    if grew:
        where = f" during {context}" if context else ""
        detail = "; ".join(f"{k}: {a} -> {b} cache entries"
                           for k, a, b in grew)
        raise JitAuditError(
            f"jit recompile{where}: {detail} — the engine contract is "
            f"ONE compile per (bucket, config) signature; a growing "
            f"cache means a shape/dtype/static-arg leaked into the "
            f"dispatch (or a weight swap stopped being swap-in-place)")
    return after


def reset():
    """Forget registered engines (test isolation)."""
    global _N_REGISTERED
    _ENGINES.clear()
    _N_REGISTERED = 0


def enabled() -> bool:
    """Mirror of the validate gate the engine wiring checks."""
    return envvars.get_bool("HETU_VALIDATE")
