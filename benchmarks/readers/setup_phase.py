"""A part of ``setup_s``, in seconds (``cache_miss_share``: in %), as the
program's compile watch and registry saw it in the process that ran the
cell (``hetu_tpu/compile_cache.py``, ``PROGRAM_SPANS.setup.md``), over
what ended before the window opened: the runners' float32 reference
compiles after it and is no part of set-up.

``part`` is one of ``import`` (the gauge ``process.import_ms``),
``build`` (the calls of ``serve.engine.build`` and ``exec.build`` that
ended before the window, ``telemetry.spanned_calls()``, less the compile
phases that ran under them: constructing, not compiling; the training
runner builds a second executor AFTER its window, for its check),
``trace``, ``lower``, ``backend`` (the union of that phase's
``compile`` records), ``cache_load`` (their plain sum: a part of
``backend``) and ``cache_miss_share`` (persistent-cache misses over hits
and misses).  Nothing where the program has no watch, as the parent has
not, or it is not listening (``HETU_TELEMETRY=0``)."""

import sys

BUILD_SPANS = ("serve.engine.build", "exec.build")


def window_opening(harness):
    """The window's opening on the ``time.perf_counter`` clock:
    ``T_PROCESS_START`` is a global of the module ``Harness`` was
    defined in (``__main__`` when ``run.py`` is the command)."""
    if harness.setup_s is None:
        return None
    start = sys.modules[type(harness).__module__].T_PROCESS_START
    return start + harness.setup_s


def read(data, part):
    from hetu_tpu import compile_cache, telemetry
    watch = getattr(compile_cache, "WATCH", None)
    before = window_opening(data["harness"])
    if watch is None or not watch.installed or before is None:
        return None
    if part == "import":
        ms = telemetry.gauge("process.import_ms").get()
        return None if ms is None else ms / 1e3
    if part == "build":
        built = [c for c in telemetry.spanned_calls()
                 if c["name"] in BUILD_SPANS and c["end_perf"] <= before]
        if not built:
            return None
        return sum(c["ms"] for c in built) / 1e3 - sum(
            watch.summary(before=before, under=name)["union_s"]
            for name in {c["name"] for c in built})
    summary = watch.summary(before=before)
    if part == "cache_miss_share":
        asked = summary["cache_hits"] + summary["cache_misses"]
        return 100.0 * summary["cache_misses"] / asked if asked else None
    return summary["seconds"][part]
