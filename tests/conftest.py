"""Test config: force a virtual 8-device CPU platform BEFORE jax initializes.

This is the TPU build's substitute for the reference's multi-process local
clusters (SURVEY.md §4 tier-2/3): N-device semantics on CPU so the
equivalence suite runs anywhere.  The platform is forced through jax.config
so that a bare ``pytest`` on a machine with a chip does not take the chip.
"""

import collections
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in flags:
    # the tests' programs are tiny and run once or twice: what they cost
    # is LLVM's optimiser, a third of a worker's CPU seconds (ROADMAP C8
    # (b)).  The CPU backend alone reads it: a compile for the described
    # chip in ``test_chip_compile.py`` is the same bytes with it
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()

# static checks default-ON for the whole suite: every Executor/
# ServingEngine build runs the pre-trace verifier + parallelism checker
# (hetu_tpu/analysis/), so a graph regression fails with the node named
# instead of an XLA stack dump.  Explicit HETU_VALIDATE=0 still wins.
os.environ.setdefault("HETU_VALIDATE", "1")

# the persistent compilation cache stays OFF for the life of every test
# process: a worker that an example's ``main()`` had switched it on for
# died in ``compilation_cache.put_executable_and_time`` several compiles
# later (ROADMAP C8 (a); the log does not say why the call faults)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def no_persistent_compile_cache(monkeypatch):
    """``compile_cache.enable_compile_cache`` does nothing under pytest,
    whoever calls it (every ``examples/*`` ``main()`` does).  The value
    is the real function, for the test of where it puts the cache."""
    from hetu_tpu import compile_cache
    real = compile_cache.enable_compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: None)
    return real


def _memory_maps():
    with open("/proc/self/maps", "rb") as f:
        return sum(1 for _ in f)


@pytest.fixture(autouse=True)
def executables_stay_under_the_map_limit():
    """A compiled executable holds about five memory maps until jax
    drops it, and the kernel allows a process ``vm.max_map_count`` maps
    (65,530).  A process at the limit dies in its next compile ("LLVM
    compilation error: Cannot allocate memory", then "Segmentation
    fault" in ``backend_compile_and_load``: reproduced alone, and the
    frame in which five trees lost a worker late in its life), and a
    worker passes HALF the limit ten minutes into a whole run (ROADMAP
    C8 (a)).  Past half the limit the caches go; what is needed again
    is compiled again."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        crowded = _memory_maps() > limit // 2
    except OSError:             # no /proc: no such limit to watch
        return
    if crowded:
        jax.clear_caches()


def pytest_collection_modifyitems(items):
    # xdist's ``load`` deals the collection in order and in ever smaller
    # runs: a file near the end goes two cases at a time over all six
    # workers, and each of them builds the file's fixtures and compiles
    # its programs again.  The files with the most cases first, as
    # xdist's own by-file schedulers order them, and what is dealt finely
    # is the small files.  The sort is stable: a file's cases stay
    # together and in order.  (Two alternating pairs of whole runs, PR
    # 52: 655 and 685 s with it, 768 and 756 s without.)
    cases = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: -cases[item.path])


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tier (excluded from tier-1 runs)")
    config.addinivalue_line(
        "markers",
        "smoke: <3-min verification tier (run with -m smoke; see "
        "ROADMAP.md tier-1 line)")
