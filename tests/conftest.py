"""Test config: force a virtual 8-device CPU platform BEFORE jax initializes.

This is the TPU build's substitute for the reference's multi-process local
clusters (SURVEY.md §4 tier-2/3): N-device semantics on CPU so the
equivalence suite runs anywhere.  The platform is forced through jax.config
so that a bare ``pytest`` on a machine with a chip does not take the chip.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

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


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tier (excluded from tier-1 runs)")
    config.addinivalue_line(
        "markers",
        "smoke: <3-min verification tier (run with -m smoke; see "
        "ROADMAP.md tier-1 line)")
