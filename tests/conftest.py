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

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tier (excluded from tier-1 runs)")
    config.addinivalue_line(
        "markers",
        "smoke: <3-min verification tier (run with -m smoke; see "
        "ROADMAP.md tier-1 line)")
