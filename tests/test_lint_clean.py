"""The repo itself must pass its own lint gate (tier-1 guard).

``bin/hetu_lint.py hetu_tpu/ bin/`` exiting 0 is an acceptance
criterion of the static-analysis subsystem: the env-registry rule is
what KEEPS the 60-raw-read migration from regressing, and the
trace-body rules keep JAX footguns out of ``Op.compute``.  Runs the
rules in-process (no subprocess jax startup) plus one CLI smoke pass.
"""

import os
import subprocess
import sys

import pytest

from hetu_tpu.analysis.lint import RULES, lint_paths

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGETS = [os.path.join(REPO, "hetu_tpu"), os.path.join(REPO, "bin")]


def test_repo_lints_clean():
    findings = lint_paths(TARGETS)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exits_zero_on_repo():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetu_lint.py"),
         *TARGETS], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_exits_nonzero_on_fixture():
    fixture = os.path.join(REPO, "tests", "fixtures", "lint",
                           "trip_env_registry.py")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetu_lint.py"),
         fixture], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "env-registry" in proc.stdout


def test_cli_env_table():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetu_lint.py"),
         "--env-table"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    assert "`HETU_VALIDATE`" in proc.stdout
    assert "| Variable | Type | Default | Description |" in proc.stdout


def test_readme_env_table_in_sync():
    """The drift gate for the knob table: README's env-var section must
    be byte-for-byte the registry's generated table (``hetu_lint
    --env-table``).  A knob added without regenerating the table — or
    documented by hand-editing the README — fails here; the dead-knob
    lint rule covers the other direction (registered but never
    read)."""
    from hetu_tpu.envvars import env_table
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        lines = f.read().splitlines()
    start = lines.index("| Variable | Type | Default | Description |")
    table = []
    for ln in lines[start:]:
        if not ln.startswith("|"):
            break
        table.append(ln)
    generated = env_table().splitlines()
    assert table == generated, (
        "README env table drifted from the registry — regenerate with "
        "`python bin/hetu_lint.py --env-table` and paste it in")


def test_every_knob_is_named_by_a_test_or_is_a_deployment_setting():
    """What may be a knob: a registry entry is named by a file under
    ``tests/`` or ``examples/`` (somebody sets it and something checks
    what it does), or it stands in ``envvars.DEPLOYMENT`` with its
    reason.  A policy value with one value in use is a parameter's
    default or a constant beside its reader."""
    import re
    from hetu_tpu import envvars
    assert set(envvars.DEPLOYMENT) <= set(envvars.REGISTRY)
    assert len(set(envvars.DEPLOYMENT)) == len(envvars.DEPLOYMENT)
    text = []
    for top in ("tests", "examples"):
        for root, _, files in os.walk(os.path.join(REPO, top)):
            for name in files:
                path = os.path.join(root, name)
                if name.endswith((".py", ".md", ".sh", ".yml")) \
                        and os.path.abspath(path) != os.path.abspath(__file__):
                    with open(path, encoding="utf-8") as f:
                        text.append(f.read())
    named = set(re.findall(r"\bHETU_[A-Z0-9]+(?:_[A-Z0-9]+)*\b",
                           "\n".join(text)))
    unnamed = sorted(set(envvars.REGISTRY) - named
                     - set(envvars.DEPLOYMENT))
    assert not unnamed, (
        f"knobs that no test or example names and that are not "
        f"deployment settings: {unnamed}: make each the default of the "
        f"parameter it shadows, or a constant beside its reader")


def test_every_rule_documented():
    # the CLI help names each rule's purpose via the module docstring
    from hetu_tpu.analysis import lint as lint_mod
    for rule in RULES:
        assert f"``{rule}``" in lint_mod.__doc__
