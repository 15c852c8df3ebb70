"""The documents describe the tree as it is.

A document that names a file names one that exists; a document that
names a ``HETU_*`` knob names a registered one; the documents and
``BENCHMARK.json`` agree on what the cells and the end-to-end metrics
are called; the README's table of the root's records lists the records
the root holds.  Each rule reads the documents and the tree and edits
nothing.
"""

import glob
import json
import os
import re
import subprocess

import pytest

from hetu_tpu import envvars

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# MIGRATING.md cites the reference's paths by design: its knobs are
# checked, its paths are not
PATH_DOCS = ["README.md", "COMPONENTS.md", "examples/README.md",
             ".claude/skills/verify/SKILL.md"]
KNOB_DOCS = PATH_DOCS + ["MIGRATING.md"]

# where a document's relative path may start: `serving/engine.py` is
# under the package, `runners/serve.py` under the benchmark, `cnn/main.py`
# beside examples/README.md
_BASES = ("", "hetu_tpu", "tests", "benchmarks", "examples")
# a bare name that is a script's, or a root-level record's (those are
# upper-case: `PERF_LEDGER.jsonl`; `config.json` is a model's, not ours)
_SCRIPT = re.compile(r"^\w+\.(py|sh)$")
_RECORD = re.compile(r"^[A-Z][A-Z0-9_]*(_r\d+)?\.(json|jsonl|md)$")


def _tracked():
    out = subprocess.run(["git", "ls-files"], cwd=REPO, text=True,
                         capture_output=True, check=True).stdout
    return out.split()


def _read(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        return f.read()


def _backticked(text):
    """The single-backticked tokens of a document, fenced blocks left
    out (a shell transcript is not a citation)."""
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    return re.findall(r"`([^`\n]+)`", text)


def _path_of(token):
    """The file a token cites, or None where it cites none: a call, an
    option, a pattern with a placeholder."""
    t = token.split("::")[0]
    t = re.sub(r":[\d,:-]+$", "", t).rstrip("/.,;")
    if not t or re.search(r"[\s()=$<>{}\[\]|\\…]", t) \
            or t.startswith(("-", "/")):
        return None
    return t


def _exists(base, path):
    """``path`` under ``base``, as a file, a pattern, or a module cited
    without its suffix or with a name inside it
    (`models/moe_decode.moe_ffn`)."""
    full = os.path.join(REPO, base, path)
    if glob.glob(full) or os.path.exists(full + ".py"):
        return True
    stem, dot, _ = path.rpartition(".")
    return bool(dot) and "/" in stem and _exists(base, stem)


def _missing(doc):
    here = os.path.dirname(doc)
    bases = _BASES + ((here,) if here else ())
    basenames = {os.path.basename(p) for p in _tracked()}
    bad = []
    for token in _backticked(_read(doc)):
        path = _path_of(token)
        if path is None:
            continue
        if "/" not in path:
            if (_SCRIPT.match(path) and path not in basenames) or (
                    _RECORD.match(path)
                    and not os.path.exists(os.path.join(REPO, path))):
                bad.append(token)
            continue
        head = path.split("/")[0]
        roots = [b for b in bases
                 if os.path.isdir(os.path.join(REPO, b, head))]
        if not roots:
            continue     # not this repo's: the reference's tree, a URL
        if not any(_exists(b, path) for b in roots):
            bad.append(token)
    return bad


@pytest.mark.parametrize("doc", PATH_DOCS)
def test_cited_paths_exist(doc):
    bad = _missing(doc)
    assert not bad, f"{doc} cites files that are not in the tree: {bad}"


@pytest.mark.parametrize("doc", KNOB_DOCS)
def test_cited_knobs_are_registered(doc):
    cited = set(re.findall(r"\bHETU_[A-Z0-9]+(?:_[A-Z0-9]+)*\b",
                           _read(doc)))
    unknown = sorted(cited - set(envvars.REGISTRY))
    assert not unknown, (
        f"{doc} cites knobs hetu_tpu/envvars.py does not register: "
        f"{unknown}")


def _benchmark_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["end_to_end"]]
    assert cells and metrics
    return cells + metrics


@pytest.mark.parametrize("doc", ["PERF.md", "README.md"])
def test_benchmark_names_appear(doc):
    """Every cell and every end-to-end metric of ``BENCHMARK.json`` is
    named by the document that reports on them."""
    text = _read(doc)
    absent = [n for n in _benchmark_names() if n not in text]
    assert not absent, f"{doc} does not name {absent}"


def test_readme_lists_the_roots_records():
    """The README's table of root records has a row for every tracked
    root-level ``*.json`` / ``*.jsonl`` and for nothing else."""
    text = _read("README.md")
    start = text.index("| Record | Written by | Read by |")
    rows = []
    for line in text[start:].splitlines()[2:]:
        if not line.startswith("|"):
            break
        rows.append(line.split("|")[1])
    listed = set()
    for cell in rows:
        for name in re.findall(r"`([^`]+)`", cell):
            listed.update(os.path.basename(p) for p in
                          glob.glob(os.path.join(REPO, name)))
            if not glob.glob(os.path.join(REPO, name)):
                listed.add(name)
    records = {p for p in _tracked()
               if "/" not in p and p.endswith((".json", ".jsonl"))}
    assert listed == records, (
        f"not in the table: {sorted(records - listed)}; "
        f"in the table but not a tracked root record: "
        f"{sorted(listed - records)}")
