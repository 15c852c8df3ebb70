"""How a measured record reaches its file (``hetu_tpu/artifact.py``).

Written whole or not at all, and a degraded run never replaces a
full-scale TPU record.
"""

import json
import os

import pytest

from hetu_tpu.artifact import atomic_json_dump, persist_artifact

pytestmark = pytest.mark.smoke

TPU_RECORD = {"platform": "tpu", "reduced_scale": False, "value": 197.0}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _read(path):
    with open(path) as f:
        return json.load(f)


def test_a_dump_that_raises_leaves_the_old_file_whole(tmp_path):
    path = tmp_path / "record.json"
    _write(path, TPU_RECORD)
    with pytest.raises(TypeError):
        # the dump fails half way: the first key is already written
        atomic_json_dump(str(path), {"value": 1.0, "bad": object()})
    assert _read(path) == TPU_RECORD
    assert os.listdir(tmp_path) == ["record.json"]     # no temp file left
    atomic_json_dump(str(path), {"value": 2.0})
    assert _read(path) == {"value": 2.0}


@pytest.mark.parametrize("art", [
    {"platform": "cpu", "value": 3.0},                       # not a TPU
    {"platform": "tpu", "reduced_scale": True, "value": 3.0},  # small probes
])
def test_a_reduced_run_keeps_a_full_scale_tpu_record(tmp_path, art):
    path = tmp_path / "record.json"
    _write(path, TPU_RECORD)
    assert persist_artifact(str(path), art, reduced=True) is False
    assert "full-scale TPU record" in art["not_written"]
    assert _read(path) == TPU_RECORD


@pytest.mark.parametrize("existing", [
    None,                                                    # no file yet
    "{ truncated",                                           # not JSON
    {"platform": "cpu", "reduced_scale": True, "value": 0.1},
    {"platform": "tpu", "reduced_scale": True, "value": 0.1},
])
def test_a_reduced_run_replaces_what_is_no_better(tmp_path, existing):
    path = tmp_path / "record.json"
    if isinstance(existing, str):
        path.write_text(existing)
    elif existing is not None:
        _write(path, existing)
    art = {"platform": "cpu", "reduced_scale": True, "value": 3.0}
    assert persist_artifact(str(path), art, reduced=True) is True
    assert "not_written" not in art
    assert _read(path) == art


def test_a_full_scale_run_replaces_a_tpu_record(tmp_path):
    path = tmp_path / "record.json"
    _write(path, TPU_RECORD)
    art = dict(TPU_RECORD, value=180.0)
    assert persist_artifact(str(path), art, reduced=False) is True
    assert _read(path) == art
