"""How a measured record reaches its file.

Two rules: a record is written whole or not at all, and a degraded run
(reduced scale, or not on a real TPU) never overwrites a full-scale TPU
record.  The planner's chip calibration and environment profile write
through here.
"""

from __future__ import annotations

import json
import os
import tempfile


def atomic_json_dump(path, obj, indent=1):
    """Write JSON via a same-directory temp file + os.replace: a
    process killed mid-write, or a dump that raises, must never leave a
    truncated record that a later run silently discards and
    overwrites."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f, indent=indent)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def persist_artifact(path, art, reduced):
    """Write ``art`` (a JSON-able dict) to ``path`` unless doing so
    would degrade the record: a ``reduced`` run (small shapes, or a
    non-TPU backend) never replaces an existing full-scale TPU record.

    When skipped, sets ``art['not_written']`` with the reason and
    returns False; otherwise writes and returns True.
    """
    existing = None
    try:
        with open(path) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        pass
    if (isinstance(existing, dict) and reduced
            and not existing.get("reduced_scale")
            and existing.get("platform") == "tpu"):
        art["not_written"] = ("full-scale TPU record already "
                              "present; reduced run not persisted")
        return False
    atomic_json_dump(path, art)
    return True
