#!/usr/bin/env python
"""Repo lint gate CLI (hetu_tpu/analysis/lint.py rules).

    python bin/hetu_lint.py hetu_tpu/ bin/  # lint, exit != 0 on findings
    python bin/hetu_lint.py --env-table             # HETU_* doc table (markdown)
    python bin/hetu_lint.py --rules env-registry hetu_tpu/

Runs without jax/device initialization: the rules are pure-AST.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hetu_tpu.analysis.lint import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
