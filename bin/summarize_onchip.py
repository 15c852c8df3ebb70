#!/usr/bin/env python
"""Summarize an on-chip suite log directory (bin/run_onchip_suite.sh).

Each stage log's last JSON line is the bench headline for that stage;
the lc_* / moe_* stages are A/B variants whose WINNER must be re-run
last so BENCH_MATRIX.json records the best measured configuration
(see the NOTE in run_onchip_suite.sh).  This tool extracts every
stage's headline, ranks the A/B groups, and prints the exact re-run
command for each winner.

Usage: python bin/summarize_onchip.py [logdir]
"""
import json
import os
import re
import sys


def headline(path):
    """Last parseable JSON object line of a stage log, or None."""
    try:
        with open(path, errors="replace") as f:
            lines = f.read().splitlines()
    except OSError:
        return None
    for ln in reversed(lines):
        ln = ln.strip()
        if ln.startswith("{") and ln.endswith("}"):
            try:
                return json.loads(ln)
            except ValueError:
                continue
    return None


# A/B stage-name -> the bench config its measurement lives under in the
# headline's nested per-config matrix.  The TOP-LEVEL headline value
# cannot be used: bench.py's headline always reports bert_base whenever
# a bert_base row exists in the merged matrix (even for CONFIGS=subset
# runs), so every variant of an A/B group would show the identical stale
# number and max() would pick "winners" by string tie-break.
_STAGE_CONFIG = (
    (re.compile(r"lc_(\d+)x(\d+)$"), "long_context"),
    (re.compile(r"moe_t(\d+)$"), "moe"),
    (re.compile(r"bert4l_(no)?flash$"), "bert4l"),
)


def stage_value(name, h):
    """(config-or-None, value) for one stage: A/B stages read their own
    config's row from the nested matrix; other stages keep the headline
    number."""
    for rx, cfg in _STAGE_CONFIG:
        if rx.match(name):
            row = h.get("matrix", {}).get(cfg, {})
            return cfg, row.get("value")
    return None, h.get("value")


def rank_ab(group):
    """Winner of one A/B group [(value, label), ...], or None when the
    group is empty or ALL values are equal (ties would be decided by a
    meaningless string comparison on the label)."""
    if not group or len({v for v, _ in group}) <= 1:
        return None
    return max(group)


def main():
    if len(sys.argv) > 1:
        logdir = sys.argv[1]
    else:
        # no canonical default exists: the suite defaults to
        # /tmp/onchip_<HHMM>
        sys.exit(f"usage: {sys.argv[0]} <suite-logdir>\n"
                 "(the logdir bin/run_onchip_suite.sh printed at start)")
    if not os.path.isdir(logdir):
        sys.exit(f"{logdir}: not a directory")
    stages = sorted(
        f[:-4] for f in os.listdir(logdir) if f.endswith(".log"))
    ab = {"lc": [], "moe": [], "bert4l": []}
    print(f"{'stage':<14} {'value':>12} {'unit':<28} {'mfu':>7} platform")
    for name in stages:
        h = headline(os.path.join(logdir, name + ".log"))
        if h is None:
            print(f"{name:<14} {'—':>12} (no JSON line — read the log)")
            continue
        cfg, val = stage_value(name, h)
        row = h.get("matrix", {}).get(cfg, {}) if cfg else h
        unit, mfu = row.get("unit", ""), row.get("mfu")
        print(f"{name:<14} {val if val is not None else '—':>12} "
              f"{unit:<28} {mfu if mfu is not None else '—':>7} "
              f"{h.get('platform', '?')}")
        m = re.match(r"lc_(\d+)x(\d+)$", name)
        if m and isinstance(val, (int, float)):
            ab["lc"].append((val, f"{m.group(1)},{m.group(2)}"))
        m = re.match(r"moe_t(\d+)$", name)
        if m and isinstance(val, (int, float)):
            ab["moe"].append((val, m.group(1)))
        m = re.match(r"bert4l_(no)?flash$", name)
        if m and isinstance(val, (int, float)):
            ab["bert4l"].append((val, "0" if m.group(1) else "1"))
    win = rank_ab(ab["lc"])
    if win:
        v, blocks = win
        print(f"\nlong-context winner: blocks {blocks} ({v})\n"
              f"  re-run: HETU_BENCH_LC_BLOCKS={blocks} "
              f"HETU_BENCH_CONFIGS=long_context python bench.py")
    win = rank_ab(ab["moe"])
    if win:
        v, tok = win
        print(f"moe winner: tokens {tok} ({v})\n"
              f"  re-run: HETU_BENCH_MOE_TOKENS={tok} "
              f"HETU_BENCH_CONFIGS=moe python bench.py")
    win = rank_ab(ab["bert4l"])
    if win:
        v, flash = win
        print(f"bert4l winner: flash={flash} ({v})\n"
              f"  re-run: HETU_BENCH_FORCE_FLASH={flash} "
              f"HETU_BENCH_CONFIGS=bert4l python bench.py\n"
              f"  then fold the winner into _bench_lm's use_flash rule")
    for key, label in (("lc", "long-context"), ("moe", "moe"),
                       ("bert4l", "bert4l")):
        if ab[key] and rank_ab(ab[key]) is None:
            print(f"{label}: all variants measured equal "
                  f"({ab[key][0][0]}) — no winner to re-run")


if __name__ == "__main__":
    main()
