"""Median device time, in ms, of one execution of the programs whose
names hold any of ``names``."""

import statistics

from benchmarks import xplane


def read(data, names):
    events = xplane.matching_events(data["trace"], xplane.MODULES_LINE, names)
    if not events:
        return None
    return statistics.median(e[2] for e in events) / 1e6
