"""Device time of the operations whose names hold any of ``names``, as a
share of the time the device was busy."""

from benchmarks import xplane


def read(data, names):
    trace = data["trace"]
    events = xplane.matching_events(trace, xplane.OPS_LINE, names)
    busy_s, _ = xplane.busy_seconds(trace)
    if not events or not busy_s:
        return None
    return 100.0 * sum(e[2] for e in events) / 1e9 / busy_s
