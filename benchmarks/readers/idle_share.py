"""Share of the traced window in which no operation ran on the device."""

from benchmarks import xplane


def read(data):
    busy_s, window_s = xplane.busy_seconds(data["trace"])
    if not window_s:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
