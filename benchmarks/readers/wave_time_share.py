"""Busy device time inside the module events of the serving waves of ONE
``kind``, as a share of the time the device was busy: how much of the
traced window's work was that kind of wave.  It is the MIX that every
window-wide share and roofline of a serving cell is taken over, and it
turns with the seed and the seconds traced."""

from benchmarks import wave_trace, xplane


def read(data, kind):
    modules = wave_trace.modules_of(data, kind)
    if modules is None:
        return None
    window = wave_trace.waves(data)["window"]
    planes = xplane.device_planes(data["trace"])
    busy = xplane.merged_intervals(
        xplane.line_events(planes[0], xplane.OPS_LINE), *window)
    total = sum(b - a for a, b in busy)
    if not total:
        return None
    inside = wave_trace.clipped(
        busy, [(m["start"], m["end"]) for m in modules])
    return 100.0 * sum(b - a for a, b in inside) / total
