"""Share of the device's idle time (gaps between ``XLA Ops`` inside the
traced window, moved onto the host's clock as ``idle_under_spans`` moves
them) that lies under a scheduler iteration (``hetu.serve.wave`` root)
whose ``order=`` is any of ``orders``.  Under ``inorder`` and ``first``
roots a gap is the device waiting for the host's landing and launch in
turn; under an ``ahead`` root it is a launch gap."""

from benchmarks import wave_trace


def read(data, orders):
    w = wave_trace.waves(data)
    gaps = wave_trace.idle_gaps(data)
    if w is None or not gaps:
        return None
    idle = sum(b - a for a, b in gaps)
    under = wave_trace.clipped(
        gaps, [(r["start"], r["end"]) for r in w["roots"]
               if r["order"] in orders])
    return 100.0 * sum(b - a for a, b in under) / idle
