"""One of the engine's counters over another, times ``scale``, over the
UNTRACED part of the window (``data["counters"]["untraced"]``, the
seconds ``readers/load_imbalance.py`` reads).  Nothing where the program
counts neither, as the parent does not, or the one underneath is 0."""


def read(data, over, under, scale=1.0):
    counters = (data.get("counters") or {}).get("untraced") or {}
    top, bottom = counters.get(over), counters.get(under)
    if top is None or not bottom:
        return None
    return scale * top / bottom
