"""A key of the engine's ``metrics.snapshot()`` as the runner narrowed it
to the window (``runners/serve.py`` ``window_view``), times ``scale``."""


def read(data, key, scale=1.0):
    value = (data.get("snapshot") or {}).get(key)
    return None if value is None else value * scale
