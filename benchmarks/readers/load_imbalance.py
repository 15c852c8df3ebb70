"""The largest expert's load over the mean load, from the engine's load
vector over the UNTRACED part of the window (``data["counters"]
["untraced"]["moe_load"]``: assignments an expert, summed over the
routed layers and the waves).  1 is even; the number of experts is all
on one.  Nothing where the engine routes nothing."""


def read(data):
    load = ((data.get("counters") or {}).get("untraced") or {}).get("moe_load")
    if not load or not sum(load):
        return None
    return max(load) / (sum(load) / len(load))
