"""A reading of the benchmark's own host clock: ``evidence["host"][key]``.

``stat``: ``value`` (a single reading), ``p50`` (median of a list of
readings); ``scale`` multiplies (1000 turns seconds into ms).
"""
from harness.clock import median


def read(spec: dict, ev: dict):
    val = ev["host"].get(spec["key"])
    if val is None:
        return None
    if spec.get("stat", "value") == "p50":
        val = median(list(val))
        if val is None:
            return None
    return float(val) * float(spec.get("scale", 1.0))
