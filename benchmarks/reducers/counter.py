"""A count the benchmark kept: ``evidence["counters"][key]``."""


def read(spec: dict, ev: dict):
    val = ev["counters"].get(spec["key"])
    return None if val is None else float(val)
