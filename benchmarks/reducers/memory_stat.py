"""A device memory statistic, taken on the fullest chip after the window."""


def read(spec: dict, ev: dict):
    if ev["platform"] == "cpu":
        return None                       # the CPU backend keeps none
    val = ev["memory"].get(spec["key"])
    return None if not val else float(val)
