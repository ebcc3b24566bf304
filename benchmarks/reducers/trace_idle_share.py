"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips."""
from harness import trace


def read(spec: dict, ev: dict):
    parsed = ev.get("trace")
    if not parsed or not parsed["devices"] or ev["platform"] == "cpu":
        return None
    win, t0, t1 = trace.traced_window(ev)
    if t1 <= t0:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(win) / ((t1 - t0) / 1e9))
