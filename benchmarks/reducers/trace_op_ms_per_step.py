"""Device milliseconds per step in HLO instructions matched by name
(``op_regex``): collectives have no scope of the program's, their kind is in
the instruction's name.  The figure is that of the chip that spends most
there: a chip that arrives early at a collective spends the wait inside it, so
the largest is what the slowest partner costs the others."""
from harness import trace


def read(spec: dict, ev: dict):
    parsed = ev.get("trace")
    steps = ev.get("trace_steps")
    if not parsed or not parsed["devices"] or not steps:
        return None
    win = trace.traced_window(ev)[0]
    secs = max(trace.op_seconds(win, spec["op_regex"], d)
               for d in trace.device_names(win))
    return secs * 1e3 / steps if secs else None
