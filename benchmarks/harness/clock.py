"""Process age and small statistics on the host's clock."""
from __future__ import annotations

import os
import time

_T_IMPORT = time.time()


def process_start_time() -> float:
    """Wall-clock time at which this process started (``/proc``), or the
    time this module was imported where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            up = float(fh.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def median(vals: list) -> float:
    s = sorted(vals)
    if not s:
        return None
    m = len(s) // 2
    return s[m] if len(s) % 2 else 0.5 * (s[m - 1] + s[m])
