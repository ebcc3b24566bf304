#!/usr/bin/env python3
"""Read a profiler trace by hand: what planes, what lines, how events are named.

    python benchmarks/tools/xplane_summary.py <trace dir or .xplane.pb> [top]

For every plane and line: the number of events, the span they cover, and the
``top`` names by total duration with the statistics of the event's metadata
(``tf_op`` is the JAX name stack).  This is how ``harness/trace.py``'s parse
was written and is how to check it again when the profiler changes.
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace, xplane  # noqa: E402


def main(argv) -> int:
    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 12
    if os.path.isdir(path):
        files = trace.xplane_files(path)
        if not files:
            print(f"no .xplane.pb under {path}")
            return 1
        path = files[-1]
    print(f"file {path} ({os.path.getsize(path)} bytes)")
    for plane in xplane.read(path):
        print(f"PLANE {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            evs = line["events"]
            if not evs:
                print(f"  LINE {line['name']!r}: empty")
                continue
            tot, cnt, stats = defaultdict(float), defaultdict(int), {}
            for s, d, name, mstats in evs:
                tot[name] += d
                cnt[name] += 1
                stats[name] = mstats
            lo = min(e[0] for e in evs)
            hi = max(e[0] + e[1] for e in evs)
            print(f"  LINE {line['name']!r}: {len(evs)} events over "
                  f"{(hi - lo) / 1e6:.3f} ms, {len(tot)} names")
            for name in sorted(tot, key=lambda k: -tot[k])[:top]:
                print(f"    {tot[name] / 1e6:12.3f} ms  x{cnt[name]:<7d} "
                      f"{name[:90]!r}")
                short = {k: str(v)[:160] for k, v in stats[name].items()
                         if k in ("tf_op", "hlo_category", "source")}
                if short:
                    print(f"        {short}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
