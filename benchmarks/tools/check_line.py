#!/usr/bin/env python3
"""Hold a run's recorded output to what ``BENCHMARK.json`` lists for its cell.

    python benchmarks/tools/check_line.py <workload> <0|1> <file> [<file> ...]

Reads the last line of each file (a run's standard output, as kept under
``chiprun_out/``), says what the driver would refuse it for
(``harness/line.py``), and exits 1 if any file has a problem.  Run it over
every output of a chip call before the numbers are believed.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import cells, line  # noqa: E402


def main(argv) -> int:
    workload, traced, files = argv[1], bool(int(argv[2])), argv[3:]
    doc, bad = cells.benchmark_doc(), 0
    for path in files:
        with open(path) as fh:
            last = fh.read().strip().splitlines()[-1:]
        try:
            why = line.problems(doc, workload, traced, json.loads(last[0]))
        except (IndexError, ValueError):
            why = ["the last line is not a JSON object"]
        bad += bool(why)
        print(f"{path}: " + ("; ".join(why) if why else "ok"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
