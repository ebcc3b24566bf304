"""A count, or a ratio of counts, that the growth program made of its own work.

After the traced window ``collect`` asks the live trainer for the counters of
the iterations just traced (``Booster.work_counters(last=trace_steps)``: the
program keeps them on the device and fetches them only now) and leaves the
whole answer, per tree and per chip, under ``evidence["counters"]
["work_counters"]``, which the ``benchmark: detail`` line prints.  A program
without the accessor (the parent of the PR that added it), or a grower that
does not count, leaves nothing, and the metric is left out of the line.

``read`` gives ``scale`` x the product of the ``num`` terms over the product
of the ``den`` terms.  A term is a fact of the run (``rows``,
``rows_per_chip``, ``chips``, ``wave_capacity``, ``block_rows``),
``iterations`` (how many the counters cover), or a counter summed over the
trees of those iterations (``bodies``, ``waves``, ``lanes``, ``routed_rows``,
``overlap``, ``kernel_rows``, ``active_rows``).  The last two are counted on
every chip: ``"chip": "sum"`` (the default) adds the chips up, ``"max"``
takes the fullest chip.
"""
from __future__ import annotations

import sys


def collect(spec: dict, live: dict, ctx) -> None:
    ev = ctx.evidence
    if "work" in ev or live.get("booster") is None:
        return
    ev["work"] = None
    try:
        work = live["booster"].work_counters(last=ev["trace_steps"])
    except (AttributeError, TypeError) as exc:
        print(f"benchmark: work_counter: the trainer has no work counters "
              f"({type(exc).__name__}: {exc}); metrics left out",
              file=sys.stderr)
        return
    ev["counters"]["work_counters"] = work
    if work.get("counted") and work.get("trees"):
        ev["work"] = work


def total(work: dict, name: str, chip: str = "sum"):
    """One term of a ratio (module docstring); None where it is not there."""
    if name == "iterations":
        return len(work["iterations"])
    if name in work:
        return work[name]
    vals = [t.get(name) for t in work["trees"]]
    if any(v is None for v in vals):
        return None
    if isinstance(vals[0], list):       # per chip: totals over the trees
        per_chip = [sum(col) for col in zip(*vals)]
        return max(per_chip) if chip == "max" else sum(per_chip)
    return sum(vals)


def product(work: dict, names, chip: str = "sum"):
    out = 1
    for name in ([names] if isinstance(names, str) else names):
        val = total(work, name, chip)
        if val is None:
            return None
        out *= val
    return out


def read(spec: dict, ev: dict):
    work = ev.get("work")
    if not work:
        return None
    chip = spec.get("chip", "sum")
    num = product(work, spec["num"], chip)
    den = product(work, spec.get("den", []), chip)
    if num is None or not den:
        return None
    return float(spec.get("scale", 1.0)) * num / den
