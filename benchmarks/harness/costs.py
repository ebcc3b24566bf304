"""What a histogram pass needs, and what the kernel is charged.

Two floors for one full pass of the wave kernel over ``rows`` x ``F``:

- ``hist_pass_min_bytes``: what the algorithm has to move.  Every bin once
  (one byte each) and a gradient and a hessian per row (two float32).  The
  histograms themselves are kilobytes.  Over the peak bytes/s this is the
  roofline of a histogram pass, whatever the formulation.
- ``wave_kernel_charged_flops``: a copy of the program's ``wave_kernel_cost``
  (``ops/pallas_hist.py``; listed in PERF.md for deletion there).  It counts
  the MACs the MXU is *charged* for the one-hot contraction, of which 255 in
  256 multiply a zero: a floor of this formulation, not of the problem.
"""
from __future__ import annotations

C_MAX = 128                      # output lanes of one MXU pass
_MXU_PASSES = {"highest": 3, "2xbf16": 2, "bf16": 1, "int16": 2, "int8": 1}


def hist_pass_min_bytes(rows: int, F: int) -> float:
    return float(rows) * F + 8.0 * float(rows)


def _feat_pack(B: int, feat_block: int) -> int:
    pack = max(1, C_MAX // B)
    return pack if C_MAX % B == 0 and feat_block % pack == 0 else 1


def wave_kernel_charged_flops(rows: int, F: int, B: int, mode: str,
                              feat_block: int, packed: bool) -> float:
    passes = _MXU_PASSES[mode] + (1 if packed else 0)
    pack = _feat_pack(B, feat_block)
    lanes = max(pack * B, C_MAX) / pack
    return passes * 2.0 * float(rows) * F * lanes * C_MAX
