"""The wave kernel alone: full passes over every row the first chip holds.

After the traced window, while the trainer is live, ``collect`` launches the
program's ``hist_pallas_wave`` over the trainer's own resident bins (the first
chip's shard under a mesh) in the trainer's own variant (mode, lane layout,
sibling fusion, block shape from ``select_wave_blocks``) with every leaf lane
in use: one launch to compile, ``LAUNCHES`` traced.  The time is the device
time of the kernel's own instruction in that trace (the Pallas custom call,
``KERNEL_OP``), the median over the launches; the
packing of the row vectors around it is not the kernel and is left out.

``read`` sets it against one of two floors (``harness/costs.py``) at the
chip's published peaks, in percent:

- ``"floor": "bytes"``: the bytes a histogram pass has to move over the peak
  bytes/s: the kernel's roofline;
- ``"floor": "mxu_charged"``: the MACs the one-hot formulation charges the
  MXU over the peak bf16 FLOP/s: how well the kernel runs the work it chose.

The launch is put together from the trainer's private state (``_wave_info``,
``_grow_bins``, ``B_phys``, ``select_wave_blocks``, the keywords of
``hist_pallas_wave``): the program has no public way to launch its kernel as
the trainer does (PERF.md 7 asks the ``tracing`` issue for one).  Where a
later change moves any of it, ``collect`` says so on standard error and the
two metrics are left out of the line; nothing else of the run depends on it.
"""
from __future__ import annotations

import sys

import numpy as np

from harness import costs, trace
from harness.cells import scratch_dir
from harness.clock import median
from harness.device import peaks

LAUNCHES = 3
KERNEL_OP = "^pallas_hist_wave"     # the Pallas call's HLO instruction


def collect(spec: dict, live: dict, ctx) -> None:
    ev = ctx.evidence
    if "fullpass" in ev or live.get("booster") is None:
        return
    ev["fullpass"] = None
    try:
        ev["fullpass"] = _launch(live["booster"], ctx)
    except (AttributeError, ImportError, KeyError, TypeError) as exc:
        print(f"benchmark: fullpass: the trainer's kernel launch moved "
              f"({type(exc).__name__}: {exc}); metric left out",
              file=sys.stderr)


def _launch(bst, ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import pallas_hist as ph

    g = bst._gbdt
    info = g._wave_info
    if not g.uses_wave or not info:
        return None
    bins = g._grow_bins
    if len(bins.sharding.device_set) > 1:
        bins = bins.addressable_shards[0].data
    dev = next(iter(bins.devices()))
    F, N = bins.shape
    B = int(g.B_phys)
    mode, packed = info["hist_mode"], bool(info["packed"])
    fused = bool(info["fused_sibling"])
    block_rows = int(g.config.tpu_block_rows)
    _, fb = ph.select_wave_blocks(B, mode=mode, packed=packed, fused=fused,
                                  block_rows=block_rows)
    P = ph.wave_capacity_max(packed)
    lanes = 2 if packed else 3
    slot = np.full(ph.C_MAX, -1, np.int32)
    slot[:lanes * P] = np.repeat(np.arange(P), lanes)
    with jax.default_device(dev):
        k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
        gv = jax.random.normal(k1, (N,), jnp.float32)
        hv = jax.random.uniform(k2, (N,), jnp.float32, 0.05, 0.25)
        if mode in ph.QUANT_MODES:
            gv, hv = jnp.rint(gv * 100.0), jnp.rint(hv * 400.0)
        leaf = jax.random.randint(k3, (N,), 0, P, jnp.int32)
        cv = jnp.ones((N,), jnp.float32)
        parent = None
        if fused:
            par = jnp.rint(jax.random.normal(k4, (F, B, ph.C_MAX)) * 64.0)
            parent = (par, par) if packed else par
        slot_d = jnp.asarray(slot)

        def launch():
            return ph.hist_pallas_wave(
                bins, gv, hv, cv, leaf, slot_d, B=B, block_rows=block_rows,
                feat_block=fb, highest=mode,
                interpret=bool(info.get("interpret", False)), packed=packed,
                parent=parent)
        jax.block_until_ready(launch())
        tdir = scratch_dir("trace", ctx.cell.name + ".fullpass")
        with trace.capture(tdir):
            for _ in range(LAUNCHES):
                with jax.profiler.TraceAnnotation("bench/fullpass_launch"):
                    jax.block_until_ready(launch())
    parsed = trace.parse_dir(tdir)
    name = trace.device_names(parsed)[0] if parsed["devices"] else None
    per_launch = []
    for s, d, nm in parsed["host"]:
        if nm != "bench/fullpass_launch" or name is None:
            continue
        win = trace.clip(parsed, s, s + d)
        per_launch.append(trace.op_seconds(win, KERNEL_OP, name))
    return {"rows": int(N), "features": int(F), "B": B, "mode": mode,
            "packed": packed, "fused": fused, "feat_block": int(fb),
            "leaves_per_launch": int(P), "kernel_s": per_launch}


def read(spec: dict, ev: dict):
    fp = ev.get("fullpass")
    if not fp or ev["platform"] == "cpu":
        return None
    kernel_s = median([s for s in fp["kernel_s"] if s > 0])
    if not kernel_s:
        return None
    pk = peaks(ev["device_kind"])
    if spec["floor"] == "bytes":
        floor_s = (costs.hist_pass_min_bytes(fp["rows"], fp["features"])
                   / pk["hbm_bytes_per_s"])
    elif spec["floor"] == "mxu_charged":
        floor_s = (costs.wave_kernel_charged_flops(
            fp["rows"], fp["features"], fp["B"], fp["mode"],
            fp["feat_block"], fp["packed"]) / pk["bf16_flops_per_s"])
    else:
        raise ValueError(f"unknown floor {spec['floor']!r}")
    return 100.0 * floor_s / kernel_s
