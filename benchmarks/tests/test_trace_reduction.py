"""The reduction from a parsed trace to numbers (harness/trace.py): on a trace
small enough to work out by hand, and on the chip trace recorded beside it
(fixtures/), whose numbers were first read by hand from the profile."""
import gzip
import json
import os

import pytest

from harness import trace

FIX = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "fixtures")

# one chip, times in ns.  A `while` spans two body ops; a gap of 20 inside the
# program; then 100 of host time between two programs.
HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [
            [0, 100, "while.1", ["lgbm/wave_split_phase"], "core/wave_grower.py:958"],
            [10, 35, "fusion.2", ["lgbm/wave_split_phase", "lgbm/split_scan"], ""],
            [50, 40, "pallas_hist_wave.3", ["lgbm/wave_hist", "lgbm/pallas_hist_wave"], ""],
            [120, 30, "fusion.4", [], "core/wave_grower.py:958"],
            [250, 50, "all-reduce.5", [], "all-reduce"],
        ],
        "modules": [[0, 150, "jit_grow_apply"], [250, 50, "jit_grow_apply"]]}},
    "host": [[0, 300, "bench/traced_window"], [0, 160, "bench/update"],
             [160, 140, "bench/sync"]],
}


def test_self_time_takes_children_out_of_a_loop():
    nested = trace.nest(HAND["devices"]["/device:TPU:0"]["ops"])
    assert {op[2]: t for t, _, op in nested} == {
        "while.1": 25, "fusion.2": 35, "pallas_hist_wave.3": 40,
        "fusion.4": 30, "all-reduce.5": 50}
    assert [op[2] for _, spans, op in nested if spans] == ["while.1"]


def test_busy_is_the_union_of_what_spans_nothing():
    # the loop is not work: its body's two instructions are
    assert trace.busy_seconds(HAND) == pytest.approx(155e-9)
    t0, t1 = trace.window_of(HAND, "bench/traced_window")
    assert (t0, t1) == (0, 300)


def test_scopes_ops_and_gaps():
    dev = "/device:TPU:0"
    assert trace.scope_seconds(HAND, ["lgbm/split_scan"]) == \
        pytest.approx(35e-9)
    assert trace.scope_seconds(HAND, ["lgbm/wave_split_phase",
                                      "lgbm/split_scan"]) == pytest.approx(60e-9)
    # innermost scope only: the kernel is not also charged to wave_hist
    assert trace.scope_seconds(HAND, ["lgbm/wave_hist"]) == 0.0
    assert trace.scope_seconds(HAND, ["lgbm/pallas_hist_wave"]) == \
        pytest.approx(40e-9)
    assert trace.scope_seconds(HAND, ["lgbm/grad"]) == 0.0
    assert trace.op_seconds(HAND, "all-reduce", dev) == pytest.approx(50e-9)
    assert trace.top_device_ops(HAND, 3) == [
        ["unscoped:all-reduce:all-reduce", pytest.approx(50e-9)],
        ["lgbm/pallas_hist_wave", pytest.approx(40e-9)],
        ["lgbm/split_scan", pytest.approx(35e-9)]]
    gaps = dict(trace.idle_gaps(HAND, 0, 300))
    # 0..10, 45..50 and 90..120 inside the first program; 150..250 while the
    # host waits in its sync
    assert gaps == {"device:in_program": pytest.approx(45e-9),
                    "bench/sync": pytest.approx(100e-9)}


def test_no_scope_at_all_is_not_measured():
    bare = {"devices": {"d": {"ops": [[0, 10, "fusion.1", [], ""]],
                              "modules": []}}, "host": []}
    assert trace.scope_seconds(bare, ["lgbm/split_scan"]) is None


def test_clip_cuts_events_at_the_edges():
    win = trace.clip(HAND, 60, 130)
    ops = win["devices"]["/device:TPU:0"]["ops"]
    assert [o[:3] for o in ops] == [[60, 40, "while.1"],
                                    [60, 30, "pallas_hist_wave.3"],
                                    [120, 10, "fusion.4"]]


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    """One iteration on a v5e (fixtures/expected.json says of what).  ``*_s``
    are this reduction's own numbers, pinned; ``second_way`` was worked out
    apart from it when the trace was read by hand: times and names through
    ``jax.profiler.ProfileData`` (whole ns), loops and branches told by their
    names, the union by sorting in NumPy, only the instructions that span no
    other summed."""
    with open(os.path.join(FIX, "expected.json")) as fh:
        want = json.load(fh)
    with gzip.open(os.path.join(FIX, want["trace"]), "rt") as fh:
        parsed = json.load(fh)
    assert trace.device_names(parsed) == want["devices"]
    t0, t1 = trace.window_of(parsed, "bench/traced_window")
    win = trace.clip(parsed, t0, t1)
    dev = want["devices"][0]
    assert (t1 - t0) / 1e9 == pytest.approx(want["window_s"], rel=1e-9)
    busy = trace.busy_seconds(win)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert busy == pytest.approx(want["second_way"]["busy_s"], rel=1e-4)
    for scope, secs in want["scope_s"].items():
        got = trace.scope_seconds(win, [scope])
        assert got == pytest.approx(secs, rel=1e-9), scope
        assert got == pytest.approx(
            want["second_way"]["leaf_scope_s"][scope], rel=1e-3), scope
    kern = trace.op_seconds(win, "^pallas_hist_wave", dev)
    assert kern == pytest.approx(want["op_s"]["^pallas_hist_wave"], rel=1e-9)
    assert kern == pytest.approx(want["second_way"]["kernel_s"], rel=1e-4)
    # every nanosecond of a program is some instruction's own, or a gap
    nested = trace.nest(win["devices"][dev]["ops"])
    assert {op[2].split(".")[0] for _, spans, op in nested if spans} == \
        {"while", "cond"}
    labels = dict(trace.top_device_ops(win, 3))
    assert "lgbm/wave_partition" in labels and "lgbm/pallas_hist_wave" in labels
    gaps = dict(trace.idle_gaps(win, t0, t1))
    assert sum(gaps.values()) == pytest.approx((t1 - t0) / 1e9 - busy,
                                               rel=1e-6)
    assert set(gaps) <= {"device:in_program", "bench/update", "bench/sync",
                         "bench/traced_window"}
