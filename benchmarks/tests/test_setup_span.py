"""The reader of the program's set-up record (reducers/setup_span.py) over a
hand-made record: a span's self time, the sums over the program records, the
``update`` spans' rest, the roots' unattributed share, and nothing where the
trainer has no accessor (the parent of the PR that added it)."""
import types

import pytest

from harness import cells
from reducers import setup_span

SPECS = {s["name"]: s for s in cells.layer_metric_specs()}


def _span(sid, name, t, dur, parent=None, **attrs):
    return {"name": name, "t": t, "dur_s": dur, "span_id": sid,
            "parent_id": parent, "attrs": attrs}


def _prog(seq, name, t, trace, lower, backend, cache, parent=None):
    return {"seq": seq, "fun_name": name, "t": t, "trace_s": trace,
            "lower_s": lower, "backend_s": backend, "cache": cache,
            "retrieval_s": 0.25 if cache == "hit" else 0.0,
            "saved_s": 9.0 if cache == "hit" else 0.0, "parent_id": parent}


TRACE = {
    "clock": "unix_s", "programs_seen": 5, "programs_at_update": 4,
    "dropped_spans": 0,
    "spans": [
        _span("a", "setup/dataset", 100.0, 10.0, path="from_matrix"),
        _span("b", "sample", 100.0, 1.0, "a"),
        _span("c", "bin_find", 101.5, 2.0, "a", features=28),
        _span("d", "bundle", 103.5, 0.5, "a"),
        _span("e", "binarize", 104.0, 5.5, "a", rows=1000),
        _span("f", "setup/booster", 111.0, 4.0),
        _span("g", "place_bins", 111.5, 2.0, "f", bytes=28000),
        _span("h", "place_scores", 114.0, 0.5, "f"),
        _span("i", "update", 116.0, 12.0, iteration=0, first_program=1,
              programs=2),
        _span("j", "update", 140.0, 3.0, iteration=11, first_program=3,
              programs=1)],
    "programs": [
        _prog(0, "jit(convert_element_type)", 111.6, 0.0, 0.1, 0.2, "off",
              "g"),
        _prog(1, "jit(_mean)", 116.0, 0.1, 0.2, 0.3, "hit", "i"),
        _prog(2, "jit(grow_apply)", 117.0, 4.0, 3.0, 2.0, "hit", "i"),
        _prog(3, "jit(goss_sample)", 140.0, 0.5, 0.5, 1.5, "miss", "j"),
        # after the newest update: a reader's own launch, not set-up's
        _prog(4, "jit(hist_pallas_wave)", 150.0, 0.1, 0.3, 29.0, "miss")],
}


def _read(name, trace=TRACE):
    return setup_span.read(SPECS[name], {"setup_trace": trace})


def test_self_time_is_the_span_less_its_children():
    selfs = setup_span.self_seconds(TRACE)
    assert selfs["a"] == pytest.approx(10.0 - 1.0 - 2.0 - 0.5 - 5.5)
    assert selfs["f"] == pytest.approx(4.0 - 2.0 - 0.5)
    assert selfs["c"] == 2.0
    assert _read("setup.bin_find_s") == pytest.approx(3.0)
    assert _read("setup.bin_group_s") == pytest.approx(0.5)
    assert _read("setup.binarize_s") == pytest.approx(5.5)
    assert _read("setup.place_s") == pytest.approx(2.5)
    # a child that outlasts its parent counts as far as it lies inside it
    late = {**TRACE, "spans": [_span("a", "setup/dataset", 0.0, 2.0),
                               _span("b", "binarize", 1.5, 1.0, "a")]}
    assert setup_span.self_seconds(late)["a"] == pytest.approx(1.5)


def test_program_sums_counts_and_the_updates_rest():
    assert _read("setup.program_trace_s") == pytest.approx(4.6)
    assert _read("setup.program_lower_s") == pytest.approx(3.8)
    assert _read("setup.program_load_s") == pytest.approx(4.0)
    assert _read("setup.programs") == 4.0
    assert _read("setup.cache_misses") == 1.0
    first, goss = setup_span.update_splits(TRACE)
    assert first["iteration"] == 0 and first["programs"] == 2
    assert first["rest_s"] == pytest.approx(12.0 - 0.6 - 9.0)
    assert goss["rest_s"] == pytest.approx(3.0 - 2.5)
    assert _read("setup.update_rest_s") == pytest.approx(2.4 + 0.5)


def test_unattributed_share_of_the_roots():
    assert _read("setup.unattributed_share") == pytest.approx(
        100.0 * (1.0 + 1.5) / (10.0 + 4.0))


def test_summary_is_what_the_detail_line_prints():
    out = setup_span.summary(TRACE)
    assert [s["name"] for s in out["spans"]][:2] == ["setup/dataset",
                                                     "sample"]
    assert out["spans"][2] == {"name": "bin_find", "at_s": 1.5, "dur_s": 2.0,
                               "self_s": 2.0, "under": "setup/dataset",
                               "attrs": {"features": 28}}
    assert out["updates"][0]["trace_s"] == pytest.approx(4.1)
    assert [p["fun_name"] for p in out["programs_10ms"]][-2:] == [
        "jit(goss_sample)", "jit(hist_pallas_wave)"]
    assert out["programs_10ms"][2]["under"] == "update"
    assert (out["programs_seen"], out["programs_at_update"]) == (5, 4)


class _Ctx:
    def __init__(self):
        self.evidence = {"counters": {}}


def test_collect_asks_once_and_a_parent_leaves_the_metrics_out(capsys):
    asked = []

    def accessor():
        asked.append(1)
        return TRACE

    ctx = _Ctx()
    live = {"booster": types.SimpleNamespace(setup_trace=accessor)}
    for name in ("setup.bin_find_s", "setup.programs"):
        setup_span.collect(SPECS[name], live, ctx)
    assert asked == [1]
    assert ctx.evidence["counters"]["setup_trace"]["programs_seen"] == 5
    assert setup_span.read(SPECS["setup.place_s"], ctx.evidence) == 2.5
    # the parent: a Booster without the accessor
    old = _Ctx()
    setup_span.collect(SPECS["setup.programs"],
                       {"booster": types.SimpleNamespace()}, old)
    assert "no setup_trace()" in capsys.readouterr().err
    assert "setup_trace" not in old.evidence["counters"]
    for name, spec in SPECS.items():
        if spec["reducer"] == "setup_span":
            assert setup_span.read(spec, old.evidence) is None, name
    # a cell without the span leaves that metric out, and no other
    bare = {**TRACE, "spans": [s for s in TRACE["spans"]
                               if s["name"] != "bundle"]}
    assert _read("setup.bin_group_s", bare) is None
    assert _read("setup.binarize_s", bare) == pytest.approx(5.5)


def test_start_is_read_off_the_benchmarks_own_clock():
    from reducers import host_clock
    spec = SPECS["setup.start_s"]
    assert (spec["reducer"], spec["key"], spec["moves"]) == (
        "host_clock", "start_s", "setup_s")
    assert host_clock.read(spec, {"host": {"start_s": 12.5}}) == 12.5
    assert host_clock.read(spec, {"host": {}}) is None
