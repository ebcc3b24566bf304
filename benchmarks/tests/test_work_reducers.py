"""The two readers of the program's work counters (reducers/work_counter.py,
reducers/trace_scope_ns_per_row.py) over made-up evidence: a count, a share,
a time per row, the fullest chip of four, and nothing where the accessor, the
counters or the scope is missing."""
import pytest

from harness import cells
from reducers import trace_scope_ns_per_row, work_counter

SPECS = {s["name"]: s for s in cells.layer_metric_specs()}


def _tree(it, bodies, waves, lanes, routed, kernel, active):
    return {"iteration": it, "class_id": 0, "bodies": bodies, "waves": waves,
            "lanes": lanes, "overlap": 0, "routed_rows": routed,
            "kernel_rows": kernel, "active_rows": active}


ONE = {"counted": True, "iterations": [4, 5], "rows": 1000,
       "rows_per_chip": 1000, "chips": 1, "wave_capacity": 63,
       "block_rows": 1024,
       "trees": [_tree(4, 10, 9, 255, 6000, [5000], [4000]),
                 _tree(5, 14, 12, 200, 9000, [7000], [5000])]}
FOUR = {**ONE, "rows": 4000, "chips": 4,
        "trees": [_tree(4, 10, 10, 255, 24000, [5000, 5000, 6000, 5000],
                        [4000, 4100, 4200, 3900]),
                  _tree(5, 10, 10, 255, 24000, [5000, 5000, 5000, 5000],
                        [4000, 4000, 4000, 4000])]}

# one chip, ns: 600 of partition inside a loop, 240 of kernel
TRACE = {"devices": {"/device:TPU:0": {"ops": [
    [0, 1000, "while.1", [], "core/wave_grower.py:958"],
    [0, 600, "fusion.2", ["lgbm/wave_split_phase", "lgbm/wave_partition"], ""],
    [700, 240, "pallas_hist_wave.3", ["lgbm/wave_hist",
                                      "lgbm/pallas_hist_wave"], ""]],
    "modules": [[0, 1000, "jit_grow_apply"]]}},
    "host": [[0, 1000, "bench/traced_window"]]}


def _ev(work, traced=TRACE):
    return {"work": work, "trace": traced, "trace_steps": 2,
            "platform": "tpu", "counters": {}}


def test_counts_and_shares_on_one_chip():
    ev = _ev(ONE)
    read = lambda name: work_counter.read(SPECS[name], ev)
    assert read("grower.bodies_per_iter") == 12.0            # (10 + 14) / 2
    assert read("grower.partition_routed_share") == \
        pytest.approx(100 * 15000 / (1000 * 24))
    assert read("kernel.tier_fill_share") == pytest.approx(100 * 9000 / 12000)
    assert read("kernel.lane_fill_share") == \
        pytest.approx(100 * 455 / (21 * 63))


def test_times_per_row():
    ev = _ev(ONE)
    read = lambda name: trace_scope_ns_per_row.read(SPECS[name], ev)
    # 600 ns of partition over 1000 rows a chip x 24 bodies
    assert read("grower.partition_ns_per_row") == pytest.approx(600 / 24000)
    # 240 ns of kernel over the 12,000 rows the launches covered
    assert read("kernel.hist_ns_per_row") == pytest.approx(240 / 12000)


def test_four_chips_sum_for_shares_and_the_fullest_for_the_kernel():
    ev = _ev(FOUR)
    assert work_counter.total(FOUR, "kernel_rows") == 41000
    assert work_counter.total(FOUR, "kernel_rows", "max") == 11000  # chip 2
    assert work_counter.read(SPECS["kernel.tier_fill_share"], ev) == \
        pytest.approx(100 * 32200 / 41000)
    # routed_rows and rows are the mesh's, bodies are replicated
    assert work_counter.read(SPECS["grower.partition_routed_share"], ev) == \
        pytest.approx(100 * 48000 / (4000 * 20))
    assert trace_scope_ns_per_row.read(SPECS["kernel.hist_ns_per_row"], ev) \
        == pytest.approx(240 / 11000)
    assert trace_scope_ns_per_row.read(
        SPECS["grower.partition_ns_per_row"], ev) == \
        pytest.approx(600 / (1000 * 20))


@pytest.mark.parametrize("name", [
    "grower.bodies_per_iter", "grower.partition_routed_share",
    "kernel.tier_fill_share", "kernel.lane_fill_share",
    "grower.partition_ns_per_row", "kernel.hist_ns_per_row"])
def test_nothing_where_nothing_was_counted(name):
    spec = SPECS[name]
    mod = {"work_counter": work_counter,
           "trace_scope_ns_per_row": trace_scope_ns_per_row}[spec["reducer"]]
    assert mod.read(spec, _ev(None)) is None
    assert mod.read(spec, {"trace": TRACE, "trace_steps": 2}) is None
    if mod is trace_scope_ns_per_row:
        # counters but no trace; a trace that resolves no such scope
        assert mod.read(spec, _ev(ONE, None)) is None
        bare = {"devices": {"/device:TPU:0": {"ops": [
            [0, 100, "fusion.1", [], ""]], "modules": []}}, "host": []}
        assert mod.read(spec, _ev(ONE, bare)) is None


class _Ctx:
    def __init__(self):
        self.evidence = {"counters": {}, "trace_steps": 2}


def test_collect_asks_for_the_traced_iterations_once():
    asked = []

    class Booster:
        def work_counters(self, last=None):
            asked.append(last)
            return ONE
    ctx = _Ctx()
    for mod in (work_counter, trace_scope_ns_per_row, work_counter):
        mod.collect({}, {"booster": Booster()}, ctx)
    assert asked == [2]
    assert ctx.evidence["work"] is ONE
    assert ctx.evidence["counters"]["work_counters"] is ONE


def test_collect_leaves_nothing_for_a_program_without_the_accessor(capsys):
    ctx = _Ctx()
    work_counter.collect({}, {"booster": object()}, ctx)   # the parent's
    assert ctx.evidence["work"] is None
    assert "work_counters" not in ctx.evidence["counters"]
    assert "no work counters" in capsys.readouterr().err

    class NotCounting:
        def work_counters(self, last=None):
            return {"counted": False, "iterations": [], "trees": []}
    ctx = _Ctx()
    work_counter.collect({}, {"booster": NotCounting()}, ctx)
    assert ctx.evidence["work"] is None
    assert ctx.evidence["counters"]["work_counters"]["counted"] is False
    ctx = _Ctx()
    work_counter.collect({}, {}, ctx)
    assert "work" not in ctx.evidence
