"""``core/plan.py select_path``: the decision "which growth path runs", as one
table.  A row is (parameters, what the code observes) -> the fields of the
``GrowthPlan`` that the row is about, and the downgrades it must give a reason
for.  No row computes anything with ``jax``: ``select_path`` is pure, and
that is what makes the decision testable as a set.

The first four rows are the benchmark's cells: their parameters and the
``stamps`` they must come out with are read from ``benchmarks/configs/``.
"""
import dataclasses
import json
import os

import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.core import plan as plan_mod
from lightgbm_tpu.core.plan import Facts, GrowthPlan, MixedCols, select_path

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")

# what a chip sees of a narrow, unbundled table (HIGGS: 28 columns, 255 bins)
CHIP = Facts(backend="tpu", num_features=28, num_phys_features=28,
             bin_dtype="uint8", B_phys=256, phys_bins=(255,) * 28)
CPU = dataclasses.replace(CHIP, backend="cpu")
# one column of 300 bins among narrow ones: the binned matrix is uint16
MIXED = dataclasses.replace(CHIP, bin_dtype="uint16", B_phys=512,
                            num_features=4, num_phys_features=4,
                            phys_bins=(255, 300, 63, 12))
# EXPO: 700 one-hot features bundled into 16 physical columns
EXPO = dataclasses.replace(CHIP, num_features=700, num_phys_features=16,
                           phys_bins=(255,) * 16, bundled=True)
CELL_FACTS = {
    "higgs": CHIP,
    "mslr": dataclasses.replace(CHIP, num_features=136,
                                num_phys_features=136,
                                phys_bins=(255,) * 136, query_sharding=True),
    "higgs-dp4": dataclasses.replace(CHIP, mesh_size=4),
    "expo": EXPO,
}
CELL_FACTS["mslr-fp4"] = dataclasses.replace(CELL_FACTS["mslr"], mesh_size=4)


def _plan(params: dict, facts: Facts) -> GrowthPlan:
    return select_path(Config.from_params({"verbose": -1, **params}), facts)


def _keys(plan: GrowthPlan) -> list:
    return [plan_mod.reason_key(r) for r in plan.reasons]


@pytest.mark.parametrize("cell", sorted(CELL_FACTS))
def test_a_cells_parameters_give_its_stamps(cell):
    with open(os.path.join(CONFIGS, cell + ".json")) as fh:
        doc = json.load(fh)
    plan = _plan({"device_type": "tpu", **doc["params"]}, CELL_FACTS[cell])
    want = dict(doc["stamps"])
    want.pop("bins_devices")            # where the bins lie, not the plan
    got = {"uses_wave": plan.wave, **plan.stamps(),
           "bundled": plan.bundled}
    assert {k: got[k] for k in want} == want
    assert plan.reasons == () and plan.batched_apply and plan.counts
    assert plan.wave_capacity == 63 and plan.block_rows == 1024
    assert plan.learner == doc["params"].get("tree_learner", "serial")
    # lambdarank's pair pass is divided by query only under a mesh of more
    # than one chip: the four-chip ranking cell's
    assert plan.rank_sharded_grad is (cell == "mslr-fp4")
    plan.check(data_parallel=plan.learner == "data")


TPU = {"device_type": "tpu"}
LAZY = {"cegb_penalty_feature_lazy": [1.0] * 28, "cegb_tradeoff": 1.0}
ROWS = {
    # ---- the seven downgrades, each with its reason -----------------------
    "quantised_to_2xbf16_under_efb": (
        {**TPU, "tpu_hist_dtype": "int16"}, EXPO,
        dict(grower="wave", hist_mode="2xbf16", fused_sibling=False,
             quant_seed=0), [plan_mod.QUANT_TO_2XBF16]),
    "quantised_to_2xbf16_under_mixed": (
        {**TPU, "tpu_hist_dtype": "int8"}, MIXED,
        dict(grower="wave", hist_mode="2xbf16", packed=False,
             wave_capacity=42), [plan_mod.QUANT_TO_2XBF16]),
    "forced_splits_take_the_serial_grower": (
        TPU, dataclasses.replace(CHIP, forced=True),
        dict(grower="serial", forced=True, hist_fn="onehot", counts=False),
        [plan_mod.FORCED_TO_SERIAL]),
    "lazy_cegb_takes_the_serial_grower": (
        {**TPU, **LAZY}, CHIP, dict(grower="serial", counts=False),
        [plan_mod.LAZY_CEGB_TO_SERIAL]),
    "bynode_sampling_takes_the_serial_grower": (
        {**TPU, "feature_fraction_bynode": 0.5}, CHIP,
        dict(grower="serial", bynode=0.5), [plan_mod.BYNODE_TO_SERIAL]),
    "mixed_widths_under_a_parallel_learner_take_xla": (
        {**TPU, "tree_learner": "data"},
        dataclasses.replace(MIXED, mesh_size=4),
        dict(grower="serial", learner="data", mixed=None, hist_fn="onehot"),
        [plan_mod.PARALLEL_WIDE_TO_XLA]),
    "bynode_and_forced_splits_ignored_under_a_parallel_learner": (
        {**TPU, "tree_learner": "data", "feature_fraction_bynode": 0.5},
        dataclasses.replace(CHIP, forced=True, mesh_size=4),
        dict(grower="wave", learner="data", bynode=None, forced=False,
             fused_sibling=False),
        [plan_mod.BYNODE_IGNORED, plan_mod.FORCED_IGNORED]),
    # ---- what is observed, not asked for ----------------------------------
    "cpu_backend_serial_with_scatter": (
        TPU, CPU, dict(grower="serial", hist_fn="scatter", interpret=False,
                       counts=False), [plan_mod.NO_CHIP]),
    "device_type_cpu_on_a_chip_is_the_plain_oracle": (
        {"device_type": "cpu"}, CHIP,
        dict(grower="serial", hist_fn="onehot"), []),
    "wide_layout_takes_scatter": (
        {"device_type": "cpu"},
        dataclasses.replace(CHIP, num_phys_features=200,
                            phys_bins=(255,) * 200),
        dict(grower="serial", hist_fn="scatter"), []),
    "interpret_hook_runs_the_wave_path_anywhere": (
        {"device_type": "cpu"}, dataclasses.replace(CPU,
                                                    force_wave="interpret"),
        dict(grower="wave", interpret=True, fused_sibling=True, counts=True),
        []),
    "mixed_widths_on_one_chip_stay_on_the_kernel": (
        TPU, MIXED,
        dict(grower="wave", packed=False, wave_capacity=42,
             fused_sibling=False,
             mixed=MixedCols(narrow=(0, 2, 3), wide=(1,), B_narrow=256)),
        []),
    "every_column_wide_takes_xla": (
        TPU, dataclasses.replace(MIXED, phys_bins=(300, 300, 300, 300)),
        dict(grower="serial", mixed=None), []),
    "capacity_is_clamped_to_the_layout": (
        {**TPU, "tpu_wave_capacity": 100}, CHIP,
        dict(wave_capacity=63, packed=True), []),
    "quantised_on_the_pure_kernel_keeps_its_mode_and_seed": (
        {**TPU, "tpu_hist_dtype": "int16", "seed": 7}, CHIP,
        dict(grower="wave", hist_mode="int16", quant_seed=7), []),
    "the_seed_is_no_part_of_an_f32_plan": (
        {**TPU, "seed": 7}, CHIP, dict(quant_seed=0), []),
    "gpu_use_dp_outranks_a_quantisation_ask": (
        {**TPU, "tpu_hist_dtype": "int16", "gpu_use_dp": True}, CHIP,
        dict(hist_mode="highest"), []),
    "split_cegb_keeps_the_wave_path_uncounted": (
        {**TPU, "cegb_penalty_split": 1e-6}, CHIP,
        dict(grower="wave", counts=False), []),
    "voting_learner_never_takes_the_wave_kernel": (
        {**TPU, "tree_learner": "voting"},
        dataclasses.replace(CHIP, mesh_size=4),
        dict(grower="serial", learner="voting", hist_fn="onehot"),
        [plan_mod.VOTING_TO_XLA]),
    "feature_learner_takes_the_wave_kernel": (
        {**TPU, "tree_learner": "feature"},
        dataclasses.replace(CHIP, mesh_size=4),
        dict(grower="wave", learner="feature", fused_sibling=True,
             packed=True, rank_sharded_grad=False, counts=True), []),
    "feature_learner_with_wide_columns_takes_xla": (
        {**TPU, "tree_learner": "feature"},
        dataclasses.replace(MIXED, mesh_size=4),
        dict(grower="serial", learner="feature", mixed=None,
             fused_sibling=False), [plan_mod.PARALLEL_WIDE_TO_XLA]),
    "feature_learner_with_a_categorical_column_takes_xla": (
        {**TPU, "tree_learner": "feature"},
        dataclasses.replace(CHIP, mesh_size=4, categorical=True),
        dict(grower="serial", learner="feature", fused_sibling=False,
             counts=False), [plan_mod.FEATURE_CAT_TO_XLA]),
    "lambdarank_under_a_mesh_shards_its_pair_pass": (
        {**TPU, "objective": "lambdarank", "tree_learner": "data"},
        dataclasses.replace(CHIP, mesh_size=4, query_sharding=True),
        dict(grower="wave", rank_sharded_grad=True), []),
    "a_mesh_of_one_does_not": (
        {**TPU, "objective": "lambdarank", "tree_learner": "data"},
        dataclasses.replace(CHIP, mesh_size=1, query_sharding=True),
        dict(rank_sharded_grad=False, fused_sibling=False), []),
    "lambdarank_under_the_feature_learner_deals_its_pair_pass": (
        {**TPU, "objective": "lambdarank", "tree_learner": "feature"},
        dataclasses.replace(CHIP, mesh_size=4, query_sharding=True),
        dict(grower="wave", learner="feature", rank_sharded_grad=True), []),
    "the_feature_learner_on_one_chip_does_not": (
        {**TPU, "objective": "lambdarank", "tree_learner": "feature"},
        dataclasses.replace(CHIP, mesh_size=1, query_sharding=True),
        dict(learner="feature", rank_sharded_grad=False), []),
    "a_binary_objective_under_the_feature_learner_has_no_pair_pass": (
        {**TPU, "objective": "binary", "tree_learner": "feature"},
        dataclasses.replace(CHIP, mesh_size=4),
        dict(learner="feature", rank_sharded_grad=False), []),
    "goss_and_rf_keep_gradients_outside_the_growth_jit": (
        TPU, dataclasses.replace(CHIP, fused_grad_ok=False),
        dict(grower="wave", fused_grad=False), []),
    "no_features_nothing_to_grow_on_the_kernel": (
        TPU, dataclasses.replace(CHIP, num_features=0, num_phys_features=0,
                                 phys_bins=()),
        dict(grower="serial", learner="serial"), []),
}


@pytest.mark.parametrize("row", sorted(ROWS))
def test_decision_table(row):
    params, facts, want, reasons = ROWS[row]
    plan = _plan(params, facts)
    assert {k: getattr(plan, k) for k in want} == want
    assert _keys(plan) == reasons
    for reason in plan.reasons:        # a level to log it at, and a text
        assert plan_mod.REASON_LEVEL[plan_mod.reason_key(reason)] in (
            "info", "warning")
        assert len(reason.partition(": ")[2]) > 20
    if plan.wave:
        plan.check(data_parallel=plan.learner == "data")
        assert set(plan.stamps()) == {"hist_mode", "wave_capacity", "packed",
                                      "fused_sibling", "interpret",
                                      "fused_grad"}
    else:
        assert plan.stamps() is None


def test_the_plan_is_a_value():
    """Frozen and hashable; equal parameters and facts give equal plans and
    one cache key; what only the trainer reads (``fused_grad``, the
    reasons) is no part of the compiled grower's key."""
    a, b = _plan(TPU, CHIP), _plan(TPU, CHIP)
    assert a == b and hash(a) == hash(b) and a.key() == b.key()
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.grower = "serial"
    assert dataclasses.replace(a, fused_grad=False).key() == a.key()
    for field in ("batched_apply", "fused_sibling", "hist_mode",
                  "wave_capacity", "gain_gate", "block_rows"):
        other = {"batched_apply": False, "fused_sibling": False,
                 "hist_mode": "bf16", "wave_capacity": 7, "gain_gate": 0.25,
                 "block_rows": 512}[field]
        assert dataclasses.replace(a, **{field: other}).key() != a.key()


# The plan of each cell the benchmark had before the feature learner took
# the kernel (PR 40), as ``select_path`` gave it then: the fields that differ
# from ``GrowthPlan()`` and the reasons.  Facts as the trainer observes each
# table (expo-cat: six narrow code columns and two of 291 / 292 bins).
ACCEPTED = {
    "higgs": (CHIP, dict(counts=True, gain_gate=0.5), []),
    "mslr": (CELL_FACTS["mslr"],
             dict(counts=True, hist_fn="scatter", gain_gate=0.5), []),
    "higgs-dp4": (CELL_FACTS["higgs-dp4"],
                  dict(learner="data", fused_sibling=False, counts=True,
                       gain_gate=0.5), []),
    "expo": (EXPO, dict(fused_sibling=False, bundled=True, counts=True,
                        gain_gate=0.5), []),
    "higgs-goss": (dataclasses.replace(CHIP, fused_grad_ok=False),
                   dict(fused_grad=False, counts=True, gain_gate=0.5),
                   [plan_mod.BOOSTER_UNFUSED_GRAD]),
    "expo-cat": (dataclasses.replace(
        CHIP, num_features=8, num_phys_features=8, bin_dtype="uint16",
        B_phys=512, phys_bins=(292, 291, 25, 14, 7, 6, 255, 255),
        categorical=True),
        dict(packed=False, wave_capacity=42, fused_sibling=False,
             mixed=MixedCols((2, 3, 4, 5, 6, 7), (0, 1), 256), counts=True,
             gain_gate=0.5), []),
    "higgs-bin63": (dataclasses.replace(CHIP, B_phys=64,
                                        phys_bins=(63,) * 28),
                    dict(counts=True, gain_gate=0.5), []),
}


@pytest.mark.parametrize("cell", sorted(ACCEPTED))
def test_an_accepted_cells_plan_is_unchanged(cell):
    with open(os.path.join(CONFIGS, cell + ".json")) as fh:
        doc = json.load(fh)
    facts, differs, reasons = ACCEPTED[cell]
    plan = _plan({"device_type": "tpu", **doc["params"]}, facts)
    assert plan == dataclasses.replace(GrowthPlan(), **differs,
                                       reasons=plan.reasons)
    assert _keys(plan) == reasons


# The wave kernel's shape each cell's plan says (``GrowthPlan.kernel``, the
# rule ``hist_pallas_wave`` launches by): the fused block of 8 widened to
# the cheapest block that fits the VMEM cap, HIGGS's 28 columns one block of
# 28 and MSLR's 136 five of 28; the feature learner deals MSLR's whole blocks
# of 8; the unfused and mixed blocks cut to the columns
KERNEL = {"higgs": (256, 28, 1, 28), "higgs-goss": (256, 28, 1, 28),
          "higgs-dp4": (256, 28, 1, 28), "higgs-bin63": (64, 28, 2, 28),
          "mslr": (256, 28, 1, 140), "mslr-fp4": (256, 8, 1, 136),
          "expo": (256, 16, 1, 16), "expo-cat": (256, 6, 1, 6)}


@pytest.mark.parametrize("cell", sorted(KERNEL))
def test_a_cells_plan_says_its_kernel_shape(cell):
    with open(os.path.join(CONFIGS, cell + ".json")) as fh:
        doc = json.load(fh)
    facts = (CELL_FACTS[cell] if cell in CELL_FACTS else ACCEPTED[cell][0])
    plan = _plan({"device_type": "tpu", **doc["params"]}, facts)
    if plan.mixed is not None:
        shape = plan.kernel(plan.mixed.B_narrow, len(plan.mixed.narrow))
    else:
        shape = plan.kernel(facts.B_phys, facts.num_phys_features)
    assert tuple(shape) == KERNEL[cell]
