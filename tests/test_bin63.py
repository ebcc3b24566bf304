"""The wave path at ``max_bin=63``, the setting of the benchmark's
``higgs-bin63-train``: 64 bin lanes a feature, two features' one-hot factors
an MXU pass, 28 columns in one block (not a multiple of the 32
``select_wave_blocks`` gives: the kernel cuts the block to the columns there
are).  The cell's parameters at toy size, the kernel interpreted, against the
serial XLA grower, staged as the cell's check (c) stages it: each iteration
grown by both from the same scores.

The hessian bound is scaled with the rows (8 over 16,000 rows: 354 leaves, as
100 over the cell's slice of 262,144 gives some 495) and the leaf cap raised
over it, as the cell's check raises it, so that the cap does not bind and
both growers grow the same tree up to ties; no gain gate, so that a phase commits every ready leaf and a launch
holds up to 63: launches of one, two and three MXU passes.  A CPU run gives
counts and correctness, never a time.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.core import wave_grower

ROWS, FEATURES, STAGES = 16000, 28, 2
HESSIAN_MIN = 8.0
PARAMS = {"objective": "binary", "num_leaves": 511, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": HESSIAN_MIN, "tpu_wave_gain_gate": 0.0,
          "verbose": -1}
SCORE_MED_MAX = 5e-6    # the benchmark's limit at 256 and at 64 lanes


def _table():
    """HIGGS-shaped: 8 of 28 lognormal columns are noisy views of one latent
    score, the label its sign under noise."""
    rng = np.random.default_rng(63)
    z = rng.standard_normal(ROWS)
    X = rng.standard_normal((ROWS, FEATURES))
    X[:, :8] = 0.5 * z[:, None] + np.sqrt(0.75) * X[:, :8]
    y = (2.0 * z + rng.standard_normal(ROWS) > 0).astype(np.float32)
    return np.exp(X).astype(np.float32), y


def _fit(params, X, y, init):
    ds = lgb.Dataset(X, label=y, init_score=init, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()
    return bst


def _tree(bst) -> dict:
    fields = dict(line.split("=", 1)
                  for line in bst.model_to_string().split("\nTree=")[1]
                  .split("end of trees")[0].splitlines() if "=" in line)
    return {"leaves": int(fields["num_leaves"]),
            "root": (int(fields["split_feature"].split()[0]),
                     float(fields["threshold"].split()[0])),
            "leaf_weight": np.asarray(fields["leaf_weight"].split(),
                                      np.float64)}


@pytest.fixture(scope="module")
def staged():
    """``{mode: [stage, ...]}``: every stage's path (``2xbf16``, the cell's,
    and one bf16 pass) and oracle from the oracle's scores so far, with the
    pending leaves of every kernel launch."""
    mp = pytest.MonkeyPatch()
    launches = []
    real = wave_grower.hist_pallas_wave

    def recording(bins, gv, hv, cv, leaf, slot_leaf, **kw):
        jax.debug.callback(lambda n: launches.append(int(n)),
                           jnp.sum(slot_leaf[::2] >= 0))
        return real(bins, gv, hv, cv, leaf, slot_leaf, **kw)
    mp.setattr(wave_grower, "hist_pallas_wave", recording)
    X, y = _table()
    out = {"2xbf16": [], "bf16": []}
    try:
        init = None
        for _ in range(STAGES):
            mp.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
            slow = _fit({**PARAMS, "device_type": "cpu"}, X, y, init)
            assert not slow.work_counters(last=0)["stamps"]["uses_wave"]
            ref = slow._raw_train_score()
            mp.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
            for mode in out:
                launches.clear()
                fast = _fit({**PARAMS, "tpu_hist_dtype": mode}, X, y, init)
                raw = fast._raw_train_score()
                jax.effects_barrier()
                out[mode].append({
                    "work": fast.work_counters(last=1),
                    "pending": list(launches),
                    "path": _tree(fast), "oracle": _tree(slow),
                    "diff": np.abs(raw - ref) / np.std(ref)})
            init = ref
        yield out
    finally:
        mp.undo()


def test_the_trainer_says_the_packed_kernel(staged):
    work = staged["2xbf16"][0]["work"]
    assert work["stamps"]["uses_wave"] and work["stamps"]["fused_sibling"]
    assert work["stamps"]["hist_mode"] == "2xbf16"
    assert (work["kernel_bins"], work["feat_block"], work["feat_pack"],
            work["kernel_columns"]) == (64, FEATURES, 2, FEATURES)


def test_launches_run_one_two_and_three_passes(staged):
    for st in staged["2xbf16"]:
        passes = [-(-n // 25) for n in st["pending"]]
        assert set(passes) == {1, 2, 3}, st["pending"]
        tree, = st["work"]["trees"]
        assert len(passes) == tree["waves"]
        assert sum(st["pending"]) == tree["lanes"] == st["path"]["leaves"]
        assert tree["kernel_pass_rows"][0] > tree["kernel_rows"][0]


@pytest.mark.parametrize("stage", range(STAGES))
def test_same_tree_as_the_serial_grower_up_to_ties(staged, stage):
    st = staged["2xbf16"][stage]
    path, oracle = st["path"], st["oracle"]
    assert path["root"] == oracle["root"]
    # the hessian bound ends the growth, not the leaf cap; a node whose sum
    # is within rounding of the bound falls either way
    assert max(path["leaves"], oracle["leaves"]) < PARAMS["num_leaves"]
    assert abs(path["leaves"] - oracle["leaves"]) <= 0.02 * oracle["leaves"]
    for tree in (path, oracle):
        assert tree["leaf_weight"].min() >= HESSIAN_MIN * (1 - 1e-3)
    # a tie moves its own node's rows; the arithmetic moves every row, a
    # little: the benchmark's median
    assert np.median(st["diff"]) <= SCORE_MED_MAX
    assert np.mean(st["diff"] > 1e-3) < 0.1


@pytest.mark.parametrize("stage", range(STAGES))
def test_one_bf16_pass_fails_the_same_check(staged, stage):
    st = staged["bf16"][stage]
    assert st["work"]["stamps"]["hist_mode"] == "bf16"
    assert st["work"]["feat_pack"] == 2
    assert np.median(st["diff"]) > 10 * SCORE_MED_MAX
