"""GOSS on the wave path (``boosting/goss.py``): the jitted sampler against
the benchmark's plain reference (``benchmarks/harness/reference_goss.py``:
NumPy, float64, nothing of the program in it) and the trainer under a bag
against the serial XLA grower.

Small enough for the interpreted kernel on the CPU (``LGBM_TPU_FORCE_WAVE``);
a CPU run gives counts and correctness, never a time.
"""
import dataclasses
import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.goss import build_sampler
from lightgbm_tpu.config import Config
from lightgbm_tpu.core import plan as plan_mod
from lightgbm_tpu.core.plan import Facts, select_path

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
LR = 0.5                        # sampling starts after int(1 / LR) = 2
PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
          "verbose": -1, "device_type": "tpu", "boosting": "goss",
          "top_rate": 0.2, "other_rate": 0.1, "learning_rate": LR}
ROWS = 3000


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference, as the benchmark imports it."""
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("harness.reference_goss")


def _table(seed: int = 7, rows: int = ROWS):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, 8))
    score = X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=rows)
    return X, (score > 0).astype(np.float64)


def _booster(monkeypatch, wave: bool = True, **extra):
    if wave:
        monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    else:
        monkeypatch.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
        extra = {"device_type": "cpu", **extra}
    X, y = _table()
    params = {**PARAMS, **extra}
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


def _gradients(n: int, classes: int, seed: int):
    """Gradients with the shape of a binary job's a dozen rounds in: a few
    hundred distinct scores, so that ``|g*h|`` has ties, at the threshold
    too."""
    rng = np.random.default_rng(seed)
    raw = rng.choice(rng.normal(size=300), size=(n, classes))
    lab = rng.choice([-1.0, 1.0], size=(n, classes))
    r = -lab / (1.0 + np.exp(lab * raw))
    return r.astype(np.float32), (np.abs(r) * (1 - np.abs(r))).astype(
        np.float32)


# ---------------------------------------------------------------------------
# the sampler alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,classes,top_rate,other_rate", [
    (5000, 1, 0.2, 0.1), (4097, 3, 0.2, 0.1), (6000, 1, 0.05, 0.5),
    (1000, 1, 0.7, 0.3)])
def test_sampler_is_a_legal_sample_by_the_reference(ref, n, classes,
                                                    top_rate, other_rate):
    """The threshold is ``lax.top_k``'s k-th value bit for bit, which is the
    reference's nth-element of the same float32 weights; the top set is
    kept whole and unamplified, the rest amplified by ``(N - top_k) /
    other_k``, and the whole is a legal sample in the reference's eyes."""
    g, h = _gradients(n, classes, seed=n)
    top_k, other_k, multiply = ref.sizes(n, top_rate, other_rate)
    sample = build_sampler(n, top_k, other_k)
    g2, h2, mask, (top_rows, bag_rows, thr) = jax.device_get(sample(
        jnp.asarray(g), jnp.asarray(h), jax.random.PRNGKey(3), jnp.int32(11)))
    weight = jnp.abs(jnp.asarray(g) * jnp.asarray(h)).sum(axis=1)
    assert thr.tobytes() == np.asarray(
        jax.lax.top_k(weight, top_k)[0][-1]).tobytes()
    w32 = np.asarray(weight)
    assert thr == np.float32(ref.threshold(w32.astype(np.float64), top_k))
    is_top = w32 >= thr
    mask = mask != 0
    assert top_rows == is_top.sum() >= top_k and bag_rows == mask.sum()
    assert mask[is_top].all()
    amp = np.where(mask & ~is_top, np.float32(multiply), np.float32(1.0))
    np.testing.assert_array_equal(g2, g * amp[:, None])
    np.testing.assert_array_equal(h2, h * amp[:, None])
    verdict = ref.judge(g, h, mask, top_rate, other_rate,
                        program_threshold=float(thr),
                        root_count=int(bag_rows),
                        root_weight=float(h2[mask].astype(np.float64).sum()))
    assert verdict["ok"], verdict
    assert ref.counts_ok(n, int(top_rows), int(bag_rows), top_rate,
                         other_rate, tie_share=0.01)


def test_rest_share_over_64_seeds(ref):
    """The rest is a Bernoulli draw at ``other_k / rest_k``: over 64 keys
    every bag is within five standard deviations of ``top_k + other_k``, the
    bags differ, and their mean is within five standard errors."""
    n = 20000
    g, h = _gradients(n, 1, seed=1)
    top_k, other_k, _ = ref.sizes(n, 0.2, 0.1)
    sample = build_sampler(n, top_k, other_k)
    gd, hd = jnp.asarray(g), jnp.asarray(h)
    bags, tops = [], set()
    for seed in range(64):
        _, _, _, (top_rows, bag_rows, _) = sample(
            gd, hd, jax.random.PRNGKey(seed), jnp.int32(10))
        assert ref.counts_ok(n, int(top_rows), int(bag_rows), 0.2, 0.1,
                             tie_share=0.01)
        bags.append(int(bag_rows))
        tops.add(int(top_rows))
    assert len(tops) == 1 and len(set(bags)) > 32
    top_rows = tops.pop()
    rest_k = n - top_rows
    p = other_k / rest_k
    want, sd = top_rows + rest_k * p, np.sqrt(rest_k * p * (1 - p))
    assert abs(np.mean(bags) - want) <= 5 * sd / 8
    # the iteration moves the draw, the key alone does too
    a = sample(gd, hd, jax.random.PRNGKey(0), jnp.int32(10))[2]
    b = sample(gd, hd, jax.random.PRNGKey(0), jnp.int32(11))[2]
    assert (np.asarray(a) != np.asarray(b)).any()


def test_bf16_gradients_fail_the_reference_by_fifty_times(ref):
    """The nearest precision below the sampler's float32: with gradients
    rounded to bf16 the threshold and the farthest misranked row read 8e-4
    to 1.1e-3, eighty times ``F32_RTOL`` and more (the limit lies between
    this reading and the chip's float32 one, 1.2e-6: PERF.md 2)."""
    n = 200000
    rng = np.random.default_rng(5)
    lab = rng.choice([-1.0, 1.0], size=(n, 1))
    r = -lab / (1.0 + np.exp(lab * rng.normal(size=(n, 1))))
    g = r.astype(np.float32)
    h = (np.abs(r) * (1 - np.abs(r))).astype(np.float32)
    top_k, other_k, _ = ref.sizes(n, 0.2, 0.1)

    def bf16(x):
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    _, h2, mask, (_, bag_rows, thr) = jax.device_get(
        build_sampler(n, top_k, other_k)(
            jnp.asarray(bf16(g)), jnp.asarray(bf16(h)),
            jax.random.PRNGKey(5), jnp.int32(12)))
    verdict = ref.judge(g, h, mask != 0, 0.2, 0.1,
                        program_threshold=float(thr),
                        root_count=int(bag_rows))
    assert not verdict["ok"] and verdict["top_missing"] > 0
    assert verdict["top_missing_margin"] > 50 * ref.F32_RTOL
    assert verdict["threshold_rel_err"] > 50 * ref.F32_RTOL


@pytest.mark.parametrize("case", ["top_row_dropped", "top_amplified",
                                  "rest_oversampled", "bf16_threshold"])
def test_the_reference_refuses_what_is_no_goss_sample(ref, case):
    n = 20000
    g, h = _gradients(n, 1, seed=2)
    top_k, other_k, multiply = ref.sizes(n, 0.2, 0.1)
    g2, h2, mask, (_, bag_rows, thr) = jax.device_get(
        build_sampler(n, top_k, other_k)(
            jnp.asarray(g), jnp.asarray(h), jax.random.PRNGKey(5),
            jnp.int32(12)))
    mask = mask != 0
    kw = {"program_threshold": float(thr), "root_count": int(bag_rows),
          "root_weight": float(h2[mask].astype(np.float64).sum())}
    assert ref.judge(g, h, mask, 0.2, 0.1, **kw)["ok"]
    if case == "top_row_dropped":
        mask = mask.copy()
        mask[np.argmax(np.abs(g * h)[:, 0])] = False
        kw["root_count"] -= 1
    elif case == "top_amplified":
        kw["root_weight"] = float(
            (h[mask].astype(np.float64) * multiply).sum())
    elif case == "rest_oversampled":
        rng = np.random.default_rng(0)
        mask = mask | (rng.random(n) < 0.05)
        kw["root_count"] = int(mask.sum())
    else:
        w = np.abs(g * h)[:, 0]
        kw["program_threshold"] = float(np.sort(np.asarray(
            jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)))[
                n - top_k])
    assert not ref.judge(g, h, mask, 0.2, 0.1, **kw)["ok"]


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def test_no_sampling_before_one_over_the_learning_rate(monkeypatch, ref):
    bst = _booster(monkeypatch)
    g = bst._gbdt
    start = ref.sampling_starts(LR)
    for it in range(start + 2):
        bst.update()
        sampled = it >= start
        assert (g._bag_mask is g._full_bag) != sampled
        assert bool(np.asarray(bst.bag_mask()).all()) != sampled
    entries = bst.work_counters()["sampler"]
    assert [e["iteration"] for e in entries] == list(range(start + 2))
    for e in entries[:start]:
        assert (e["top_rows"], e["bag_rows"], e["threshold"]) == (
            ROWS, ROWS, 0.0)
    for e in entries[start:]:
        assert e["threshold"] > 0
        # two trees of 15 leaves in: a few hundred distinct scores
        assert ref.counts_ok(ROWS, e["top_rows"], e["bag_rows"], 0.2, 0.1,
                             tie_share=0.2)


def test_update_moves_nothing_implicitly_once_sampling_is_on(monkeypatch):
    """``update()`` under ``jax.transfer_guard("disallow")``: the iteration's
    scalars go to the device explicitly, the lag-1 stop check comes back
    explicitly, and nothing else crosses: the mask stays on the device
    (the guard refuses implicit transfers to the device on every backend,
    and from it wherever the device is not the host; ``_bag_host`` says
    the same of the mask on the CPU)."""
    bst = _booster(monkeypatch)
    for _ in range(3):
        bst.update()
    with jax.transfer_guard("disallow"):
        for _ in range(2):
            assert bst.update() is False
    assert bst._gbdt._bag_host is None
    assert bst.num_trees() == 5


def test_work_counters_under_a_bag(monkeypatch, ref):
    """``top_rows`` / ``bag_rows`` ride beside the growth program's own
    counters; under a bag the root's wave compacts too, and what the
    launches histogram is the bag's rows, not the table's."""
    bst = _booster(monkeypatch)
    for _ in range(5):
        bst.update()
    work = bst.work_counters()
    assert (work["boosting"], work["top_rate"], work["other_rate"]) == (
        "goss", 0.2, 0.1)
    assert work["stamps"]["fused_grad"] is False and work["counted"]
    assert work["top_rows"] == sum(s["top_rows"] for s in work["sampler"])
    assert work["bag_rows"] == sum(s["bag_rows"] for s in work["sampler"])
    by_iter = {s["iteration"]: s for s in work["sampler"]}
    for t, tree in zip(work["trees"], _tree_fields(bst)):
        bag = by_iter[t["iteration"]]["bag_rows"]
        sampled = bag < ROWS
        # the root's launch takes a tier below the full one under a bag
        assert (t["compact_waves"][0] == t["waves"]) == sampled
        assert int(tree["internal_count"].split()[0]) == bag
        # every launch histograms in-bag rows only: the root's are the bag
        assert t["active_rows"][0] <= t["waves"] * bag
        assert t["active_rows"][0] >= bag
    last = bst.work_counters(last=2)
    assert [s["iteration"] for s in last["sampler"]] == [3, 4]
    assert bst.work_counters(last=0)["sampler"] == []
    assert "bag_rows" not in bst.work_counters(last=0)
    # a booster that does not sample says so, and carries no sums
    monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    X, y = _table()
    p = {**PARAMS, "boosting": "gbdt"}
    plain = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    plain.update()
    work = plain.work_counters()
    assert work["boosting"] == "gbdt" and work["top_rate"] is None
    assert work["sampler"] == [] and "bag_rows" not in work
    assert bool(np.asarray(plain.bag_mask()).all())


def _tree_fields(bst) -> list:
    """The ``key=value`` lines of every tree of the model text."""
    return [dict(line.split("=", 1) for line in chunk.splitlines()
                 if "=" in line)
            for chunk in bst.model_to_string().split("\nTree=")[1:]]


def _continue_one(monkeypatch, base, wave: bool):
    """One iteration on from ``base``'s forest (``train(init_model=...)``),
    on the wave path or on the serial grower."""
    if wave:
        monkeypatch.setenv("LGBM_TPU_FORCE_WAVE", "interpret")
    else:
        monkeypatch.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
    X, y = _table()
    params = {**PARAMS, **({} if wave else {"device_type": "cpu"})}
    return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                     num_boost_round=1, init_model=base,
                     keep_training_booster=True, verbose_eval=False)


def _last_root(bst) -> tuple:
    kv = _tree_fields(bst)[-1]
    return kv["split_feature"].split()[0], kv["threshold"].split()[0]


def test_wave_path_under_goss_matches_the_serial_grower(monkeypatch):
    """``int(1 / lr) + 2`` iterations, staged as the benchmark's check (c2)
    stages them (a tie in one tree moves the next tree's gradients, and
    under GOSS its bag: run on independently, two growers that break one
    tie differently draw different bags two rounds later).  The serial
    grower runs the unsampled iterations; then, twice, the wave path
    (kernel interpreted) and the serial grower each grow one sampled
    iteration from that same forest: the same bag to the row, the same
    root split, and scores for ALL rows, in and out of the bag, that agree
    to rounding."""
    base = _booster(monkeypatch, wave=False)
    for _ in range(int(1 / LR)):
        base.update()
    for stage in range(2):
        fast = _continue_one(monkeypatch, base, wave=True)
        slow = _continue_one(monkeypatch, base, wave=False)
        assert fast._gbdt.uses_wave and not slow._gbdt.uses_wave
        it = int(1 / LR) + stage
        a = fast.work_counters(last=1)["sampler"]
        assert a == slow.work_counters(last=1)["sampler"]
        assert a[0]["iteration"] == it and a[0]["bag_rows"] < ROWS
        np.testing.assert_array_equal(np.asarray(fast.bag_mask()),
                                      np.asarray(slow.bag_mask()))
        assert _last_root(fast) == _last_root(slow)
        ra, rb = fast._raw_train_score(), slow._raw_train_score()
        assert np.median(np.abs(ra - rb)) / np.std(rb) <= 5e-6
        # out-of-bag rows are scored too: the model's walk over every row
        X, _ = _table()
        np.testing.assert_allclose(fast.predict(X, raw_score=True), ra,
                                   rtol=1e-5, atol=1e-5)
        # the root's wave compacted, and histogrammed the bag alone
        tree = fast.work_counters(last=1)["trees"][-1]
        assert tree["compact_waves"][0] == tree["waves"]
        base = slow


def test_checkpoint_and_rollback_with_the_lazy_host_mask(monkeypatch):
    bst = _booster(monkeypatch)
    g = bst._gbdt
    for _ in range(4):
        bst.update()
    assert g._bag_host is None              # training fetched nothing
    _, arrays = g.checkpoint_state()
    device_mask = np.asarray(bst.bag_mask())
    assert arrays["bag_mask"].dtype == np.bool_
    np.testing.assert_array_equal(arrays["bag_mask"], device_mask)
    assert g._bag_host is not None          # the checkpoint asked for it
    # the next iteration's bag is another one, and the host copy goes stale
    bst.update()
    assert g._bag_host is None
    assert (np.asarray(bst.bag_mask()) != device_mask).any()
    # a restore puts the saved bag back on both sides
    meta, arrays = g.checkpoint_state()
    g.restore_checkpoint_state(meta, {**arrays, "bag_mask": device_mask})
    np.testing.assert_array_equal(np.asarray(bst.bag_mask()), device_mask)
    np.testing.assert_array_equal(g._bag_mask_host, device_mask)
    # rolling back an iteration and growing it again draws the same bag
    # (the key is the seed and the iteration) and leaves a model of the
    # same length
    before = bst.work_counters(last=1)["sampler"][0]
    bst.rollback_one_iter()
    assert bst.num_trees() == 4
    bst.update()
    again = bst.work_counters(last=1)["sampler"][0]
    assert again["iteration"] == before["iteration"] == 4
    assert abs(again["bag_rows"] - before["bag_rows"]) <= 25
    assert bst.num_trees() == 5 and g._bag_host is None


# ---------------------------------------------------------------------------
# the plan's reason line
# ---------------------------------------------------------------------------

CHIP = Facts(backend="tpu", num_features=28, num_phys_features=28,
             bin_dtype="uint8", B_phys=256, phys_bins=(255,) * 28)


@pytest.mark.parametrize("params,fused_ok,reason", [
    ({"boosting": "goss"}, False, True),
    ({"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.5},
     False, True),
    ({"boosting": "gbdt"}, False, False),   # the objective's doing: K > 1
    ({"boosting": "gbdt"}, True, False),
    ({"boosting": "dart"}, True, False)],
    ids=["goss", "rf", "gbdt_unfusable_objective", "gbdt", "dart"])
def test_plan_says_why_the_booster_keeps_gradients_unfused(params, fused_ok,
                                                           reason):
    cfg = Config.from_params({"verbose": -1, "device_type": "tpu", **params})
    plan = select_path(cfg, dataclasses.replace(CHIP,
                                                fused_grad_ok=fused_ok))
    assert plan.wave and plan.fused_grad is fused_ok
    assert plan.stamps()["fused_grad"] is fused_ok
    keys = [plan_mod.reason_key(r) for r in plan.reasons]
    assert keys == ([plan_mod.BOOSTER_UNFUSED_GRAD] if reason else [])
    for r in plan.reasons:
        assert plan_mod.REASON_LEVEL[plan_mod.reason_key(r)] == "info"
        assert params["boosting"] in r


def test_the_goss_cells_parameters_give_its_stamps():
    with open(os.path.join(BENCH, "configs", "higgs-goss.json")) as fh:
        doc = json.load(fh)
    plan = select_path(
        Config.from_params({"verbose": -1, "device_type": "tpu",
                            **doc["params"]}),
        dataclasses.replace(CHIP, fused_grad_ok=False))
    want = {k: v for k, v in doc["stamps"].items()
            if k not in ("bins_devices", "boosting", "top_rate",
                         "other_rate")}
    got = {"uses_wave": plan.wave, **plan.stamps()}
    assert {k: got[k] for k in want} == want
    assert want["fused_grad"] is False and want["fused_sibling"] is True
    assert [plan_mod.reason_key(r) for r in plan.reasons] == [
        plan_mod.BOOSTER_UNFUSED_GRAD]


def test_the_trainer_logs_the_reason(monkeypatch):
    seen = []
    from lightgbm_tpu.utils import log
    monkeypatch.setattr(log, "info",
                        lambda msg, *a: seen.append(msg % a if a else msg))
    bst = _booster(monkeypatch)
    assert any("boosting=goss" in m and "gradient pass" in m for m in seen)
    assert bst._gbdt.fused_grad_active() is False
    assert bst._gbdt._grow_apply_fused is None
