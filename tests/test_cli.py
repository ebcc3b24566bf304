"""CLI driver + text loader + auc_mu
(reference: src/main.cpp, application.cpp:48-81, dataset_loader.cpp)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, cwd, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    r = subprocess.run([sys.executable, "-m", "lightgbm_tpu"] + args,
                       cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    return r


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cli")
    rng = np.random.default_rng(0)
    N = 800
    X = rng.normal(size=(N, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    np.savetxt(d / "data.train", np.column_stack([y, X]), delimiter="\t",
               fmt="%.8f")
    np.savetxt(d / "data.test", np.column_stack([y, X])[:200], delimiter="\t",
               fmt="%.8f")
    (d / "train.conf").write_text(
        "task = train\nobjective = binary\ndata = data.train\n"
        "valid_data = data.test\nmetric = auc\nnum_trees = 8\n"
        "num_leaves = 15\nmin_data_in_leaf = 5\n"
        "output_model = model.txt\nverbosity = -1\n")
    return d


def test_cli_train_predict_matches_python_api(workdir):
    _run_cli(["config=train.conf"], workdir)
    assert (workdir / "model.txt").exists()
    _run_cli(["task=predict", "data=data.test", "input_model=model.txt",
              "output_result=pred.txt"], workdir)
    pred_cli = np.loadtxt(workdir / "pred.txt")

    bst = lgb.Booster(model_file=str(workdir / "model.txt"))
    data = np.loadtxt(workdir / "data.test", delimiter="\t")
    np.testing.assert_allclose(bst.predict(data[:, 1:]), pred_cli, atol=1e-10)


def test_cli_predict_from_model_file_only(workdir, tmp_path):
    """Satellite round-trip: train -> save -> predict from the model file
    ALONE (fresh directory, no training config present, `model_file`
    alias) -> outputs match the python API.  task=serve is rejected
    without a model the same way predict is."""
    _run_cli(["config=train.conf", "output_model=mrt.txt"], workdir)
    data = np.loadtxt(workdir / "data.test", delimiter="\t")

    # a bare predict conf in a DIFFERENT directory: only the model file,
    # the data to score, and the output path
    (tmp_path / "predict.conf").write_text(
        f"task = predict\ndata = {workdir / 'data.test'}\n"
        f"model_file = {workdir / 'mrt.txt'}\n"
        f"output_result = {tmp_path / 'pred.txt'}\nverbosity = -1\n")
    _run_cli(["config=predict.conf"], tmp_path)
    pred_cli = np.loadtxt(tmp_path / "pred.txt")

    bst = lgb.Booster(model_file=str(workdir / "mrt.txt"))
    np.testing.assert_allclose(bst.predict(data[:, 1:]), pred_cli,
                               atol=1e-10)

    # raw-score route too (stays self-contained)
    _run_cli(["task=predict", f"data={workdir / 'data.test'}",
              f"model_file={workdir / 'mrt.txt'}", "predict_raw_score=true",
              f"output_result={tmp_path / 'raw.txt'}"], tmp_path)
    raw_cli = np.loadtxt(tmp_path / "raw.txt")
    np.testing.assert_allclose(bst.predict(data[:, 1:], raw_score=True),
                               raw_cli, atol=1e-10)

    # the SESSION branch (heavy-input routing): force it with the
    # work-threshold override and require device-path parity
    _run_cli(["task=predict", f"data={workdir / 'data.test'}",
              f"model_file={workdir / 'mrt.txt'}",
              f"output_result={tmp_path / 'sess.txt'}"], tmp_path,
             extra_env={"LGBM_TPU_PREDICT_MIN_WORK": "0"})
    sess_cli = np.loadtxt(tmp_path / "sess.txt")
    np.testing.assert_allclose(bst.predict(data[:, 1:]), sess_cli,
                               atol=1e-6)


def test_cli_snapshots_and_continue(workdir):
    _run_cli(["config=train.conf", "num_trees=4", "snapshot_freq=2",
              "output_model=m2.txt"], workdir)
    assert (workdir / "m2.txt.snapshot_iter_2").exists()
    # continued training from the snapshot
    _run_cli(["config=train.conf", "num_trees=4",
              "input_model=m2.txt", "output_model=m_cont.txt"], workdir)
    b = lgb.Booster(model_file=str(workdir / "m_cont.txt"))
    assert b.num_trees() == 8


def test_cli_overrides_beat_config_file(workdir):
    _run_cli(["config=train.conf", "num_trees=3",
              "output_model=m3.txt"], workdir)
    b = lgb.Booster(model_file=str(workdir / "m3.txt"))
    assert b.num_trees() == 3


def test_text_loader_query_sidecar(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.text_loader import load_text
    rng = np.random.default_rng(1)
    X = rng.normal(size=(30, 3))
    y = rng.integers(0, 3, 30)
    np.savetxt(tmp_path / "r.train", np.column_stack([y, X]), delimiter="\t")
    (tmp_path / "r.train.query").write_text("10\n12\n8\n")
    Xl, yl, w, group, names = load_text(str(tmp_path / "r.train"), Config())
    assert Xl.shape == (30, 3)
    np.testing.assert_array_equal(group, [10, 12, 8])
    assert w is None


def test_text_loader_libsvm(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.text_loader import load_text
    (tmp_path / "s.train").write_text(
        "1 0:0.5 2:1.5\n0 1:2.0\n1 0:-1.0 1:3.0 2:0.25\n")
    X, y, w, g, names = load_text(str(tmp_path / "s.train"), Config())
    np.testing.assert_array_equal(y, [1, 0, 1])
    # LibSVM input stays sparse end to end (r5; Dataset/predict accept CSR)
    np.testing.assert_allclose(
        X.toarray(), [[0.5, 0.0, 1.5], [0.0, 2.0, 0.0], [-1.0, 3.0, 0.25]])


def test_auc_mu_matches_pairwise_auc_binary_case():
    """With 2 classes and default weights, auc_mu reduces to plain AUC on
    the score difference (the paper's Proposition 1 sanity case)."""
    from sklearn.metrics import roc_auc_score
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metric import AucMuMetric

    rng = np.random.default_rng(2)
    n = 400
    y = rng.integers(0, 2, n)
    score = np.column_stack([rng.normal(size=n), rng.normal(size=n)])
    cfg = Config.from_params({"objective": "multiclass", "num_class": 2})
    m = AucMuMetric(cfg)

    class MD:
        label = y.astype(np.float64)
        weights = None
    m.init(MD(), n)
    (_, got, _), = m.eval(score, None)
    want = roc_auc_score(y, score[:, 1] - score[:, 0])
    assert abs(got - want) < 1e-9


@pytest.mark.skipif(not os.path.isdir("/root/reference/examples"),
                    reason="reference not mounted")
@pytest.mark.parametrize("example,metric_key", [
    ("regression", "l2"),
    ("multiclass_classification", "multi_logloss"),
    ("lambdarank", "ndcg@3"),
])
def test_reference_example_confs_run_unchanged(example, metric_key, tmp_path):
    """Consistency harness over the reference's own example configs
    (reference: tests/python_package_test/test_consistency.py): each
    examples/*/train.conf must run through the CLI unchanged, with only
    num_trees reduced and the model redirected for test speed."""
    d = f"/root/reference/examples/{example}"
    out = str(tmp_path / "model.txt")
    r = _run_cli(["config=train.conf", "num_trees=5",
                  f"output_model={out}"], cwd=d)
    assert os.path.exists(out)
    txt = open(out).read()
    assert txt.count("\nTree=") >= 5
    # the configured metric was actually evaluated on the valid set
    # (the log stream goes to stderr)
    assert metric_key.split("@")[0] in (r.stdout + r.stderr).lower()


def test_init_score_sidecar_and_param(tmp_path):
    """<data>.init sidecar and initscore_filename seed training scores
    (reference: Metadata::LoadInitialScore)."""
    rng = np.random.default_rng(41)
    N = 500
    X = rng.normal(size=(N, 4))
    y = (X[:, 0] > 0).astype(int)
    np.savetxt(tmp_path / "d.train", np.column_stack([y, X]), delimiter="\t",
               fmt="%.8f")
    np.savetxt(tmp_path / "d.train.init", np.full(N, 2.5), fmt="%.6f")
    (tmp_path / "t.conf").write_text(
        "task = train\nobjective = binary\ndata = d.train\n"
        "num_trees = 2\nnum_leaves = 7\nmin_data_in_leaf = 5\n"
        "output_model = m.txt\nverbosity = 1\n")
    r = _run_cli(["config=t.conf"], cwd=str(tmp_path))
    assert "Loaded 500 init scores" in r.stdout + r.stderr
    # explicit initscore_filename branch, and the scores must actually
    # shift training: a +2.5 offset changes the gradients, so the trees
    # (raw predictions) differ from a run without init scores
    np.savetxt(tmp_path / "other.init", np.full(N, 2.5), fmt="%.6f")
    (tmp_path / "t2.conf").write_text(
        "task = train\nobjective = binary\ndata = d.train\n"
        "initscore_filename = other.init\n"
        "num_trees = 2\nnum_leaves = 7\nmin_data_in_leaf = 5\n"
        "output_model = m2.txt\nverbosity = 1\n")
    (tmp_path / "d.train.init").unlink()  # only the explicit file remains
    r2 = _run_cli(["config=t2.conf"], cwd=str(tmp_path))
    assert "other.init" in r2.stdout + r2.stderr
    (tmp_path / "t3.conf").write_text(
        "task = train\nobjective = binary\ndata = d.train\n"
        "num_trees = 2\nnum_leaves = 7\nmin_data_in_leaf = 5\n"
        "output_model = m3.txt\nverbosity = -1\n")
    _run_cli(["config=t3.conf"], cwd=str(tmp_path))
    b_init = lgb.Booster(model_file=str(tmp_path / "m2.txt"))
    b_none = lgb.Booster(model_file=str(tmp_path / "m3.txt"))
    X2 = np.loadtxt(tmp_path / "d.train")[:, 1:]
    assert not np.allclose(b_init.predict(X2, raw_score=True),
                           b_none.predict(X2, raw_score=True))


def test_multi_error_top_k():
    rng = np.random.default_rng(42)
    X = rng.normal(size=(400, 5))
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)
    p = {"objective": "multiclass", "num_class": 3, "verbose": -1,
         "num_leaves": 7, "min_data_in_leaf": 5,
         "metric": "multi_error", "multi_error_top_k": 2}
    ds = lgb.Dataset(X, label=y.astype(float), params=p)
    res = {}
    bst = lgb.train(p, ds, 5, valid_sets=[ds], valid_names=["t"],
                    callbacks=[lgb.record_evaluation(res)])
    assert "multi_error@2" in res["t"]
    # top-2 error must be <= top-1 error by construction
    prob = bst.predict(X)
    top1 = float((prob.argmax(1) != y).mean())
    assert res["t"]["multi_error@2"][-1] <= top1 + 1e-12


def test_cli_task_refit(workdir, tmp_path):
    """task=refit re-estimates leaf values on new data, keeping structure
    (reference: Application task kRefitTree -> GBDT::RefitTree)."""
    # reuse the trained model.txt from the workdir fixture's train run
    _run_cli(["config=train.conf"], cwd=str(workdir))
    rng = np.random.default_rng(9)
    data = np.loadtxt(os.path.join(str(workdir), "data.train"))
    y2 = 1 - data[:, 0]  # flipped labels -> leaf values must move
    np.savetxt(tmp_path / "new.train",
               np.column_stack([y2, data[:, 1:]]), delimiter="\t", fmt="%.8f")
    (tmp_path / "refit.conf").write_text(
        "task = refit\nobjective = binary\n"
        f"data = new.train\ninput_model = {workdir}/model.txt\n"
        "output_model = refitted.txt\nverbosity = -1\n")
    _run_cli(["config=refit.conf"], cwd=str(tmp_path))
    orig = lgb.Booster(model_file=os.path.join(str(workdir), "model.txt"))
    refit = lgb.Booster(model_file=str(tmp_path / "refitted.txt"))
    d_orig = orig.dump_model()
    d_refit = refit.dump_model()
    for a, b in zip(d_orig["tree_info"], d_refit["tree_info"]):
        assert a["tree_structure"].get("split_feature") == \
            b["tree_structure"].get("split_feature")  # structure kept
    X = data[:, 1:]
    assert not np.allclose(orig.predict(X), refit.predict(X))
