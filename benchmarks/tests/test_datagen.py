"""What the seed draws and what it does not (harness/datagen.py)."""
import numpy as np

from harness import datagen

SPEC = {"task": "binary", "rows": 300000, "features": 6, "informative": 3,
        "loading": 0.5, "signal": 2.0, "label_noise": 1.0, "label_seed": 9}
RANK = {"task": "rank", "rows": 200000, "features": 5, "informative": 2,
        "loading": 0.4, "signal": 1.0, "label_noise": 1.5, "label_seed": 9,
        "queries": {"log_mean": 4.47, "log_sigma": 0.8, "min": 1, "max": 1251}}


def test_same_seed_same_table_other_seed_other_features_same_labels():
    X1, y1, _ = datagen.make_table(SPEC, 1)
    X1b, y1b, _ = datagen.make_table(SPEC, 1)
    X2, y2, _ = datagen.make_table(SPEC, 2)
    assert np.array_equal(X1, X1b) and np.array_equal(y1, y1b)
    assert np.array_equal(y1, y2)            # labels are the configuration's
    assert not np.array_equal(X1, X2)
    assert X1.flags.c_contiguous and X1.dtype == np.float64
    assert (X1 > 0).all()                    # 0.0 lies under every bin
    # the informative features carry the label, the others do not
    z = np.log(X1)
    c = [abs(np.corrcoef(z[:, j], y1)[0, 1]) for j in range(6)]
    assert min(c[:3]) > 0.2 and max(c[3:]) < 0.02


def test_result_does_not_depend_on_threads(monkeypatch):
    X1, _, _ = datagen.make_table(SPEC, 3)
    monkeypatch.setattr(datagen, "_threads", lambda: 1)
    X2, _, _ = datagen.make_table(SPEC, 3)
    assert np.array_equal(X1, X2)


def test_slice_is_a_prefix_with_whole_queries():
    X, y, sizes = datagen.make_table(RANK, 4)
    Xs, ys, ss = datagen.make_table(RANK, 4, rows=50000)
    n = len(ys)
    assert n == ss.sum() <= 50000 and n > 50000 - 1251
    assert np.array_equal(ss, sizes[:len(ss)])
    assert np.array_equal(Xs, X[:n]) and np.array_equal(ys, y[:n])
    assert set(np.unique(y)) == {0.0, 1.0, 2.0, 3.0, 4.0}
    assert sizes.sum() == 200000 and sizes.max() <= 1251 and sizes.min() >= 1


def test_query_sizes_are_the_configurations_and_do_not_move_with_the_seed():
    from harness import cells
    spec = cells.load_cell("mslr-train").config["data"]
    sizes = datagen.query_sizes(spec["rows"], spec["queries"],
                                spec["label_seed"])
    # MSLR-WEB30K: 31,531 queries over 3,771,125 documents, 1..1251 each
    assert sizes.sum() == spec["rows"] == 3771125
    assert abs(len(sizes) - 31531) < 32 and 119 < sizes.mean() < 120.5
    assert sizes.min() >= 1 and sizes.max() == 1251
    _, _, s1 = datagen.make_table(RANK, 1, rows=20000)
    _, _, s2 = datagen.make_table(RANK, 2, rows=20000)
    assert np.array_equal(s1, s2)
