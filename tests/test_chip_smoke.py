"""chip_smoke.py on the CPU: a test of control flow only.

The command itself must refuse to run here (no chip), and its phase
functions must run green at toy size with the kernel interpreted, so the
command is debugged before chip time is spent on it.  Nothing in this file
says anything about the chip.
"""
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = {"num_leaves": 15, "min_data_in_leaf": 20}


def _run_cli(extra_env):
    env = {k: v for k, v in os.environ.items()
           if k != "LGBM_TPU_FORCE_WAVE"}
    env.update(extra_env)
    return subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)


def test_command_refuses_the_cpu_and_names_it():
    r = _run_cli({"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout          # what it found, said first
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout              # and no result line


def test_command_refuses_the_interpret_switch():
    r = _run_cli({"JAX_PLATFORMS": "cpu", "LGBM_TPU_FORCE_WAVE": "interpret"})
    assert r.returncode != 0
    assert "LGBM_TPU_FORCE_WAVE" in r.stderr
    assert r.stdout == ""


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.phase_train(rows=3000, iters=3, oracle_rows=1500,
                                  interpret=True, params=TOY)


def test_train_phase(trained):
    report, bst, X = trained
    assert report["stamps"]["interpret"] is True
    assert report["kernel_vs_scatter"]["feat_block"] > 0
    assert report["oracle"]["loss_wave"] <= 1.03 * report["oracle"][
        "loss_serial"]
    assert "LGBM_TPU_FORCE_WAVE" not in os.environ   # the hook is restored
    json.dumps(report)


def test_train_phase_refuses_another_path(trained):
    """Uninterpreted on the CPU the trainer takes the XLA grower, silently
    but for one warning; the smoke's stamps must turn that into a failure."""
    with pytest.raises(AssertionError, match="another path"):
        chip_smoke.phase_train(rows=600, iters=2, oracle_rows=300,
                               interpret=False, params=TOY)


def test_serve_phase(trained):
    _, bst, X = trained
    report = chip_smoke.phase_serve(bst, X, max_rows=256, n_requests=8)
    assert report["degraded"] is False and report["max_abs_err"] <= 1e-6


def test_rank_phase():
    report = chip_smoke.phase_rank(
        rows=1500, iters=2, oracle_rows=600, interpret=True,
        params={"num_leaves": 7, "min_data_in_leaf": 10,
                "min_sum_hessian_in_leaf": 1e-3})
    assert report["features"] == 136 and report["stamps"]["interpret"]
    # device NDCG against the host loop is asserted inside, unrounded
    assert report["train_ndcg10"][-1] == pytest.approx(
        report["host_ndcg10"], abs=2e-6)


def test_mesh_phase(trained):
    report, bst, _ = trained
    mesh, mesh_bst = chip_smoke.phase_mesh(2, rows=3000, iters=3,
                                           interpret=True, params=TOY)
    assert mesh["bins_devices"] == 2 and mesh["bytes_spread"] <= 0.05
    assert mesh["stamps"]["fused_sibling"] is False
    same = chip_smoke.mesh_vs_one_chip(mesh_bst, mesh, bst, report)
    assert same["tree0_equals_one_chip"] or \
        same["auc_delta_vs_one_chip"] <= 1e-3


@pytest.fixture
def fake_chip(monkeypatch, tmp_path):
    """main() with a pretend v5e and stub phases: its own control flow."""
    import jax

    from lightgbm_tpu.utils import compile_cache as cc
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.delenv("LGBM_TPU_FORCE_WAVE", raising=False)
    monkeypatch.setattr(cc, "_state", {"dir": None, "warm": None})
    monkeypatch.setattr(chip_smoke, "phase_train",
                        lambda: ({"train_auc": [0.5, 0.6]}, None, None))
    monkeypatch.setattr(chip_smoke, "phase_serve", lambda b, x: {})
    monkeypatch.setattr(chip_smoke, "phase_rank", lambda: {})


def test_main_result_line(fake_chip, capsys):
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    # the last line is the chip check's contract: these keys and no others
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert lines[-1] == json.dumps({"ok": True, "device": device})
    # the report is the line before it
    head = "chip_smoke: summary "
    assert lines[-2].startswith(head)
    summary = json.loads(lines[-2][len(head):])
    assert summary["claim"] is None and list(summary)[-1] == "claim"
    assert summary["device"] == device
    assert set(summary["phases"]) == {"train", "serve", "rank"}
    assert summary["compile_cache"]["dir"].endswith("cc")
    assert summary["binning"] in ("native", "numpy")
    assert set(summary["versions"]) == {"jax", "jaxlib", "libtpu"}


def test_main_a_raising_phase_ends_the_run(fake_chip, monkeypatch, capsys):
    def boom(b, x):
        raise RuntimeError("serve phase failed")
    monkeypatch.setattr(chip_smoke, "phase_serve", boom)
    with pytest.raises(RuntimeError, match="serve phase failed"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_main_fewer_chips_than_asked(fake_chip, capsys):
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out
