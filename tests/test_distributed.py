"""Multi-host bootstrap plumbing (reference: network.cpp Network::Init,
config.h network parameters). Actual multi-process bring-up needs real
hosts; these cover the config surface and single-host no-op guarantees."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import distributed, mesh


def test_parse_machine_list_forms(tmp_path):
    got = distributed.parse_machine_list("10.0.0.1:121,10.0.0.2:122")
    assert got == ["10.0.0.1:121", "10.0.0.2:122"]
    # missing ports get the default
    got = distributed.parse_machine_list("hostA,hostB", default_port=9000)
    assert got == ["hostA:9000", "hostB:9000"]
    # file form, one "ip port" per line like the reference's mlist
    p = tmp_path / "mlist.txt"
    p.write_text("10.0.0.1 121\n10.0.0.2 122\n")
    got = distributed.parse_machine_list(machine_list_filename=str(p))
    assert got == ["10.0.0.1:121", "10.0.0.2:122"]


def test_single_machine_is_noop():
    assert distributed.init_distributed(num_machines=1) is False
    cfg = lgb.Config.from_params({"verbose": -1})
    assert distributed.init_distributed(cfg) is False


def test_machine_count_mismatch_is_fatal():
    with pytest.raises(lgb.LightGBMError, match="machine list"):
        distributed.init_distributed(machines="a:1,b:2,c:3", num_machines=2)


def test_missing_machine_list_file_is_fatal(tmp_path):
    with pytest.raises(lgb.LightGBMError, match="does not exist"):
        distributed.parse_machine_list(
            machine_list_filename=str(tmp_path / "nope.txt"))


def test_set_network_records_topology():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
         "min_data_in_leaf": 5}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 1)
    try:
        bst.set_network(["10.1.1.1:121", "10.1.1.2:121"], num_machines=2)
        assert mesh.NETWORK["num_machines"] == 2
        assert mesh.NETWORK["machines"] == "10.1.1.1:121,10.1.1.2:121"
        bst.free_network()
    finally:
        mesh.NETWORK.update(machines="", num_machines=1, rank=0)


def test_process_id_resolution(monkeypatch):
    monkeypatch.setitem(mesh.NETWORK, "rank", 0)
    monkeypatch.setenv("LGBM_TPU_RANK", "3")
    assert distributed.process_id() == 3
    monkeypatch.setitem(mesh.NETWORK, "rank", 1)
    assert distributed.process_id() == 1


def test_process_id_from_machine_list(monkeypatch):
    monkeypatch.setitem(mesh.NETWORK, "rank", 0)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    monkeypatch.delenv("LGBM_TPU_RANK", raising=False)
    # local host appears second -> rank 1 (reference: Network::Init finds
    # the local machine in the list)
    assert distributed.process_id(["10.9.9.9:12400", "localhost:12400"]) == 1
    # unknown everywhere -> None, deferring to jax cluster auto-detection
    assert distributed.process_id(["10.9.9.8:1", "10.9.9.9:1"]) is None


def test_global_bin_sample_single_host_identity():
    s = np.random.default_rng(0).normal(size=(50, 3))
    out, n_global = distributed.global_bin_sample(s, 200)
    assert out is s  # no-op outside an initialized multi-host runtime
    assert n_global == 200


def test_two_process_data_parallel_bitmatch(tmp_path):
    """REAL 2-process bring-up on the CPU backend: spawn two ranks with a
    local coordinator, run init_distributed + global_bin_sample + 5 rounds
    of data-parallel boosting (histogram psum ACROSS processes), and
    assert both ranks produced identical trees that bit-match the serial
    single-process oracle.  Closes the gap the reference never closed in
    CI (docs/Parallel-Learning-Guide.rst:55-100 is manual-run only)."""
    import json
    import os
    import socket
    import subprocess
    import sys

    # one free port for the coordinator (hosts[0]); the machine list's
    # second entry is address-only metadata — nothing binds it
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    base_port = s.getsockname()[1]
    s.close()

    worker = os.path.join(os.path.dirname(__file__), "dist_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # 1 CPU device per process -> 2-device mesh
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(worker)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    outs = [str(tmp_path / f"rank{r}.json") for r in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, worker, str(r), str(base_port), outs[r]],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    logs = []
    for pr in procs:
        try:
            # generous: the pass/fail signal is the fingerprint match, not
            # wall-clock — the 1-CPU container is compile-bound and two
            # concurrent ranks compile everything twice
            out, _ = pr.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            pytest.fail("2-process worker timed out; partial output:\n"
                        + "\n".join(logs))
        logs.append(out)
    assert all(pr.returncode == 0 for pr in procs), "\n".join(logs)

    res = [json.load(open(o)) for o in outs]
    assert all(r["ok"] for r in res)
    if any(r.get("skipped") for r in res):
        # the workers probed the runtime and found the backend cannot move
        # data through cross-process device collectives (fleet/launch.py
        # device_collective_support) — an environment gap, not a product
        # failure; the host-TCP fleet transport covers this path in CI
        pytest.skip(res[0].get("reason") or res[1].get("reason")
                    or "cross-process device collectives unsupported")
    assert all(r["global_devices"] == 2 for r in res)
    assert all(r["pooled_rows"] == 512 for r in res)
    # sparse sample pooling: both ranks pooled to the same matrix AND
    # derived IDENTICAL bin mappers from their different half-samples
    assert res[0]["pooled_sparse_nnz"] == res[1]["pooled_sparse_nnz"] > 0
    assert res[0]["sparse_bin_offsets"] == res[1]["sparse_bin_offsets"]
    assert res[0]["sparse_bounds_fp"] == res[1]["sparse_bounds_fp"]
    # ...and they match a SINGLE-HOST oracle built from the full matrix
    # (catches symmetric pooling bugs both ranks would share)
    import scipy.sparse as sp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 5))
    Xs = X.copy()
    Xs[Xs < 0.5] = 0.0
    Xp = np.concatenate([Xs[0::2], Xs[1::2]])  # pooled host order
    oracle = BinnedDataset.from_sample(
        sp.csc_matrix(Xp), 512, Config.from_params(
            {"verbose": -1, "max_bin": 31}))
    assert res[0]["sparse_bin_offsets"] == np.asarray(
        oracle.bin_offsets).tolist()
    fp = [round(float(np.asarray(m.bin_upper_bound)[:-1].sum()), 9)
          for m in oracle.bin_mappers]
    assert res[0]["sparse_bounds_fp"] == fp
    # pre-sharded streaming ingestion (ingest/, ISSUE 14): both ranks —
    # each streaming ONLY its contiguous half — derived IDENTICAL bin
    # mappers via the real-collective sample pooling...
    assert res[0]["ingest_bin_offsets"] == res[1]["ingest_bin_offsets"]
    assert res[0]["ingest_bounds_fp"] == res[1]["ingest_bounds_fp"]
    # ...matching the single-host oracle built from the full matrix,
    # and their locally-binned halves concatenate to the oracle's
    # bin matrix bit-exactly
    import hashlib
    from lightgbm_tpu.ingest import ArraySource, ingest_dataset
    icfg = Config.from_params({"verbose": -1, "max_bin": 31})
    ing_oracle = ingest_dataset(
        ArraySource(X, label=(X[:, 0] + X[:, 1] * X[:, 2] > 0)
                    .astype(np.float64), chunk_rows=100), icfg)
    assert res[0]["ingest_bin_offsets"] == np.asarray(
        ing_oracle.bin_offsets).tolist()
    fp = [round(float(np.nansum(np.asarray(m.bin_upper_bound)[:-1])), 9)
          for m in ing_oracle.bin_mappers]
    assert res[0]["ingest_bounds_fp"] == fp
    assert res[0]["ingest_xbin_sha"] == hashlib.sha256(
        np.ascontiguousarray(ing_oracle.X_bin[:256]).tobytes()).hexdigest()
    assert res[1]["ingest_xbin_sha"] == hashlib.sha256(
        np.ascontiguousarray(ing_oracle.X_bin[256:]).tobytes()).hexdigest()
    # both ranks saw identical data-parallel trees (replicated outputs)
    assert res[0]["dp_trees"] == res[1]["dp_trees"]
    # the cross-process psum'd training matches the serial oracle:
    # structure bit-exact, leaf values up to f32 psum reduction order
    # (the same tolerance mesh.py documents for single-process psum)
    for dp, sr in zip(res[0]["dp_trees"], res[0]["serial_trees"]):
        assert dp["num_leaves"] == sr["num_leaves"]
        assert dp["split_feature"] == sr["split_feature"]
        assert dp["threshold_bin"] == sr["threshold_bin"]
        np.testing.assert_allclose(dp["leaf_value"], sr["leaf_value"],
                                   rtol=1e-5, atol=1e-7)
    # the health divergence audit over the REAL cross-process gather:
    # identical replicated state passed, and after rank 1 corrupted its
    # score copy every rank caught the mismatch (obs/health.py)
    assert all(r["divergence_caught"] for r in res)
