"""Two-process jax.distributed worker — spawned by test_distributed.py.

Each rank bootstraps the real multi-host runtime over a local coordinator
(CPU backend, 1 device per process), then drives the three layers the
single-process suite cannot reach:

  1. ``init_distributed`` bring-up (parallel/distributed.py:87-152) —
     machine-list parsing, coordinator handshake, rank resolution;
  2. ``global_bin_sample`` cross-host sample pooling (the reference syncs
     per-feature bin bounds over Network::Allgather,
     dataset_loader.cpp:807-1042; we pool the samples instead);
  3. data-parallel boosting through the engine grower: rows sharded over
     the 2-process mesh, histograms psum'd ACROSS PROCESSES, trees
     replicated — the reference's socket ReduceScatter
     (data_parallel_tree_learner.cpp:119-164) as a cross-process XLA
     collective.

Writes a JSON summary (per-iteration tree fingerprints + the serial
oracle's) for the parent test to cross-check between ranks.

Usage: dist_worker.py <rank> <base_port> <out_json>
"""
import json
import sys

rank = int(sys.argv[1])
base_port = int(sys.argv[2])
out_path = sys.argv[3]

import jax  # noqa: E402

# two ranks on one host can only share the CPU backend (a chip belongs
# to one process); pin it regardless of the caller's environment
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

result = {"rank": rank}

from lightgbm_tpu.parallel.distributed import (  # noqa: E402
    global_bin_sample, init_distributed)

machines = f"127.0.0.1:{base_port},127.0.0.1:{base_port + 1}"
assert init_distributed(machines=machines, num_machines=2, rank=rank)
assert jax.process_count() == 2, jax.process_count()
assert jax.process_index() == rank
result["global_devices"] = len(jax.devices())

# ---- 1b. collective-support probe (fleet/launch.py) ------------------
# Some jax builds in the vetted range bring the 2-process CPU runtime UP
# but cannot move data through cross-process device collectives — the
# very first ``process_allgather`` below would die with an opaque
# runtime error.  Probe the truth with a 1-int32 allgather and turn an
# unsupported backend into a STRUCTURED skip artifact the parent test
# reads, instead of a red failure that looks like a product bug.
from lightgbm_tpu.fleet.launch import device_collective_support  # noqa: E402

if not device_collective_support(probe=True):
    result["skipped"] = True
    result["reason"] = (
        f"jax {jax.__version__} backend {jax.default_backend()!r} cannot "
        "run cross-process device collectives")
    result["ok"] = True
    with open(out_path, "w") as fh:
        json.dump(result, fh)
    print("WORKER_SKIP", rank)
    sys.exit(0)

# ---- 2. cross-host bin-sample pooling --------------------------------
rng = np.random.default_rng(0)
n, f = 512, 5
X = rng.normal(size=(n, f))
y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)

sample = X[rank::2]  # each rank contributes a different half
pooled, total = global_bin_sample(sample, num_local_rows=len(sample))
assert total == n, total
# bit-exact: the gather rides as uint32 pairs, no f32 truncation
np.testing.assert_array_equal(pooled, np.concatenate([X[0::2], X[1::2]]))
result["pooled_rows"] = int(pooled.shape[0])

# sparse pooling: same halves as CSC triplets -> identical pooled matrix
import scipy.sparse as sp  # noqa: E402

from lightgbm_tpu.parallel.distributed import (  # noqa: E402
    global_bin_sample_sparse)

Xs = X.copy()
Xs[Xs < 0.5] = 0.0  # sparsify deterministically
pooled_sp, total_sp = global_bin_sample_sparse(
    sp.csc_matrix(Xs[rank::2]), num_local_rows=len(sample))
assert total_sp == n, total_sp
np.testing.assert_array_equal(
    pooled_sp.toarray(), np.concatenate([Xs[0::2], Xs[1::2]]))
result["pooled_sparse_nnz"] = int(pooled_sp.nnz)

# and the full sparse construction path derives identical mappers on
# both ranks (each builds from ITS OWN half-sample + LOCAL row count;
# pooling makes the result global) — fingerprinted for the parent, which
# also compares them against a single-host oracle built from the full Xs
from lightgbm_tpu.config import Config as _Cfg  # noqa: E402
from lightgbm_tpu.io.dataset import BinnedDataset  # noqa: E402

h_sp = BinnedDataset.from_sample(
    sp.csc_matrix(Xs[rank::2]), len(Xs[rank::2]), _Cfg.from_params(
        {"verbose": -1, "max_bin": 31}))
result["sparse_bin_offsets"] = np.asarray(h_sp.bin_offsets).tolist()
result["sparse_bounds_fp"] = [
    round(float(np.asarray(m.bin_upper_bound)[:-1].sum()), 9)
    for m in h_sp.bin_mappers]

# ---- 2b. pre-sharded streaming ingestion (ingest/, ISSUE 14) ---------
# each rank streams ONLY its contiguous half of the rows through the
# two-pass ingest; the reservoir sample pools over the REAL collectives
# inside from_sample, so both ranks must derive bit-identical mappers —
# and binning only local rows, the halves must concatenate to the
# single-host oracle.  Fingerprinted for the parent to cross-check.
import hashlib  # noqa: E402

from lightgbm_tpu.config import Config as _ICfg  # noqa: E402
from lightgbm_tpu.ingest import ArraySource, ingest_dataset  # noqa: E402

icfg = _ICfg.from_params({"verbose": -1, "max_bin": 31})
half = X[:256] if rank == 0 else X[256:]
half_y = y[:256] if rank == 0 else y[256:]
ing = ingest_dataset(ArraySource(half, label=half_y, chunk_rows=100),
                     icfg)
assert ing.num_data == 256, ing.num_data
result["ingest_bin_offsets"] = np.asarray(ing.bin_offsets).tolist()
result["ingest_bounds_fp"] = [
    round(float(np.nansum(np.asarray(m.bin_upper_bound)[:-1])), 9)
    for m in ing.bin_mappers]
result["ingest_xbin_sha"] = hashlib.sha256(
    np.ascontiguousarray(ing.X_bin).tobytes()).hexdigest()

# ---- 3. data-parallel boosting over the 2-process mesh ---------------
import jax.numpy as jnp  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.core.grower import make_grower  # noqa: E402
from lightgbm_tpu.core.meta import SplitConfig, build_device_meta  # noqa: E402
from lightgbm_tpu.core.plan import GrowthPlan  # noqa: E402
from lightgbm_tpu.parallel.mesh import (  # noqa: E402
    build_mesh, engine_pad_bins, make_engine_grower)

params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1}
ds = lgb.Dataset(X, label=y, params=params)
ds.construct()
handle = ds._handle
cfg = Config.from_params(params)
meta, B = build_device_meta(handle, cfg)
scfg = SplitConfig.from_config(cfg)
mesh = build_mesh()
assert mesh.devices.size == 2, mesh.devices.size

# the CPU devices' data-parallel XLA grower (scatter-add histograms)
grow_dp = make_engine_grower(
    GrowthPlan(grower="serial", learner="data", hist_fn="scatter",
               fused_sibling=False), meta, scfg, B, mesh)
serial = make_grower(meta, scfg, B)
bins = engine_pad_bins(handle.X_bin, mesh.devices.size, feature_major=False)
fmask = np.ones(f, bool)
ones = np.ones(n, np.float32)


def fingerprint(tree):
    nn = int(tree.num_leaves) - 1
    return {
        "num_leaves": int(tree.num_leaves),
        "split_feature": np.asarray(tree.split_feature[:nn]).tolist(),
        "threshold_bin": np.asarray(tree.threshold_bin[:nn]).tolist(),
        "leaf_value": np.round(
            np.asarray(tree.leaf_value, np.float64), 10).tolist(),
    }


score = np.zeros(n, np.float32)
score_s = np.zeros(n, np.float32)
dp_trees, serial_trees = [], []
for it in range(5):
    p = 1.0 / (1.0 + np.exp(-score))
    g = (p - y).astype(np.float32)
    h = (p * (1.0 - p)).astype(np.float32)
    tree, leaf_id = grow_dp(bins, g, h, ones, fmask)
    # leaf_id is row-sharded across processes: fetch the local block and
    # allgather blocks (mesh device order == process order)
    lid_local = multihost_utils.global_array_to_host_local_array(
        leaf_id, mesh, P("data"))
    lid = np.asarray(multihost_utils.process_allgather(
        jnp.asarray(lid_local))).reshape(-1)[:n]
    lv = np.asarray(tree.leaf_value)
    score = score + 0.1 * lv[lid]
    dp_trees.append(fingerprint(tree))

    # serial oracle: plain local jit, identical on both ranks
    ps = 1.0 / (1.0 + np.exp(-score_s))
    gs = (ps - y).astype(np.float32)
    hs = (ps * (1.0 - ps)).astype(np.float32)
    t_s, lid_s = serial(jnp.asarray(handle.X_bin), jnp.asarray(gs),
                        jnp.asarray(hs), jnp.asarray(ones),
                        jnp.asarray(fmask))
    score_s = score_s + 0.1 * np.asarray(t_s.leaf_value)[np.asarray(lid_s)]
    serial_trees.append(fingerprint(t_s))

result["dp_trees"] = dp_trees
result["serial_trees"] = serial_trees

# ---- 4. cross-rank divergence audit (obs/health.py) ------------------
# Replicated training just produced identical scores on both ranks: the
# audit must pass on the honest state and fire after rank 1 corrupts its
# copy — the real-collective leg of the simulated test in test_health.py.
from lightgbm_tpu import obs  # noqa: E402

obs.enable_health("monitor")
score_d = jnp.asarray(score)
rec = obs.model_fingerprint(score_d, iteration=0)
assert obs.divergence_audit(rec["stats"], iteration=0)
corrupted = score_d.at[0].add(1.0) if rank == 1 else score_d
rec2 = obs.model_fingerprint(corrupted, iteration=1)
caught = False
try:
    obs.divergence_audit(rec2["stats"], iteration=1)
except obs.TrainingHealthError:
    caught = True  # both ranks see the mismatch and abort
obs.enable_health("")
result["divergence_caught"] = caught

result["ok"] = True
with open(out_path, "w") as fh:
    json.dump(result, fh)
print("WORKER_DONE", rank)
