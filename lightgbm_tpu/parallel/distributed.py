"""Multi-host bootstrap: the Network::Init analog over jax.distributed.

The reference brings up its own TCP mesh — parse a machine list, bind a
listen port, link every pair of workers, then run Bruck/recursive-halving
collectives over the sockets (reference: src/network/network.cpp:24-74
Network::Init, linkers.cpp, socket_wrapper.hpp).  On TPU pods none of
that socket stack exists to port: collectives are XLA programs riding
ICI/DCN, and the only host-side job is PROCESS BOOTSTRAP — every host
must call ``jax.distributed.initialize`` with the same coordinator so
``jax.devices()`` becomes the global device list.  After that, the
existing mesh growers (``parallel/mesh.py``) scale to multi-host
unchanged: ``build_mesh`` sees every chip in the pod, ``shard_map`` +
``psum`` compile to cross-host collectives, and the reference's
ReduceScatter/AllGather calls have no host analog at all.

Config mapping (reference: config.h "Network Parameters"):

- ``machines`` ("ip1:port1,ip2:port2,...") or ``machine_list_filename``
  (one host per line) — the FIRST entry is the coordinator, matching the
  reference's rank-0 convention;
- ``num_machines`` — process count; must equal the machine list length;
- ``local_listen_port`` — used only to derive the coordinator port when
  the machine list omits one.

The reference's ``LGBM_NetworkInit``/``set_network`` route here via
``mesh.NETWORK``.  ``init_distributed`` is idempotent and a no-op for
``num_machines <= 1``.
"""
from __future__ import annotations

import os
from typing import List, Optional

from ..utils import log
from . import mesh as _mesh

_initialized = False

# host-TCP collective backend (fleet/transport.HostCollectives): when a
# jax build cannot run cross-process device collectives (CPU CI, the
# fleet's CI-twin transport), the fleet installs an adapter here and
# every collective in this module — bin-sample pooling, the divergence
# audit, the straggler stats exchange — rides its ordered TCP gathers
# instead of ``multihost_utils.process_allgather``, bit-exactly (the
# payloads move as pickled numpy arrays, no dtype truncation at all)
_HOST_COLLECTIVES = None


def set_host_collectives(handle) -> None:
    """Install (or clear, with None) the host-collective backend.  The
    handle needs ``world_size``/``rank`` properties, ``active()`` and
    ``allgather(arr) -> [world, *arr.shape]`` in rank order."""
    global _HOST_COLLECTIVES
    _HOST_COLLECTIVES = handle


def host_collectives():
    """The ACTIVE host-collective backend, or None (inactive counts as
    none: the fleet pauses it around replicate-mode ingest, whose
    whole-stream sample must not be pooled)."""
    h = _HOST_COLLECTIVES
    if h is not None and h.active():
        return h
    return None


def world_size() -> int:
    """Process count of whichever multi-host runtime is up: the host
    transport's world when installed, else jax's.  1 single-process —
    without touching a (possibly wedged) accelerator backend."""
    h = host_collectives()
    if h is not None:
        return int(h.world_size)
    if not _runtime_active():
        return 1
    import jax
    return jax.process_count()


def parse_machine_list(machines: str = "",
                       machine_list_filename: str = "",
                       default_port: int = 12400) -> List[str]:
    """Normalize both machine-list forms to ["host:port", ...]
    (reference: Network::Init's two sources, config.h machines /
    machine_list_filename)."""
    entries: List[str] = []
    if machines:
        entries = [tok.strip() for tok in machines.replace("\n", ",").split(",")
                   if tok.strip()]
    elif machine_list_filename:
        if not os.path.exists(machine_list_filename):
            log.fatal(f"Machine list file {machine_list_filename} "
                      "does not exist")
        with open(machine_list_filename) as fh:
            entries = [ln.strip().replace(" ", ":") for ln in fh
                       if ln.strip()]
    return [e if ":" in e else f"{e}:{default_port}" for e in entries]


def process_id(hosts=()) -> Optional[int]:
    """This host's rank, or None when it must come from cluster
    auto-detection.  Resolution order: explicit rank recorded via the
    C API / set_network, rank env vars, then matching this host's
    addresses against the machine list (the reference's approach:
    Network::Init finds the local machine in the list,
    network.cpp:50-60)."""
    if _mesh.NETWORK.get("rank"):
        return int(_mesh.NETWORK["rank"])
    for var in ("JAX_PROCESS_ID", "LGBM_TPU_RANK"):
        if os.environ.get(var):
            return int(os.environ[var])
    if hosts:
        import socket
        local = {socket.gethostname()}
        try:
            name, aliases, addrs = socket.gethostbyname_ex(
                socket.gethostname())
            local |= {name, *aliases, *addrs, "localhost", "127.0.0.1"}
        except OSError:
            pass
        for i, h in enumerate(hosts):
            if h.rsplit(":", 1)[0] in local:
                return i
    return None


def init_distributed(config=None, *, machines: str = "",
                     machine_list_filename: str = "",
                     num_machines: int = 1,
                     local_listen_port: int = 12400,
                     rank: Optional[int] = None,
                     time_out: Optional[int] = None) -> bool:
    """Bootstrap the multi-host runtime; True when running distributed.

    Call on EVERY host before constructing a Booster (the driver script
    runs once per host, like the reference CLI under mpirun —
    docs/Parallel-Learning-Guide analog).  Single-machine configs return
    False without touching jax.distributed.
    """
    global _initialized
    if config is not None:
        machines = machines or getattr(config, "machines", "")
        machine_list_filename = (machine_list_filename
                                 or getattr(config, "machine_list_filename", ""))
        num_machines = max(num_machines,
                           int(getattr(config, "num_machines", 1)))
        local_listen_port = int(getattr(config, "local_listen_port",
                                        local_listen_port))
        if time_out is None:
            time_out = int(getattr(config, "time_out", 120))
    hosts = parse_machine_list(machines, machine_list_filename,
                               local_listen_port)
    if num_machines <= 1 and len(hosts) <= 1:
        return False
    if hosts and num_machines > 1 and len(hosts) != num_machines:
        log.fatal(f"num_machines={num_machines} but the machine list has "
                  f"{len(hosts)} entries")
    num_machines = max(num_machines, len(hosts))
    if _initialized:
        return True

    import jax

    pid = process_id(hosts) if rank is None else int(rank)
    kwargs = {"num_processes": num_machines}
    if pid is not None:
        # unknown rank stays unset so jax's cluster auto-detection (TPU
        # metadata, SLURM, ...) can resolve it
        kwargs["process_id"] = pid
    if hosts:
        kwargs["coordinator_address"] = hosts[0]
    if time_out:
        # the reference's listen/connect time_out (minutes, config.h:845)
        # becomes the coordinator handshake bound — a dead host fails the
        # job instead of hanging it (its only failure-detection story, and
        # ours: SURVEY.md §5)
        kwargs["initialization_timeout"] = int(time_out) * 60
    log.info("Initializing distributed runtime: %d processes, rank %s, "
             "coordinator %s", num_machines,
             "<auto>" if pid is None else pid,
             kwargs.get("coordinator_address", "<from environment>"))
    # jax.distributed resolves coordinator/rank from cluster env vars
    # (TPU metadata, SLURM, ...) when not given explicitly
    jax.distributed.initialize(**kwargs)
    _initialized = True
    _mesh.NETWORK.update(machines=",".join(hosts),
                         num_machines=num_machines,
                         rank=jax.process_index(),
                         local_listen_port=local_listen_port)
    log.info("Distributed runtime up: %d global devices across %d hosts",
             len(jax.devices()), num_machines)
    return True


def shutdown() -> None:
    """Network::Dispose analog (reference: network.cpp:76-84)."""
    global _initialized
    if _initialized:
        import jax

        jax.distributed.shutdown()
        _initialized = False
    _mesh.NETWORK.update(machines="", num_machines=1, rank=0)


def _runtime_active() -> bool:
    """True when a multi-host runtime is up — via init_distributed OR an
    external jax.distributed.initialize (an embedding launcher).
    ``jax.distributed.is_initialized`` reads the client handle only, so
    no backend is initialized on the single-host fast path."""
    if host_collectives() is not None or _initialized:
        return True
    import jax
    return jax.distributed.is_initialized()


def _allgather_exact(arr):
    """process_allgather that survives jax's default 32-bit dtype
    truncation: 64-bit payloads ride as uint32 pairs (bit-exact), so
    pooled bin-finding samples are NOT silently rounded to float32.
    Returns a numpy array with a leading process axis."""
    import numpy as np

    from .. import obs

    a = np.ascontiguousarray(arr)
    # collective fault point + transient retry (robust/): the guard is a
    # passthrough unless the fault harness is armed, but the injection
    # site is THE place a real cross-host gather fails — bin-sample
    # pooling and the divergence audit both route through here
    from ..robust.watchdog import guarded_call

    host = host_collectives()
    if host is not None:
        # fleet CI-twin transport: ordered TCP gather, already bit-exact
        # for any width (payloads ride as pickled numpy — no 32-bit
        # truncation to dodge)
        g = guarded_call(lambda: host.allgather(a), point="collective")
        obs.record_collective_host("host_allgather", g.nbytes)
        return g

    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    def _gather():
        if a.dtype.itemsize == 8:
            u = a.view(np.uint32)
            return np.asarray(
                multihost_utils.process_allgather(jnp.asarray(u))
            ).view(a.dtype)
        return np.asarray(multihost_utils.process_allgather(jnp.asarray(a)))

    g = guarded_call(_gather, point="collective")
    # host-driven collective: the gathered result size IS the runtime
    # receive traffic (every process materializes all hosts' payloads)
    obs.record_collective_host("process_allgather", g.nbytes)
    return g


def global_bin_sample(sample, num_local_rows=None):
    """Distributed bin finding: make every host derive IDENTICAL bin
    mappers by gathering all hosts' bin-finding row samples before
    GreedyFindBin runs (the reference syncs per-feature bin bounds found
    from per-host samples over Network::Allgather,
    dataset_loader.cpp:807-1042; gathering the samples themselves is the
    collective-cheap TPU equivalent — the sample is small and the result
    is exactly the single-host mapper on the pooled sample).

    Returns ``(pooled_sample, global_num_rows)`` so callers can scale
    sample-vs-dataset ratios (bin filter counts) by the GLOBAL row count.
    No-op (identity sample, local rows) outside an initialized multi-host
    runtime.  Handles unequal per-host sample sizes by padding to the max
    and slicing per true count after the gather.
    """
    import numpy as np

    if num_local_rows is None:
        num_local_rows = len(sample)
    if not _runtime_active() or world_size() <= 1:
        return sample, int(num_local_rows)

    n, f = sample.shape
    counts = _allgather_exact(
        np.asarray([n, int(num_local_rows)], np.int64)).reshape(-1, 2)
    m = int(counts[:, 0].max())
    # keep the sample's own float width: f32 samples gather at half the
    # traffic and are already bit-exact on the 4-byte path
    dt = (sample.dtype if np.issubdtype(sample.dtype, np.floating)
          else np.float64)
    padded = np.full((m, f), np.nan, dtype=dt)
    padded[:n] = sample
    gathered = _allgather_exact(padded).reshape(len(counts), m, f)
    pooled = np.concatenate([gathered[p, :counts[p, 0]]
                             for p in range(len(counts))])
    return pooled.astype(sample.dtype), int(counts[:, 1].sum())


def global_bin_sample_sparse(sample_csc, num_local_rows: int):
    """Sparse analog of ``global_bin_sample``: pool every host's
    bin-finding sample as COO triplets (rows offset by cumulative host
    row counts) so all processes derive identical mappers from sparse
    input without densifying.  No-op outside an initialized multi-host
    runtime.  Returns ``(pooled_csc, global_num_rows)``."""
    import numpy as np

    if not _runtime_active() or world_size() <= 1:
        return sample_csc, int(num_local_rows)
    import scipy.sparse as sp

    coo = sample_csc.tocoo()
    n, f = coo.shape
    meta = _allgather_exact(np.asarray(
        [n, coo.nnz, int(num_local_rows), f], np.int64)).reshape(-1, 4)
    log.check(int(meta[:, 3].max()) == int(meta[:, 3].min()),
              "hosts disagree on the sparse sample's feature count")
    m = int(meta[:, 1].max())

    # one payload gather: (row, col, value) stacked as f64 [3, m] —
    # indices are exact in f64 far beyond any sample size
    buf = np.zeros((3, m), np.float64)
    buf[0, :coo.nnz] = coo.row
    buf[1, :coo.nnz] = coo.col
    buf[2, :coo.nnz] = coo.data
    g = _allgather_exact(buf).reshape(len(meta), 3, m)

    row_off = np.concatenate([[0], np.cumsum(meta[:-1, 0])])
    rows, cols, vals = [], [], []
    for p in range(len(meta)):
        k = int(meta[p, 1])
        rows.append(g[p, 0, :k].astype(np.int64) + row_off[p])
        cols.append(g[p, 1, :k].astype(np.int64))
        vals.append(g[p, 2, :k])
    pooled = sp.coo_matrix(
        (np.concatenate(vals),
         (np.concatenate(rows), np.concatenate(cols))),
        shape=(int(meta[:, 0].sum()), f)).tocsc()
    return pooled, int(meta[:, 2].sum())


def rank_allgather_stats(vec):
    """Rank-compare collective for the divergence audit (obs/health.py):
    gather one small f64 stats vector from EVERY process, bit-exact (the
    64-bit payload rides the uint32-pair path of ``_allgather_exact``).

    Returns ``[num_processes, len(vec)]`` with rows in rank order — a
    strict superset of a psum'd min/max over the fingerprint hash: the
    caller gets the min/max spread AND which rank diverged.  None outside
    an initialized multi-host runtime (single-process callers skip the
    audit entirely, no backend is touched)."""
    import numpy as np

    if not _runtime_active():
        return None
    w = world_size()
    if w <= 1:
        return None
    v = np.ascontiguousarray(np.asarray(vec, np.float64).reshape(-1))
    return _allgather_exact(v).reshape(w, -1)


def train_stats_exchange(vec):
    """Per-iteration training-stats exchange for the live straggler
    detector (obs/ranks.py): every rank contributes its windowed phase
    walls, every rank gets the ``[num_processes, len(vec)]`` matrix
    back.  Delegates to :func:`rank_allgather_stats` — the same
    bit-exact uint32-pair allgather the divergence audit rides — and is
    called ONLY on the fingerprint cadence, which already synchronizes
    the fleet, so the exchange piggybacks on an existing barrier rather
    than adding a per-iteration sync point.  None when single-process
    or before the runtime is up (callers skip detection entirely)."""
    return rank_allgather_stats(vec)
