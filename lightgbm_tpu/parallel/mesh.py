"""Mesh-sharded tree growers (reference: src/treelearner/
data_parallel_tree_learner.cpp, feature_parallel_tree_learner.cpp,
voting_parallel_tree_learner.cpp; collective layer network.cpp).

All three modes reuse the single-device grower body
(``core.grower.build_grow_fn``); only the histogram/statistic reduction and
the best-split combination differ, expressed as ``jax.lax`` collectives
inside ``shard_map``.  Tree outputs are replicated (identical on every
device); ``leaf_id`` stays with the rows.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..core import splitter
from ..core.grower import build_grow_fn
from ..core.histogram import hist_onehot
from ..core.meta import DeviceMeta, SplitConfig, pad_meta

AXIS = "data"

# Recorded network topology (reference: network.cpp Network::Init state).
# Collectives themselves are emitted by XLA; multi-host bootstrap reads
# this via ``init_distributed`` — see also capi.LGBM_NetworkInit.
NETWORK = {"machines": "", "num_machines": 1, "rank": 0,
           "local_listen_port": 12400}


def pad_rows(mesh: Mesh, bins, g, h, mask):
    """Pad the row axis to a multiple of the mesh size with mask=0 rows —
    exact under psum reduction since masked rows contribute nothing."""
    D = mesh.devices.size
    N = bins.shape[0]
    pad = (-N) % D
    if pad == 0:
        return bins, g, h, mask
    zf = jnp.zeros((pad,), g.dtype)
    return (jnp.pad(bins, ((0, pad), (0, 0))),
            jnp.concatenate([g, zf]), jnp.concatenate([h, zf]),
            jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)]))


def shard_rows(mesh: Mesh, *arrays):
    """Place row-axis arrays onto the mesh ('data'-axis sharding).

    The row count must be a multiple of the mesh size — use ``pad_rows``
    first for arbitrary N (padded rows carry mask 0 and change nothing).
    """
    out = []
    for a in arrays:
        spec = P(AXIS) if getattr(a, "ndim", 0) >= 1 else P()
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def row_sharded(mesh: Mesh):
    return NamedSharding(mesh, P(AXIS))


def place_rows(mesh: Mesh, a, row_axis: int = 0, replicate: bool = False):
    """Commit ``a`` to the mesh ONCE, at construction: sharded along
    ``row_axis`` when the row count divides the mesh — each device then
    holds only its rows and the jitted growers take the array as it lies
    — or replicated for a learner that reads every row on every device.

    Left as an uncommitted single-device array (which jit re-shards on
    every call) in two cases: rows that do not divide the mesh, and a
    mesh that spans processes, where each host holds different rows and
    no chip run has exercised the path."""
    if jax.process_count() > 1:
        return jnp.asarray(a)
    if replicate:
        spec = P()
    elif a.shape[row_axis] % mesh.devices.size == 0:
        spec = P(*([None] * row_axis), AXIS)
    else:
        return jnp.asarray(a)
    return jax.device_put(a, NamedSharding(mesh, spec))


def _psum(x):
    # accounted at TRACE time (once per compiled program); see
    # obs.record_collective for the traced_* counter semantics
    obs.record_collective("psum", x)
    return jax.lax.psum(x, AXIS)


def _all_gather(x):
    obs.record_collective("all_gather", x)
    return jax.lax.all_gather(x, AXIS)


def _pmax(x):
    # global max for the quantized-histogram scale factors (ISSUE 11):
    # every shard must derive the SAME s_g/s_h or the psum'd integer
    # histograms would mix quantization units
    obs.record_collective("pmax", x)
    return jax.lax.pmax(x, AXIS)


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


_ROW_SHARDED = ((P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()), (P(), P(AXIS)))


def make_data_parallel_grower(meta: DeviceMeta, cfg: SplitConfig, B: int,
                              mesh: Mesh, hist_fn=hist_onehot,
                              B_phys=None, bundled: bool = False):
    """Rows sharded; histograms and root stats psum'd — same algorithm as
    single-device growth; trees match up to f32 reduction-order effects on
    near-tied gains (reference: data_parallel_tree_learner.cpp:119-164,246).

    Returns jitted ``grow(bins, g, h, sample_mask, feature_mask)`` with
    bins/g/h/sample_mask sharded on axis 0; the tree is replicated, leaf_id
    sharded.
    """
    grow = build_grow_fn(meta, cfg, B, hist_fn=hist_fn, reduce_fn=_psum,
                         B_phys=B_phys, bundled=bundled)
    return _shard_map(grow, mesh, *_ROW_SHARDED)


def make_voting_parallel_grower(meta: DeviceMeta, cfg: SplitConfig, B: int,
                                mesh: Mesh, top_k: int = 20,
                                hist_fn=hist_onehot, B_phys=None,
                                bundled: bool = False):
    """Rows sharded with a per-device top-k feature vote gating the
    histogram exchange (PV-Tree; reference:
    voting_parallel_tree_learner.cpp:170-200,262-377).

    Devices vote for their locally-strongest ``top_k`` features; only
    features voted by at least one device have their histograms summed
    across the mesh — the rest are zeroed, cutting interconnect traffic to
    O(top_k/F) of full data-parallel like the reference's gated
    ReduceScatter.  Approximate by design.  Because each pass may keep a
    different feature set, sibling histograms are computed explicitly
    rather than by parent-minus-child subtraction.

    EFB datasets vote on whole PHYSICAL columns (the reference packs
    per-group histograms the same way,
    voting_parallel_tree_learner.cpp:203-259); the surviving-column mask
    rides along so gated-off members skip the default-bin reconstruction
    (core/grower.py hist_leaf) instead of fabricating leaf mass.
    """
    def gated_reduce(x):
        if getattr(x, "ndim", 0) == 3:  # [F_phys, B_phys, 3] histograms
            F = x.shape[0]
            k = min(top_k, F)
            local_score = jnp.abs(x[..., 0]).sum(axis=1)
            thresh = jax.lax.top_k(local_score, k)[0][-1]
            votes = (local_score >= thresh).astype(jnp.float32)
            alive = _psum(votes) > 0.0                   # [F_phys]
            summed = _psum(jnp.where(alive[:, None, None], x, 0.0))
            if bundled:
                return summed, alive
            return summed
        return _psum(x)

    grow = build_grow_fn(meta, cfg, B, hist_fn=hist_fn,
                         reduce_fn=gated_reduce, subtract_sibling=False,
                         B_phys=B_phys, bundled=bundled)
    return _shard_map(grow, mesh, *_ROW_SHARDED)


def make_feature_parallel_grower(meta: DeviceMeta, cfg: SplitConfig, B: int,
                                 mesh: Mesh, hist_fn=hist_onehot):
    """Features sharded for the SEARCH; data replicated on every device
    (reference: feature_parallel_tree_learner.cpp:33-76 — workers all hold
    the full data, each searches its feature block, then one small
    argmax-gain sync replaces any histogram exchange).

    Each device histograms and scans only its block of columns; the winning
    ``BestSplit`` is chosen with an all-gather + argmax (the 2xSplitInfo
    allreduce, parallel_tree_learner.h:190-213).  The partition step then
    runs locally on the replicated rows.  Returns jitted ``grow`` taking
    REPLICATED inputs.
    """
    D = mesh.devices.size
    F = int(meta.num_bins.shape[0])
    F_block = -(-F // D)
    F_pad = F_block * D
    # the bundle mapping is the identity: make_engine_grower refuses EFB
    meta_pad = pad_meta(meta, F_pad)
    # static: the sliced per-device meta is a tracer inside shard_map, so
    # the categorical-path gate must be decided here from the full meta
    has_cat = bool(np.any(np.asarray(meta.is_categorical)))

    def block_slice(a, axis=0):
        idx = jax.lax.axis_index(AXIS)
        return jax.lax.dynamic_slice_in_dim(a, idx * F_block, F_block, axis)

    local_meta_fn = lambda: DeviceMeta(*[block_slice(a) for a in meta_pad])

    def local_hist(bins, g, h, mask, B):
        pad_cols = F_pad - F
        if pad_cols:
            bins = jnp.pad(bins, ((0, 0), (0, pad_cols)))
        return hist_fn(block_slice(bins, axis=1), g, h, mask, B=B)

    def synced_best_split(hist, sg, sh, sc, min_c, max_c, feature_mask):
        lm = local_meta_fn()
        fm = None
        if feature_mask is not None:
            fmp = (jnp.concatenate([feature_mask,
                                    jnp.zeros((F_pad - F,), bool)])
                   if F_pad > F else feature_mask)
            fm = block_slice(fmp)
        bs = splitter.best_split(hist, sg, sh, sc, lm, cfg, min_c, max_c,
                                 feature_mask=fm, has_cat=has_cat)
        offset = jax.lax.axis_index(AXIS) * F_block
        bs = bs._replace(feature=jnp.where(bs.feature >= 0,
                                           bs.feature + offset,
                                           bs.feature).astype(jnp.int32))
        gains = _all_gather(bs.gain)
        winner = jnp.argmax(gains)
        pick = lambda x: _all_gather(x)[winner]
        return splitter.BestSplit(
            gain=gains[winner], feature=pick(bs.feature),
            threshold=pick(bs.threshold), default_left=pick(bs.default_left),
            left_g=pick(bs.left_g), left_h=pick(bs.left_h),
            left_c=pick(bs.left_c), left_out=pick(bs.left_out),
            right_out=pick(bs.right_out), cat_bitset=pick(bs.cat_bitset))

    grow = build_grow_fn(meta, cfg, B, hist_fn=local_hist,
                         best_split_fn=synced_best_split)
    return _shard_map(grow, mesh, (P(), P(), P(), P(), P()), (P(), P()))


def make_data_parallel_wave_grower(meta: DeviceMeta, cfg: SplitConfig, B: int,
                                   mesh: Mesh, plan, B_phys=None):
    """Row-sharded WAVE growth: the Pallas kernel histograms local rows,
    psum makes the result global, every device replays identical split
    decisions (reference: data_parallel_tree_learner.cpp composed with the
    GPU learner's kernel).  Takes feature-major bins [F, N] sharded on the
    row axis; ``plan`` is the ``core.plan.GrowthPlan`` the grower is built
    as.

    The split phase runs on replicated [L]-sized state (identical on every
    device, like the histograms after psum), then each device routes its
    LOCAL shard of the rows in one pass for all the phase's splits
    (``build_split_apply_fn``); nothing crosses chips.  The packed
    channel layout composes with sharding unchanged: each
    device's kernel emits its local (gh, cnt) pair and both arrays are
    psum'd.  The sibling is parent minus the GLOBAL child histogram, so the
    subtraction happens after the psum and ``plan.fused_sibling`` must be
    off (the reference likewise subtracts after its histogram exchange,
    data_parallel_tree_learner.cpp:246); trees stay bit-identical to the
    single-device fused path.

    ``plan.counts`` adds the grower's ``WaveStats`` as a third output:
    ``shared`` once (every chip replays the same loop), ``per_chip`` with
    one row a chip, since each chip compacts and histograms its own
    shard."""
    from ..core.wave_grower import WaveStats, build_wave_grow_fn
    grow = build_wave_grow_fn(meta, cfg, B, plan, B_phys=B_phys,
                              reduce_fn=_psum, reduce_max_fn=_pmax)
    out_specs = (P(), P(AXIS))
    if plan.counts:
        out_specs += (WaveStats(shared=P(), per_chip=P(AXIS)),)
    return _shard_map(grow, mesh,
                      (P(None, AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
                      out_specs)


def column_runs(F: int, feat_block: int, D: int):
    """``(blocks, starts, counts)``: ``F`` columns cut into kernel feature
    blocks of ``feat_block`` and dealt over ``D`` chips as runs of whole
    blocks in ascending order, the first ``nblocks % D`` chips one block
    more (MSLR's 17 blocks of 8 over four chips: 5/4/4/4); ``blocks`` is
    the fullest chip's count, ``starts`` / ``counts`` a chip's first block
    and blocks."""
    base, extra = divmod(-(-int(F) // int(feat_block)), int(D))
    starts = [c * base + min(c, extra) for c in range(D)]
    counts = [base + (c < extra) for c in range(D)]
    return base + (extra > 0), starts, counts


def _split_sync(bs: splitter.BestSplit) -> splitter.BestSplit:
    """A wave's ``BestSplit`` batch (each field [2P], the bitset [2P, W])
    as every chip's best: ONE all-gather of the batch, every field as
    32-bit words, then the argmax of the gain over the chips, candidate by
    candidate.  ``argmax`` takes the first of equal gains: the lowest chip,
    which holds the lowest feature index, as the serial learner's scan
    keeps the lowest (reference: the SplitInfo allreduce of
    feature_parallel_tree_learner.cpp:62-76)."""
    def word(x):
        x = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        return jax.lax.bitcast_convert_type(x, jnp.uint32)[:, None]
    fields = bs._fields[:-1]                         # every scalar field
    words = jnp.concatenate([word(getattr(bs, f)) for f in fields]
                            + [bs.cat_bitset.astype(jnp.uint32)], axis=1)
    every = _all_gather(words)                       # [D, 2P, K]
    gain = jax.lax.bitcast_convert_type(every[..., 0], jnp.float32)
    pick = jnp.take_along_axis(
        every, jnp.argmax(gain, axis=0)[None, :, None], axis=0)[0]
    out = {}
    for k, f in enumerate(fields):
        dt = getattr(bs, f).dtype
        col = pick[:, k]
        out[f] = (col != 0 if dt == jnp.bool_ else
                  jax.lax.bitcast_convert_type(col, dt))
    return splitter.BestSplit(**out, cat_bitset=pick[:, len(fields):])


def make_feature_parallel_wave_grower(meta: DeviceMeta, cfg: SplitConfig,
                                      B: int, mesh: Mesh, plan,
                                      B_phys=None):
    """Feature-parallel WAVE growth (reference:
    feature_parallel_tree_learner.cpp, docs/Features.rst "Feature
    Parallel"): every chip holds every row and the whole feature-major bins
    ``[F, N]`` (replicated; the column count padded to whole kernel
    blocks where the trainer places them), histograms its own run of
    feature blocks (``column_runs``) with the wave kernel reading them
    through its index map, scans them, and ``_split_sync`` all-gathers the
    wave's best splits once a wave.  Every chip then commits the same
    splits and routes its own full copy of the rows, so the tree and
    ``leaf_id`` are replicated.  The sibling subtraction stays fused: a
    chip holds every row of its own columns.

    ``plan.counts`` adds the ``WaveStats`` as a third output, ``shared``
    once (with ``split_syncs``), ``per_chip`` one row a chip."""
    from ..core.wave_grower import ColumnShare, WaveStats, build_wave_grow_fn
    D = mesh.devices.size
    F = int(meta.num_bins.shape[0])
    fb = plan.kernel(B_phys or B, F).feat_block
    blocks, _, _ = column_runs(F, fb, D)
    base, extra = divmod(-(-F // fb), D)

    def window():
        c = jax.lax.axis_index(AXIS)
        return jnp.stack([c * base + jnp.minimum(c, extra),
                          base + (c < extra).astype(jnp.int32)])

    grow = build_wave_grow_fn(
        meta, cfg, B, plan, B_phys=B_phys,
        columns=ColumnShare(blocks, fb, window, _split_sync))
    out_specs = (P(), P())
    if plan.counts:
        out_specs += (WaveStats(shared=P(), per_chip=P(AXIS)),)
    return _shard_map(grow, mesh, (P(), P(), P(), P(), P()), out_specs)


def build_mesh(tpu_mesh_shape: str = "") -> Mesh:
    """Mesh over the available devices; ``tpu_mesh_shape`` ("data:8")
    optionally caps the device count on the data axis."""
    import jax

    from ..utils import log
    devices = jax.devices()
    n = len(devices)
    if tpu_mesh_shape:
        for part in tpu_mesh_shape.split(","):
            name, _, cnt = part.partition(":")
            if name.strip() == AXIS and cnt:
                try:
                    want = int(cnt)
                except ValueError:
                    log.fatal(f"tpu_mesh_shape count is not an integer: "
                              f"{tpu_mesh_shape!r}")
                if want < 1:
                    log.fatal(f"tpu_mesh_shape needs at least 1 device on "
                              f"'{AXIS}', got {want}")
                n = min(n, want)
    return Mesh(np.asarray(devices[:n]), (AXIS,))


def make_engine_grower(plan, meta: DeviceMeta, cfg: SplitConfig, B: int,
                       mesh: Mesh, top_k: int = 20, B_phys=None):
    """Engine-facing TreeLearner factory for the parallel modes (reference:
    tree_learner.cpp:13-36): wraps the mesh growers behind the serial
    signature ``grow(bins, g, h, mask, fmask) -> (tree, leaf_id)`` (and
    the wave grower's ``WaveStats`` where ``plan.counts``) on UNsharded
    inputs: row padding to a mesh multiple, resharding, and the unpad of
    leaf_id all happen inside the jitted wrapper.

    ``plan`` (``core.plan.GrowthPlan``) names the learner ("data" and
    "feature": the wave kernel where ``plan.wave``, else XLA; "voting",
    XLA) and the XLA
    growers' histogram (CPU devices take the scatter-add: no MXU, and the
    one-hot materialization is ~300x slower there).  Bins are
    feature-major [F, N] for the wave path, row-major [N, F] otherwise.
    """
    import jax
    import jax.numpy as jnp

    from ..core.histogram import hist_scatter

    mode, bundled = plan.learner, plan.bundled
    hist_fn = hist_scatter if plan.hist_fn == "scatter" else hist_onehot
    if mode == "data" and plan.wave:
        inner = make_data_parallel_wave_grower(meta, cfg, B, mesh, plan,
                                               B_phys=B_phys)
        feature_major = True
    elif mode == "data":
        inner = make_data_parallel_grower(meta, cfg, B, mesh,
                                          hist_fn=hist_fn,
                                          B_phys=B_phys, bundled=bundled)
        feature_major = False
    elif mode == "voting":
        inner = make_voting_parallel_grower(meta, cfg, B, mesh, top_k=top_k,
                                            hist_fn=hist_fn,
                                            B_phys=B_phys, bundled=bundled)
        feature_major = False
    elif mode == "feature":
        if bundled:
            # per-device column slicing assumes identity bundle mapping
            raise ValueError(
                "EFB-bundled datasets are not supported by the feature-"
                "parallel learner; set enable_bundle=false or use "
                "tree_learner=data/voting/serial")
        # replicated inputs — no padding or resharding needed
        if plan.wave:
            return make_feature_parallel_wave_grower(meta, cfg, B, mesh,
                                                     plan, B_phys=B_phys)
        return make_feature_parallel_grower(meta, cfg, B, mesh,
                                            hist_fn=hist_fn)
    else:
        raise ValueError(f"unknown parallel mode: {mode}")

    row_axis = 1 if feature_major else 0

    def grow(bins, g, h, mask, fmask):
        # the engine pre-pads the constant bin matrix once (engine_pad_bins)
        # — only the per-iteration row vectors are padded here
        N = g.shape[0]
        pad = bins.shape[row_axis] - N
        if pad:
            g = jnp.pad(g, (0, pad))
            h = jnp.pad(h, (0, pad))
            mask = jnp.pad(mask, (0, pad))  # mask 0: padded rows inert
        tree, leaf_id, *stats = inner(bins, g, h, mask, fmask)
        return (tree, leaf_id[:N], *stats)

    return jax.jit(grow)


def engine_pad_bins(bins: np.ndarray, D: int, feature_major: bool):
    """Pad the host bin matrix's row axis to a multiple of the mesh size —
    done ONCE at engine init so the per-iteration grow never copies it."""
    axis = 1 if feature_major else 0
    pad = (-bins.shape[axis]) % D
    if pad == 0:
        return bins
    widths = [(0, 0), (0, pad)] if feature_major else [(0, pad), (0, 0)]
    return np.pad(bins, widths)
