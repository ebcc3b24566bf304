"""Which growth path runs: decided once, here, and stamped from there.

``select_path(config, facts)`` is the one place that reads the parameters and
what the code can observe (``Facts``) and says which grower is built and how:
the ``GrowthPlan``.  ``boosting/gbdt.py _init_grower`` logs the plan's
``reasons``, places the bins and constructs the grower from it;
``core/wave_grower.py build_wave_grow_fn`` and ``parallel/mesh.py
make_engine_grower`` take the plan as it is and assert on a combination that
cannot run, they downgrade nothing; ``Booster.work_counters()["stamps"]``,
the telemetry records and the checkpoint digest read it.  A reference path (the
unfused sibling, the sequential split commit, the unfused gradient pass, the
global pair pass of lambdarank) is a field of the plan that a test replaces
(``dataclasses.replace``), not a parameter a user sets.

Pure: no ``jax`` array, no logging, no state.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import NamedTuple, Optional, Tuple

from ..ops.pallas_hist import (QUANT_MODES, select_wave_blocks,
                               wave_capacity_max, wave_feature_blocks)
from .meta import _padded_bin_width

# Above this many physical bins in all (wide-sparse EFB layouts) the serial
# grower's one-hot contraction, O(N x F x B), is intractable; scatter-add is
# O(N x F).
WIDE_LAYOUT_BINS = 32768


def resolve_hist_mode(config) -> str:
    """Histogram precision, resolved to the kernel-mode name: "2xbf16" (the
    default: hi/lo bf16 split, ~16 mantissa bits on g/h, f32 accumulation;
    the reference keeps float histograms even in single-precision GPU mode,
    gpu_tree_learner.h:80-84), "highest" for ``gpu_use_dp`` or explicit
    opt-in, "bf16" on explicit opt-in, "int16"/"int8" for quantised
    accumulation (``gpu_use_dp`` still wins: an explicit double-precision ask
    outranks a quantisation ask).  ``tpu_hist_dtype`` accepts the kernel-mode
    names directly; "float32"/"bfloat16" survive as back-compat aliases.
    ``robust/checkpoint.py config_digest`` hashes this resolution, so an
    alias spelling can never refuse a legitimate resume."""
    if config.gpu_use_dp or config.tpu_hist_dtype == "highest":
        return "highest"
    if config.tpu_hist_dtype in ("bfloat16", "bf16"):
        return "bf16"
    if config.tpu_hist_dtype in QUANT_MODES:
        return config.tpu_hist_dtype
    return "2xbf16"  # "2xbf16" or its alias "float32"


class MixedCols(NamedTuple):
    """The physical columns of a mixed-width dataset, hashable: ``narrow``
    (at most 256 bins: the Pallas kernel's) and ``wide`` (the XLA side-pass's)
    as tuples of column indices, and the padded bin width of the narrow
    group (``core/wave_grower.py MixedWidth`` is the same as arrays)."""
    narrow: Tuple[int, ...]
    wide: Tuple[int, ...]
    B_narrow: int


class KernelShape(NamedTuple):
    """The wave kernel's shape over the columns it holds, under the names
    ``Booster.work_counters()`` says it by: ``kernel_bins`` lanes a feature
    (the narrow width under the mixed plan), ``feat_block`` features a grid
    step covers, ``feat_pack`` features whose one-hot factors share an MXU
    pass (1 above 64 bins, and wherever the pack does not divide the block),
    and ``kernel_columns``, the feature columns a launch covers, the padding
    to whole blocks counted.  Each None off the wave path."""
    kernel_bins: Optional[int] = None
    feat_block: Optional[int] = None
    feat_pack: Optional[int] = None
    kernel_columns: Optional[int] = None


@dataclass(frozen=True)
class Facts:
    """What ``select_path`` may observe beside the parameters."""
    backend: str                    # jax.default_backend()
    num_features: int               # inner features of the training set
    num_phys_features: int          # columns of the binned matrix
    bin_dtype: str                  # "uint8" | "uint16"
    B_phys: int                     # padded bin width of the physical columns
    phys_bins: Tuple[int, ...] = ()  # bins of each physical column
    bundled: bool = False           # EFB packed the columns
    forced: bool = False            # a forced-splits file loaded
    query_sharding: bool = False    # the objective's pair pass can be cut
    #   on query boundaries (lambdarank)
    fused_grad_ok: bool = True      # booster and objective allow gradients
    #   inside the growth jit (one tree an iteration, no GOSS / RF: where
    #   ``boosting`` is one of those the plan gives the reason)
    mesh_size: int = 1              # devices of the parallel learner's mesh
    force_wave: str = ""            # the LGBM_TPU_FORCE_WAVE test hook
    categorical: bool = False       # a column is declared categorical


# reason -> the level ``_init_grower`` logs it at
NO_CHIP = "no-chip"
QUANT_TO_2XBF16 = "quantised->2xbf16"
FORCED_TO_SERIAL = "forced-splits->serial"
LAZY_CEGB_TO_SERIAL = "lazy-cegb->serial"
BYNODE_TO_SERIAL = "bynode->serial"
PARALLEL_WIDE_TO_XLA = "parallel-wide->xla"
FEATURE_CAT_TO_XLA = "feature-categorical->xla"
VOTING_TO_XLA = "voting->xla"
BYNODE_IGNORED = "bynode-ignored"
FORCED_IGNORED = "forced-splits-ignored"
BOOSTER_UNFUSED_GRAD = "booster->unfused-grad"
REASON_LEVEL = {
    NO_CHIP: "warning", QUANT_TO_2XBF16: "info", FORCED_TO_SERIAL: "info",
    LAZY_CEGB_TO_SERIAL: "warning", BYNODE_TO_SERIAL: "info",
    PARALLEL_WIDE_TO_XLA: "info", FEATURE_CAT_TO_XLA: "info",
    VOTING_TO_XLA: "info", BYNODE_IGNORED: "warning",
    FORCED_IGNORED: "warning", BOOSTER_UNFUSED_GRAD: "info",
}


def reason_key(reason: str) -> str:
    """The ``REASON_LEVEL`` key a reason string starts with."""
    return reason.partition(": ")[0]


# what only the trainer reads of a plan: no part of a compiled grower
_NOT_IN_KEY = ("fused_grad", "rank_sharded_grad", "reasons")


@dataclass(frozen=True)
class GrowthPlan:
    """The growth path, every field effective (what runs, not what was asked
    for).  The defaults are the wave path of one chip over narrow, unbundled
    columns, so a test that builds a grower by hand names what differs."""
    grower: str = "wave"            # "wave" (Pallas kernel) | "serial" (XLA)
    learner: str = "serial"         # serial | data | voting | feature
    hist_mode: str = "2xbf16"       # highest | 2xbf16 | bf16 | int16 | int8
    packed: bool = True             # packed channel layout (63 leaves a
    #   launch); False: the triple layout (42), the mixed side-pass's
    wave_capacity: int = 63         # leaves a launch, clamped to the layout's
    fused_sibling: bool = True      # parent - child inside the launch; the
    #   XLA subtraction after it is the reference, and the only form under
    #   a row-sharded mesh (after the psum), EFB (after the default-bin
    #   fix) or mixed
    batched_apply: bool = True      # a phase's [L]-sized commits in one scan;
    #   False: ``_split_once``, one commit and its walk at a time (reference)
    fused_grad: bool = True         # gradients inside the growth jit where
    #   the iteration allows (``GBDT.fused_grad_active``)
    rank_sharded_grad: bool = False  # lambdarank's pair pass divided by query
    #   over the mesh (the data learner's query-aligned row shards, the
    #   feature learner's dealt queries); False: whole (reference)
    interpret: bool = False         # the Pallas interpreter (test hook)
    mixed: Optional[MixedCols] = None
    bundled: bool = False
    counts: bool = False            # the grower returns its ``WaveStats``
    hist_fn: str = "onehot"         # the serial grower's: onehot | scatter
    gain_gate: float = 0.0          # tpu_wave_gain_gate
    block_rows: int = 1024          # tpu_block_rows
    quant_seed: int = 0             # the stochastic rounding's, where it runs
    bynode: Optional[float] = None  # feature_fraction_bynode where it applies
    forced: bool = False            # the forced splits are followed
    reasons: Tuple[str, ...] = ()   # one string a downgrade

    @property
    def wave(self) -> bool:
        return self.grower == "wave"

    def check(self, data_parallel: bool = False) -> None:
        """Raise on a combination no kernel or layout can run: the builders
        call this where they used to downgrade in silence."""
        assert self.grower in ("wave", "serial"), self.grower
        mixed, quant = self.mixed is not None, self.hist_mode in QUANT_MODES
        assert not (self.fused_sibling and (mixed or self.bundled
                                            or data_parallel)), \
            "the fused sibling needs an un-mixed, un-bundled wave on one " \
            "device: under a mesh the subtraction follows the psum, under " \
            "EFB the default-bin fix"
        assert not (self.packed and mixed), \
            "the mixed-width side-pass speaks the triple layout"
        assert 1 <= self.wave_capacity <= wave_capacity_max(self.packed), \
            (self.wave_capacity, self.packed)
        assert not (quant and (mixed or self.bundled)), \
            "quantised histogram modes need the pure-kernel un-bundled " \
            "wave path (the mixed-width XLA side-pass is f32 and the EFB " \
            "default-bin fix mixes integer and value units); select_path " \
            "downgrades the mode"

    def kernel(self, bins: int, columns: int) -> KernelShape:
        """The wave kernel's shape over ``columns`` columns of ``bins`` bins
        under this plan: the one call of ``select_wave_blocks`` (the grower
        launches at its block, ``work_counters()`` says it), cut, packed and
        padded, taken whole, or widened to the cheapest block that fits the
        VMEM cap, by the kernel's own rule (``wave_feature_blocks``; the
        feature learner's launches are windowed)."""
        layout = dict(mode=self.hist_mode, packed=self.packed,
                      fused=self.fused_sibling, block_rows=self.block_rows)
        fb = select_wave_blocks(bins, **layout)[1]
        return KernelShape(int(bins), *wave_feature_blocks(
            bins, columns, fb, windowed=self.learner == "feature", **layout))

    def key(self) -> tuple:
        """What a compiled grower depends on, of the plan: the cache key's
        share (with the meta, the split configuration and the widths)."""
        return tuple(getattr(self, f.name) for f in fields(self)
                     if f.name not in _NOT_IN_KEY)

    def stamps(self) -> Optional[dict]:
        """The wave pipeline's stamps (``GBDT._wave_info``: the benchmark,
        ``chip_smoke.py`` and the telemetry read these names); None off the
        wave path."""
        if not self.wave:
            return None
        return {"hist_mode": self.hist_mode,
                "wave_capacity": self.wave_capacity,
                "packed": self.packed,
                "fused_sibling": self.fused_sibling,
                "interpret": self.interpret,
                "fused_grad": self.fused_grad}


def select_path(config, facts: Facts) -> GrowthPlan:
    """The growth plan of ``config`` on what ``facts`` say."""
    reasons = []
    tl = getattr(config, "tree_learner", "serial")
    parallel = tl != "serial" and facts.num_features > 0
    interpret = facts.force_wave == "interpret"
    wants_chip = config.device_type in ("tpu", "gpu")
    on_chip = facts.backend == "tpu"
    backend_ok = facts.num_features > 0 and (interpret
                                             or (wants_chip and on_chip))
    if wants_chip and not on_chip and not interpret:
        reasons.append(
            f"{NO_CHIP}: device_type={config.device_type} but the JAX "
            f"backend is {facts.backend!r}: training on the XLA serial "
            "grower, not the wave kernel")

    # ---- the columns: all on the kernel, or the wide ones side-passed ----
    narrow_all = facts.bin_dtype == "uint8" and facts.B_phys <= 256
    mixed = None
    if backend_ok and not narrow_all:
        wide = [b > 256 for b in facts.phys_bins]
        if any(wide) and not all(wide):
            mixed = MixedCols(
                tuple(i for i, w in enumerate(wide) if not w),
                tuple(i for i, w in enumerate(wide) if w),
                _padded_bin_width(max(b for b in facts.phys_bins
                                      if b <= 256)))

    hist_mode = resolve_hist_mode(config)
    if (mixed is not None or facts.bundled) and hist_mode in QUANT_MODES:
        # a per-column precision split would make the accuracy budget
        # unauditable, so the whole dataset downgrades
        reasons.append(
            f"{QUANT_TO_2XBF16}: tpu_hist_dtype={hist_mode} needs the "
            "pure-kernel un-bundled wave path; falling back to 2xbf16")
        hist_mode = "2xbf16"

    # ---- what a parallel learner ignores, before it can downgrade --------
    forced = facts.forced
    bynode = float(getattr(config, "feature_fraction_bynode", 1.0))
    bynode = bynode if bynode < 1.0 else None
    if parallel and bynode is not None:
        reasons.append(
            f"{BYNODE_IGNORED}: feature_fraction_bynode is ignored with "
            f"tree_learner={tl} (supported on the serial learner only)")
        bynode = None
    if parallel and forced:
        reasons.append(
            f"{FORCED_IGNORED}: forcedsplits_filename is ignored with "
            f"tree_learner={tl} (supported on the serial learner only)")
        forced = False

    # ---- wave or serial ---------------------------------------------------
    cegb_lazy = bool(config.cegb_penalty_feature_lazy)
    cegb = bool(config.cegb_penalty_split > 0 or cegb_lazy
                or config.cegb_penalty_feature_coupled)
    wave = backend_ok and (narrow_all or mixed is not None)
    if wave and forced:
        reasons.append(
            f"{FORCED_TO_SERIAL}: forcedsplits_filename set: using the XLA "
            "serial grower (the wave grower splits many leaves per pass and "
            "cannot follow a BFS prescription)")
        wave = False
    if wave and cegb_lazy:
        reasons.append(
            f"{LAZY_CEGB_TO_SERIAL}: cegb_penalty_feature_lazy needs "
            "per-row state; falling back to the XLA serial grower")
        wave = False
    if wave and bynode is not None:
        reasons.append(
            f"{BYNODE_TO_SERIAL}: feature_fraction_bynode set: using the XLA "
            "serial grower (per-node masks need the one-split-at-a-time "
            "loop)")
        wave = False
    if parallel and backend_ok and tl == "voting":
        reasons.append(
            f"{VOTING_TO_XLA}: tree_learner=voting: using the XLA "
            "voting-parallel grower (one-hot histograms, a vote by each "
            "column's sum over bins of |sum g|); the wave kernel has no "
            "voting path")
    elif parallel and backend_ok and not narrow_all:
        # engine growers take one bins array: mixed-width stays
        # serial-only and a parallel uint16 layout keeps the XLA path
        reasons.append(
            f"{PARALLEL_WIDE_TO_XLA}: columns wider than 256 bins with "
            f"tree_learner={tl}: using the XLA {tl}-parallel grower")
    elif parallel and backend_ok and tl == "feature" and facts.categorical:
        reasons.append(
            f"{FEATURE_CAT_TO_XLA}: a declared categorical column with "
            "tree_learner=feature: using the XLA feature-parallel grower "
            "(the feature-parallel wave grower scans numerical columns "
            "only)")
    if parallel:
        wave = (wave and mixed is None
                and (tl == "data" or (tl == "feature"
                                      and not facts.categorical)))
        mixed = None

    # ---- the booster, not a parameter, may keep the gradients a program
    # of their own: said here, so that the log and the stamps agree ---------
    boosting = getattr(config, "boosting", "gbdt")
    if not facts.fused_grad_ok and boosting in ("goss", "rf"):
        reasons.append(
            f"{BOOSTER_UNFUSED_GRAD}: boosting={boosting} reads the "
            "materialised gradients ("
            + ("the sampler ranks and amplifies them" if boosting == "goss"
               else "frozen once from the init score")
            + "): the gradient pass stays a program of its own, outside "
            "the growth program")

    # ---- the pipeline gates (the one place they live) ---------------------
    packed = mixed is None
    # the feature learner holds every row of its columns: its sibling is
    # the one chip's subtraction, in the launch
    fused = (wave and mixed is None and not facts.bundled
             and (not parallel or tl == "feature"))
    cap = max(1, min(int(config.tpu_wave_capacity), wave_capacity_max(packed)))
    if parallel:
        scatter = facts.backend == "cpu"
    else:
        # CPU takes scatter ALWAYS: no MXU to feed, and the one-hot
        # materialisation is pure memory traffic there (~340x slower per
        # tree measured at 20k rows x 28 features)
        scatter = (facts.backend == "cpu" or facts.B_phys * max(
            facts.num_phys_features, 1) > WIDE_LAYOUT_BINS)
    quant = hist_mode in QUANT_MODES
    return GrowthPlan(
        grower="wave" if wave else "serial",
        learner=tl if parallel else "serial",
        hist_mode=hist_mode, packed=packed, wave_capacity=cap,
        fused_sibling=fused, batched_apply=True,
        fused_grad=bool(facts.fused_grad_ok),
        rank_sharded_grad=bool(parallel and tl in ("data", "feature")
                               and facts.mesh_size > 1
                               and facts.query_sharding),
        interpret=interpret, mixed=mixed if wave else None,
        bundled=bool(facts.bundled),
        counts=bool(wave and not cegb),
        hist_fn="scatter" if scatter else "onehot",
        gain_gate=float(config.tpu_wave_gain_gate),
        block_rows=int(config.tpu_block_rows),
        # traced into the grower only under the quantised modes: carrying it
        # otherwise would make seed-averaged ensembles recompile identical
        # growers
        quant_seed=int(config.seed) if quant and wave else 0,
        bynode=bynode, forced=bool(forced), reasons=tuple(reasons))
