"""Atomic, versioned training checkpoints with bit-exact resume.

A crash at iteration 499/500 must cost at most ``tpu_checkpoint_freq``
iterations, and the resumed run must produce the SAME model a straight
run would have — bit-identical, RNG state and all — so the differential
test (tests/test_robust.py) can prove recovery the way the sequential-
split oracle proved the wave apply.

One checkpoint is one directory ``ckpt_{iteration:08d}/`` holding:

- ``model.txt`` — the full forest in the LightGBM v3 text format
  (shortest-round-trip float formatting: the f64 leaf/threshold values
  reload bit-exactly);
- ``state.npz`` — the device state that CANNOT be replayed without
  rounding drift: the f32 ``[N, K]`` train score, every valid-set score,
  and the live bagging mask.  Replaying trees onto a fresh score would
  re-round f64 sums into f32 in a different order; saving the array
  sidesteps the whole question;
- ``meta.json`` — iteration, the boosting-specific RNG/weight state
  (``GBDT.checkpoint_state``; DART adds its drop RNG and tree weights),
  the recorded eval history (replayed through the stateful callbacks on
  resume so early stopping continues mid-stream), a digest of the
  training config (resume REFUSES a mismatched config rather than
  silently diverging), and sha256 checksums of the other two files.

Atomicity is write-temp → fsync(every file) → ``os.rename`` (atomic on
POSIX) → fsync(parent dir).  A crash mid-write leaves a ``.tmp-*``
orphan the next save sweeps; a torn rename cannot happen; a corrupt or
truncated checkpoint fails its checksum and the loader falls back to
the next-newest valid one.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import shutil
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from ..utils import log
from . import faults

_CKPT_RE = re.compile(r"ckpt_(\d{8})$")
FORMAT_VERSION = 1

# config fields that may differ between the crashed and the resuming
# invocation without changing the training trajectory
_DIGEST_SKIP = frozenset((
    "config", "task", "output_model", "output_result", "input_model",
    "snapshot_freq", "verbosity", "convert_model",
    "tpu_checkpoint_dir", "tpu_checkpoint_freq", "tpu_checkpoint_keep",
    "tpu_telemetry", "tpu_profile", "tpu_trace", "tpu_flight_len",
    "tpu_health", "tpu_fingerprint_freq", "tpu_compile_cache_dir",
    "tpu_watchdog", "tpu_on_device_error", "tpu_device_retries",
    "tpu_wedge_timeout_s",
    # (tpu_hist_dtype is deliberately NOT here: it changes the trees a
    # resumed run would grow.)
    # eval-only: the device NDCG kernel never touches gradients or
    # trees, so flipping it must not refuse a resume
    "tpu_rank_device_eval",
    # streamed ingestion is bit-identical to the in-RAM load given the
    # same sample (tests/test_ingest_stream.py), and chunk size / memmap
    # backing never change the constructed dataset — so flipping them
    # must not refuse a resume.  (tpu_ingest_sample_seed and the shard
    # knobs are deliberately NOT here: they change the sample / the
    # local rows, hence the trees.)
    "tpu_ingest", "tpu_ingest_chunk_rows", "tpu_ingest_memmap",
))

# world-shape knobs, additionally skipped in FLEET mode (tpu_fleet set):
# the elastic fleet trains a full replica on every rank (fleet/elastic.py
# replicate mode — provably world-independent), so a resume after the
# world shrank or healed must not be refused just because the shard
# count changed.  Outside fleet mode these knobs keep refusing a resume:
# they change the local rows, hence the trees.
_DIGEST_SKIP_FLEET_WORLD = frozenset((
    "tpu_ingest_shards", "tpu_ingest_shard_id",
    "num_machines", "machines", "machine_list_filename",
    "local_listen_port", "time_out",
))


def config_digest(config) -> str:
    """Stable hash of the training-relevant config surface."""
    import dataclasses
    fleet = bool(getattr(config, "tpu_fleet", 0))
    items = {}
    for f in dataclasses.fields(config):
        if f.name in _DIGEST_SKIP or f.name == "is_parallel":
            continue
        # the tpu_fleet_* family is always operational (heartbeat cadence,
        # heal policy, rendezvous dir) — never training-relevant
        if f.name.startswith("tpu_fleet"):
            continue
        v = getattr(config, f.name)
        if fleet and f.name in _DIGEST_SKIP_FLEET_WORLD:
            # neutralize (don't drop) the world-geometry knobs: the
            # keyset stays identical, so a fleet checkpoint resumes at
            # ANY world size — including world 1, the single-process
            # digest an elastic shrink-to-one lands on
            v = f.default
        if isinstance(v, (list, tuple)):
            v = list(v)
        if f.name == "tpu_hist_dtype":
            # hash the RESOLVED kernel mode — covering the quantized
            # modes too, the same way — so back-compat aliases
            # ("float32" -> "2xbf16", "bfloat16" -> "bf16"), the ISSUE 8
            # default rename and the ISSUE 11 int16/int8 names can never
            # refuse a resume whose effective mode did not change
            from ..core.plan import resolve_hist_mode
            v = resolve_hist_mode(config)
        items[f.name] = v
    # The double-buffered wave schedule (``tpu_wave_overlap``) went with
    # PR 30; it was hashed, and it changed the trees.  Kept as the constant
    # every run now has: the digests of checkpoints written before stay
    # what they were (a resume across that PR is not refused), and one
    # written with the schedule on still is.
    items["tpu_wave_overlap"] = False
    blob = json.dumps(items, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _world_size() -> int:
    """Informational world size stamped into checkpoint meta (fleet
    post-mortems read it); never part of the digest — a shrunk-world
    resume is exactly the point of the elastic fleet."""
    try:
        from ..parallel.distributed import world_size
        return int(world_size())
    except Exception:  # noqa: BLE001 — meta decoration only
        return 1


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _fsync_write(path: str, data, binary: bool = False) -> None:
    with open(path, "wb" if binary else "w") as fh:
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:  # platforms without dir fsync
        pass


@dataclass
class RestoreState:
    iteration: int
    path: str
    eval_history: List[Tuple] = field(default_factory=list)
    meta: dict = field(default_factory=dict)


class CheckpointManager:
    """Owns one checkpoint directory: periodic save, prune, scan,
    validate, restore."""

    def __init__(self, ckpt_dir: str, freq: int = 100, keep: int = 3,
                 digest: Optional[str] = None):
        self.dir = ckpt_dir
        self.freq = max(int(freq), 0)
        self.keep = max(int(keep), 1)
        # the digest is captured from the PRISTINE config (from_config
        # runs before the first iteration): reset_parameter schedules
        # mutate booster.config.learning_rate mid-run, and a digest
        # computed at save time would never match the resuming
        # process's fresh config
        self.digest = digest

    @classmethod
    def from_config(cls, config) -> Optional["CheckpointManager"]:
        d = getattr(config, "tpu_checkpoint_dir", "") or ""
        if not d:
            return None
        return cls(d, freq=int(getattr(config, "tpu_checkpoint_freq", 100)),
                   keep=int(getattr(config, "tpu_checkpoint_keep", 3)),
                   digest=config_digest(config))

    def should_save(self, iteration: int) -> bool:
        return self.freq > 0 and iteration > 0 and iteration % self.freq == 0

    # ------------------------------------------------------------------
    def list_checkpoints(self) -> List[str]:
        """Checkpoint dirs, newest iteration first."""
        out = []
        for d in glob.glob(os.path.join(self.dir, "ckpt_*")):
            m = _CKPT_RE.search(os.path.basename(d))
            if m and os.path.isdir(d):
                out.append((int(m.group(1)), d))
        return [d for _, d in sorted(out, reverse=True)]

    def trim_to(self, iteration: int) -> int:
        """Drop every checkpoint NEWER than ``iteration`` — the elastic
        rollback: survivors agree on the fleet-wide common iteration and
        trim so the auto-resume lands exactly there on every rank.
        Returns the number of checkpoints removed."""
        removed = 0
        for d in self.list_checkpoints():
            m = _CKPT_RE.search(os.path.basename(d))
            if m and int(m.group(1)) > int(iteration):
                shutil.rmtree(d, ignore_errors=True)
                removed += 1
                log.info("checkpoint: trimmed %s (rollback to iteration "
                         "%d)", d, iteration)
        return removed

    def _sweep_orphans(self) -> None:
        for d in glob.glob(os.path.join(self.dir, ".tmp-*")):
            shutil.rmtree(d, ignore_errors=True)

    # ------------------------------------------------------------------
    def save(self, booster, iteration: int, eval_history=(),
             reason: str = "periodic") -> Optional[str]:
        """Write one atomic checkpoint; returns its path (None when the
        write failed — checkpointing must never kill training)."""
        from .. import obs
        from ..io.model_io import model_to_string
        t0 = time.perf_counter()
        gbdt = booster._gbdt
        try:
            faults.check("checkpoint_write", iteration=iteration)
            os.makedirs(self.dir, exist_ok=True)
            self._sweep_orphans()
            model_txt = model_to_string(gbdt, num_iteration=-1)
            state_meta, arrays = gbdt.checkpoint_state()
            tmp = os.path.join(self.dir, f".tmp-{os.getpid()}-{iteration}")
            os.makedirs(tmp, exist_ok=True)
            _fsync_write(os.path.join(tmp, "model.txt"), model_txt)
            with open(os.path.join(tmp, "state.npz"), "wb") as fh:
                np.savez(fh, **arrays)
                fh.flush()
                os.fsync(fh.fileno())
            meta = {
                "kind": "lgbm_tpu_checkpoint",
                "format": FORMAT_VERSION,
                "iteration": int(iteration),
                "t": round(time.time(), 6),
                "reason": reason,
                "config_digest": (self.digest
                                  or config_digest(booster.config)),
                "num_data": int(gbdt.train_ds.num_data),
                "world_size": _world_size(),
                "num_class": int(gbdt.num_tpi),
                "best_iteration": int(booster.best_iteration),
                "eval_history": [[int(it), [list(e) for e in entries]]
                                 for it, entries in eval_history],
                "state": state_meta,
                "sha256": {
                    "model.txt": _sha256_file(
                        os.path.join(tmp, "model.txt")),
                    "state.npz": _sha256_file(
                        os.path.join(tmp, "state.npz")),
                },
            }
            _fsync_write(os.path.join(tmp, "meta.json"),
                         json.dumps(meta, indent=1))
            final = os.path.join(self.dir, f"ckpt_{iteration:08d}")
            if os.path.isdir(final):   # re-save of the same iteration
                shutil.rmtree(final)
            os.rename(tmp, final)
            _fsync_dir(self.dir)
            self._prune(meta["config_digest"])
            ms = round((time.perf_counter() - t0) * 1e3, 3)
            size = sum(os.path.getsize(os.path.join(final, f))
                       for f in os.listdir(final))
            obs.event("checkpoint", iteration=int(iteration), path=final,
                      bytes=int(size), ms=ms, reason=reason)
            log.info("checkpoint: wrote %s (%d bytes, %.1fms, %s)",
                     final, size, ms, reason)
            return final
        except Exception as exc:  # noqa: BLE001 — never kill training
            log.warning("checkpoint write failed at iteration %d (%s: %s)",
                        iteration, type(exc).__name__, exc)
            return None

    def _prune(self, digest: Optional[str] = None) -> None:
        """Drop checkpoints beyond ``keep``.  Checkpoints written under
        a DIFFERENT config digest are removed first regardless of their
        iteration number: a fresh run in a reused directory must not
        have its (lower-iteration) checkpoints shadowed — and then
        pruned away — by a previous run's stale higher-iteration ones,
        which ``peek`` could never resume from anyway."""
        keep_pool = []
        for d in self.list_checkpoints():
            if digest is not None:
                try:
                    with open(os.path.join(d, "meta.json")) as fh:
                        have = json.load(fh).get("config_digest")
                except (OSError, ValueError):
                    have = None
                if have != digest:
                    log.warning("checkpoint prune: removing %s (written "
                                "under a different training config)", d)
                    shutil.rmtree(d, ignore_errors=True)
                    continue
            keep_pool.append(d)
        for d in keep_pool[self.keep:]:
            shutil.rmtree(d, ignore_errors=True)

    # ------------------------------------------------------------------
    def _validate(self, path: str) -> Optional[dict]:
        """Meta of a structurally valid checkpoint (checksums included),
        else None."""
        try:
            with open(os.path.join(path, "meta.json")) as fh:
                meta = json.load(fh)
            if meta.get("kind") != "lgbm_tpu_checkpoint":
                return None
            if int(meta.get("format", -1)) > FORMAT_VERSION:
                log.warning("checkpoint %s has newer format %s; skipping",
                            path, meta.get("format"))
                return None
            for fname, want in (meta.get("sha256") or {}).items():
                got = _sha256_file(os.path.join(path, fname))
                if got != want:
                    log.warning("checkpoint %s: %s checksum mismatch "
                                "(corrupt/truncated); skipping",
                                path, fname)
                    return None
            return meta
        except (OSError, ValueError, KeyError) as exc:
            log.warning("checkpoint %s unreadable (%s); skipping",
                        path, exc)
            return None

    def peek(self, config=None) -> Optional[Tuple[str, dict]]:
        """Newest valid checkpoint compatible with this manager's
        (pristine) config digest: returns ``(path, meta)`` without
        touching any trainer state.  A config digest mismatch refuses
        the WHOLE resume (older checkpoints are from the same run —
        they'd mismatch too)."""
        want = self.digest or (config_digest(config)
                               if config is not None else None)
        for path in self.list_checkpoints():
            meta = self._validate(path)
            if meta is None:
                continue
            if want is not None and meta.get("config_digest") != want:
                log.warning(
                    "checkpoint %s was written under a different training "
                    "config (digest %s != %s); refusing to resume — "
                    "starting fresh", path, meta.get("config_digest"),
                    want)
                return None
            return path, meta
        return None

    def resume(self, booster, peeked: Tuple[str, dict]) -> RestoreState:
        """Load a peeked checkpoint into ``booster`` (call AFTER valid
        sets are attached so their score slots exist)."""
        from .. import obs
        from ..io.model_io import load_model_string
        path, meta = peeked
        gbdt = booster._gbdt
        if int(meta.get("num_data", -1)) != int(gbdt.train_ds.num_data):
            raise ValueError(
                f"checkpoint {path} was trained on "
                f"{meta.get('num_data')} rows but this dataset has "
                f"{gbdt.train_ds.num_data}")
        with open(os.path.join(path, "model.txt")) as fh:
            loaded, _ = load_model_string(fh.read())
        gbdt.load_initial_models(list(loaded.models), replay_scores=False)
        with np.load(os.path.join(path, "state.npz")) as npz:
            arrays = {k: npz[k] for k in npz.files}
        gbdt.restore_checkpoint_state(meta["state"], arrays)
        booster.best_iteration = int(meta.get("best_iteration", -1))
        history = [(int(it), [tuple(e) for e in entries])
                   for it, entries in meta.get("eval_history", [])]
        obs.event("restore", iteration=int(meta["iteration"]), path=path)
        log.info("checkpoint: resumed from %s at iteration %d "
                 "(%d trees, %d recorded eval rounds)", path,
                 int(meta["iteration"]), len(loaded.models), len(history))
        return RestoreState(iteration=int(meta["iteration"]), path=path,
                            eval_history=history, meta=meta)
