"""Atomic versioned training checkpoints with bit-identical resume.

A checkpoint captures everything a boosting run needs to continue *as
if it had never stopped*:

* the model so far (reference model-text format — the repo's exact
  round-trip interchange format);
* the device score cache (train + every valid set, float32 exactly as
  accumulated on device) — optional via ``checkpoint_score_cache``;
* host RNG positions (bagging / feature-fraction / DART MT19937
  states) and the cached bagging mask — the device bagging stream is a
  pure function of ``(bagging_seed, iteration)`` (PR 2) and needs no
  state;
* the eval history, replayed into early-stopping / record-evaluation
  callbacks on resume so their closure state matches the uninterrupted
  run;
* fingerprints of the training config and the dataset bin layout, so a
  checkpoint is never resumed against a different experiment.

Write protocol (crash-safe on POSIX): everything lands in a hidden
temp directory first — each file is flushed + fsync'd, the manifest
(with per-file sizes and sha256 digests) is written **last** — then
one ``rename`` publishes the checkpoint and the parent directory is
fsync'd. A reader either sees a complete checkpoint or none; a torn
payload that somehow survives (fs corruption, non-atomic copies) is
caught by the manifest digest check and the loader falls back to the
previous retained checkpoint (``keep-last-K`` retention,
``checkpoint_keep``).

Layout::

    <checkpoint_dir>/
      ckpt_00000020/
        model.txt        # model text at iteration 20
        state.npz        # score cache + RNG states
        manifest.json    # written last; sizes+digests of the above

Config: ``checkpoint_dir`` (enables the subsystem), ``checkpoint_freq``
(iterations between periodic checkpoints; preemption always writes a
final one), ``checkpoint_keep``, ``checkpoint_score_cache``,
``resume=auto|off``.

**Coordinated (multi-rank) checkpoints.** In a multi-process run the
score cache is a mesh-row-sharded *global* jax.Array — no single rank
can serialize it — and per-rank independent writes give no agreement
on the last complete version. The coordinated layout commits in two
phases over the shared checkpoint directory::

    <checkpoint_dir>/
      ckpt_00000020/
        model.txt          # rank 0 (model state is replicated)
        shard_00000.npz    # rank r's addressable score rows + ranges
        shard_00001.npz    #   ... + RNG states, one per rank
        done_00000.json    # rank r's fsync receipt (size + sha256)
        done_00001.json
        manifest.json      # rank 0, after ALL done markers: + world
        COMMIT.json        # rank 0, AFTER the dir rename + fsync

Phase 1: every rank fsyncs its shard then its ``done`` marker (the
markers double as the commit barrier — no sockets in the checkpoint
path). Phase 2: rank 0 collects all markers (bounded by
``elastic_barrier_s``), writes the manifest with a ``world`` section
(size, machine list, per-rank bin-layout fingerprints), renames the
temp dir into place, and only then drops the ``COMMIT.json`` marker. A
coordinated checkpoint without its marker is torn by definition —
validation skips it and rank 0 prunes it — so resume always picks the
newest version with a **full quorum**. Shards store raw f32 score rows
with their global row ranges, so resume on ANY world size (elastic
``N -> M`` reshard, gated by ``elastic_resume``) reassembles the exact
bytes and stays bit-identical to an uninterrupted run — sharding moves
data, never values.
"""

from __future__ import annotations

import hashlib
import io as _io
import json
import os
import shutil
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..utils.log import LightGBMError, log_info, log_warning
from .faults import get_fault_plan
from .retry import read_bytes, read_text, retry_call

CKPT_FORMAT = "lightgbm_tpu.checkpoint.v1"
CKPT_PREFIX = "ckpt_"
_TMP_PREFIX = ".tmp_ckpt_"
# phase-2 marker of a coordinated checkpoint: its presence IS the
# full-quorum commit (rank 0 writes it only after every rank's shard
# fsync'd and the dir rename + fsync landed)
COMMIT_MARKER = "COMMIT.json"

# host RNG streams that advance per iteration on some paths; every one
# present on the booster is captured so resume continues the stream
_RNG_ATTRS = ("_bag_rng", "_feature_rng", "_drop_rng", "_extra_rng",
              "_goss_rng")

# params that must NOT invalidate a resume: IO paths, robustness /
# serving / telemetry knobs, prediction-only settings, and the target
# round count itself (resuming toward a longer target is the point)
_FINGERPRINT_EXCLUDE = frozenset({
    "task", "config", "data", "valid", "input_model", "output_model",
    "output_result", "snapshot_freq", "verbosity", "telemetry_out",
    "convert_model", "convert_model_language",
    "checkpoint_dir", "checkpoint_freq", "checkpoint_keep",
    "checkpoint_score_cache", "resume", "faults", "guard_policy",
    "guard_loss_spike", "guard_max_rollbacks", "num_iterations",
    "num_iteration_predict", "predict_raw_score", "predict_leaf_index",
    "predict_contrib", "predict_disable_shape_check", "pred_early_stop",
    "pred_early_stop_freq", "pred_early_stop_margin",
    "serving_host", "serving_port", "serving_buckets",
    "serving_max_queue", "serving_flush_ms", "serving_timeout_ms",
    "serving_shed_policy", "serving_device", "serving_warmup",
    "serving_replicas", "serving_models", "serving_max_pending",
    "serving_quota_qps", "serving_quota_burst",
    "serving_quota_tenants", "serving_canary_model",
    "serving_canary_weight", "serving_shadow_model",
    "pipeline_mode", "pipeline_source", "pipeline_log_path",
    "pipeline_window_rows", "pipeline_holdout_rows",
    "pipeline_cycles", "pipeline_interval_s", "pipeline_dir",
    "pipeline_canary_stages", "pipeline_stage_requests",
    "pipeline_latency_slo_pct", "pipeline_quality_drop",
    "pipeline_continue_iters", "pipeline_replay_seed",
    "pipeline_replay_noise", "pipeline_serve_http",
    "num_threads",
    # the machine list names WHERE the job runs, not WHAT it computes:
    # elastic resume onto a different host set must reach the explicit
    # world-size check below, not die on a silent fingerprint mismatch
    # (num_machines stays IN the fingerprint — it selects the learner
    # mesh and therefore the training programs)
    "machines", "machine_list_filename", "local_listen_port",
    "time_out",
    "elastic_watchdog", "elastic_heartbeat_ms",
    "elastic_heartbeat_timeout_ms", "elastic_stall_timeout_ms",
    "elastic_abort_grace_ms", "elastic_port", "elastic_resume",
    "elastic_shutdown", "elastic_barrier_s",
})


# ----------------------------------------------------------------------
# atomic file primitives (shared: CLI snapshots and final model writes
# route through these too)
def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # platforms without directory fds
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(path: str, data: bytes) -> None:
    """Write-temp + fsync + rename: ``path`` either keeps its previous
    content or atomically becomes ``data`` — never a torn mix."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{os.path.basename(path)}.{os.getpid()}.tmp")
    try:
        with open(tmp, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        _fsync_dir(d)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_text(path: str, text: str) -> None:
    atomic_write_bytes(path, text.encode("utf-8"))


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def config_fingerprint(config) -> str:
    """Digest of every training-relevant parameter (IO/robustness/
    serving knobs excluded): equal fingerprints mean a checkpoint can
    legally continue under this config."""
    params = {k: v for k, v in config.to_params().items()
              if k not in _FINGERPRINT_EXCLUDE}
    payload = json.dumps(params, sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()


def _local_score_blocks(arr) -> List[Tuple[int, int, np.ndarray]]:
    """``[(row_start, row_stop, block)]`` for the rows of ``arr`` this
    process can address. A fully-addressable array is one block
    covering everything; a mesh-row-sharded global jax.Array yields its
    unique local row ranges (replicas across local devices deduped)."""
    try:
        fully = bool(getattr(arr, "is_fully_addressable", True))
    except Exception:
        fully = True
    if fully:
        a = np.asarray(arr, np.float32)
        return [(0, int(a.shape[0]), a)]
    blocks: Dict[Tuple[int, int], np.ndarray] = {}
    for sh in arr.addressable_shards:
        idx = sh.index[0] if sh.index else slice(None)
        start = int(idx.start or 0)
        data = np.asarray(sh.data, np.float32)
        blocks[(start, start + int(data.shape[0]))] = data
    return [(s, e, d) for (s, e), d in sorted(blocks.items())]


def _pack_blocked(arrays: Dict[str, np.ndarray], key: str,
                  arr) -> None:
    """Store ``arr`` into the npz dict as global shape + this rank's
    row-range blocks (the shard half of the reassembly protocol)."""
    blocks = _local_score_blocks(arr)
    arrays[f"{key}_shape"] = np.asarray(arr.shape, np.int64)
    arrays[f"{key}_ranges"] = np.asarray(
        [[s, e] for s, e, _ in blocks], np.int64).reshape(-1, 2)
    for j, (_s, _e, d) in enumerate(blocks):
        arrays[f"{key}_block_{j}"] = d


def _reassemble_blocked(shards: List[Any], key: str,
                        what: str) -> Optional[np.ndarray]:
    """Rebuild the FULL host array named ``key`` from every rank's
    recorded row ranges — raw f32 values, no arithmetic, so the result
    is byte-identical regardless of the world size that wrote it or
    the one reading it. None when no shard carries the key; raises on
    incomplete row coverage (a shard from a third world size slipped
    in)."""
    shape = None
    for z in shards:
        if f"{key}_shape" in z.files:
            shape = tuple(int(v) for v in z[f"{key}_shape"])
            break
    if shape is None:
        return None
    full = np.zeros(shape, np.float32)
    filled = np.zeros(shape[0] if shape else 0, bool)
    for z in shards:
        if f"{key}_ranges" not in z.files:
            continue
        for j, (s, e) in enumerate(np.asarray(z[f"{key}_ranges"],
                                              np.int64)):
            full[int(s):int(e)] = z[f"{key}_block_{j}"]
            filled[int(s):int(e)] = True
    if not filled.all():
        missing = int((~filled).sum())
        raise LightGBMError(
            f"coordinated checkpoint: {what} row coverage incomplete "
            f"({missing} of {shape[0]} rows missing across "
            f"{len(shards)} shards)")
    return full


class ResumeInfo(NamedTuple):
    iteration: int
    begin_iteration: int
    eval_history: List
    path: str


class CheckpointManager:
    """Writes, validates, retains and restores training checkpoints."""

    def __init__(self, directory: str, freq: int = 0, keep: int = 3,
                 save_scores: bool = True):
        self.directory = directory
        self.freq = int(freq)
        self.keep = max(int(keep), 1)
        self.save_scores = bool(save_scores)
        self._writes = 0
        self._last_saved: Optional[int] = None

    @classmethod
    def from_config(cls, cfg) -> "CheckpointManager":
        return cls(cfg.checkpoint_dir,
                   freq=int(getattr(cfg, "checkpoint_freq", 0)),
                   keep=int(getattr(cfg, "checkpoint_keep", 3)),
                   save_scores=bool(getattr(cfg,
                                            "checkpoint_score_cache",
                                            True)))

    # -- listing -------------------------------------------------------
    def checkpoints(self) -> List[Tuple[int, str]]:
        """[(iteration, path)] sorted ascending by iteration."""
        if not os.path.isdir(self.directory):
            return []
        out = []
        for name in os.listdir(self.directory):
            if not name.startswith(CKPT_PREFIX):
                continue
            try:
                it = int(name[len(CKPT_PREFIX):])
            except ValueError:
                continue
            out.append((it, os.path.join(self.directory, name)))
        return sorted(out)

    def has_checkpoint(self) -> bool:
        return bool(self.checkpoints())

    # -- writing -------------------------------------------------------
    def maybe_save(self, booster, eval_history: List,
                   begin_iteration: int) -> Optional[str]:
        """Periodic save at the ``checkpoint_freq`` cadence; call at
        iteration boundaries (after eval)."""
        it = booster._gbdt.iter
        if self.freq <= 0 or it <= 0 or it % self.freq != 0:
            return None
        return self.save(booster, eval_history, begin_iteration)

    def save(self, booster, eval_history: List,
             begin_iteration: int) -> Optional[str]:
        """Write one checkpoint for the booster's current state.
        Idempotent per iteration (a preemption right after a periodic
        save does not write twice)."""
        gbdt = booster._gbdt
        it = int(gbdt.iter)
        if self._last_saved == it:
            return None
        from ..observability.telemetry import get_telemetry
        tel = get_telemetry()
        with tel.span("checkpoint.write"):
            path = self._write(booster, it, eval_history,
                               begin_iteration)
        self._last_saved = it
        world = self._world()
        if world is None or world.rank == 0:
            self._retain()  # retention races are rank 0's job alone
        return path

    @staticmethod
    def _world():
        """This process's WorldInfo when a multi-process runtime is up
        (routes the write/restore paths to the coordinated protocol)."""
        try:
            from ..parallel.distributed import current_world
            return current_world()
        except Exception:
            return None

    def _write(self, booster, it: int, eval_history: List,
               begin_iteration: int) -> str:
        world = self._world()
        if world is not None:
            return self._write_coordinated(booster, it, eval_history,
                                           begin_iteration, world)
        gbdt = booster._gbdt
        os.makedirs(self.directory, exist_ok=True)
        self._cleanup_tmp()
        from ..io.model_text import save_model_to_string
        model_text = save_model_to_string(gbdt)
        state_bytes = self._state_npz_bytes(gbdt)

        name = f"{CKPT_PREFIX}{it:08d}"
        final = os.path.join(self.directory, name)
        tmp = os.path.join(self.directory,
                           f"{_TMP_PREFIX}{it:08d}_{os.getpid()}")
        if os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        try:
            files: Dict[str, Dict[str, Any]] = {}
            payloads = {"model.txt": model_text.encode("utf-8"),
                        "state.npz": state_bytes}
            for fname, data in payloads.items():
                with open(os.path.join(tmp, fname), "wb") as fh:
                    fh.write(data)
                    fh.flush()
                    os.fsync(fh.fileno())
                files[fname] = {"bytes": len(data),
                                "sha256": _digest(data)}

            self._writes += 1
            plan = get_fault_plan()
            if plan is not None and plan.take(
                    "torn_checkpoint", nth=self._writes) is not None:
                # simulate a torn write that still got published: the
                # manifest keeps the pre-truncation digests, so the
                # validator MUST reject this checkpoint later
                victim = os.path.join(tmp, "state.npz")
                with open(victim, "r+b") as fh:
                    fh.truncate(max(len(state_bytes) // 2, 1))

            manifest = {
                "format": CKPT_FORMAT,
                "iteration": it,
                "begin_iteration": int(begin_iteration),
                "num_models": len(gbdt.models),
                "num_tree_per_iteration": gbdt.num_tree_per_iteration,
                "num_valid_sets": len(gbdt.valid_scores),
                "shrinkage_rate": float(gbdt.shrinkage_rate),
                "score_cache": self.save_scores,
                "config_fingerprint": config_fingerprint(gbdt.config),
                "data_fingerprint":
                    gbdt.train_data.bin_layout_fingerprint(),
                "eval_history": eval_history,
                "files": files,
            }
            mbytes = json.dumps(manifest, default=float).encode("utf-8")
            with open(os.path.join(tmp, "manifest.json"), "wb") as fh:
                fh.write(mbytes)
                fh.flush()
                os.fsync(fh.fileno())

            if os.path.isdir(final):  # pre-rollback leftover: replace
                shutil.rmtree(final)
            os.rename(tmp, final)
            _fsync_dir(self.directory)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        from ..observability.telemetry import get_telemetry
        tel = get_telemetry()
        tel.count("checkpoint.writes")
        tel.count("checkpoint.bytes",
                  sum(f["bytes"] for f in files.values()) + len(mbytes))
        log_info(f"checkpoint: wrote iteration {it} -> {final}")
        return final

    # -- coordinated (multi-rank) writing ------------------------------
    def _write_coordinated(self, booster, it: int, eval_history: List,
                           begin_iteration: int,
                           world) -> Optional[str]:
        """Two-phase commit over the shared checkpoint directory (see
        module docstring): write-all-fsync (per-rank shards + done
        markers), then rank 0 publishes manifest + rename + COMMIT."""
        gbdt = booster._gbdt
        os.makedirs(self.directory, exist_ok=True)
        name = f"{CKPT_PREFIX}{it:08d}"
        final = os.path.join(self.directory, name)
        # deterministic temp name: every rank of this iteration must
        # land in the SAME directory (contrast the pid-suffixed serial
        # temp, which exists to isolate concurrent writers)
        tmp = os.path.join(self.directory, f"{_TMP_PREFIX}{it:08d}")
        os.makedirs(tmp, exist_ok=True)
        barrier_s = float(getattr(gbdt.config, "elastic_barrier_s",
                                  120.0))
        from ..observability.telemetry import get_telemetry
        tel = get_telemetry()

        def put(fname: str, data: bytes) -> Dict[str, Any]:
            with open(os.path.join(tmp, fname), "wb") as fh:
                fh.write(data)
                fh.flush()
                os.fsync(fh.fileno())
            return {"bytes": len(data), "sha256": _digest(data)}

        # phase 1 (every rank): shard, then the fsync receipt. A stale
        # marker from a crashed attempt is harmless: rank 0 only
        # accepts a marker whose digest matches the shard on disk, and
        # this attempt overwrites both.
        shard_name = f"shard_{world.rank:05d}.npz"
        shard_bytes = self._shard_npz_bytes(gbdt, world)
        info = put(shard_name, shard_bytes)
        put(f"done_{world.rank:05d}.json", json.dumps({
            "rank": world.rank, "file": shard_name, **info,
            "data_fingerprint":
                gbdt.train_data.bin_layout_fingerprint(),
        }).encode("utf-8"))
        _fsync_dir(tmp)

        if world.rank != 0:
            # wait for rank 0's phase 2; a timeout does NOT fail
            # training — the torn attempt is simply never committed
            # and the validator will skip it
            deadline = time.monotonic() + barrier_s
            commit = os.path.join(final, COMMIT_MARKER)
            while time.monotonic() < deadline:
                if os.path.exists(commit):
                    return final
                time.sleep(0.05)
            tel.count("elastic.barrier_timeouts")
            log_warning(
                f"checkpoint: rank {world.rank} timed out after "
                f"{barrier_s:.0f}s waiting for the iteration-{it} "
                "commit marker; continuing without this checkpoint")
            return None

        # phase 2 (rank 0): model text, quorum, manifest, publish
        files: Dict[str, Dict[str, Any]] = {shard_name: info}
        from ..io.model_text import save_model_to_string
        files["model.txt"] = put(
            "model.txt", save_model_to_string(gbdt).encode("utf-8"))
        fingerprints: Dict[str, str] = {}
        got: Dict[int, Dict[str, Any]] = {}
        deadline = time.monotonic() + barrier_s
        while len(got) < world.size - 1:
            for r in range(1, world.size):
                if r in got:
                    continue
                mpath = os.path.join(tmp, f"done_{r:05d}.json")
                if not os.path.exists(mpath):
                    continue
                try:
                    marker = json.loads(read_text(mpath))
                    data = read_bytes(os.path.join(
                        tmp, marker["file"]))
                except (OSError, ValueError, KeyError):
                    continue  # mid-write; poll again
                if _digest(data) != marker.get("sha256"):
                    continue  # stale marker vs fresh shard: re-poll
                got[r] = marker
                files[marker["file"]] = {
                    "bytes": marker["bytes"],
                    "sha256": marker["sha256"]}
                fingerprints[str(r)] = marker.get(
                    "data_fingerprint", "")
            if time.monotonic() > deadline:
                tel.count("elastic.barrier_timeouts")
                log_warning(
                    f"checkpoint: quorum timeout at iteration {it}: "
                    f"{len(got) + 1}/{world.size} ranks fsync'd "
                    f"within {barrier_s:.0f}s; abandoning this "
                    "checkpoint (not committed)")
                return None
            if len(got) < world.size - 1:
                time.sleep(0.05)
        fingerprints["0"] = gbdt.train_data.bin_layout_fingerprint()

        manifest = {
            "format": CKPT_FORMAT,
            "iteration": it,
            "begin_iteration": int(begin_iteration),
            "num_models": len(gbdt.models),
            "num_tree_per_iteration": gbdt.num_tree_per_iteration,
            "num_valid_sets": len(gbdt.valid_scores),
            "shrinkage_rate": float(gbdt.shrinkage_rate),
            "score_cache": self.save_scores,
            "config_fingerprint": config_fingerprint(gbdt.config),
            "data_fingerprint": fingerprints["0"],
            "eval_history": eval_history,
            "files": files,
            "world": {
                "size": world.size,
                "machines": self._machine_strings(gbdt.config),
                "data_fingerprints": fingerprints,
            },
        }
        put("manifest.json", json.dumps(manifest,
                                        default=float).encode("utf-8"))
        if os.path.isdir(final):  # pre-rollback / torn leftover
            shutil.rmtree(final)
        os.rename(tmp, final)
        _fsync_dir(self.directory)
        # the commit marker goes in LAST: rename without marker = torn
        atomic_write_text(os.path.join(final, COMMIT_MARKER),
                          json.dumps({"iteration": it,
                                      "world_size": world.size}))
        tel.count("checkpoint.writes")
        tel.count("checkpoint.coordinated_writes")
        tel.count("checkpoint.bytes",
                  sum(f["bytes"] for f in files.values()))
        log_info(f"checkpoint: committed coordinated iteration {it} "
                 f"({world.size} ranks) -> {final}")
        return final

    @staticmethod
    def _machine_strings(config) -> List[str]:
        try:
            from ..parallel.distributed import parse_machines
            return [f"{h}:{p}" for h, p in parse_machines(config)]
        except Exception:
            return []

    def _shard_npz_bytes(self, gbdt, world) -> bytes:
        """This rank's half of phase 1: addressable score rows with
        their global row ranges (raw f32 — reassembly does no
        arithmetic), plus the host RNG states (identical streams on
        every rank; restore reads rank 0's)."""
        arrays: Dict[str, np.ndarray] = {}
        if self.save_scores:
            _pack_blocked(arrays, "train_score", gbdt.train_score)
            for i, vs in enumerate(gbdt.valid_scores):
                _pack_blocked(arrays, f"valid_score_{i}", vs)
        if gbdt.bag_weight is not None and not gbdt._device_bagging():
            _pack_blocked(arrays, "bag_weight", gbdt.bag_weight)
        for attr in _RNG_ATTRS:
            rng = getattr(gbdt, attr, None)
            if isinstance(rng, np.random.RandomState):
                name, keys, pos, has_gauss, cached = rng.get_state()
                arrays[f"rng{attr}_keys"] = np.asarray(keys, np.uint32)
                arrays[f"rng{attr}_meta"] = np.asarray(
                    [pos, has_gauss], np.int64)
                arrays[f"rng{attr}_cached"] = np.asarray(
                    [cached], np.float64)
        buf = _io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()

    def _state_npz_bytes(self, gbdt) -> bytes:
        arrays: Dict[str, np.ndarray] = {}
        if self.save_scores:
            arrays["train_score"] = np.asarray(gbdt.train_score,
                                               np.float32)
            for i, vs in enumerate(gbdt.valid_scores):
                arrays[f"valid_score_{i}"] = np.asarray(vs, np.float32)
        # cached bagging mask: only the host-RNG path needs it (the
        # device draw is recomputed from (seed, iteration) exactly)
        if gbdt.bag_weight is not None and not gbdt._device_bagging():
            arrays["bag_weight"] = np.asarray(gbdt.bag_weight,
                                              np.float32)
        for attr in _RNG_ATTRS:
            rng = getattr(gbdt, attr, None)
            if isinstance(rng, np.random.RandomState):
                name, keys, pos, has_gauss, cached = rng.get_state()
                arrays[f"rng{attr}_keys"] = np.asarray(keys, np.uint32)
                arrays[f"rng{attr}_meta"] = np.asarray(
                    [pos, has_gauss], np.int64)
                arrays[f"rng{attr}_cached"] = np.asarray(
                    [cached], np.float64)
        buf = _io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()

    def _cleanup_tmp(self) -> None:
        """Drop temp dirs left by crashed writers (best effort)."""
        try:
            for name in os.listdir(self.directory):
                if name.startswith(_TMP_PREFIX):
                    shutil.rmtree(os.path.join(self.directory, name),
                                  ignore_errors=True)
        except OSError:
            pass

    def _retain(self) -> None:
        ckpts = self.checkpoints()
        for it, path in ckpts[:-self.keep]:
            shutil.rmtree(path, ignore_errors=True)

    # -- validation / restore ------------------------------------------
    def validate(self, path: str) -> Optional[Dict[str, Any]]:
        """Parse + verify one checkpoint dir; returns the manifest when
        every payload matches its recorded size and sha256."""
        try:
            mtext = retry_call(read_text,
                               os.path.join(path, "manifest.json"),
                               attempts=3, base_delay_s=0.05,
                               desc=f"checkpoint manifest {path}")
            manifest = json.loads(mtext)
            if manifest.get("format") != CKPT_FORMAT:
                log_warning(f"checkpoint: {path} has unknown format "
                            f"{manifest.get('format')!r}")
                return None
            if manifest.get("world") and not os.path.exists(
                    os.path.join(path, COMMIT_MARKER)):
                # a coordinated checkpoint without its phase-2 marker
                # never reached full quorum — torn by definition
                log_warning(f"checkpoint: {path} lacks the commit "
                            "marker (torn coordinated write)")
                return None
            for fname, info in manifest.get("files", {}).items():
                data = retry_call(read_bytes,
                                  os.path.join(path, fname),
                                  attempts=3, base_delay_s=0.05,
                                  desc=f"checkpoint file {fname}")
                if len(data) != int(info["bytes"]) \
                        or _digest(data) != info["sha256"]:
                    log_warning(
                        f"checkpoint: {path}/{fname} is torn "
                        f"({len(data)} bytes vs recorded "
                        f"{info['bytes']}; digest mismatch)")
                    return None
            return manifest
        except (OSError, ValueError, KeyError, json.JSONDecodeError) \
                as e:
            log_warning(f"checkpoint: cannot validate {path}: {e}")
            return None

    def latest_valid(self) -> Optional[Tuple[str, Dict[str, Any]]]:
        """Newest checkpoint that passes validation; invalid ones fall
        back to the previous retained checkpoint (counted + warned)."""
        from ..observability.telemetry import get_telemetry
        for it, path in reversed(self.checkpoints()):
            manifest = self.validate(path)
            if manifest is not None:
                return path, manifest
            get_telemetry().count("checkpoint.fallbacks")
            log_warning(f"checkpoint: {path} failed validation; "
                        "falling back to the previous checkpoint")
            self._maybe_prune_torn(path)
        return None

    def _maybe_prune_torn(self, path: str) -> None:
        """Remove a torn COORDINATED checkpoint (world manifest, no
        commit marker) so it never shadows an older full-quorum
        version again. Rank 0 / single-process only; serial torn
        checkpoints are left for post-mortems (unchanged behavior)."""
        world = self._world()
        if world is not None and world.rank != 0:
            return
        try:
            manifest = json.loads(read_text(
                os.path.join(path, "manifest.json")))
        except (OSError, ValueError):
            return
        if not manifest.get("world") or os.path.exists(
                os.path.join(path, COMMIT_MARKER)):
            return
        shutil.rmtree(path, ignore_errors=True)
        from ..observability.telemetry import get_telemetry
        get_telemetry().count("checkpoint.pruned_torn")
        log_warning(f"checkpoint: pruned torn coordinated checkpoint "
                    f"{path}")

    def restore_latest(self, booster) -> Optional[ResumeInfo]:
        """Restore the newest valid, fingerprint-matching checkpoint
        into the booster. Returns None (with a warning) when nothing
        valid/compatible exists — callers then start fresh."""
        found = self.latest_valid()
        if found is None:
            return None
        path, manifest = found
        gbdt = booster._gbdt
        cfg_fp = config_fingerprint(gbdt.config)
        if manifest.get("config_fingerprint") != cfg_fp:
            log_warning(
                "checkpoint: config fingerprint mismatch (training "
                "parameters changed since the checkpoint was written); "
                f"ignoring {path}")
            return None
        data_fp = gbdt.train_data.bin_layout_fingerprint()
        if manifest.get("data_fingerprint") != data_fp:
            log_warning(
                "checkpoint: dataset bin-layout fingerprint mismatch "
                f"(different data/binning); ignoring {path}")
            return None
        if int(manifest.get("num_valid_sets", 0)) \
                != len(gbdt.valid_scores):
            log_warning(
                "checkpoint: validation-set count changed since the "
                f"checkpoint was written; ignoring {path}")
            return None
        self._check_world_compat(manifest, gbdt.config, path)
        self._apply(booster, path, manifest)
        from ..observability.telemetry import get_telemetry
        get_telemetry().count("checkpoint.restores")
        log_info(f"checkpoint: restored iteration "
                 f"{manifest['iteration']} from {path}")
        return ResumeInfo(int(manifest["iteration"]),
                          int(manifest.get("begin_iteration", 0)),
                          manifest.get("eval_history") or [], path)

    def _check_world_compat(self, manifest: Dict[str, Any], config,
                            path: str) -> None:
        """World-shape agreement between the checkpoint and this run:
        a mismatch is a structured error naming BOTH sides — never a
        silent wrong-mesh resume — unless ``elastic_resume=true``
        explicitly opts into the N->M reshard."""
        world_m = manifest.get("world") or {}
        cur = self._world()
        if not world_m and cur is None:
            return  # serial checkpoint, serial run: nothing to agree on
        ck_size = int(world_m.get("size", 1))
        ck_machines = [str(m) for m in world_m.get("machines", [])]
        cur_size = cur.size if cur is not None else 1
        cur_machines = self._machine_strings(config) \
            if cur is not None else []
        if ck_size == cur_size and ck_machines == cur_machines:
            return
        if bool(getattr(config, "elastic_resume", False)):
            log_info(
                f"checkpoint: elastic resume {ck_size} -> {cur_size} "
                f"ranks (checkpoint machines={ck_machines or ['-']}, "
                f"current={cur_machines or ['-']}); re-sharding "
                f"{path}")
            return
        raise LightGBMError(
            "checkpoint: world mismatch — checkpoint was written by "
            f"{ck_size} rank(s) on machines "
            f"[{', '.join(ck_machines) or '-'}] but this run has "
            f"{cur_size} rank(s) on machines "
            f"[{', '.join(cur_machines) or '-'}]. Set "
            "elastic_resume=true to re-shard onto the new world, or "
            "restart on the original machine list. "
            f"(checkpoint: {path})")

    def _apply(self, booster, path: str,
               manifest: Dict[str, Any]) -> None:
        self._apply_model(booster, path, manifest)
        if manifest.get("world"):
            self._apply_world_state(booster, path, manifest)
        else:
            self._apply_serial_state(booster, path)

    def _apply_model(self, booster, path: str,
                     manifest: Dict[str, Any]) -> None:
        gbdt = booster._gbdt
        from ..io.model_text import load_model_from_string
        model_text = read_text(os.path.join(path, "model.txt"))
        loaded = load_model_from_string(model_text)
        if loaded.num_tree_per_iteration \
                != gbdt.num_tree_per_iteration:
            raise LightGBMError(
                "checkpoint model has "
                f"{loaded.num_tree_per_iteration} trees/iteration; "
                f"booster expects {gbdt.num_tree_per_iteration}")
        gbdt.models = list(loaded.models)
        gbdt.iter = int(manifest["iteration"])
        gbdt.shrinkage_rate = float(
            manifest.get("shrinkage_rate", gbdt.shrinkage_rate))

    @staticmethod
    def _apply_rngs(gbdt, z) -> None:
        names = set(z.files)
        for attr in _RNG_ATTRS:
            if f"rng{attr}_keys" not in names:
                continue
            rng = getattr(gbdt, attr, None)
            if not isinstance(rng, np.random.RandomState):
                continue
            meta = z[f"rng{attr}_meta"]
            rng.set_state((
                "MT19937", np.asarray(z[f"rng{attr}_keys"],
                                      np.uint32),
                int(meta[0]), int(meta[1]),
                float(z[f"rng{attr}_cached"][0])))

    def _apply_serial_state(self, booster, path: str) -> None:
        gbdt = booster._gbdt
        import jax.numpy as jnp
        with np.load(_io.BytesIO(
                read_bytes(os.path.join(path, "state.npz"))),
                allow_pickle=False) as z:
            names = set(z.files)
            if "train_score" in names:
                gbdt.train_score = jnp.asarray(z["train_score"],
                                               jnp.float32)
                for i in range(len(gbdt.valid_scores)):
                    gbdt.valid_scores[i] = jnp.asarray(
                        z[f"valid_score_{i}"], jnp.float32)
            else:
                self._recompute_scores(booster)
            if "bag_weight" in names:
                gbdt.bag_weight = jnp.asarray(z["bag_weight"],
                                              jnp.float32)
            else:
                gbdt.bag_weight = None
            self._apply_rngs(gbdt, z)

    def _apply_world_state(self, booster, path: str,
                           manifest: Dict[str, Any]) -> None:
        """Coordinated restore: reassemble the FULL score arrays from
        every writer rank's recorded row ranges (raw values, no
        arithmetic), then hand them to jax exactly like a fresh run's
        initial scores — the current mesh re-shards them on first use,
        so any reader world size M continues bit-identical to the
        writer's N."""
        gbdt = booster._gbdt
        import jax.numpy as jnp
        shard_names = sorted(
            f for f in manifest.get("files", {})
            if f.startswith("shard_") and f.endswith(".npz"))
        shards = [np.load(_io.BytesIO(
            read_bytes(os.path.join(path, f))), allow_pickle=False)
            for f in shard_names]
        try:
            train = _reassemble_blocked(shards, "train_score",
                                        "train_score")
            if train is not None:
                gbdt.train_score = jnp.asarray(train, jnp.float32)
                for i in range(len(gbdt.valid_scores)):
                    v = _reassemble_blocked(
                        shards, f"valid_score_{i}", f"valid_score_{i}")
                    gbdt.valid_scores[i] = jnp.asarray(v, jnp.float32)
            else:
                self._recompute_scores(booster)
            bag = _reassemble_blocked(shards, "bag_weight",
                                      "bag_weight")
            gbdt.bag_weight = (jnp.asarray(bag, jnp.float32)
                               if bag is not None else None)
            # rank 0's RNG states: the host streams advance in lockstep
            # on every rank, so one copy continues them all
            self._apply_rngs(gbdt, shards[0])
        finally:
            for z in shards:
                z.close()

    def _recompute_scores(self, booster) -> None:
        """Score-cache-less restore: rebuild the score buffers by
        re-predicting every checkpointed tree over the RAW feature
        matrices. f64 accumulation re-cast to f32 — NOT guaranteed
        bit-identical to the device-accumulated cache; prefer
        ``checkpoint_score_cache=true`` (the default) when exact resume
        matters."""
        import jax.numpy as jnp
        log_warning(
            "checkpoint: score cache absent; recomputing scores from "
            "the raw data (resume is approximate, not bit-identical)")
        gbdt = booster._gbdt
        k = gbdt.num_tree_per_iteration

        def raw_matrix(ds):
            from ..basic import (_apply_pandas_categorical,
                                 _is_pandas_df, _to_matrix)
            X = ds.data
            if X is None:
                raise LightGBMError(
                    "cannot recompute scores: the raw feature matrix "
                    "was freed (free_raw_data) — re-run with "
                    "checkpoint_score_cache=true")
            if isinstance(X, str):
                from ..config import Config as _Cfg
                from ..data.file_loader import load_file
                X = load_file(X, _Cfg.from_params(
                    ds._merged_params()))[0]
            if _is_pandas_df(X):
                X = _apply_pandas_categorical(X, ds.pandas_categorical)
            else:
                X = _to_matrix(X)
            return np.asarray(X, np.float64)

        def rebuilt(score0, ds):
            X = raw_matrix(ds)
            out = np.zeros((X.shape[0], k))
            for i, t in enumerate(gbdt.models):
                out[:, i % k] += t.predict(X)
            return score0 + jnp.asarray(out, jnp.float32)

        gbdt.train_score = rebuilt(gbdt.train_score,
                                   booster.train_set)
        for i, vd in enumerate(booster.valid_sets):
            gbdt.valid_scores[i] = rebuilt(gbdt.valid_scores[i], vd)
