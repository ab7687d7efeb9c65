"""Checkpoint / resume — orbax-backed, with the config dataclass serialized
alongside so a checkpoint alone can rebuild the model.

Parity targets (reference: SURVEY §5.4):
- training checkpoints monitored on ``val_loss`` with best-k retention and
  weights-only option (reference: perceiver/scripts/trainer.yaml:7-12),
- hyperparameters-in-checkpoint so restore needs no external files
  (reference: perceiver/model/core/lightning.py:24,108 save_hyperparameters),
- a warm-start matrix: full-state resume, params-only load, and encoder-only
  load with optional freezing (reference:
  perceiver/model/text/classifier/lightning.py:28-36),
- an inference-side ``save_pretrained`` / ``load_pretrained`` seam analogous
  to the HF wrappers (reference: perceiver/model/text/clm/huggingface.py:11-22).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time
from typing import Any, Callable, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp
from flax import serialization

CONFIG_FILE = "config.json"
PARAMS_FILE = "params.msgpack"


class ResumePreflightError(RuntimeError):
    """A checkpoint is structurally incompatible with the state (or config)
    it is being restored into — raised by :meth:`CheckpointManager.preflight`
    with every detected problem in one actionable message, instead of the
    deep orbax ``ValueError`` a blind restore would die on.

    ``problems`` holds the individual findings (machine-readable)."""

    def __init__(self, directory: str, step, problems: list):
        self.directory = directory
        self.step = step
        self.problems = list(problems)
        lines = "\n".join(f"  - {p}" for p in self.problems)
        super().__init__(
            f"resume preflight failed for checkpoint step {step} under "
            f"{directory}:\n{lines}\n(the checkpoint belongs to a different "
            "model/config; fix the config, point at the right run dir, or "
            "start fresh with resume=False)"
        )


# ---------------------------------------------------------------------------
# config (de)serialization — nested dataclasses tagged with their class path
# ---------------------------------------------------------------------------


def config_to_dict(config) -> dict:
    """Recursively convert a config dataclass to a JSON-safe dict; each
    dataclass is tagged with its import path so ``config_from_dict`` can
    rebuild the exact class (including encoder/decoder subclasses)."""
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        d = {f.name: config_to_dict(getattr(config, f.name)) for f in dataclasses.fields(config)}
        d["__config_class__"] = f"{type(config).__module__}.{type(config).__qualname__}"
        return d
    if isinstance(config, (list, tuple)):
        return [config_to_dict(v) for v in config]
    if isinstance(config, dict):
        return {k: config_to_dict(v) for k, v in config.items()}
    if isinstance(config, (np.integer,)):
        return int(config)
    if isinstance(config, (np.floating,)):
        return float(config)
    return config


def _coerce_tuples(cls, kwargs: dict) -> dict:
    """JSON has no tuples; restore list values to tuples for fields annotated
    as (or defaulting to) tuples, e.g. ``image_shape``."""
    import typing

    try:
        hints = typing.get_type_hints(cls)
    except Exception:
        hints = {}
    for f in dataclasses.fields(cls):
        v = kwargs.get(f.name)
        if not isinstance(v, list):
            continue
        origin = typing.get_origin(hints.get(f.name))
        default_is_tuple = isinstance(f.default, tuple) if f.default is not dataclasses.MISSING else False
        if origin is tuple or default_is_tuple:
            kwargs[f.name] = tuple(v)
    return kwargs


def config_from_dict(d: Any):
    """Inverse of :func:`config_to_dict`."""
    if isinstance(d, dict) and "__config_class__" in d:
        path = d["__config_class__"]
        module_name, _, class_name = path.rpartition(".")
        cls = getattr(importlib.import_module(module_name), class_name)
        kwargs = {k: config_from_dict(v) for k, v in d.items() if k != "__config_class__"}
        field_names = {f.name for f in dataclasses.fields(cls)}
        kwargs = _coerce_tuples(cls, {k: v for k, v in kwargs.items() if k in field_names})
        return cls(**kwargs)
    if isinstance(d, list):
        return [config_from_dict(v) for v in d]
    if isinstance(d, dict):
        return {k: config_from_dict(v) for k, v in d.items()}
    return d


def save_config(directory: str, config) -> None:
    # single-writer on shared filesystems (orbax coordinates its own
    # multi-host writes; this JSON sidecar is ours to gate)
    from perceiver_io_tpu.parallel.dist import is_main_process

    if not is_main_process():
        return
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, CONFIG_FILE), "w") as f:
        json.dump(config_to_dict(config), f, indent=2)


def load_config(directory: str):
    with open(os.path.join(directory, CONFIG_FILE)) as f:
        return config_from_dict(json.load(f))


# ---------------------------------------------------------------------------
# pretrained (inference) seam: params + config in one directory
# ---------------------------------------------------------------------------


def save_pretrained(directory: str, params, config=None) -> None:
    """Weights-only artifact for inference/distribution — msgpack params +
    config.json, the torch-free analog of HF ``save_pretrained``.

    Single-writer: on a multi-host program only process 0 writes (params must
    be process-local/replicated — gather sharded trees first)."""
    from perceiver_io_tpu.parallel.dist import is_main_process

    if not is_main_process():
        return
    os.makedirs(directory, exist_ok=True)
    params = jax.device_get(params)
    with open(os.path.join(directory, PARAMS_FILE), "wb") as f:
        f.write(serialization.to_bytes(params))
    if config is not None:
        save_config(directory, config)


def load_pretrained(directory: str, template_params=None):
    """Returns ``(params, config)``; ``config`` is None when absent. When
    ``template_params`` is given the loaded tree is validated/coerced against
    it (shapes and dtypes), otherwise the raw tree of numpy arrays returns.

    Accepts either a ``save_pretrained`` artifact (params.msgpack) or an
    orbax *training* checkpoint directory — a run's ``checkpoints/`` root (or
    the run dir containing it) — so warm starts can point straight at a
    training run, mirroring the reference's load-from-.ckpt UX
    (reference: perceiver/model/core/lightning.py:145-147)."""
    msgpack_path = os.path.join(directory, PARAMS_FILE)
    if os.path.exists(msgpack_path):
        with open(msgpack_path, "rb") as f:
            data = f.read()
        if template_params is not None:
            params = serialization.from_bytes(template_params, data)
        else:
            params = serialization.msgpack_restore(data)
        config_path = os.path.join(directory, CONFIG_FILE)
        config = load_config(directory) if os.path.exists(config_path) else None
        return params, config
    return _load_orbax_pretrained(directory, template_params)


def _load_orbax_pretrained(directory: str, template_params=None):
    root = os.path.abspath(directory)
    if not _has_orbax_steps(root):
        nested = os.path.join(root, "checkpoints")
        if _has_orbax_steps(nested):
            root = nested
        else:
            raise FileNotFoundError(
                f"{directory} has neither {PARAMS_FILE} nor orbax checkpoint steps"
            )
    # prefer the best retained step by the standard monitor (the reference's
    # ModelCheckpoint monitors val_loss); fall back to the latest when no
    # per-step metrics were recorded. NaN/missing metrics sanitize to worst
    # so a diverged-val checkpoint can never win the comparison.
    options = ocp.CheckpointManagerOptions(
        best_fn=lambda metrics: _monitor_value(metrics, "val_loss", "min"), best_mode="min"
    )
    mngr = ocp.CheckpointManager(root, options=options)
    try:
        step = mngr.best_step()
        if step is None:
            step = mngr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint steps under {root}")
        # a fresh manager has no handler registered for another run's
        # saved item, so the restore names one; no target tree = a raw
        # numpy pytree, template-coerced below
        payload = mngr.restore(step, args=ocp.args.StandardRestore())
    finally:
        mngr.close()
    params = payload["params"] if isinstance(payload, dict) and "params" in payload else payload
    if template_params is not None:
        params = serialization.from_state_dict(
            template_params, serialization.to_state_dict(params)
        )
    config_path = os.path.join(root, CONFIG_FILE)
    config = load_config(root) if os.path.exists(config_path) else None
    return params, config


def _has_orbax_steps(root: str) -> bool:
    if not os.path.isdir(root):
        return False
    return any(
        name.isdigit() and os.path.isdir(os.path.join(root, name)) for name in os.listdir(root)
    )


def load_params_into(params, source_params, subtree: Optional[str] = None):
    """Warm start: replace ``params`` (or its ``subtree``, e.g. the encoder)
    with values from ``source_params``. Mirrors the classifier's encoder-only
    init from an MLM checkpoint (reference: text/classifier/lightning.py:28-36)."""

    def pick(tree, key):
        inner = tree["params"] if "params" in tree else tree
        if key not in inner:
            raise KeyError(f"subtree {key!r} not found; available: {list(inner)}")
        return inner[key]

    if subtree is None:
        return serialization.from_state_dict(params, serialization.to_state_dict(source_params))
    src = pick(source_params, subtree)
    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy via rebuild
    dst_root = params["params"] if "params" in params else params
    dst_root = dict(dst_root)
    dst_root[subtree] = serialization.from_state_dict(
        dst_root[subtree], serialization.to_state_dict(src)
    )
    if "params" in params:
        return {**params, "params": dst_root}
    return dst_root


# ---------------------------------------------------------------------------
# training checkpoints: orbax CheckpointManager over the TrainState pytree
# ---------------------------------------------------------------------------


def _state_payload(state, save_weights_only: bool) -> dict:
    payload = {"step": state.step, "params": state.params, "rng": state.rng}
    if not save_weights_only:
        payload["opt_state"] = state.opt_state
    return payload


# -- mesh/sharding fingerprints (elastic resume; docs/robustness.md) --------
#
# Every save records WHERE the payload lived: mesh axis names/sizes, the
# per-leaf PartitionSpec, shapes/dtypes/bytes, and the process count. On
# restore the fingerprint is compared against the *target* placement — a
# mismatch is not an error but a RESHARD: the abstract pytree handed to
# orbax carries the target ``NamedSharding`` per leaf, so every shard is
# read from storage directly into its new layout (no replicate-then-reshard
# HBM spike), and a structured ``resume.reshard`` event records old/new
# mesh, leaves moved, bytes and wall time. Payloads that predate
# fingerprints fall back to a host-gather compat path (full arrays
# materialize on host before placement — safe on any topology, but the
# host must fit the full state) with a warning.

FINGERPRINT_VERSION = 1


def _leaf_spec(leaf) -> Optional[str]:
    """The placement of one leaf: a PartitionSpec string for NamedSharding
    leaves, ``"single"`` for other committed jax arrays, None for host."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return None
    from jax.sharding import NamedSharding

    if isinstance(sharding, NamedSharding):
        return str(sharding.spec)
    return "single"


def sharding_fingerprint(payload) -> dict:
    """Mesh/sharding fingerprint of a (possibly sharded) state payload."""
    mesh_axes = None
    leaves = {}
    from jax.sharding import NamedSharding

    for path, leaf in jax.tree_util.tree_flatten_with_path(payload)[0]:
        if not hasattr(leaf, "shape"):
            continue
        sharding = getattr(leaf, "sharding", None)
        if mesh_axes is None and isinstance(sharding, NamedSharding):
            mesh_axes = {str(k): int(v) for k, v in sharding.mesh.shape.items()}
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        leaves[jax.tree_util.keystr(path)] = {
            "spec": _leaf_spec(leaf),
            "shape": [int(s) for s in leaf.shape],
            "dtype": str(dtype),
            "bytes": int(dtype.itemsize * max(1, int(np.prod(leaf.shape or (1,))))),
        }
    try:
        process_count = int(jax.process_count())
    except Exception:  # noqa: BLE001 — fingerprinting must work pre-init
        process_count = 1
    return {
        "version": FINGERPRINT_VERSION,
        "mesh": mesh_axes,
        "process_count": process_count,
        "leaves": leaves,
    }


def diff_fingerprints_for_reshard(saved: dict, target: dict) -> dict:
    """What a restore onto ``target`` placement moves relative to ``saved``:
    leaves whose (mesh, spec) changed, and their total bytes. Feeds the
    ``resume.reshard`` event."""
    mesh_changed = saved.get("mesh") != target.get("mesh")
    moved, bytes_moved = 0, 0
    saved_leaves = saved.get("leaves", {})
    for path, rec in target.get("leaves", {}).items():
        old = saved_leaves.get(path)
        if old is None:
            continue
        if mesh_changed or old.get("spec") != rec.get("spec"):
            moved += 1
            bytes_moved += int(rec.get("bytes", 0))
    return {
        "mesh_changed": mesh_changed,
        "leaves_resharded": moved,
        "bytes_moved": bytes_moved,
        "old_mesh": saved.get("mesh"),
        "new_mesh": target.get("mesh"),
        "old_process_count": saved.get("process_count"),
        "new_process_count": target.get("process_count"),
    }


def _payload_on_mesh(payload) -> bool:
    """Whether any leaf of ``payload`` carries a multi-device placement."""
    from jax.sharding import NamedSharding

    for leaf in jax.tree_util.tree_leaves(payload):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
            return True
    return False


def _diff_config_dicts(saved: dict, current: dict, prefix: str = "config") -> list:
    """Named field-level differences between two ``config_to_dict`` trees
    (preflight's config-compatibility leg)."""
    problems = []
    if isinstance(saved, dict) and isinstance(current, dict):
        for key in sorted(set(saved) | set(current)):
            path = f"{prefix}.{key}"
            if key not in saved:
                problems.append(f"{path}: absent in checkpoint, current={current[key]!r}")
            elif key not in current:
                problems.append(f"{path}: checkpoint={saved[key]!r}, absent in current config")
            else:
                problems.extend(_diff_config_dicts(saved[key], current[key], path))
        return problems
    # tuples serialize as lists; compare loosely
    s = list(saved) if isinstance(saved, (list, tuple)) else saved
    c = list(current) if isinstance(current, (list, tuple)) else current
    if s != c:
        problems.append(f"{prefix}: checkpoint={saved!r} != current={current!r}")
    return problems


def _diff_payload_structure(fp_saved: dict, fp_target: dict) -> list:
    """Structural incompatibilities between a saved fingerprint and the
    restore target (preflight's second leg): shape/dtype mismatches on
    common leaves, and missing/extra PARAMETERS. Optimizer-state presence
    differences are legitimate (weights-only ↔ full-state fallback) and
    never reported."""
    problems = []
    saved = fp_saved.get("leaves", {})
    target = fp_target.get("leaves", {})
    for path in sorted(set(saved) | set(target)):
        in_params = path.startswith("['params']")
        if path not in saved:
            if in_params:
                problems.append(f"parameter {path} absent in checkpoint")
            continue
        if path not in target:
            if in_params:
                problems.append(f"checkpoint parameter {path} has no target in the state")
            continue
        s, t = saved[path], target[path]
        if list(s.get("shape", [])) != list(t.get("shape", [])):
            problems.append(
                f"{path}: shape checkpoint={s.get('shape')} != state={t.get('shape')}"
            )
        elif s.get("dtype") != t.get("dtype"):
            problems.append(
                f"{path}: dtype checkpoint={s.get('dtype')} != state={t.get('dtype')}"
            )
    return problems


# -- atomic-save hygiene (docs/robustness.md) -------------------------------
#
# orbax commits a step by writing into a tmp-suffixed directory and renaming
# it into place, but (this version, local fs) its *read* side is not torn-
# proof: ``latest_step`` happily returns a digit directory whose contents
# were half-deleted or half-copied (e.g. a host killed mid-rsync of a
# restored run dir), and ``restore`` then dies instead of falling back.
# Three guards close that:
#   1. a startup sweep quarantines leftover tmp dirs and non-finalized step
#      dirs (missing orbax's ``_CHECKPOINT_METADATA`` commit marker) into
#      ``_quarantine/`` — a non-digit name orbax ignores forever,
#   2. a post-commit integrity record (``integrity.json``: file count +
#      total bytes + the save-time metrics per step) written atomically
#      (tmp + ``os.replace``) lets ``restore`` detect a step dir that is
#      finalized-but-mutilated, quarantine it, and fall back to the next
#      valid step,
#   3. ``best_step`` is computed from the recorded metrics with NaN/missing
#      monitor values excluded — a diverged-val checkpoint is never "best".

QUARANTINE_DIR = "_quarantine"
INTEGRITY_FILE = "integrity.json"
COMMIT_MARKER = "_CHECKPOINT_METADATA"  # orbax's per-step commit metadata file


def _monitor_value(metrics: Optional[dict], monitor: str, mode: str) -> float:
    """Sanitized monitor value for best-step comparison: NaN or missing
    becomes the WORST possible value for ``mode``, so it never wins."""
    worst = float("inf") if mode == "min" else float("-inf")
    if not metrics:
        return worst
    try:
        v = float(metrics.get(monitor, worst))
    except (TypeError, ValueError):
        return worst
    return v if v == v else worst  # NaN != NaN


def _dir_stats(path: str) -> dict:
    """File count + total byte size under ``path`` — the integrity signature
    a torn step dir fails (missing payload files / truncated shards)."""
    n_files = 0
    n_bytes = 0
    for root, _dirs, files in os.walk(path):
        for name in files:
            try:
                n_bytes += os.path.getsize(os.path.join(root, name))
                n_files += 1
            except OSError:
                continue
    return {"files": n_files, "bytes": n_bytes}


def _is_tmp_checkpoint(path: str) -> bool:
    name = os.path.basename(path)
    if ".orbax-checkpoint-tmp" in name:
        return True
    try:
        return bool(ocp.utils.is_tmp_checkpoint(path))
    except Exception:
        return False


def _quarantine_path(directory: str, name: str) -> str:
    qdir = os.path.join(directory, QUARANTINE_DIR)
    os.makedirs(qdir, exist_ok=True)
    k = 0
    while True:
        target = os.path.join(qdir, name if k == 0 else f"{name}.{k}")
        if not os.path.exists(target):
            return target
        k += 1


class CheckpointManager:
    """Best-k training checkpoints monitored on a metric, with torn-save
    protection (sweep / integrity records / valid-step fallback — see the
    atomic-save hygiene block above and docs/robustness.md).

    Reference semantics: ModelCheckpoint(monitor=val_loss, mode=min,
    save_weights_only) (reference: perceiver/scripts/trainer.yaml:7-12), plus
    full-state (optimizer included) checkpoints for exact resume.
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: Optional[int] = 1,
        monitor: Optional[str] = "val_loss",
        mode: str = "min",
        save_weights_only: bool = False,
        enable_async: bool = False,
        retry=None,
        event_sink=None,
    ):
        """``enable_async=True`` overlaps checkpoint serialization/IO with
        continued training (orbax async checkpointing — the Trainer turns
        this on): ``save`` returns once the on-device state is snapshotted
        and the write proceeds in the background. Every read-side method
        (``latest_step``/``best_step``/``restore``) and ``close`` first
        ``wait_until_finished``, so save-then-restore stays correct.

        ``max_to_keep=None`` retains every step (the Trainer's preemption
        saves use this so a final save never evicts the best-val step).

        ``retry`` — a ``training.faults.RetryPolicy`` (or True for the
        default policy) wrapping the save/restore orbax I/O: a transient
        filesystem error (flaky NFS/GCS mount) is retried with the same
        bounded-backoff discipline as loader fetches, each attempt emitted
        as a ``fault.ckpt_retry`` event through ``event_sink``.
        ``FileNotFoundError`` is never retried — it is the torn-checkpoint
        fallback ladder's control signal, not a transient fault.

        ``event_sink`` — an ``obs.events.EventLog`` (or any ``emit(kind,
        **fields)`` sink; the Trainer wires its own) that receives
        ``fault.ckpt_retry`` and ``resume.reshard`` events."""
        from perceiver_io_tpu.parallel.dist import is_main_process

        self.directory = os.path.abspath(directory)
        self.monitor = monitor
        self.mode = mode
        self.save_weights_only = save_weights_only
        self.enable_async = enable_async
        if retry is True:
            from perceiver_io_tpu.training.faults import RetryPolicy

            retry = RetryPolicy(max_retries=2, base_delay=0.2, max_delay=5.0)
        self.retry = retry
        self.event_sink = event_sink
        self._retry_sleep: Callable[[float], None] = time.sleep  # injectable (tests)
        self._config_written = False
        self._main_process = is_main_process()
        self._pending_integrity: dict = {}
        # startup sweep BEFORE the orbax manager scans the directory, so a
        # torn step never even enters its checkpoint-info cache
        self.quarantined: list = self._sweep() if self._main_process else []
        self._integrity = self._read_integrity()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            # NaN-sanitized: orbax also uses best_fn for best-k RETENTION —
            # an unsanitized fn would evict good steps in favor of NaN ones
            best_fn=(lambda metrics: _monitor_value(metrics, monitor, mode)) if monitor else None,
            best_mode=mode,
            create=True,
            enable_async_checkpointing=enable_async,
        )
        self._mngr = ocp.CheckpointManager(self.directory, options=options)

    # -- integrity bookkeeping -------------------------------------------

    def _integrity_path(self) -> str:
        return os.path.join(self.directory, INTEGRITY_FILE)

    def _read_integrity(self) -> dict:
        try:
            with open(self._integrity_path()) as f:
                data = json.load(f)
            return dict(data.get("steps", {}))
        except (OSError, ValueError):
            return {}

    def _write_integrity(self) -> None:
        if not self._main_process:
            return
        tmp = self._integrity_path() + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"steps": self._integrity}, f, indent=1, default=str)
            os.replace(tmp, self._integrity_path())  # atomic on POSIX
        except OSError as e:
            import warnings

            warnings.warn(f"checkpoint integrity record not written: {e}")

    def _flush_integrity(self) -> None:
        """Record integrity signatures for saves that have committed. Runs
        after every ``wait_until_finished`` — for async saves the record
        lands at the first barrier after commit (a crash in between leaves
        a committed-but-unrecorded step, which validation accepts on the
        orbax commit marker alone)."""
        if not self._pending_integrity:
            return
        done = []
        for step, rec in self._pending_integrity.items():
            path = self._step_path(step)
            if not os.path.exists(os.path.join(path, COMMIT_MARKER)):
                continue  # save was skipped (should_save) or still in flight
            self._integrity[str(step)] = {**_dir_stats(path), **rec}
            done.append(step)
        for step in done:
            self._pending_integrity.pop(step, None)
        if done:
            self._write_integrity()

    # -- torn-checkpoint detection / quarantine ---------------------------

    def _step_path(self, step: int) -> str:
        return os.path.join(self.directory, str(int(step)))

    def _sweep(self) -> list:
        """Quarantine leftover orbax tmp dirs and non-finalized step dirs
        (present but missing the commit marker: a save torn mid-rename or a
        step dir half-copied onto shared storage). Returns quarantined
        names."""
        moved = []
        if not os.path.isdir(self.directory):
            return moved
        for name in sorted(os.listdir(self.directory)):
            path = os.path.join(self.directory, name)
            if name == QUARANTINE_DIR or not os.path.isdir(path):
                continue
            torn = _is_tmp_checkpoint(path) or (
                name.isdigit() and not os.path.exists(os.path.join(path, COMMIT_MARKER))
            )
            if torn:
                self._quarantine(path)
                moved.append(name)
        return moved

    def _quarantine(self, path: str) -> None:
        import shutil
        import warnings

        target = _quarantine_path(self.directory, os.path.basename(path))
        shutil.move(path, target)
        warnings.warn(
            f"quarantined checkpoint dir {os.path.basename(path)!r} -> {target} "
            "(torn save — tmp leftover, missing commit marker, integrity "
            "mismatch — or a weights-only commit superseded by a forced "
            "full-state save)"
        )

    def _step_valid(self, step: int) -> bool:
        """A step is restorable iff its dir carries the orbax commit marker
        AND (when an integrity record exists) its file count/bytes match the
        post-commit signature."""
        path = self._step_path(step)
        if not os.path.exists(os.path.join(path, COMMIT_MARKER)):
            return False
        rec = self._integrity.get(str(int(step)))
        if rec is None:
            return True  # legacy/unrecorded: the commit marker is all we have
        stats = _dir_stats(path)
        return stats["files"] == rec.get("files") and stats["bytes"] == rec.get("bytes")

    def _payload_has_opt_state(self, step: int) -> bool:
        """Whether a committed step's payload tree carries optimizer state
        (orbax StandardSave records the tree structure in the item's
        ``_METADATA``). Unreadable/absent metadata reads as False — for a
        forced full-state save, replacing an ambiguous commit with a known
        full payload is the safe direction."""
        meta = os.path.join(self._step_path(step), "default", "_METADATA")
        try:
            with open(meta) as f:
                return '"opt_state"' in f.read()
        except OSError:
            return False

    def _quarantine_step(self, step: int) -> None:
        if self._main_process:
            self._quarantine(self._step_path(step))
        self._integrity.pop(str(int(step)), None)
        self._write_integrity()
        self._mngr.reload()  # drop it from the orbax checkpoint-info cache

    def valid_steps(self) -> list:
        """Committed, integrity-clean steps (ascending). Invalid steps found
        here are quarantined so no later read can select them."""
        self.wait_until_finished()
        steps = []
        for step in sorted(self._mngr.all_steps()):
            if self._step_valid(step):
                steps.append(int(step))
            else:
                self._quarantine_step(step)
        return steps

    # -- event + transient-I/O-retry plumbing ------------------------------

    def _emit(self, kind: str, **fields) -> None:
        """Best-effort event emission (telemetry must never take a
        checkpoint op down); no-op without a sink."""
        if self.event_sink is None:
            return
        try:
            self.event_sink.emit(kind, **fields)
        except Exception:  # noqa: BLE001 — telemetry-only
            pass

    def _io_with_retry(self, fn: Callable, op: str):
        """Run one orbax I/O call under the retry policy (None = no retry).

        Same backoff/emitter discipline as ``faults.call_with_retry`` (the
        loader path), with two checkpoint-specific differences: a
        ``FileNotFoundError`` propagates immediately (it drives the
        torn-step fallback ladder in :meth:`restore` — retrying it would
        only delay the fallback), and exhaustion re-raises the ORIGINAL
        error so restore's layout/ladder handling sees the real exception
        type, not a retry wrapper."""
        policy = self.retry
        if policy is None:
            return fn()
        for attempt in range(policy.max_retries + 1):
            try:
                return fn()
            except policy.retry_on as e:  # noqa: PERF203 — retry loop
                if isinstance(e, FileNotFoundError) or attempt >= policy.max_retries:
                    raise
                delay = policy.delay(attempt)
                self._emit(
                    "fault.ckpt_retry",
                    op=op,
                    attempt=int(attempt),
                    error=str(e),
                    delay_s=round(delay, 6),
                )
                self._retry_sleep(delay)

    # -- save / read API ---------------------------------------------------

    def save(self, state, metrics: Optional[dict] = None, config=None, force: bool = False) -> bool:
        """``force=True`` bypasses the monitored-metric requirement (the
        Trainer's preemption save: there is no fresh val metric at an
        arbitrary step boundary, and the save must happen anyway)."""
        metrics = {k: float(v) for k, v in (metrics or {}).items()}
        if self.monitor and self.monitor not in metrics and not force:
            raise ValueError(f"metrics must contain monitored key {self.monitor!r}")
        if force and os.path.exists(os.path.join(self._step_path(int(state.step)), COMMIT_MARKER)):
            # a forced (preemption) save colliding with an already-committed
            # step — e.g. preempted right after a val-interval save. Skip
            # only when the existing commit is at least as complete as this
            # payload: a weights-only commit must NOT swallow a full-state
            # preemption save (exact resume needs the optimizer), so the
            # thinner commit is quarantined and replaced (its monitored
            # metric goes with it — exact resume wins)
            if self.save_weights_only or self._payload_has_opt_state(int(state.step)):
                return False
            self._quarantine_step(int(state.step))
        payload = _state_payload(state, self.save_weights_only)
        saved = self._io_with_retry(
            lambda: self._mngr.save(
                int(state.step), metrics=metrics, args=ocp.args.StandardSave(payload), force=force
            ),
            "save",
        )
        if saved:
            # the mesh/sharding fingerprint rides in the same per-step
            # integrity record; restore compares it against the target
            # placement to drive the direct-reshard path (elastic resume)
            self._pending_integrity[int(state.step)] = {
                "metrics": metrics,
                "fingerprint": sharding_fingerprint(payload),
            }
        if not self.enable_async:
            self._mngr.wait_until_finished()
            self._flush_integrity()
        if config is not None and not self._config_written:
            # config.json must never exist without a committed checkpoint
            # (warm-start tooling reads config then restores): wait for the
            # first save to commit before the one-time config write — the
            # config is static per run, so later async saves skip this
            self.wait_until_finished()
            save_config(self.directory, config)
            self._config_written = True
        return saved

    def wait_until_finished(self) -> None:
        """Block until any in-flight async save has committed (and record
        its integrity signature)."""
        self._mngr.wait_until_finished()
        self._flush_integrity()

    def latest_step(self) -> Optional[int]:
        steps = self.valid_steps()
        return steps[-1] if steps else None

    def best_step(self) -> Optional[int]:
        """Best valid step by the monitored metric; NaN/missing-metric steps
        NEVER win. Steps without a recorded metric (legacy dirs, ``force``
        saves) are excluded; returns None when nothing has a finite metric
        (callers fall back to ``latest_step``)."""
        if not self.monitor:
            return None
        candidates = []
        for step in self.valid_steps():
            rec = self._integrity.get(str(step))
            metrics = rec.get("metrics") if rec else self._orbax_metrics(step)
            v = _monitor_value(metrics, self.monitor, self.mode)
            if v == v and abs(v) != float("inf"):
                candidates.append((v, step))
        if not candidates:
            return None
        pick = min(candidates) if self.mode == "min" else max(candidates)
        return pick[1]

    def _orbax_metrics(self, step: int) -> Optional[dict]:
        """Save-time metrics for steps that predate integrity records, read
        from the orbax checkpoint-info cache (no public accessor in this
        version — best-effort)."""
        for info in getattr(self._mngr, "_checkpoints", []) or []:
            if getattr(info, "step", None) == step:
                m = getattr(info, "metrics", None)
                return dict(m) if m else None
        return None

    def restore(self, state, step: Optional[int] = None, mesh=None, min_weight_size: int = 2**14):
        """Restore into (a copy of) ``state``; returns the updated state.
        ``step=None`` restores the latest VALID checkpoint — a torn step dir
        discovered mid-restore is quarantined and the next-newest valid step
        is tried, so auto-resume never dies on (or silently loads) a partial
        write. Restores whatever the checkpoint actually contains: resuming
        from a weights-only checkpoint restores params/step/rng and leaves
        the optimizer state fresh (Lightning ``save_weights_only`` resume
        semantics).

        **Mesh-elastic** (docs/robustness.md#elastic-resume): the restore
        target is wherever ``state``'s leaves currently live — the abstract
        pytree handed to orbax carries each leaf's ``NamedSharding``, so a
        checkpoint written under a different mesh (8-chip kill, 4-chip
        resume; flat ↔ sharded) lands every leaf DIRECTLY in the new
        layout, no replicate-then-reshard pass. Pass ``mesh=`` to (re)place
        ``state`` onto a target mesh first (``shard_train_state`` placement
        rules with ``min_weight_size``); callers that already placed the
        state (the Trainer) leave it None. When the saved fingerprint and
        the target placement differ, a ``resume.reshard`` event (old/new
        mesh, leaves and bytes moved, wall time) goes through
        ``event_sink``. Payloads that predate fingerprints restore via a
        host-gather compat path with a warning."""
        self.wait_until_finished()
        if mesh is not None:
            from perceiver_io_tpu.training.loop import shard_train_state

            state = shard_train_state(state, mesh, min_weight_size=min_weight_size)
        if step is not None:
            if not self._step_valid(step):
                raise FileNotFoundError(
                    f"checkpoint step {step} under {self.directory} is missing or torn"
                )
            return self._restore_step(state, step)
        candidates = self.valid_steps()
        if not candidates:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        last_err: Optional[Exception] = None
        for step in reversed(candidates):
            try:
                return self._restore_step(state, step)
            except FileNotFoundError as e:
                # integrity said ok but payload structure is gone (deep tear
                # the file-count signature missed, e.g. a truncated manifest):
                # quarantine and fall back to the next-newest valid step
                last_err = e
                self._quarantine_step(step)
        raise FileNotFoundError(
            f"every checkpoint under {self.directory} failed to restore; last: {last_err}"
        )

    def step_fingerprint(self, step: int) -> Optional[dict]:
        """The mesh/sharding fingerprint recorded at save time for ``step``
        (None for payloads that predate fingerprints)."""
        rec = self._integrity.get(str(int(step)))
        return rec.get("fingerprint") if rec else None

    def _restore_step(self, state, step: int):
        # deep-tear precheck: a committed step whose PAYLOAD item is gone
        # (default/ deleted or its _METADATA truncated — a tear the
        # file-count integrity signature can miss when the record was
        # forged/raced) makes orbax raise an opaque "Must provide args of
        # type Composite" ValueError. Surface it as the fallback ladder's
        # FileNotFoundError control signal instead, so restore(step=None)
        # quarantines and falls back in ONE call. (StandardSave always
        # writes default/_METADATA in this orbax version —
        # _payload_has_opt_state relies on the same layout.)
        item_meta = os.path.join(self._step_path(step), "default", "_METADATA")
        if not os.path.exists(item_meta):
            raise FileNotFoundError(
                f"checkpoint step {step} payload is missing or torn (no {item_meta})"
            )
        fp_saved = self.step_fingerprint(step)
        t0 = time.perf_counter()

        def attempt(weights_only: bool):
            payload = _state_payload(state, weights_only)
            if fp_saved is None and _payload_on_mesh(payload):
                # legacy payload (no fingerprint) into a sharded target:
                # orbax would read per-leaf sharding FILES written on the
                # old topology — unsafe when the device set changed — so
                # take the documented host-gather compat path instead
                return self._restore_host_then_place(step, payload)
            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, payload)
            return self._io_with_retry(
                lambda: self._mngr.restore(step, args=ocp.args.StandardRestore(abstract)),
                "restore",
            )

        # try the layout this manager would have written first; fall back to
        # the other layout (e.g. resuming full-state training from a
        # weights-only checkpoint). Re-raise the ORIGINAL error when both
        # fail so genuine mismatches (shape/optimizer changes) stay visible.
        try:
            restored = attempt(self.save_weights_only)
        except ValueError as primary_err:
            try:
                restored = attempt(not self.save_weights_only)
            except ValueError:
                raise primary_err
        fp_target = sharding_fingerprint(restored)
        if fp_saved is not None:
            diff = diff_fingerprints_for_reshard(fp_saved, fp_target)
            if diff["mesh_changed"] or diff["leaves_resharded"]:
                self._emit(
                    "resume.reshard",
                    step=int(step),
                    wall_s=round(time.perf_counter() - t0, 6),
                    path="direct",
                    **diff,
                )
        elif _payload_on_mesh(restored):
            # legacy checkpoint landed on a mesh via the compat path: the
            # old placement is unknown, but the reshard still happened
            self._emit(
                "resume.reshard",
                step=int(step),
                wall_s=round(time.perf_counter() - t0, 6),
                path="host_gather",
                old_mesh=None,
                new_mesh=fp_target.get("mesh"),
                leaves_resharded=len(fp_target.get("leaves", {})),
                bytes_moved=sum(r["bytes"] for r in fp_target.get("leaves", {}).values()),
                mesh_changed=True,
            )
        return state.replace(**restored)

    def _restore_host_then_place(self, step: int, payload):
        """Compat path for fingerprint-less payloads restored onto a mesh:
        restore every leaf as a HOST numpy array (ignoring the stale
        sharding files entirely), then ``device_put`` onto the target
        placement. Correct on any topology, but each host must hold the
        full state — the direct fingerprinted path exists to avoid exactly
        this; new checkpoints never take it."""
        import warnings

        warnings.warn(
            f"checkpoint step {step} under {self.directory} predates mesh "
            "fingerprints; restoring via the host-gather compat path "
            "(full state materializes on host before placement)"
        )
        # numpy-template abstract tree => orbax restores plain host arrays,
        # never touching the per-leaf sharding files (which reference the
        # topology the checkpoint was WRITTEN on)
        abstract = jax.tree.map(
            lambda x: np.zeros(np.shape(x), np.dtype(getattr(x, "dtype", type(x)))), payload
        )
        restored = self._io_with_retry(
            lambda: self._mngr.restore(step, args=ocp.args.StandardRestore(abstract)),
            "restore",
        )

        def place(host_leaf, target_leaf):
            sharding = getattr(target_leaf, "sharding", None)
            if sharding is None:
                return host_leaf
            return jax.device_put(host_leaf, sharding)

        return jax.tree.map(place, restored, payload)

    def preflight(self, state, step: Optional[int] = None, model_config=None) -> Optional[dict]:
        """Resume preflight: cheap compatibility checks BEFORE touching the
        orbax payload, so an incompatible resume fails with one actionable
        :class:`ResumePreflightError` instead of a deep orbax ``ValueError``
        three stacks down.

        Checks (each skipped when its input is absent):

        - **config**: ``model_config`` vs the run's committed config.json —
          differing fields are named;
        - **structure**: the saved fingerprint's param/step/rng leaves vs
          the target ``state`` — shape/dtype mismatches and missing/extra
          parameters are named (optimizer-state differences are NOT errors;
          the weights-only ↔ full-state fallback handles those).

        A mesh/sharding difference is never an error — that is the reshard
        path working as designed. Returns an info dict ``{step, reshard,
        old_mesh, new_mesh}`` (None when there is nothing to resume
        from)."""
        if step is None:
            steps = self.valid_steps()
            if not steps:
                return None
            step = steps[-1]
        problems = []
        if model_config is not None:
            cfg_path = os.path.join(self.directory, CONFIG_FILE)
            if os.path.exists(cfg_path):
                with open(cfg_path) as f:
                    saved_cfg = json.load(f)
                problems.extend(
                    _diff_config_dicts(saved_cfg, config_to_dict(model_config))
                )
        fp_saved = self.step_fingerprint(step)
        reshard = False
        old_mesh = new_mesh = None
        if fp_saved is not None:
            fp_target = sharding_fingerprint(_state_payload(state, self.save_weights_only))
            problems.extend(_diff_payload_structure(fp_saved, fp_target))
            diff = diff_fingerprints_for_reshard(fp_saved, fp_target)
            reshard = bool(diff["mesh_changed"] or diff["leaves_resharded"])
            old_mesh, new_mesh = diff["old_mesh"], diff["new_mesh"]
        if problems:
            raise ResumePreflightError(self.directory, step, problems)
        return {"step": int(step), "reshard": reshard, "old_mesh": old_mesh, "new_mesh": new_mesh}

    def load_config(self):
        return load_config(self.directory)

    def close(self):
        self.wait_until_finished()
        self._mngr.close()
