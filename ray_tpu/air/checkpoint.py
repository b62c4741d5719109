"""Checkpoint: the canonical training artifact.

Capability parity with the reference's AIR Checkpoint
(python/ray/air/checkpoint.py:42 — dict ↔ directory ↔ URI interconversion,
passed between workers/trainables/driver). TPU-native twist: array pytrees
(including sharded `jax.Array`s) are persisted via orbax — the
distributed-checkpoint path that makes gang restarts cheap (SURVEY.md §7
hard part 6); non-array metadata rides alongside as a pickle.

Durability contract (the preemption-tolerance substrate): a checkpoint
directory is NEVER observable half-written. ``to_directory`` stages the
full payload in a sibling temp directory, fsyncs every file, writes a
content manifest (per-file SHA-256 + byte counts + step + wall time)
LAST, and commits with one atomic ``os.rename``. A reader therefore sees
either nothing or a complete, self-describing checkpoint; anything else
(a crash mid-write, a preempted host, a torn copy) leaves only a
``.tmp-*`` directory that every resolver ignores. ``from_directory``
refuses directories without a valid manifest with a typed
:class:`InvalidCheckpointError` so torn state can never flow back into a
resuming gang.
"""
from __future__ import annotations

import ctypes
import hashlib
import json
import os
import shutil
import tempfile
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ray_tpu._private import serialization

_ARRAY_SUBDIR = "arrays"
_META_FILE = "meta.pkl"
MANIFEST_FILE = "manifest.json"
MANIFEST_FORMAT = 1
_TMP_PREFIX = ".tmp-"


class InvalidCheckpointError(RuntimeError):
    """The directory is not a complete committed checkpoint: missing,
    unparseable, or inconsistent manifest, or files that disagree with
    it (torn write / partial copy / bit rot)."""

    def __init__(self, path: str, reason: str):
        self.path = path
        self.reason = reason
        super().__init__(f"invalid checkpoint at {path}: {reason}")


def _is_array(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def _split(data: Dict[str, Any]):
    """Split a checkpoint dict into (array-pytree entries, other entries).
    An entry goes to orbax iff every leaf of its value is an array."""
    arrays, other = {}, {}
    for k, v in data.items():
        leaves = jax.tree_util.tree_leaves(v)
        if leaves and all(_is_array(l) for l in leaves):
            arrays[k] = v
        else:
            other[k] = v
    return arrays, other


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _payload_files(root: str) -> List[str]:
    """Every regular file under ``root`` except the manifest itself,
    as sorted relative paths."""
    out = []
    for dirpath, _dirnames, filenames in os.walk(root):
        for fn in filenames:
            full = os.path.join(dirpath, fn)
            rel = os.path.relpath(full, root)
            if rel != MANIFEST_FILE:
                out.append(rel)
    return sorted(out)


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def write_manifest(root: str, step: Optional[int] = None) -> Dict[str, Any]:
    """Hash every payload file under ``root`` and write the manifest
    (fsynced). The manifest is written LAST so its presence implies the
    payload preceded it onto disk."""
    files = {}
    for rel in _payload_files(root):
        full = os.path.join(root, rel)
        files[rel] = {"sha256": _sha256(full),
                      "bytes": os.path.getsize(full)}
        _fsync_file(full)
    manifest = {"format": MANIFEST_FORMAT, "step": step,
                "wall_time": time.time(), "files": files}
    mpath = os.path.join(root, MANIFEST_FILE)
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    _fsync_dir(root)
    return manifest


def load_manifest(path: str) -> Dict[str, Any]:
    """Read and structurally validate the manifest of a committed
    checkpoint directory. Raises :class:`InvalidCheckpointError`."""
    mpath = os.path.join(path, MANIFEST_FILE)
    if not os.path.isfile(mpath):
        raise InvalidCheckpointError(path, "missing manifest (torn or "
                                     "pre-manifest checkpoint)")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise InvalidCheckpointError(path, f"unreadable manifest: {e}")
    if not isinstance(manifest, dict) or \
            not isinstance(manifest.get("files"), dict) or \
            manifest.get("format") != MANIFEST_FORMAT:
        raise InvalidCheckpointError(path, "malformed manifest")
    return manifest


def verify_checkpoint_dir(path: str, deep: bool = False
                          ) -> Tuple[bool, Optional[str],
                                     Optional[Dict[str, Any]]]:
    """Is ``path`` a complete committed checkpoint? Shallow mode checks
    the manifest parses and every listed file exists with the recorded
    byte count; ``deep`` re-hashes contents (catches silent corruption,
    not just truncation). Returns (ok, reason_if_not, parsed_manifest)
    — the manifest rides along so callers that need ``step`` or the
    file table never re-read ``manifest.json`` after verifying."""
    try:
        manifest = load_manifest(path)
    except InvalidCheckpointError as e:
        return False, e.reason, None
    for rel, rec in manifest["files"].items():
        full = os.path.join(path, rel)
        if not os.path.isfile(full):
            return False, f"manifest lists missing file {rel!r}", manifest
        if os.path.getsize(full) != rec.get("bytes"):
            return False, (f"file {rel!r} is {os.path.getsize(full)}B, "
                           f"manifest says {rec.get('bytes')}B"), manifest
        if deep and _sha256(full) != rec.get("sha256"):
            return False, f"file {rel!r} fails its manifest hash", manifest
    # Extra payload files not in the manifest mean the directory was
    # tampered with after commit; tolerate (orbax may leave lockfiles)
    # but a missing/short file above is always fatal.
    return True, None, manifest


class Checkpoint:
    """Immutable checkpoint; create via ``from_dict``/``from_directory``."""

    def __init__(self, data: Optional[Dict[str, Any]] = None,
                 path: Optional[str] = None):
        if (data is None) == (path is None):
            raise ValueError("Provide exactly one of data / path")
        self._data = data
        self._path = path

    # --- constructors -----------------------------------------------------

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Checkpoint":
        return cls(data=dict(data))

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        if not os.path.isdir(path):
            raise FileNotFoundError(path)
        ok, reason, _manifest = verify_checkpoint_dir(path)
        if not ok:
            # a re-commit of the same slot swaps the directory under
            # a reader that takes the manifest and then looks for its
            # files: it may hold the old manifest against the new
            # files (or, where the swap is two renames, against
            # nothing). Look once more before calling it torn.
            time.sleep(0.02)
            ok, reason, _manifest = verify_checkpoint_dir(path)
        if not ok:
            raise InvalidCheckpointError(path, reason)
        return cls(path=path)

    # --- conversions ------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        if self._data is not None:
            return dict(self._data)
        meta_path = os.path.join(self._path, _META_FILE)
        out: Dict[str, Any] = {}
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                out.update(serialization.loads(f.read()))
        arr_dir = os.path.join(self._path, _ARRAY_SUBDIR)
        if os.path.isdir(arr_dir):
            import orbax.checkpoint as ocp
            with ocp.PyTreeCheckpointer() as ckptr:
                restored = ckptr.restore(os.path.abspath(arr_dir))
            out.update(restored)
        return out

    def to_directory(self, path: Optional[str] = None,
                     step: Optional[int] = None) -> str:
        """Materialize as a directory via stage → fsync → manifest →
        atomic rename. ``step`` is recorded in the manifest (falls back
        to an integer ``data['step']`` when present) so resolvers can
        order checkpoints without deserializing payloads."""
        if path is None:
            path = tempfile.mkdtemp(prefix="ray_tpu_ckpt_")
            # mkdtemp created the target itself; commit must swap it.
        path = os.path.abspath(path)
        if self._path is not None and os.path.abspath(self._path) == path:
            return path
        if step is None and self._data is not None:
            maybe = self._data.get("step")
            if isinstance(maybe, int) and not isinstance(maybe, bool):
                step = maybe
        parent = os.path.dirname(path) or "."
        os.makedirs(parent, exist_ok=True)
        stage = os.path.join(
            parent, f"{_TMP_PREFIX}{os.path.basename(path)}-"
                    f"{uuid.uuid4().hex[:8]}")
        try:
            if self._path is not None:
                shutil.copytree(self._path, stage)
                # Re-manifest: hashes re-verify the copy, and a torn
                # copy can never masquerade as the committed source.
                old = os.path.join(stage, MANIFEST_FILE)
                if step is None and os.path.isfile(old):
                    try:
                        with open(old) as f:
                            step = json.load(f).get("step")
                    except (OSError, json.JSONDecodeError):
                        step = None
                if os.path.exists(old):
                    os.remove(old)
            else:
                os.makedirs(stage)
                arrays, other = _split(self._data)
                with open(os.path.join(stage, _META_FILE), "wb") as f:
                    f.write(serialization.dumps(other))
                    f.flush()
                    os.fsync(f.fileno())
                if arrays:
                    import orbax.checkpoint as ocp
                    arr_dir = os.path.join(stage, _ARRAY_SUBDIR)
                    with ocp.PyTreeCheckpointer() as ckptr:
                        ckptr.save(arr_dir, arrays)
            write_manifest(stage, step=step)
            _commit_dir(stage, path)
        finally:
            if os.path.isdir(stage):
                shutil.rmtree(stage, ignore_errors=True)
        return path

    # --- helpers ----------------------------------------------------------

    def __getitem__(self, key: str):
        return self.to_dict()[key]

    def get(self, key: str, default=None):
        return self.to_dict().get(key, default)

    def __contains__(self, key: str) -> bool:
        return key in self.to_dict()

    def __repr__(self):
        src = "dict" if self._data is not None else self._path
        return f"Checkpoint({src})"


_RENAME_EXCHANGE = 2
_AT_FDCWD = -100


def _exchange(a: str, b: str) -> bool:
    """Swap what two paths name in ONE step (Linux ``renameat2`` with
    ``RENAME_EXCHANGE``). False where the platform's C library or the
    filesystem has no such call: the caller then renames twice."""
    try:
        renameat2 = ctypes.CDLL(None, use_errno=True).renameat2
    except (OSError, AttributeError):
        return False
    return renameat2(_AT_FDCWD, os.fsencode(a), _AT_FDCWD,
                     os.fsencode(b), _RENAME_EXCHANGE) == 0


def _commit_dir(stage: str, path: str) -> None:
    """Atomically install ``stage`` at ``path``. A pre-existing target
    (re-save over an old checkpoint, or mkdtemp's empty dir) is
    exchanged with the stage in one step and removed after — at every
    instant ``path`` is either the old complete state or the new one.
    Only where the platform cannot exchange is the target renamed away
    first, and ``path`` names nothing between the two renames."""
    parent = os.path.dirname(path) or "."
    displaced = None
    if os.path.exists(path):
        if _exchange(stage, path):
            _fsync_dir(parent)
            shutil.rmtree(stage, ignore_errors=True)   # the old state
            return
        displaced = os.path.join(
            parent, f"{_TMP_PREFIX}displaced-{uuid.uuid4().hex[:8]}")
        os.rename(path, displaced)
    try:
        os.rename(stage, path)
    except OSError:
        if displaced is not None:
            os.rename(displaced, path)     # roll back
        raise
    _fsync_dir(parent)
    if displaced is not None:
        shutil.rmtree(displaced, ignore_errors=True)


def restore_sharded(path: str, target, mesh=None, rules=None):
    """Restore an array pytree with target shardings (for gang restarts:
    each host restores only its shards). `target` is a pytree of
    ShapeDtypeStructs or arrays giving shapes/dtypes; shardings from
    `rules` over `mesh` when given. Because shardings are supplied by
    the RESTORING gang, the same checkpoint reshards onto a smaller or
    larger mesh — the elastic-resume path after a preemption shrank the
    slice."""
    import orbax.checkpoint as ocp
    arr_dir = os.path.abspath(os.path.join(path, _ARRAY_SUBDIR))
    if rules is not None and mesh is not None:
        from ray_tpu.mesh.sharding import infer_sharding
        shardings = infer_sharding(target, rules, mesh)
        target = jax.tree_util.tree_map(
            lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                              sharding=s),
            target, shardings)
    with ocp.PyTreeCheckpointer() as ckptr:
        return ckptr.restore(arr_dir, ocp.args.PyTreeRestore(
            restore_args=ocp.checkpoint_utils.construct_restore_args(
                target)))
