"""Checkpoint tests (reference analogue: python/ray/air/tests/test_checkpoints.py)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.air import Checkpoint


def test_dict_roundtrip():
    ckpt = Checkpoint.from_dict({"step": 3, "note": "hi"})
    assert ckpt.to_dict() == {"step": 3, "note": "hi"}
    assert ckpt["step"] == 3
    assert "note" in ckpt
    assert ckpt.get("missing", 7) == 7


def test_directory_roundtrip_with_arrays(tmp_path):
    params = {"w": jnp.arange(8.0), "b": np.ones((4,), np.float32)}
    ckpt = Checkpoint.from_dict({
        "params": params, "step": 42, "name": "trial-1"})
    path = ckpt.to_directory(str(tmp_path / "ckpt"))
    restored = Checkpoint.from_directory(path).to_dict()
    assert restored["step"] == 42
    assert restored["name"] == "trial-1"
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(8.0))
    np.testing.assert_array_equal(np.asarray(restored["params"]["b"]),
                                  np.ones((4,)))


def test_constructor_validation():
    with pytest.raises(ValueError):
        Checkpoint()
    with pytest.raises(FileNotFoundError):
        Checkpoint.from_directory("/nonexistent/path")


def test_sharded_restore(tmp_path, cpu_mesh_devices):
    from jax.sharding import PartitionSpec as P
    from ray_tpu.air.checkpoint import restore_sharded
    from ray_tpu.mesh import ShardingRules, create_mesh

    mesh = create_mesh({"data": 8})
    w = jnp.arange(64.0).reshape(8, 8)
    path = Checkpoint.from_dict({"params": {"w": w}}).to_directory(
        str(tmp_path / "s"))
    rules = ShardingRules([(r"w$", P("data", None))])
    restored = restore_sharded(
        path, {"params": {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}},
        mesh=mesh, rules=rules)
    rw = restored["params"]["w"]
    np.testing.assert_array_equal(np.asarray(rw), np.asarray(w))
    # Restored shards are placed per the rules (8-way split on dim 0).
    assert {s.data.shape for s in rw.addressable_shards} == {(1, 8)}


# ---------------------------------------------------------------------------
# Durability: manifests, torn-checkpoint detection, atomic commit
# ---------------------------------------------------------------------------


def _commit(tmp_path, name="c", step=7, extra=None):
    data = {"w": np.arange(4.0), "step": step}
    data.update(extra or {})
    return Checkpoint.from_dict(data).to_directory(
        str(tmp_path / name), step=step)


def test_to_directory_writes_manifest(tmp_path):
    from ray_tpu.air.checkpoint import (MANIFEST_FILE, load_manifest,
                                        verify_checkpoint_dir)
    path = _commit(tmp_path, step=42)
    manifest = load_manifest(path)
    assert manifest["step"] == 42
    assert manifest["files"], "manifest must list the payload files"
    for rel, rec in manifest["files"].items():
        assert rel != MANIFEST_FILE
        assert len(rec["sha256"]) == 64
        assert rec["bytes"] == os.path.getsize(os.path.join(path, rel))
    assert verify_checkpoint_dir(path)[0]
    assert verify_checkpoint_dir(path, deep=True)[0]


def test_from_directory_refuses_missing_manifest(tmp_path):
    from ray_tpu.air import InvalidCheckpointError
    bogus = tmp_path / "not_a_ckpt"
    bogus.mkdir()
    (bogus / "meta.pkl").write_bytes(b"whatever")
    with pytest.raises(InvalidCheckpointError) as ei:
        Checkpoint.from_directory(str(bogus))
    assert "manifest" in str(ei.value)


def test_from_directory_refuses_invalid_manifest(tmp_path):
    from ray_tpu.air import InvalidCheckpointError
    from ray_tpu.air.checkpoint import MANIFEST_FILE
    bogus = tmp_path / "bad_manifest"
    bogus.mkdir()
    (bogus / MANIFEST_FILE).write_text("{not json")
    with pytest.raises(InvalidCheckpointError):
        Checkpoint.from_directory(str(bogus))
    (bogus / MANIFEST_FILE).write_text('{"format": 99, "files": {}}')
    with pytest.raises(InvalidCheckpointError):
        Checkpoint.from_directory(str(bogus))


def test_from_directory_refuses_torn_payload(tmp_path):
    """Truncating a payload file after commit = torn copy; the shallow
    size check already refuses it."""
    from ray_tpu.air import InvalidCheckpointError
    from ray_tpu.air.checkpoint import load_manifest
    path = _commit(tmp_path)
    rel = sorted(load_manifest(path)["files"])[0]
    full = os.path.join(path, rel)
    with open(full, "rb") as f:
        content = f.read()
    with open(full, "wb") as f:
        f.write(content[: max(0, len(content) - 1)])
    with pytest.raises(InvalidCheckpointError):
        Checkpoint.from_directory(str(path))


def test_deep_verify_catches_same_size_corruption(tmp_path):
    """Bit rot that preserves file size passes shallow verification
    but MUST fail the deep (re-hash) pass latest_complete() uses."""
    from ray_tpu.air.checkpoint import load_manifest, verify_checkpoint_dir
    path = _commit(tmp_path)
    rel = sorted(load_manifest(path)["files"])[0]
    full = os.path.join(path, rel)
    with open(full, "r+b") as f:
        first = f.read(1)
        f.seek(0)
        f.write(bytes([first[0] ^ 0xFF]))
    ok_shallow, _, manifest = verify_checkpoint_dir(path)
    ok_deep, reason, _ = verify_checkpoint_dir(path, deep=True)
    assert ok_shallow
    assert manifest["files"], "verify must return the parsed manifest"
    assert not ok_deep
    assert "hash" in reason


@pytest.mark.parametrize("exchange", [True, False])
def test_commit_displaces_existing_directory(tmp_path, monkeypatch,
                                             exchange):
    """Re-saving over an old checkpoint swaps it atomically — the
    target is never a half-written mix of the two — by one exchange of
    the two directories, or by two renames where the platform has no
    such call; neither leaves a staged or displaced directory behind."""
    from ray_tpu.air import checkpoint as ckpt
    from ray_tpu.air.checkpoint import load_manifest
    if not exchange:
        monkeypatch.setattr(ckpt, "_exchange", lambda a, b: False)
    target = tmp_path / "slot"
    Checkpoint.from_dict({"v": 1, "step": 1}).to_directory(
        str(target), step=1)
    Checkpoint.from_dict({"v": 2, "step": 2}).to_directory(
        str(target), step=2)
    assert load_manifest(str(target))["step"] == 2
    assert Checkpoint.from_directory(str(target)).to_dict()["v"] == 2
    assert os.listdir(tmp_path) == ["slot"]
