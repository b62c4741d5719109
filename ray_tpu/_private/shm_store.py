"""ctypes binding for the C++ shared-memory object store
(src/object_store/shm_store.cc — the plasma-equivalent host-RAM tier).

The library is built on demand with g++ (no pybind11 in the image; the
C ABI + ctypes keeps the binding dependency-free). Zero-copy reads: get()
returns a memoryview into the shm mapping; put/get of numpy arrays never
copy through Python byte strings on the read side.
"""
from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import Optional, Tuple

from ray_tpu._private.native_build import ensure_built

from ray_tpu._private.ids import ObjectID

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_SRC = os.path.join(_REPO_ROOT, "src", "object_store", "shm_store.cc")
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")
_LIB = os.path.join(_BUILD_DIR, "libshm_store.so")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None

SHM_OK = 0
SHM_ERR_EXISTS = -1
SHM_ERR_NOT_FOUND = -2
SHM_ERR_FULL = -3
SHM_ERR_TOO_MANY = -7

_ERRORS = {
    -1: "object already exists",
    -2: "object not found",
    -3: "store full (after eviction)",
    -4: "invalid object state",
    -5: "timeout",
    -6: "system error",
    -7: "too many objects",
}


class ShmStoreError(RuntimeError):
    def __init__(self, code: int, op: str):
        self.code = code
        self.op = op
        super().__init__(f"shm_store.{op}: "
                         f"{_ERRORS.get(code, f'error {code}')}")

    def __reduce__(self):
        # Default exception pickling replays __init__ with args=(msg,)
        # — wrong arity for this two-arg signature, so a worker's
        # ShmStoreError would morph into a TypeError on the driver.
        return (type(self), (self.code, self.op))


class ShmTimeout(ShmStoreError):
    pass


def _load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(ensure_built(_SRC, _LIB))
        lib.store_create.restype = ctypes.c_void_p
        lib.store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.store_attach.restype = ctypes.c_void_p
        lib.store_attach.argtypes = [ctypes.c_char_p]
        lib.store_detach.argtypes = [ctypes.c_void_p]
        lib.store_destroy.argtypes = [ctypes.c_void_p]
        lib.store_create_object.restype = ctypes.c_int64
        lib.store_create_object.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.store_create_object_ex.restype = ctypes.c_int64
        lib.store_create_object_ex.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_int]
        lib.store_lru_candidate.restype = ctypes.c_int
        lib.store_lru_candidate.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p]
        lib.store_seal.restype = ctypes.c_int
        lib.store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_get.restype = ctypes.c_int
        lib.store_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.store_release.restype = ctypes.c_int
        lib.store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_delete.restype = ctypes.c_int
        lib.store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_contains.restype = ctypes.c_int
        lib.store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_stats.argtypes = [
            ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_uint64)] * 4
        lib.store_base.restype = ctypes.c_void_p
        lib.store_base.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def _check(code: int, op: str):
    if code == SHM_OK:
        return
    if code == -5:
        raise ShmTimeout(code, op)
    raise ShmStoreError(code, op)


class _PinnedExporter:
    """Buffer-protocol owner of one read pin on a sealed object.

    memoryview(_PinnedExporter(...)) re-exports the shm mapping; every
    derived slice / numpy array keeps THIS object alive through the
    buffer chain (PEP 688 __buffer__), and the pin (store refcount) is
    released exactly once when the last reference dies. store_delete
    refuses refcount>0 entries, so pinned pages can never be reused
    under a live view (the plasma client-mapping safety contract,
    plasma/store.h:55)."""

    __slots__ = ("_store", "_oid", "_view", "_released", "__weakref__")

    def __init__(self, store, oid, view):
        self._store = store
        self._oid = oid
        self._view = view
        self._released = False

    def __buffer__(self, flags):
        return memoryview(self._view)

    def __len__(self):
        return len(self._view)

    def __del__(self):
        if not self._released:
            self._released = True
            try:
                self._store.release(self._oid)
            except Exception:
                pass    # store torn down first (interpreter exit)


class ShmObjectStore:
    """One node-local store segment. The node runtime calls create();
    workers attach() by name."""

    def __init__(self, handle: int, name: str, owner: bool):
        self._lib = _load()
        self._h = handle
        self.name = name
        self._owner = owner
        base = self._lib.store_base(self._h)
        self._base = base
        # Spill directory is derived from the store name so every
        # process attached to the same segment agrees on it (reference:
        # N15 object spilling, raylet/local_object_manager.h:38 +
        # _private/external_storage.py filesystem backend).
        from ray_tpu._private.config import GlobalConfig
        self._spill_dir = os.path.join(
            GlobalConfig.object_spill_dir, name.lstrip("/"))
        self._num_spilled = 0
        self._num_restored = 0

    # --- lifecycle --------------------------------------------------------

    @classmethod
    def create(cls, name: str, capacity: int) -> "ShmObjectStore":
        lib = _load()
        h = lib.store_create(name.encode(), capacity)
        if not h:
            raise ShmStoreError(-6, "create")
        return cls(h, name, owner=True)

    @classmethod
    def attach(cls, name: str) -> "ShmObjectStore":
        lib = _load()
        h = lib.store_attach(name.encode())
        if not h:
            raise ShmStoreError(-2, "attach")
        return cls(h, name, owner=False)

    def close(self):
        if self._h:
            if self._owner:
                self._lib.store_destroy(self._h)
                import shutil
                shutil.rmtree(self._spill_dir, ignore_errors=True)
            else:
                self._lib.store_detach(self._h)
            self._h = None

    # --- object lifecycle -------------------------------------------------

    def put_bytes(self, oid: ObjectID, data: bytes) -> None:
        self.put_parts(oid, [data], len(data))

    def put_parts(self, oid: ObjectID, parts, total: int) -> None:
        """Create + stream buffer-like parts straight into the shm
        mapping + seal. With serialization.serialize_parts this is the
        single-copy put path (reference: plasma CreateAndSeal writes
        the serialized object directly into the store buffer).

        No-evict create: under memory pressure cold LRU objects are
        spilled to disk to make room (never silently dropped); if the
        incoming object still doesn't fit, it spills itself."""
        while True:
            off = self._lib.store_create_object_ex(
                self._h, oid.binary(), total, 0)
            if off == SHM_ERR_FULL:
                if self._spill_lru_one():
                    continue
                self._spill_parts(oid, parts)
                return
            if off == SHM_ERR_TOO_MANY:
                self._spill_parts(oid, parts)
                return
            if off < 0:
                _check(int(off), "create_object")
            break
        dst = (ctypes.c_char * total).from_address(self._base + off)
        view = memoryview(dst).cast("B")
        pos = 0
        for p in parts:
            if isinstance(p, memoryview):
                p = p.cast("B")
            n = len(p)
            view[pos:pos + n] = p
            pos += n
        _check(self._lib.store_seal(self._h, oid.binary()), "seal")

    # --- raw create/seal (streamed remote pulls) ---------------------------

    def create_for_write(self, oid: ObjectID, size: int) -> Optional[
            memoryview]:
        """Allocate an unsealed object and return a writable view into
        the mapping, or None if it cannot fit (caller falls back to a
        buffered pull + spill). Readers block until seal_raw()."""
        while True:
            off = self._lib.store_create_object_ex(
                self._h, oid.binary(), size, 0)
            if off == SHM_ERR_FULL:
                if self._spill_lru_one():
                    continue
                return None
            if off in (SHM_ERR_TOO_MANY, SHM_ERR_EXISTS):
                return None
            if off < 0:
                _check(int(off), "create_object")
            dst = (ctypes.c_char * size).from_address(self._base + off)
            return memoryview(dst).cast("B")

    def seal_raw(self, oid: ObjectID) -> None:
        _check(self._lib.store_seal(self._h, oid.binary()), "seal")

    def abort_raw(self, oid: ObjectID) -> None:
        """Drop an unsealed allocation after a failed streamed write."""
        try:
            self._lib.store_delete(self._h, oid.binary())
        except Exception:
            pass

    def _spill_lru_one(self) -> bool:
        """Spill+delete the LRU sealed refcount-0 object. False if no
        candidate exists."""
        buf = ctypes.create_string_buffer(len(ObjectID.nil().binary()))
        rc = self._lib.store_lru_candidate(self._h, buf)
        if rc != SHM_OK:
            return False
        victim = ObjectID(buf.raw)
        return self.spill(victim)

    # --- spilling ---------------------------------------------------------

    def _spill_path(self, oid: ObjectID) -> str:
        return os.path.join(self._spill_dir, oid.hex())

    def _spill_bytes(self, oid: ObjectID, data: bytes) -> None:
        self._spill_parts(oid, [data])

    def _spill_parts(self, oid: ObjectID, parts) -> None:
        os.makedirs(self._spill_dir, exist_ok=True)
        path = self._spill_path(oid)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            for p in parts:
                f.write(p)
        os.replace(tmp, path)   # atomic: readers see whole objects only
        self._num_spilled += 1

    def _read_spilled(self, oid: ObjectID) -> Optional[bytes]:
        try:
            with open(self._spill_path(oid), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def spill(self, oid: ObjectID) -> bool:
        """Explicitly move a sealed object from shm to disk."""
        try:
            data = self.get_bytes_shm_only(oid, timeout_ms=0)
        except ShmStoreError:
            return False
        self._spill_bytes(oid, data)
        try:
            # shm copy only — the spill file IS the object now.
            _check(self._lib.store_delete(self._h, oid.binary()),
                   "delete")
        except ShmStoreError:
            pass
        return True

    def restore(self, oid: ObjectID) -> bool:
        """Try to bring a spilled object back into shm."""
        data = self._read_spilled(oid)
        if data is None:
            return False
        off = self._lib.store_create_object_ex(self._h, oid.binary(),
                                               len(data), 0)
        if off < 0:
            return off == SHM_ERR_EXISTS
        ctypes.memmove(self._base + off, data, len(data))
        _check(self._lib.store_seal(self._h, oid.binary()), "seal")
        self._num_restored += 1
        try:
            os.unlink(self._spill_path(oid))   # shm copy is primary now
        except OSError:
            pass
        return True

    def get_view(self, oid: ObjectID,
                 timeout_ms: int = -1) -> memoryview:
        """Zero-copy view; caller must release(oid) when done."""
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        _check(self._lib.store_get(self._h, oid.binary(), timeout_ms,
                                   ctypes.byref(off), ctypes.byref(size)),
               "get")
        buf = (ctypes.c_char * size.value).from_address(
            self._base + off.value)
        return memoryview(buf)

    def get_bytes_shm_only(self, oid: ObjectID,
                           timeout_ms: int = -1) -> bytes:
        view = self.get_view(oid, timeout_ms)
        try:
            return bytes(view)
        finally:
            self.release(oid)

    # Objects at or above this size are returned as PINNED shm views
    # instead of heap copies (get_blob): on the 1-core rig a 1 GiB
    # heap copy costs ~1s alone and SECONDS under process concurrency
    # (the host throttles concurrent bulk memory traffic superlinearly
    # — measured 0.8s solo vs 6s x2 vs 28s x4), and the reference's
    # plasma contract is zero-copy reads anyway (ray_object.h:28).
    PIN_THRESHOLD = 1 << 20

    def get_blob(self, oid: ObjectID, timeout_ms: int = -1):
        """Zero-copy get: large sealed objects return a READ-ONLY
        memoryview whose exporter holds the store pin — the object's
        pages stay mapped and unevictable until every derived view
        (including numpy arrays deserialized over it) is GC'd.
        Small objects and spill-resident objects return bytes.
        Blocking + spill-fallback semantics match get_bytes."""
        deadline = None if timeout_ms < 0 else \
            time.monotonic() + timeout_ms / 1000.0
        slice_cap = 250   # re-check the spill dir only on slice expiry
        first = True
        while True:
            slice_ms = 0 if first else (
                slice_cap if deadline is None else
                max(0, min(slice_cap,
                           int((deadline - time.monotonic()) * 1000))))
            first = False
            view = None
            try:
                view = self.get_view(oid, timeout_ms=slice_ms)
            except ShmTimeout:
                pass
            except ShmStoreError as e:
                if e.code not in (-2, -4):
                    raise
            if view is not None:
                if len(view) < self.PIN_THRESHOLD:
                    try:
                        return bytes(view)
                    finally:
                        self.release(oid)
                return memoryview(
                    _PinnedExporter(self, oid, view)).toreadonly()
            data = self._read_spilled(oid)
            if data is not None:
                return data
            if deadline is not None and time.monotonic() >= deadline:
                raise ShmTimeout(-5, "get")

    def get_bytes(self, oid: ObjectID, timeout_ms: int = -1) -> bytes:
        """Get with spill fallback: poll shm in slices, checking the
        spill directory between slices (a spilled object never signals
        the shm condvar)."""
        deadline = None if timeout_ms < 0 else \
            time.monotonic() + timeout_ms / 1000.0
        # Probe shm first (0-timeout): resident objects — the common
        # case — never pay a disk syscall.
        try:
            return self.get_bytes_shm_only(oid, timeout_ms=0)
        except ShmStoreError:
            pass
        data = self._read_spilled(oid)
        if data is not None:
            return data
        slice_cap = 250   # re-check the spill dir only on slice expiry
        while True:
            slice_ms = slice_cap if deadline is None else \
                max(0, min(slice_cap,
                           int((deadline - time.monotonic()) * 1000)))
            try:
                return self.get_bytes_shm_only(oid, timeout_ms=slice_ms)
            except ShmTimeout:
                pass
            except ShmStoreError as e:
                # 0-slice probes report not-found/unsealed, not timeout.
                if e.code not in (-2, -4):
                    raise
            data = self._read_spilled(oid)
            if data is not None:
                return data
            if deadline is not None and time.monotonic() >= deadline:
                raise ShmTimeout(-5, "get")

    def release(self, oid: ObjectID):
        self._lib.store_release(self._h, oid.binary())
        # Deferred delete: a delete() that arrived while this process
        # held read pins completes at the last release (the plasma
        # delete-on-release contract). Cross-process pins degrade to
        # LRU eviction once the refcount drops — never a leak, just
        # lazier reclamation.
        deferred = getattr(self, "_deferred_deletes", None)
        if deferred and oid in deferred:
            rc = self._lib.store_delete(self._h, oid.binary())
            if rc in (SHM_OK, SHM_ERR_NOT_FOUND):
                deferred.discard(oid)

    def delete(self, oid: ObjectID):
        had_spill = False
        try:
            os.unlink(self._spill_path(oid))
            had_spill = True
        except OSError:
            pass
        rc = self._lib.store_delete(self._h, oid.binary())
        if had_spill and rc == SHM_ERR_NOT_FOUND:
            return   # spilled-only object: the unlink was the delete
        if rc == -4:
            # Pinned by live views (zero-copy gets): defer to the
            # last release in this process; other processes' pins
            # leave a refcount-0 entry for LRU once dropped.
            deferred = getattr(self, "_deferred_deletes", None)
            if deferred is None:
                deferred = self._deferred_deletes = set()
            deferred.add(oid)
            return
        _check(rc, "delete")

    def contains(self, oid: ObjectID) -> bool:
        if self._lib.store_contains(self._h, oid.binary()):
            return True
        return os.path.exists(self._spill_path(oid))

    def stats(self) -> dict:
        vals = [ctypes.c_uint64() for _ in range(4)]
        self._lib.store_stats(self._h, *[ctypes.byref(v) for v in vals])
        return {"bytes_in_use": vals[0].value,
                "num_objects": vals[1].value,
                "num_evictions": vals[2].value,
                "capacity": vals[3].value,
                "num_spilled": self._num_spilled,
                "num_restored": self._num_restored}

    # --- serialization-aware helpers --------------------------------------

    def put_object(self, oid: ObjectID, value) -> None:
        from ray_tpu._private import serialization
        self.put_bytes(oid, serialization.dumps(value))

    def get_object(self, oid: ObjectID, timeout_ms: int = -1):
        from ray_tpu._private import serialization
        return serialization.loads(self.get_bytes(oid, timeout_ms))
