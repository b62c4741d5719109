"""Cross-replica KV page migration tests (serve/kv_migration.py and
its engine/pool integration).

Three layers:

- host-only protocol tests drive ``KVDonor`` + ``pull_prefix`` over a
  fake engine (pin/export/release bookkeeping, chunk planning under
  the max-frame knob, (digest, chunk_idx) dedupe under a faulty
  transport, typed aborts, pin-TTL GC);
- engine integration proves the user-visible contract: a pulled
  prefix lands through the normal allocator/prefix-cache path and
  decodes TOKEN-IDENTICALLY to a cold recompute, and every failure
  (donor eviction, dead donor, broken fetcher) degrades to plain
  prefill — never a wedge, never a wrong token;
- pool integration proves hint-driven routing end to end:
  ``share_prefixes=True`` advertises digests, names donors, pulls,
  and the pool-level counters account for it.
"""
import base64
import socket
import threading
import time
import types

import pytest

from ray_tpu.serve import kv_migration
from ray_tpu.serve.fleet import transport as fleet_transport
from ray_tpu.serve.fleet.transport import (FaultyTransport,
                                           LoopbackTransport,
                                           TransportError)
from ray_tpu.serve.fleet.wire import KVPullAborted
from ray_tpu.serve.prefix_cache import path_hashes


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeEngine:
    """Donor-contract double: a resident hash chain, page bytes per
    layer, and pin refcounts — everything ``KVDonor`` touches."""

    def __init__(self, n_pages=6, page_size=4, n_layers=2,
                 page_bytes=64, kv_dtype="int8"):
        self.Pg = page_size
        self.page_bytes = page_bytes
        self.kv_dtype = kv_dtype
        self.cfg = types.SimpleNamespace(n_layers=n_layers)
        self.chain = [1000 + i for i in range(n_pages)]
        self.refs = {p: 0 for p in range(n_pages)}
        # per page: one [k_bytes, v_bytes] pair per layer
        self.data = {
            p: [[b"K%d.%d" % (p, l), b"V%d.%d" % (p, l)]
                for l in range(n_layers)]
            for p in range(n_pages)}

    def kv_pin_prefix(self, hashes):
        pages = []
        for i, h in enumerate(hashes):
            if i < len(self.chain) and self.chain[i] == h:
                self.refs[i] += 1
                pages.append(i)
            else:
                break
        return pages

    def kv_export_pages(self, pages):
        return [self.data[p] for p in pages]

    def kv_release_pages(self, pages):
        for p in pages:
            self.refs[p] -= 1


def _pull(donor, hashes, **kw):
    return kv_migration.pull_prefix(
        kv_migration.loopback_call(donor), hashes, **kw)


def _decoded(payload):
    return payload["pages"]


# ----------------------------------------------------- protocol layer


def test_donor_pull_roundtrip_pins_and_releases():
    eng = FakeEngine(n_pages=6, page_bytes=64)
    # 128-byte chunk budget over 64-byte pages: 2 pages per chunk,
    # 3 chunks for the 6-page run
    donor = kv_migration.KVDonor(eng, max_chunk_bytes=128)
    stats = kv_migration.new_stats()
    payload = _pull(donor, eng.chain, stats=stats)
    assert payload is not None
    assert payload["n_pages"] == 6
    assert payload["page_size"] == eng.Pg
    assert payload["kv_dtype"] == "int8"
    assert payload["n_layers"] == eng.cfg.n_layers
    assert payload["digest"] == eng.chain[-1]
    # bytes arrive in page order, per-page per-layer, verbatim (the
    # int8 scales travel inside the same per-layer blobs)
    assert _decoded(payload) == [eng.data[p] for p in range(6)]
    # wire_bytes is the honest ON-WIRE size (base64, as framed)
    assert payload["wire_bytes"] == sum(
        len(base64.b64encode(b)) for p in range(6)
        for layer in eng.data[p] for b in layer)
    assert stats["pulls"] == 1 and stats["pulled_pages"] == 6
    assert stats["wire_bytes"] == payload["wire_bytes"]
    assert stats["aborts"] == 0 and stats["fallbacks"] == 0
    # end() released the transfer pin; nothing leaks
    assert donor.open_transfers() == 0
    assert all(r == 0 for r in eng.refs.values())


def test_pull_matches_longest_resident_run_only():
    eng = FakeEngine(n_pages=4)
    donor = kv_migration.KVDonor(eng)
    # requester's view says 6 pages; donor only holds 4
    payload = _pull(donor, eng.chain + [7777, 8888])
    assert payload["n_pages"] == 4
    assert payload["digest"] == eng.chain[3]
    assert all(r == 0 for r in eng.refs.values())


def test_pull_aborts_typed_when_nothing_resident():
    eng = FakeEngine(n_pages=4)
    donor = kv_migration.KVDonor(eng)
    stats = kv_migration.new_stats()
    # stale directory view: the advertised chain was evicted
    assert _pull(donor, [5555, 6666], stats=stats) is None
    assert stats["pulls"] == 1 and stats["aborts"] == 1
    assert stats["pulled_pages"] == 0
    assert all(r == 0 for r in eng.refs.values())


def test_pull_deadline_aborts_and_gc_reclaims_pin():
    clock = FakeClock()
    eng = FakeEngine(n_pages=6, page_bytes=64)
    donor = kv_migration.KVDonor(eng, max_chunk_bytes=64,
                                 pin_ttl_s=5.0, time_fn=clock)
    call = kv_migration.loopback_call(donor)

    def slow_call(method, args):
        if method == "kv_pull_chunk":
            clock.advance(10.0)       # every chunk blows the budget
        return call(method, args)

    stats = kv_migration.new_stats()
    out = kv_migration.pull_prefix(slow_call, eng.chain,
                                   deadline_s=1.0, stats=stats,
                                   time_fn=clock)
    assert out is None and stats["aborts"] == 1
    # the requester never sent end; the pin-TTL GC is the backstop
    assert donor.open_transfers() == 0
    assert all(r == 0 for r in eng.refs.values())


def test_chunk_dedupe_under_faulty_transport():
    """Satellite fault arm: drops and duplicate deliveries mid-pull.
    The (digest, chunk_idx) dedupe must keep the payload — and the
    wire-byte accounting — identical to a clean pull."""
    eng = FakeEngine(n_pages=6, page_bytes=64)
    clean = _pull(kv_migration.KVDonor(eng, max_chunk_bytes=64),
                  eng.chain)
    exercised = False
    for seed in range(24):
        eng2 = FakeEngine(n_pages=6, page_bytes=64)
        clock = FakeClock()
        donor = kv_migration.KVDonor(eng2, max_chunk_bytes=64,
                                     pin_ttl_s=1.0, time_fn=clock)
        ft = FaultyTransport(
            LoopbackTransport(
                lambda m, a, _t, d=donor: d.handle(m, a)),
            seed=seed, drop_p=0.15, dup_p=0.3)
        stats = kv_migration.new_stats()
        out = kv_migration.pull_prefix(
            lambda m, a: ft.call(m, a), eng2.chain,
            max_attempts=8, backoff_s=0.0, stats=stats)
        if out is None:
            # a dropped begin (no retry by design) aborts the pull
            # typed; the requester falls back — never a wrong payload
            assert stats["aborts"] == 1
        else:
            assert _decoded(out) == _decoded(clean)
            assert out["wire_bytes"] == clean["wire_bytes"], \
                "duplicate delivery double-counted wire bytes"
            assert stats["pulled_pages"] == 6, \
                "duplicate delivery landed a chunk twice"
            if (ft.stats["dropped"] >= 1
                    and ft.stats["duplicated"] >= 1):
                exercised = True
        # a duplicated begin (or a lost end) pins a transfer the
        # requester never ends; the TTL GC reclaims it
        clock.advance(2.0)
        assert donor.open_transfers() == 0
        assert all(r == 0 for r in eng2.refs.values()), \
            f"seed {seed}: leaked pins {eng2.refs}"
    assert exercised, ("no seed completed a pull through both a "
                       "drop and a duplicate — the fault arm proved "
                       "nothing")


def test_donor_refuses_unknown_or_expired_transfer():
    clock = FakeClock()
    eng = FakeEngine(n_pages=2)
    donor = kv_migration.KVDonor(eng, pin_ttl_s=1.0, time_fn=clock)
    begin = donor.begin(eng.chain[:2])
    with pytest.raises(KVPullAborted):
        donor.chunk("never-issued", 0)
    with pytest.raises(KVPullAborted):
        donor.chunk(begin["xfer_id"], 99)       # out of range
    clock.advance(2.0)                          # pin lapsed
    with pytest.raises(KVPullAborted):
        donor.chunk(begin["xfer_id"], 0)
    assert all(r == 0 for r in eng.refs.values())


# ------------------------------------------------- max-frame knob


def test_max_frame_knob_rejects_oversize_frames():
    prev = fleet_transport.set_max_frame_bytes(2048)
    try:
        a, b = socket.socketpair()
        try:
            with pytest.raises(TransportError,
                               match="max-frame knob"):
                fleet_transport.send_frame(a, b"x" * 4096)
            # a peer ANNOUNCING an oversize frame is refused before
            # any payload byte is read
            a.sendall(fleet_transport._LEN.pack(1 << 20))
            with pytest.raises(TransportError,
                               match="max-frame knob"):
                fleet_transport.recv_frame(b)
        finally:
            a.close()
            b.close()
        with pytest.raises(ValueError):
            fleet_transport.set_max_frame_bytes(100)  # below floor
    finally:
        fleet_transport.set_max_frame_bytes(prev)


def test_kv_chunks_size_themselves_under_the_frame_knob():
    """One explicit knob, shared: shrinking the frame ceiling makes
    the donor plan MORE, SMALLER chunks — never an oversize frame."""
    eng = FakeEngine(n_pages=8, page_bytes=1024)
    donor = kv_migration.KVDonor(eng)
    prev = fleet_transport.set_max_frame_bytes(4096)
    try:
        b1 = donor.begin(eng.chain)
        # 4096 // 2 = 2048-byte budget over 1 KiB pages: 2 per chunk
        assert b1["pages_per_chunk"] == 2 and b1["n_chunks"] == 4
        donor.end(b1["xfer_id"])
        fleet_transport.set_max_frame_bytes(2048)
        b2 = donor.begin(eng.chain)
        assert b2["pages_per_chunk"] == 1 and b2["n_chunks"] == 8
        donor.end(b2["xfer_id"])
    finally:
        fleet_transport.set_max_frame_bytes(prev)
    assert all(r == 0 for r in eng.refs.values())


# ------------------------------------------------ engine integration


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _mk_engine(tiny_model, **kw):
    from ray_tpu.serve.engine import LLMEngine
    model, params = tiny_model
    knobs = dict(max_slots=2, page_size=8, n_pages=16, chunk=4,
                 prefill_chunk=4, temperature=0.0, eos_id=-1,
                 seed=0, prefix_cache=True)
    knobs.update(kw)
    return LLMEngine(model, params, **knobs)


def _drain(eng):
    while eng.step():
        pass


def _run(eng, prompt, n=6, pull=None):
    h = eng.submit(list(prompt), max_new_tokens=n, pull=pull)
    _drain(eng)
    return h.result()


PREFIX = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3,
          2, 3, 8, 4, 6, 2, 6, 4, 3, 3, 8, 3, 2, 7, 9, 5]  # 4 pages


def test_engine_pull_lands_token_identical(tiny_model):
    """The tentpole contract: pulled-prefix decode is token-identical
    to a cold recompute, and the landed pages enter the normal
    prefix-cache path (the next request hits them locally)."""
    donor_eng = _mk_engine(tiny_model)
    req_eng = _mk_engine(tiny_model)
    try:
        prompt = PREFIX + [11, 22, 33, 44]
        # cold recompute on a THIRD engine is the reference
        ref_eng = _mk_engine(tiny_model)
        want = _run(ref_eng, prompt)
        ref_eng.shutdown()
        # donor computes (and caches) the shared prefix
        _run(donor_eng, PREFIX + [7, 7, 7, 7])
        donor = kv_migration.KVDonor(donor_eng)
        req_eng.kv_fetcher = lambda pull: kv_migration.pull_prefix(
            kv_migration.loopback_call(donor), pull["hashes"],
            stats=req_eng.kv_migration_stats)
        hint = {"hashes": path_hashes(PREFIX, req_eng.Pg)}
        got = _run(req_eng, prompt, pull=hint)
        assert got == want, "pulled-prefix decode diverged"
        st = req_eng.kv_migration_stats
        assert st["pulls"] == 1 and st["pulled_pages"] == 4
        assert st["fallbacks"] == 0 and st["aborts"] == 0
        assert st["wire_bytes"] > 0
        assert req_eng.stats["kv_pull_landed"] == 1
        # landed pages are ordinary cache residents: a second request
        # over the same prefix hits locally, no second pull
        hits0 = req_eng.prefix_stats()["hit_tokens"]
        got2 = _run(req_eng, prompt, pull=dict(hint))
        assert got2 == want
        assert req_eng.kv_migration_stats["pulls"] == 1
        assert req_eng.prefix_stats()["hit_tokens"] - hits0 \
            >= len(PREFIX)
        # donor side: transfer ended, pins released, cache balanced
        assert donor.open_transfers() == 0
    finally:
        donor_eng.shutdown()
        req_eng.shutdown()


def test_engine_falls_back_when_donor_evicted_or_fetcher_dies(
        tiny_model):
    """Every pull failure degrades to plain prefill: typed donor
    abort (prefix evicted), fetcher returning None, and a fetcher
    that raises — all complete token-identically with the fallback
    counter ticking."""
    ref_eng = _mk_engine(tiny_model)
    prompt = PREFIX + [11, 22, 33, 44]
    want = _run(ref_eng, prompt)
    ref_eng.shutdown()
    hint = {"hashes": path_hashes(PREFIX, 8)}

    # donor whose cache never held the prefix: typed abort
    empty_donor = kv_migration.KVDonor(_FakeEmptyDonorEngine())
    fetchers = [
        lambda pull, d=empty_donor: kv_migration.pull_prefix(
            kv_migration.loopback_call(d), pull["hashes"]),
        lambda pull: None,
        _raising_fetcher,
    ]
    for i, fetcher in enumerate(fetchers):
        eng = _mk_engine(tiny_model)
        try:
            eng.kv_fetcher = fetcher
            got = _run(eng, prompt, pull=dict(hint))
            assert got == want, f"fetcher {i}: fallback diverged"
            assert eng.kv_migration_stats["fallbacks"] == 1, \
                f"fetcher {i}: fallback not counted"
            assert eng.stats["kv_pull_landed"] == 0
        finally:
            eng.shutdown()


class _FakeEmptyDonorEngine(FakeEngine):
    def __init__(self):
        super().__init__(n_pages=0)


def _raising_fetcher(pull):
    raise RuntimeError("fetcher transport exploded")


def test_export_refuses_on_stopped_engine(tiny_model):
    """A dead donor must look dead over every seam: export from a
    stopped engine raises the typed abort (in-process pools mirror
    what a killed peer process looks like over the socket)."""
    eng = _mk_engine(tiny_model)
    _run(eng, PREFIX + [7, 7, 7, 7])
    pages = eng.kv_pin_prefix(path_hashes(PREFIX, eng.Pg))
    assert len(pages) == 4
    assert len(eng.kv_export_pages(pages)) == 4   # alive: exports
    eng.shutdown()
    with pytest.raises(KVPullAborted):
        eng.kv_export_pages(pages)
    eng.kv_release_pages(pages)   # release stays permissive on a
    #                               corpse: the donor GC needs it


def test_stopped_engine_pins_nothing(tiny_model):
    eng = _mk_engine(tiny_model)
    _run(eng, PREFIX + [7, 7, 7, 7])
    eng.drain()
    assert eng.kv_pin_prefix(path_hashes(PREFIX, eng.Pg)) == []
    eng.shutdown()


# -------------------------------------------------- pool integration


def test_pool_share_prefixes_pulls_token_identical(tiny_model):
    """End to end through routing: the pool advertises digests,
    names the warm sibling as donor, and the cold replica pulls
    instead of recomputing — token-identical, with the pool-level
    counters accounting for the migration."""
    from ray_tpu.serve.engine_pool import EnginePool
    ref_eng = _mk_engine(tiny_model)
    prompt = PREFIX + [11, 22, 33, 44]
    want = _run(ref_eng, prompt)
    ref_eng.shutdown()

    built = []

    def factory(idx):
        eng = _mk_engine(tiny_model)
        built.append(eng)
        eng.start()
        return eng

    pool = EnginePool(factory, 2, share_prefixes=True, seed=0)
    try:
        hw = pool.submit(PREFIX + [7, 7, 7, 7], max_new_tokens=2,
                         session_id="w")
        hw.result()
        warm, cold = hw.replica_idx, 1 - hw.replica_idx
        # hold a long request on the warm replica so P2C tips the
        # measured session onto the cold one
        h_busy = pool.submit([9, 8, 7, 6, 5, 4, 3, 2],
                             max_new_tokens=48, session_id="w")
        for _ in range(30):
            hp = pool.submit([13, 17, 19, 23], max_new_tokens=2,
                             session_id="m")
            hp.result()
            if hp.replica_idx == cold:
                break
            with pool._lock:
                pool._sticky.pop("m", None)
        else:
            pytest.fail("could not land the session cold")
        hm = pool.submit(prompt, max_new_tokens=6, session_id="m")
        assert hm.replica_idx == cold
        assert hm.result() == want
        h_busy.result()
        st = pool.kv_migration_stats()
        assert st["pulls"] >= 1 and st["pulled_pages"] >= 4
        assert st["fallbacks"] == 0
        ps = pool.pool_stats()
        assert ps["kv_migration"]["pulled_pages"] >= 4
        assert ps.get("pull_hints", 0) >= 1
    finally:
        pool.shutdown()
        for eng in built:
            eng.shutdown()
