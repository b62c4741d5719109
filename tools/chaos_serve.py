"""Serving chaos harness: a seeded fault campaign against a live
multi-replica engine pool under trace load.

Runs a real EnginePool (llama_tiny replicas, fp32 greedy so every
completion has ONE correct answer) with an attached PoolWatchdog and
PoolAutoscaler while a seeded ChaosInjector (serve/chaos.py) fires
replica kills, a dispatch hang (wedge), slow steps, readback faults,
a capacity stockout, and a kill-during-drain race. Client threads
keep submitting throughout.

After the campaign it PROVES the pool's availability contract:

- zero admitted requests lost: every submitted request either
  completes token-identically to the greedy reference or fails with
  a TYPED lifecycle error (and sheds carry an honest Retry-After);
- the wedged replica is detected within the stall deadline and
  replaced without restarting any replica the campaign didn't touch;
- a slow (but moving) replica never trips the watchdog;
- the released zombie is fenced: no tokens committed, no prefix
  pages published, and every engine ever built — including corpses
  replaced mid-run — quiesces leak-free;
- attainment (completed / admitted) stays above a recorded floor;
- every headline fault left a flight-recorder bundle (serve/obs.py)
  that EXPLAINS it: the killed replica's event tail ends at the
  ReplicaKilled death, the wedge bundle records the heartbeat gap
  that justified the hang->death escalation;
- cross-replica KV migration (share_prefixes) degrades, never
  wedges: a donor killed mid-pull leaves the requester falling back
  to plain prefill token-identically, and a session whose home
  replica dies after its prefix migrated resumes token-identically
  on the peer FROM the migrated pages — both flight-explained;
- prefill/decode disaggregation degrades the same way: a prefill
  replica killed mid-handoff leaves the decode side aborting the
  pull typed and prefilling in place, a decode replica killed
  post-handoff fails the partial stream typed and the resubmit
  lands decode-in-place on the prefill replica through the typed
  handoff-fallback ladder — token-identical throughout, both
  flight-explained;
- live weight rollout (serve/weight_rollout.py) survives its chaos:
  a replica killed with a drain-mode hot swap PENDING is rebuilt and
  re-swapped (the fleet converges on the new weights_id), a torn
  checkpoint is refused typed before any replica is touched, and a
  controller killed mid-rollout is resumable — a fresh controller
  skips already-converged replicas and completes, with traffic
  token-identical across every swap.

Writes a SERVE_CHAOS json artifact gated by
tools/check_bench_schema.py (serve_chaos family).

Run: JAX_PLATFORMS=cpu python tools/chaos_serve.py [--seed N] [--out FILE]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ATTAINMENT_FLOOR = 0.5


def _reference_completion(model, params, prompt, n):
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.llama import generate
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _reference_completions_int8(model, params, prompts, n):
    """Greedy references for an int8-KV campaign: computed by a
    REFERENCE ENGINE with the same knobs as the pool's replicas, not
    by dense ``generate``.

    Quantized KV is tolerance-equal to fp, never bit-equal, so a
    dense-fp reference would turn honest rounding into "mismatched"
    verdicts. What IS bit-exact — and what the chaos contract
    actually protects — is failover: a request's quantized write
    history (its token values, page-chunk boundaries, scale growth)
    is identical on every replica with identical knobs, so a
    resubmitted request must still complete token-identically to
    this engine-derived reference (docs/serving.md, Failure
    semantics)."""
    from ray_tpu.serve.engine import LLMEngine
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, temperature=0.0,
                    seed=0, prefix_cache=True, eos_id=-1,
                    kv_dtype="int8")
    want = {}
    for p in prompts:
        h = eng.submit(list(p), max_new_tokens=n)
        while eng.step():
            pass
        want[tuple(p)] = h.result()
    eng.shutdown()
    return want


def _run_migration_phases(model, params, flight_dir, seed, kv_dtype,
                          max_new_tokens=8):
    """KV-migration fault drill: two seeded phases, each against a
    fresh 2-replica pool with ``share_prefixes=True``.

    A. donor kill mid-pull — the transfer is stretched with a
       per-chunk delay (one page per chunk), the donor replica is
       killed while chunks are still in flight, and the requester
       must FALL BACK to plain prefill and complete token-identically
       (typed abort, never a wedge; zero lost, zero mismatched).
    B. peer resume from migrated pages — a session's prefix is pulled
       to a peer replica by normal hint-driven migration, the replica
       that COMPUTED it is killed, and the session's next request
       resumes on the peer hitting the MIGRATED pages (prefix
       hit-token delta >= prefix length) token-identically. The peer
       never recomputed the prefix: migration is its only source.

    Both kills leave engine-fail-all flight bundles (ReplicaKilled in
    the event tail); the drill dumps migration postmortems whose
    event tails carry the pull_fallback / pull_land proof and asserts
    the bundles on disk explain both faults. Every engine ever built
    — including the corpses — must quiesce leak-free (the donor's
    transfer pins are reclaimed by the pin-TTL GC even though the
    requester aborted and never sent ``end``). Returns the
    ``kv_migration`` artifact block."""
    import glob

    import numpy as np

    from ray_tpu.serve import kv_migration, obs
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.errors import (DeadlineExceeded,
                                      EngineDraining,
                                      EngineOverloaded,
                                      EngineShutdown,
                                      RequestCancelled)
    from ray_tpu.serve.faults import FaultInjector, check_quiesced

    typed = (RequestCancelled, DeadlineExceeded, EngineOverloaded,
             EngineDraining, EngineShutdown)
    Pg, prefix_pages = 8, 12
    rng = np.random.RandomState(seed * 7 + 173)

    def toks(n):
        return rng.randint(1, 250, size=n).tolist()

    shared = toks(Pg * prefix_pages)      # 96-token shared prefix
    tail_w, tail_m = toks(8), toks(8)     # phase A tails
    tail_a1, tail_b, tail_a2 = toks(8), toks(8), toks(8)  # phase B
    busy = toks(16)       # short prompt, long decode: busy-tips P2C
    pin = toks(12)        # unrelated pin prompt (no shared pages)
    sac = toks(12)        # sacrificial: forces the armed kill to fire
    mnt = max_new_tokens

    def mk_engine(inj=None):
        # same knobs everywhere — replicas AND the reference engine —
        # so the int8 quantized write history is bit-identical and
        # "token-identical" has one right answer (docs/serving.md)
        return LLMEngine(model, params, max_slots=2, page_size=Pg,
                         n_pages=48, chunk=4, prefill_chunk=4,
                         temperature=0.0, eos_id=-1, seed=0,
                         prefix_cache=True, kv_dtype=kv_dtype,
                         fault_injector=inj, flight_dir=flight_dir)

    # Greedy ground truth from a same-knobs reference engine.
    ref = mk_engine()
    want = {}
    for p, n in [(shared + tail_w, 2), (shared + tail_m, mnt),
                 (shared + tail_a1, 4), (shared + tail_b, mnt),
                 (shared + tail_a2, mnt), (busy, 64), (pin, 4),
                 (sac, 2)]:
        h = ref.submit(list(p), max_new_tokens=n)
        while ref.step():
            pass
        want[tuple(p)] = h.result()
    ref.shutdown()

    results = {"completed": 0, "failed_typed": 0, "lost": 0,
               "mismatched": 0}

    def settle(handle, prompt, may_fail_typed=False):
        """Resolve a handle against the reference; returns the
        outcome label and updates the loss/mismatch ledger."""
        try:
            out = handle.result()
        except typed as e:
            if not may_fail_typed:
                results["lost"] += 1
                return f"unexpected_typed:{type(e).__name__}"
            results["failed_typed"] += 1
            return f"typed:{type(e).__name__}"
        except BaseException as e:  # noqa: BLE001
            results["lost"] += 1
            return f"untyped:{type(e).__name__}"
        if out == want[tuple(prompt)]:
            results["completed"] += 1
            return "completed"
        results["mismatched"] += 1
        return "mismatched"

    def mk_pool(engines):
        def factory(idx):
            eng = mk_engine(FaultInjector())
            engines.append(eng)
            eng.start()
            # warm the jitted prefill/decode paths before joining so
            # phase timing never stalls on XLA compilation
            eng.submit(list(pin), max_new_tokens=4).result()
            eng.reset_latency_stats()
            return eng
        return EnginePool(factory, 2, share_prefixes=True, seed=seed)

    def pin_session(pool, sid, idx):
        """Stick ``sid`` to replica ``idx`` with unrelated pin
        requests (popping the sticky entry on wrong placement; the
        busy replica tips P2C toward the target)."""
        for _ in range(30):
            h = pool.submit(list(pin), max_new_tokens=4,
                            session_id=sid)
            settle(h, pin)
            if h.replica_idx == idx:
                return
            with pool._lock:
                pool._sticky.pop(sid, None)
        raise AssertionError(
            f"could not pin session {sid} on replica {idx}")

    # ------------------------------- phase A: donor kill mid-pull
    engines_a = []
    pool = mk_pool(engines_a)
    hw = pool.submit(shared + tail_w, max_new_tokens=2,
                     session_id="w")
    settle(hw, shared + tail_w)
    warm = hw.replica_idx
    cold = 1 - warm
    donor_eng = pool._replicas[warm].engine
    cold_eng = pool._replicas[cold].engine
    h_busy = pool.submit(list(busy), max_new_tokens=64,
                         session_id="w")   # sticky -> warm replica
    pin_session(pool, "m", cold)
    # Stretch the transfer: one page per chunk, a delay per chunk —
    # the 12-page pull now spans ~1s, so the kill below lands with
    # chunks still in flight. Short pin TTL so teardown's GC check
    # doesn't wait 30s to reclaim the aborted transfer's pins.
    chaos_donor = kv_migration.KVDonor(
        donor_eng, max_chunk_bytes=2048, chunk_delay_s=0.08,
        pin_ttl_s=0.6)
    with pool._lock:
        pool._kv_donors[warm] = chaos_donor
    hm = pool.submit(shared + tail_m, max_new_tokens=mnt,
                     session_id="m")
    assert hm.replica_idx == cold, "measured request left its pin"
    time.sleep(0.3)               # well inside the ~1s transfer
    donor_eng._injector.kill_replica()
    # the armed kill fires at the donor's next scheduling round; a
    # sacrificial request guarantees one even if the busy decode
    # already drained
    try:
        h_sac = pool.submit(list(sac), max_new_tokens=2,
                            session_id="w")
        sac_outcome = settle(h_sac, sac, may_fail_typed=True)
    except typed as e:            # kill won the submit race: typed
        results["failed_typed"] += 1
        sac_outcome = f"typed:{type(e).__name__}"
    measured_outcome = settle(hm, shared + tail_m)
    busy_outcome = settle(h_busy, busy, may_fail_typed=True)
    stats_a = dict(cold_eng.kv_migration_stats)
    assert stats_a.get("fallbacks", 0) >= 1, (
        f"donor kill mid-pull produced no plain-prefill fallback "
        f"(requester stats {stats_a})")
    assert measured_outcome == "completed", (
        f"measured request did not complete token-identically "
        f"after the donor died mid-pull: {measured_outcome}")
    obs.dump_flight_bundle(
        flight_dir, "migration-donor-kill", engine=cold_eng,
        pool=pool, extra={"phase": "donor_kill_mid_pull",
                          "donor_idx": warm, "requester_idx": cold,
                          "measured": measured_outcome})
    pool.shutdown()
    for eng in engines_a:
        eng.shutdown()
    # aborted transfer: the requester never sent end — the donor's
    # pin-TTL GC must reclaim the pins or the corpse leaks
    time.sleep(0.7)
    assert chaos_donor.open_transfers() == 0, \
        "pin-TTL GC left the aborted transfer pinned"
    for eng in engines_a:
        check_quiesced(eng)
    phase_a = {
        "prefix_pages": prefix_pages,
        "aborts": stats_a.get("aborts", 0),
        "fallbacks": stats_a.get("fallbacks", 0),
        "completed_token_identical": measured_outcome == "completed",
        "busy_outcome": busy_outcome,
        "sacrifice_outcome": sac_outcome,
    }

    # --------------------- phase B: peer resume from migrated pages
    engines_b = []
    pool = mk_pool(engines_b)
    ha = pool.submit(shared + tail_a1, max_new_tokens=4,
                     session_id="a")
    settle(ha, shared + tail_a1)
    a_idx = ha.replica_idx
    b_idx = 1 - a_idx
    eng_a = pool._replicas[a_idx].engine
    eng_b = pool._replicas[b_idx].engine
    h_busy = pool.submit(list(busy), max_new_tokens=64,
                         session_id="a")   # sticky -> replica A
    pin_session(pool, "b", b_idx)
    hb = pool.submit(shared + tail_b, max_new_tokens=mnt,
                     session_id="b")
    assert hb.replica_idx == b_idx, "migration request left its pin"
    migrate_outcome = settle(hb, shared + tail_b)
    busy_outcome_b = settle(h_busy, busy, may_fail_typed=True)
    stats_b = dict(eng_b.kv_migration_stats)
    assert migrate_outcome == "completed", (
        f"hint-driven migration request diverged: {migrate_outcome}")
    assert stats_b.get("pulled_pages", 0) >= prefix_pages, (
        f"peer pulled {stats_b.get('pulled_pages', 0)} pages, want "
        f">= {prefix_pages} (hint-driven migration never happened)")
    assert stats_b.get("fallbacks", 0) == 0, (
        f"unfaulted migration fell back: {stats_b}")
    hit0 = (eng_b.prefix_stats() or {}).get("hit_tokens", 0)
    eng_a._injector.kill_replica()
    # session "a" was computed on A; its next request either admits
    # to A and dies with it (pool resubmits) or routes straight to
    # the survivor — both must land on B and hit the MIGRATED pages
    try:
        hr = pool.submit(shared + tail_a2, max_new_tokens=mnt,
                         session_id="a")
        resume_outcome = settle(hr, shared + tail_a2)
    except typed as e:
        results["lost"] += 1
        resume_outcome = f"refused:{type(e).__name__}"
    hit1 = (eng_b.prefix_stats() or {}).get("hit_tokens", 0)
    assert resume_outcome == "completed", (
        f"session did not resume token-identically on the peer "
        f"after its home replica died: {resume_outcome}")
    assert hit1 - hit0 >= Pg * prefix_pages, (
        f"peer served only {hit1 - hit0} prefix hit-tokens on "
        f"resume, want >= {Pg * prefix_pages}: the session was "
        f"recomputed, not resumed from migrated pages")
    obs.dump_flight_bundle(
        flight_dir, "migration-peer-resume", engine=eng_b,
        pool=pool, extra={"phase": "peer_resume",
                          "killed_idx": a_idx, "peer_idx": b_idx,
                          "hit_tokens_delta": hit1 - hit0})
    pool.shutdown()
    for eng in engines_b:
        eng.shutdown()
    for eng in engines_b:
        check_quiesced(eng)
    phase_b = {
        "migrated_pages": stats_b.get("pulled_pages", 0),
        "pull_fallbacks": stats_b.get("fallbacks", 0),
        "resume_token_identical": resume_outcome == "completed",
        "peer_prefix_hit_tokens_delta": hit1 - hit0,
        "busy_outcome": busy_outcome_b,
    }

    assert results["lost"] == 0, \
        f"migration drill lost {results['lost']} admitted requests"
    assert results["mismatched"] == 0, (
        f"{results['mismatched']} migration-drill completions "
        f"diverged from greedy")

    # ------------------------ the bundles on disk explain the drill
    kill_bundles, fallback_seen, land_seen = 0, False, False
    for bdir in sorted(glob.glob(os.path.join(flight_dir, "*"))):
        if not os.path.isdir(bdir):
            continue
        try:
            b = obs.load_flight_bundle(bdir)
        except Exception:  # noqa: BLE001  half-written dir: skip
            continue
        evs = (b.get("engine") or {}).get("events") or []
        names = {e.get("type") for e in evs}
        last = evs[-1] if evs else {}
        if (b.get("reason") == "engine-fail-all"
                and last.get("type") == "fail_all"
                and "ReplicaKilled" in str((last.get("data") or {})
                                           .get("error"))):
            kill_bundles += 1
        if (b.get("reason") == "migration-donor-kill"
                and "pull_fallback" in names):
            fallback_seen = True
        if (b.get("reason") == "migration-peer-resume"
                and "pull_land" in names):
            land_seen = True
    assert kill_bundles >= 2, (
        f"want >= 2 engine-fail-all/ReplicaKilled bundles (one per "
        f"migration-drill kill), found {kill_bundles}")
    assert fallback_seen, (
        "no migration-donor-kill bundle carries a pull_fallback "
        "event: the donor-kill fault is not flight-explained")
    assert land_seen, (
        "no migration-peer-resume bundle carries a pull_land event: "
        "the migration is not flight-explained")

    return {
        "donor_kill_mid_pull": phase_a,
        "peer_resume": phase_b,
        "requests": dict(results,
                         admitted=sum(results.values())),
        "flight": {
            "donor_kill_explained": True,
            "peer_resume_explained": True,
            "kill_bundles": kill_bundles,
        },
        "quiesced": True,
    }


def _run_disagg_phases(model, params, flight_dir, seed, kv_dtype):
    """Prefill/decode disaggregation fault drill: two seeded phases,
    each against a fresh role-split pool (1 prefill + 1 decode
    replica over the KV-migration handoff path —
    serve/engine_pool.py roles).

    A. prefill replica killed MID-HANDOFF — the handoff pull is
       stretched with a per-chunk delay, the prefill (donor) replica
       is killed while page chunks are still in flight, and the
       decode replica must abort the pull TYPED and fall back to
       prefilling in place, completing token-identically (the
       tentpole's contract: disaggregation may cost time, never
       correctness).
    B. decode replica killed POST-HANDOFF — the decode leg is paced
       with a per-round delay and killed after it has streamed >= 1
       token. A partially-streamed request must fail typed (never a
       silent hang, never a duplicated token); the client's resubmit
       re-runs the two-leg service against the dead decode side and
       must land decode-in-place on the prefill replica through the
       typed handoff fallback, token-identically.

    Both kills leave engine-fail-all flight bundles; the drill dumps
    postmortems whose event tails carry the pull_fallback /
    handoff_fallback proof and asserts the bundles on disk explain
    both faults. Every engine ever built — including the corpses —
    must quiesce leak-free. Returns the ``disagg`` artifact block."""
    import glob

    import numpy as np

    from ray_tpu.serve import kv_migration, obs
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.errors import (DeadlineExceeded,
                                      EngineDraining,
                                      EngineOverloaded,
                                      EngineShutdown,
                                      RequestCancelled)
    from ray_tpu.serve.faults import FaultInjector, check_quiesced
    from ray_tpu.serve.scheduler import ROLE_DECODE, ROLE_PREFILL

    typed = (RequestCancelled, DeadlineExceeded, EngineOverloaded,
             EngineDraining, EngineShutdown)
    Pg, prompt_pages = 8, 12
    rng = np.random.RandomState(seed * 11 + 271)

    def toks(n):
        return rng.randint(1, 250, size=n).tolist()

    p_a = toks(Pg * prompt_pages)    # phase A: 96-token prompt
    p_b = toks(Pg * prompt_pages)    # phase B: distinct prompt
    pin = toks(12)                   # factory warmup prompt
    sac = toks(12)                   # sacrificial: forces an armed
    mnt_a, mnt_b = 8, 24             # kill to fire on an idle donor

    def mk_engine(inj=None):
        # same knobs everywhere — replicas AND the reference engine —
        # so the int8 quantized write history is bit-identical and
        # "token-identical" has one right answer (docs/serving.md).
        # chunk=2 keeps decode rounds short so phase B's paced kill
        # lands mid-stream with many rounds still to go.
        return LLMEngine(model, params, max_slots=2, page_size=Pg,
                         n_pages=48, chunk=2, prefill_chunk=8,
                         temperature=0.0, eos_id=-1, seed=0,
                         prefix_cache=True, kv_dtype=kv_dtype,
                         fault_injector=inj, flight_dir=flight_dir)

    ref = mk_engine()
    want = {}
    for p, n in [(p_a, mnt_a), (p_b, mnt_b)]:
        h = ref.submit(list(p), max_new_tokens=n)
        while ref.step():
            pass
        want[tuple(p)] = h.result()
    ref.shutdown()

    results = {"completed": 0, "failed_typed": 0, "lost": 0,
               "mismatched": 0}

    def mk_pool(engines):
        def factory(idx):
            eng = mk_engine(FaultInjector())
            engines.append(eng)
            eng.start()
            eng.submit(list(pin), max_new_tokens=4).result()
            eng.reset_latency_stats()
            return eng
        return EnginePool(factory, 2, share_prefixes=True,
                          roles=[ROLE_PREFILL, ROLE_DECODE],
                          seed=seed)

    def consume(handle, box):
        """Drive the handle on its own thread (the two-leg stream is
        pulled by its consumer); box collects outcome or error."""
        try:
            box["tokens"] = handle.result()
        except BaseException as e:  # noqa: BLE001
            box["error"] = e

    # -------------------- phase A: prefill replica killed mid-handoff
    engines_a = []
    pool = mk_pool(engines_a)
    prefill_eng = pool._replicas[0].engine
    decode_eng = pool._replicas[1].engine
    # Stretch the handoff transfer: one page per chunk, a delay per
    # chunk — the 12-page pull spans ~1s, so the kill lands with
    # chunks still in flight. Short pin TTL so the aborted transfer's
    # pins are reclaimed without waiting out the default 30s.
    chaos_donor = kv_migration.KVDonor(
        prefill_eng, max_chunk_bytes=2048, chunk_delay_s=0.08,
        pin_ttl_s=0.6)
    with pool._lock:
        pool._kv_donors[0] = chaos_donor
    h = pool.submit(list(p_a), max_new_tokens=mnt_a)
    box_a = {}
    t = threading.Thread(target=consume, args=(h, box_a), daemon=True)
    t.start()
    deadline = time.monotonic() + 10.0
    while h.ttft_s is None and time.monotonic() < deadline:
        time.sleep(0.005)          # leg 1 bridging token
    assert h.ttft_s is not None, "prefill leg never produced a token"
    time.sleep(0.3)                # well inside the ~1s stretched pull
    prefill_eng._injector.kill_replica()
    try:                           # idle donor: force a round so the
        prefill_eng.submit(list(sac), max_new_tokens=2).result()
    except BaseException:          # noqa: BLE001  armed kill fires
        pass
    t.join(timeout=30.0)
    assert not t.is_alive(), "phase A request wedged after the kill"
    if "error" in box_a:
        results["lost"] += 1
        outcome_a = f"failed:{type(box_a['error']).__name__}"
    elif box_a.get("tokens") == want[tuple(p_a)]:
        results["completed"] += 1
        outcome_a = "completed"
    else:
        results["mismatched"] += 1
        outcome_a = "mismatched"
    stats_a = dict(decode_eng.kv_migration_stats)
    assert stats_a.get("fallbacks", 0) >= 1, (
        f"prefill kill mid-handoff produced no pull fallback on the "
        f"decode replica (stats {stats_a})")
    assert outcome_a == "completed", (
        f"handed-off request did not complete token-identically "
        f"after the prefill replica died mid-pull: {outcome_a}")
    obs.dump_flight_bundle(
        flight_dir, "disagg-prefill-kill", engine=decode_eng,
        pool=pool, extra={"phase": "prefill_kill_mid_handoff",
                          "killed_idx": 0, "decode_idx": 1,
                          "outcome": outcome_a})
    pool.shutdown()
    for eng in engines_a:
        eng.shutdown()
    # aborted transfer: the decode side never sent end — the donor's
    # pin-TTL GC must reclaim the pins or the corpse leaks
    time.sleep(0.7)
    assert chaos_donor.open_transfers() == 0, \
        "pin-TTL GC left the aborted handoff transfer pinned"
    for eng in engines_a:
        check_quiesced(eng)
    phase_a = {
        "prompt_pages": prompt_pages,
        "aborts": stats_a.get("aborts", 0),
        "fallbacks": stats_a.get("fallbacks", 0),
        "completed_token_identical": outcome_a == "completed",
    }

    # -------------------- phase B: decode replica killed post-handoff
    engines_b = []
    pool = mk_pool(engines_b)
    prefill_eng = pool._replicas[0].engine
    decode_eng = pool._replicas[1].engine
    # pace the decode replica's rounds so the kill lands with most of
    # the stream still to go (the armed kill fires at a round edge)
    decode_eng._injector.slow("step", 0.03, times=1000)
    h = pool.submit(list(p_b), max_new_tokens=mnt_b)
    box_b = {}
    t = threading.Thread(target=consume, args=(h, box_b), daemon=True)
    t.start()
    deadline = time.monotonic() + 15.0
    while len(h._generated) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)          # leg 1 token + >= 1 decode token
    assert len(h._generated) >= 2, \
        "decode leg never streamed past the handoff"
    decode_eng._injector.kill_replica()
    t.join(timeout=30.0)
    assert not t.is_alive(), "phase B request wedged after the kill"
    err = box_b.get("error")
    assert err is not None and isinstance(err, typed), (
        f"partially-streamed request must fail TYPED after its "
        f"decode replica died, got {box_b}")
    results["failed_typed"] += 1
    # the client resubmits: the two-leg service now finds the decode
    # side dead and must fall back decode-in-place on the prefill
    # replica through the typed handoff-fallback ladder
    fb0 = pool.route_stats["disagg_handoff_fallbacks"]
    try:
        out = pool.submit(list(p_b), max_new_tokens=mnt_b).result()
    except typed as e:
        results["lost"] += 1
        out = None
        outcome_b = f"refused:{type(e).__name__}"
    if out is not None:
        if out == want[tuple(p_b)]:
            results["completed"] += 1
            outcome_b = "completed"
        else:
            results["mismatched"] += 1
            outcome_b = "mismatched"
    fallbacks_b = pool.route_stats["disagg_handoff_fallbacks"] - fb0
    assert outcome_b == "completed", (
        f"resubmitted stream did not re-prefill token-identically "
        f"after the decode replica died: {outcome_b}")
    assert fallbacks_b >= 1, (
        "resubmit against the dead decode side took no typed "
        "handoff fallback")
    obs.dump_flight_bundle(
        flight_dir, "disagg-decode-kill", engine=prefill_eng,
        pool=pool, extra={"phase": "decode_kill_post_handoff",
                          "killed_idx": 1, "prefill_idx": 0,
                          "streamed_before_kill": len(h._generated),
                          "outcome": outcome_b})
    pool.shutdown()
    for eng in engines_b:
        eng.shutdown()
    for eng in engines_b:
        check_quiesced(eng)
    phase_b = {
        "streamed_before_kill": len(h._generated),
        "resubmits": 1,
        "handoff_fallbacks": fallbacks_b,
        "completed_token_identical": outcome_b == "completed",
    }

    assert results["lost"] == 0, \
        f"disagg drill lost {results['lost']} admitted requests"
    assert results["mismatched"] == 0, (
        f"{results['mismatched']} disagg-drill completions diverged "
        f"from greedy")

    # ------------------------ the bundles on disk explain the drill
    pull_fb_seen, handoff_fb_seen = False, False
    for bdir in sorted(glob.glob(os.path.join(flight_dir, "*"))):
        if not os.path.isdir(bdir):
            continue
        try:
            b = obs.load_flight_bundle(bdir)
        except Exception:  # noqa: BLE001  half-written dir: skip
            continue
        eng_names = {e.get("type") for e in
                     (b.get("engine") or {}).get("events") or []}
        pool_names = {e.get("type") for e in
                      (b.get("pool") or {}).get("events") or []}
        if (b.get("reason") == "disagg-prefill-kill"
                and "pull_fallback" in eng_names):
            pull_fb_seen = True
        if (b.get("reason") == "disagg-decode-kill"
                and "handoff_fallback" in pool_names):
            handoff_fb_seen = True
    assert pull_fb_seen, (
        "no disagg-prefill-kill bundle carries a pull_fallback "
        "event: the prefill kill is not flight-explained")
    assert handoff_fb_seen, (
        "no disagg-decode-kill bundle carries a handoff_fallback "
        "event: the decode kill is not flight-explained")

    return {
        "prefill_kill_mid_handoff": phase_a,
        "decode_kill_post_handoff": phase_b,
        "requests": dict(results,
                         admitted=sum(results.values())),
        "flight": {
            "prefill_kill_explained": True,
            "decode_kill_explained": True,
        },
        "quiesced": True,
    }


def _run_rollout_phases(model, params, flight_dir, seed, kv_dtype):
    """Live weight-rollout fault drill: three seeded phases against a
    2-replica auto-restart pool under pooled traffic
    (serve/weight_rollout.py).

    A. replica killed MID-SWAP — the canary replica is paced and kept
       busy so the drain-mode flip PENDS, then killed with the swap
       pending. The controller's swap attempt fails typed, pooled
       traffic makes the corpse visible (death -> backoff rebuild),
       and the retry lands on the fresh incarnation: the rollout
       completes, the fleet converges on the new weights_id, and the
       successful transition records attempt >= 1 (the kill provably
       landed mid-swap).
    B. torn checkpoint — a published checkpoint gets one payload byte
       flipped; ``load_weights`` deep-verifies and refuses TYPED
       (InvalidCheckpointError) before any replica is touched.
    C. controller killed mid-rollout — one replica is pre-swapped to
       the next payload (the work a dead controller finished), then a
       FRESH controller rolls out the same payload: it resumes
       (skips the already-converged replica, never re-swaps it) and
       completes.

    The new payload is the SAME tensors republished under a release
    tag, so every traffic completion has ONE greedy answer across the
    swap — mixed-fleet serving is adjudicated token-identically
    throughout. Hard-asserts inside; returns the ``weight_rollout``
    artifact block."""
    import glob
    import shutil
    import tempfile

    import numpy as np

    from ray_tpu.air.checkpoint import InvalidCheckpointError
    from ray_tpu.serve import obs
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.errors import (DeadlineExceeded,
                                      EngineDraining,
                                      EngineOverloaded,
                                      EngineShutdown,
                                      RequestCancelled)
    from ray_tpu.serve.faults import FaultInjector, check_quiesced
    from ray_tpu.serve.weight_rollout import (WeightRolloutController,
                                              load_weights,
                                              publish_weights)

    typed = (RequestCancelled, DeadlineExceeded, EngineOverloaded,
             EngineDraining, EngineShutdown)
    rng = np.random.RandomState(seed * 13 + 409)

    def toks(n):
        return rng.randint(1, 250, size=n).tolist()

    traffic = [toks(24) for _ in range(3)]   # pooled client prompts
    busy_p = toks(32)                        # pins the canary's slot
    probe_p = toks(16)                       # controller parity probe
    pin = toks(12)                           # factory warmup prompt
    mnt = 8

    def mk_engine(inj=None):
        return LLMEngine(model, params, max_slots=2, page_size=8,
                         n_pages=48, chunk=2, temperature=0.0,
                         eos_id=-1, seed=0, prefix_cache=True,
                         kv_dtype=kv_dtype, fault_injector=inj,
                         flight_dir=flight_dir)

    # same-knobs reference engine: ONE right answer per prompt (the
    # republished payload is tensor-identical, so the references hold
    # across every generation the drill serves)
    ref = mk_engine()
    want = {}
    for p in traffic + [probe_p]:
        h = ref.submit(list(p), max_new_tokens=mnt)
        while ref.step():
            pass
        want[tuple(p)] = h.result()
    ref.shutdown()

    engines = []

    def factory(idx):
        eng = mk_engine(FaultInjector())
        engines.append(eng)
        eng.start()
        eng.submit(list(pin), max_new_tokens=4).result()
        eng.reset_latency_stats()
        return eng

    pool = EnginePool(factory, 2, auto_restart=True,
                      restart_backoff_s=0.05, seed=seed)
    results = {"completed": 0, "failed_typed": 0, "lost": 0,
               "mismatched": 0}

    def tick(n=1):
        """Pooled traffic: every admitted request must complete
        token-identically or fail typed — including the ticks that
        make the mid-swap corpse visible to the routing plane."""
        for i in range(n):
            p = traffic[rng.randint(0, len(traffic))]
            try:
                out = pool.submit(list(p),
                                  max_new_tokens=mnt).result()
            except typed:
                results["failed_typed"] += 1
                continue
            except BaseException:  # noqa: BLE001
                results["lost"] += 1
                continue
            if out == want[tuple(p)]:
                results["completed"] += 1
            else:
                results["mismatched"] += 1

    workdir = tempfile.mkdtemp(prefix="chaos_rollout_")
    try:
        # the new payload: SAME tensors, distinct release tag ->
        # distinct weights_id, token-identical outputs (round-tripped
        # through the sha256-verified checkpoint on purpose)
        v2_dir, wid2 = publish_weights(
            params, os.path.join(workdir, "v2"), step=2,
            extra={"release": "chaos-v2"})
        v2_params, wid2_rt = load_weights(v2_dir)
        assert wid2_rt == wid2

        # ------------------------- phase A: replica killed mid-swap
        tick(4)
        eng0 = pool.replica(0).engine
        # pace the canary's rounds and pin a slot so the drain-mode
        # flip PENDS instead of applying at the next idle boundary.
        # The busy request must still be decoding when the kill
        # lands: 48 tokens in 2-token rounds at 0.15 s a round hold
        # it open ~3.6 s (0.03 s lost the race on a loaded host).
        eng0._injector.slow("step", 0.15, times=2000)
        busy_box = {}

        def consume_busy():
            try:
                busy_box["tokens"] = eng0.submit(
                    list(busy_p), max_new_tokens=48).result()
            except BaseException as e:  # noqa: BLE001
                busy_box["error"] = e

        bt = threading.Thread(target=consume_busy, daemon=True)
        bt.start()
        deadline = time.monotonic() + 10.0
        while (not any(eng0.slots)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert any(eng0.slots), "busy request never took a slot"

        ctl = WeightRolloutController(
            pool, canary_fraction=0.5, probes=[(probe_p,
                                                want[tuple(probe_p)])],
            ttft_ratio_limit=None, swap_mode="drain",
            max_swap_attempts=4, rebuild_wait_s=20.0,
            flight_dir=flight_dir)
        roll_box = {}

        def run_rollout():
            try:
                roll_box["report"] = ctl.rollout(
                    v2_params, weights_id=wid2,
                    baseline_params=params,
                    baseline_weights_id="g0")
            except BaseException as e:  # noqa: BLE001
                roll_box["error"] = e

        rt = threading.Thread(target=run_rollout, daemon=True)
        rt.start()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if any(e[2] == "weight_swap_pending"
                   for e in eng0.events.snapshot()):
                break
            time.sleep(0.005)
        assert any(e[2] == "weight_swap_pending"
                   for e in eng0.events.snapshot()), \
            "drain-mode swap never pended on the busy canary"
        eng0._injector.kill_replica()     # fires at the next round
        deadline = time.monotonic() + 10.0
        while not eng0._stopped and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng0._stopped, "armed kill never fired mid-swap"
        bt.join(timeout=30.0)
        assert "error" in busy_box, \
            "the busy request survived its replica's death"
        # routed traffic is how an idle corpse becomes visible: tick
        # until the pool has noted the death and rebuilt the replica
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            tick(1)
            rep0 = pool.replica(0)
            if rep0.engine is not eng0 and rep0.state in ("healthy",
                                                          "suspect"):
                break
            time.sleep(0.05)
        rt.join(timeout=90.0)
        assert not rt.is_alive(), "rollout wedged after the kill"
        assert "error" not in roll_box, \
            f"rollout raised: {roll_box.get('error')!r}"
        report = roll_box["report"]
        assert report["status"] == "completed", (
            f"rollout did not complete past the mid-swap kill: "
            f"{report.get('rollback_reason', report['status'])}")
        tr0 = [t for t in report["transitions"] if t["idx"] == 0]
        assert tr0 and tr0[-1]["attempt"] >= 1, (
            f"canary swapped on the first attempt — the kill never "
            f"landed mid-swap (transitions {report['transitions']})")
        swap_attempts = tr0[-1]["attempt"] + 1
        fleet = ctl.fleet_weights()
        assert all(w == wid2 for _g, w in fleet.values()), \
            f"fleet did not converge on {wid2}: {fleet}"
        tick(4)
        kinds = [e[2] for e in pool.events.snapshot()]
        assert "weight_swap_failed" in kinds, \
            "the failed mid-swap attempt was never evented"
        assert "replica_death" in kinds and "rollout_done" in kinds
        obs.dump_flight_bundle(
            flight_dir, "rollout-kill-mid-swap", engine=eng0,
            pool=pool, extra={"phase": "kill_mid_swap",
                              "killed_idx": 0,
                              "swap_attempts": swap_attempts,
                              "weights_id": wid2})
        phase_a = {
            "completed": True,
            "converged": True,
            "swap_attempts": swap_attempts,
            "weights_id": wid2,
        }

        # ------------------------------ phase B: torn checkpoint
        fleet_before = ctl.fleet_weights()
        v3_dir, _wid3 = publish_weights(
            params, os.path.join(workdir, "v3"), step=3,
            extra={"release": "chaos-v3"})
        from ray_tpu.air.checkpoint import verify_checkpoint_dir
        ok, _reason, manifest = verify_checkpoint_dir(v3_dir)
        assert ok and manifest.get("files")
        victim = sorted(manifest["files"])[0]
        with open(os.path.join(v3_dir, victim), "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        torn_err = None
        try:
            load_weights(v3_dir)
        except InvalidCheckpointError as e:
            torn_err = e
        assert torn_err is not None, (
            "bit-flipped checkpoint was NOT refused — corrupt "
            "weights could reach a serving fleet")
        fleet_untouched = ctl.fleet_weights() == fleet_before
        assert fleet_untouched, "a refused checkpoint mutated weights"
        tick(2)
        phase_b = {
            "refused_typed": True,
            "fleet_untouched": True,
            "flipped_file": victim,
            "reason": str(torn_err),
        }

        # --------------------- phase C: controller death -> resume
        v4_dir, wid4 = publish_weights(
            params, os.path.join(workdir, "v4"), step=4,
            extra={"release": "chaos-v4"})
        v4_params, _ = load_weights(v4_dir)
        # the work a dead controller finished before dying: replica 0
        # already serves the new payload
        pool.swap_replica_weights(0, v4_params, weights_id=wid4,
                                  mode="preempt")
        ctl2 = WeightRolloutController(
            pool, canary_fraction=0.5,
            probes=[(probe_p, want[tuple(probe_p)])],
            ttft_ratio_limit=None, swap_mode="preempt",
            flight_dir=flight_dir)
        rpt2 = ctl2.rollout(v4_params, weights_id=wid4,
                            baseline_params=v2_params,
                            baseline_weights_id=wid2)
        assert rpt2["status"] == "completed", (
            f"resumed rollout did not complete: "
            f"{rpt2.get('rollback_reason', rpt2['status'])}")
        assert rpt2["resumed"] == [0], (
            f"resumed controller did not skip the already-swapped "
            f"replica: {rpt2['resumed']}")
        assert all(t["idx"] != 0 for t in rpt2["transitions"]), \
            "the resumed controller RE-swapped the converged replica"
        fleet = ctl2.fleet_weights()
        assert all(w == wid4 for _g, w in fleet.values()), \
            f"resumed rollout did not converge on {wid4}: {fleet}"
        tick(2)
        phase_c = {
            "completed": True,
            "converged": True,
            "resumed_replicas": len(rpt2["resumed"]),
            "weights_id": wid4,
        }

        assert results["lost"] == 0, (
            f"rollout drill lost {results['lost']} admitted "
            f"requests")
        assert results["mismatched"] == 0, (
            f"{results['mismatched']} rollout-drill completions "
            f"diverged from greedy across the swap")

        pool.shutdown()
        for eng in engines:
            eng.shutdown()
        for eng in engines:
            check_quiesced(eng)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    # ------------------------ the bundles on disk explain the drill
    kill_seen, done_seen = False, False
    for bdir in sorted(glob.glob(os.path.join(flight_dir, "*"))):
        if not os.path.isdir(bdir):
            continue
        try:
            b = obs.load_flight_bundle(bdir)
        except Exception:  # noqa: BLE001  half-written dir: skip
            continue
        eng_names = {e.get("type") for e in
                     (b.get("engine") or {}).get("events") or []}
        pool_names = {e.get("type") for e in
                      (b.get("pool") or {}).get("events") or []}
        if (b.get("reason") == "rollout-kill-mid-swap"
                and "weight_swap_pending" in eng_names
                and "weight_swap_failed" in pool_names):
            kill_seen = True
        if (b.get("reason") == "weight-rollout-done"
                and "rollout_done" in pool_names):
            done_seen = True
    assert kill_seen, (
        "no rollout-kill-mid-swap bundle carries the pending-swap/"
        "failed-attempt events: the kill is not flight-explained")
    assert done_seen, (
        "no weight-rollout-done bundle carries a rollout_done event: "
        "the completed rollout is not flight-explained")

    return {
        "kill_mid_swap": phase_a,
        "torn_checkpoint": phase_b,
        "controller_resume": phase_c,
        "requests": dict(results,
                         admitted=sum(results.values())),
        "flight": {
            "kill_mid_swap_explained": True,
            "rollout_done_explained": True,
        },
        "quiesced": True,
    }


def run_chaos(seed=47, replicas=3, duration_s=3.0, clients=3,
              max_new_tokens=10, stall_deadline_s=1.0,
              watchdog_poll_s=0.05, drain_timeout_s=2.0,
              attainment_floor=ATTAINMENT_FLOOR, flight_dir=None,
              kv_dtype=None):
    """One seeded serving chaos run. Returns the artifact dict after
    hard-asserting the availability contract (the schema checker
    re-refuses the same violations on the checked-in artifact).

    Every faulted replica leaves a flight-recorder bundle
    (serve/obs.py) in ``flight_dir`` (a fresh temp dir by default):
    a kill dumps from the dying engine's ``_fail_all``, the wedge
    dumps from the watchdog BEFORE the force-kill, and the campaign
    end dumps a pool-level postmortem. The run asserts the bundles
    EXPLAIN the injected faults — the kill bundle's event tail ends
    at the ReplicaKilled death, the wedge bundle shows the heartbeat
    gap that justified the escalation."""
    import glob
    import tempfile

    import jax.numpy as jnp

    from ray_tpu.autoscaler.node_provider import (
        ImmediateCapacityProvider)
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve import chaos
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.errors import (DeadlineExceeded,
                                      EngineDraining,
                                      EngineOverloaded,
                                      EngineShutdown,
                                      RequestCancelled,
                                      retry_after_s)
    from ray_tpu.serve import obs
    from ray_tpu.serve.faults import (FaultInjector,
                                      check_pool_quiesced,
                                      check_quiesced)
    from ray_tpu.serve.pool_autoscaler import (PoolAutoscaler,
                                               SLOPolicy)
    from ray_tpu.serve.watchdog import PoolWatchdog

    import jax
    if flight_dir is None:
        flight_dir = tempfile.mkdtemp(prefix="chaos-flight-")

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))

    # Prompt set + greedy ground truth (computed before the campaign;
    # fp32 greedy decode is replica-independent, so "token-identical
    # after resubmission" has one right answer). An int8 campaign
    # derives its references from a same-knobs reference ENGINE
    # instead — the quantized write history is what replicas
    # reproduce bit-for-bit, not the dense fp math.
    from ray_tpu.models.kv_cache import check_kv_dtype
    kv_dtype = check_kv_dtype(kv_dtype)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [10 + i, 20 + i] for i in range(8)]
    if kv_dtype == "int8":
        want = _reference_completions_int8(model, params, prompts,
                                           max_new_tokens)
    else:
        want = {tuple(p): _reference_completion(model, params, p,
                                                max_new_tokens)
                for p in prompts}

    # Every engine ever built — including corpses the pool replaced —
    # goes through the teardown + quiescence check at the end.
    all_engines = []

    def factory(idx):
        inj = FaultInjector()
        # eos_id=-1: eos-BOUNDED scheduling with an id that never
        # samples, so the campaign drives the overlapped
        # double-buffered hot loop (stale-frontier planning, trailing
        # drain) — the loop production engines run — while the greedy
        # references stay full-length
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=64, chunk=4, temperature=0.0,
                        seed=idx, prefix_cache=True, eos_id=-1,
                        admit_timeout_s=0.25,
                        fault_injector=inj,
                        flight_dir=flight_dir,
                        kv_dtype=kv_dtype)
        all_engines.append(eng)
        # Warm the jitted prefill/decode/prefix-copy paths BEFORE
        # the replica joins the pool (deployments do the same — see
        # reset_latency_stats): a cold engine's first dispatch holds
        # the scheduler lock through seconds of XLA compilation with
        # zero heartbeat movement, which a progress watchdog rightly
        # cannot tell apart from a wedge.
        eng.start()
        try:
            eng.submit(prompts[0], max_new_tokens=4).result()
            eng.submit(prompts[1], max_new_tokens=4).result()
        except EngineShutdown:
            # teardown raced a late auto-restart rebuild and stopped
            # this engine mid-warmup; hand it back un-warmed — the
            # pool it would join is stopping too
            pass
        eng.reset_latency_stats()
        return eng

    pool = EnginePool(factory, replicas, auto_restart=True,
                      restart_backoff_s=0.02, seed=seed)
    watchdog = PoolWatchdog(pool, stall_deadline_s=stall_deadline_s,
                            poll_interval_s=watchdog_poll_s,
                            flight_dir=flight_dir).run()
    provider = chaos.StockoutCapacityProvider(
        ImmediateCapacityProvider())
    policy = SLOPolicy(min_replicas=replicas,
                       max_replicas=replicas + 1,
                       cooldown_up_s=0.2, cooldown_down_s=60.0,
                       idle_stable_s=60.0,
                       drain_timeout_s=drain_timeout_s)
    autoscaler = PoolAutoscaler(pool, policy, provider).run(0.1)

    schedule = chaos.make_schedule(seed, duration_s)
    baseline_gen = {r.idx: r.generation for r in pool._replicas}
    injector = chaos.ChaosInjector(pool, schedule, seed=seed,
                                   provider=provider,
                                   drain_timeout_s=drain_timeout_s)

    # -------------------------------------------------- trace load
    results = {"completed": 0, "failed_typed": 0,
               "failed_injected": 0, "lost": 0,
               "mismatched": 0, "shed": 0}
    failures = []            # (type name, retry_after hint or None)
    res_lock = threading.Lock()
    stop_load = threading.Event()
    typed = (RequestCancelled, DeadlineExceeded, EngineOverloaded,
             EngineDraining, EngineShutdown)

    def client(ci):
        import random as _random
        rng = _random.Random(seed * 1000 + ci)
        while not stop_load.is_set():
            prompt = prompts[rng.randrange(len(prompts))]
            try:
                h = pool.submit(prompt,
                                max_new_tokens=max_new_tokens)
            except EngineOverloaded as e:
                with res_lock:
                    results["shed"] += 1
                    failures.append((type(e).__name__,
                                     retry_after_s(e, default=0.0)))
                time.sleep(0.05)
                continue
            except EngineShutdown as e:
                # pre-admission typed refusal (pool mid-teardown)
                with res_lock:
                    results["shed"] += 1
                    failures.append((type(e).__name__,
                                     retry_after_s(e, default=0.0)))
                time.sleep(0.05)
                continue
            # admitted: from here on, lost == contract violation
            try:
                toks = h.result()
            except typed as e:
                with res_lock:
                    results["failed_typed"] += 1
                    failures.append((type(e).__name__,
                                     retry_after_s(e, default=0.0)))
                continue
            except BaseException as e:  # noqa: BLE001
                with res_lock:
                    if "injected readback fault" in str(e):
                        # the contained fault's planned culprit —
                        # exactly one request per injection may land
                        # here (the campaign asserts the count)
                        results["failed_injected"] += 1
                    else:
                        results["lost"] += 1
                        failures.append((type(e).__name__, None))
                continue
            with res_lock:
                if toks == want[tuple(prompt)]:
                    results["completed"] += 1
                else:
                    results["mismatched"] += 1

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"chaos-client-{i}",
                                daemon=True)
               for i in range(clients)]
    t0 = time.time()
    for t in threads:
        t.start()
    injector.start()

    # Run until every event fired AND the wedge was detected (or a
    # hard wall). The wedge needs stall_deadline_s of silence after
    # the hang fires, so the campaign outlives the schedule.
    deadline = t0 + duration_s + stall_deadline_s + 30.0
    while time.time() < deadline:
        if all(e.fired for e in injector.schedule) \
                and watchdog.counts["wedged"] >= 1:
            break
        time.sleep(0.05)
    # let in-flight resubmissions settle on the survivors
    time.sleep(0.3)
    stop_load.set()
    for t in threads:
        t.join(timeout=30)

    # ---------------------------------------------------- teardown
    injector.stop()            # joins drains, releases current hangs
    # corpse engines replaced mid-run still own wedged threads:
    # release their hangs too, then give every zombie a beat to
    # unwind through the generation fence and exit
    for eng in all_engines:
        if eng._injector is not None:
            eng._injector.release_all()
    autoscaler.stop()
    watchdog.stop()
    pool.shutdown()
    for eng in all_engines:
        eng.shutdown()         # idempotent; completes the deferred
        #                        cleanup of force-killed corpses
    wall = time.time() - t0

    # --------------------------------------------------- invariants
    counts = injector.injected_counts()
    for kind in chaos.KINDS:
        assert counts[kind] >= 1, f"schedule never fired a {kind}"
    admitted = (results["completed"] + results["failed_typed"]
                + results["failed_injected"] + results["lost"]
                + results["mismatched"])
    assert admitted > 0, "campaign saw no admitted requests"
    assert results["failed_injected"] <= counts["readback"], (
        f"{results['failed_injected']} requests hit an injected "
        f"readback fault but only {counts['readback']} were planned "
        f"(containment leaked past the culprit)")
    assert results["lost"] == 0, (
        f"{results['lost']} admitted requests lost (untyped "
        f"failure); failure types seen: {[n for n, _ in failures]}")
    assert results["mismatched"] == 0, \
        f"{results['mismatched']} completions diverged from greedy"
    # sheds/refusals must carry an honest hint or none — never a lie;
    # EngineOverloaded specifically contracts a positive Retry-After
    for name, hint in failures:
        if name == "EngineOverloaded":
            assert hint and hint > 0, \
                "shed without a Retry-After hint"

    wd = watchdog.stats()
    assert wd["wedged"] >= 1, "injected hang was never detected"
    wedge_events = [e for e in watchdog.log if e["event"] == "wedged"]
    detect_age = max(e["heartbeat_age_s"] for e in wedge_events)
    # detected WITHIN the deadline: the stall age at detection is the
    # deadline plus at most a few poll intervals of scheduling noise
    # (generous slack for a loaded CPU box)
    assert detect_age >= stall_deadline_s * 0.9
    assert detect_age <= stall_deadline_s + 2.0, \
        f"wedge detected only after {detect_age:.2f}s stall"

    # untouched replicas were never restarted: generation moved only
    # where the campaign aimed a kill / hang / drain race
    touched = {e.target_idx for e in injector.schedule
               if e.kind in ("kill", "hang", "kill_during_drain")
               and e.target_idx is not None}
    with pool._lock:
        gen_moves = {r.idx: r.generation - baseline_gen.get(r.idx, 0)
                     for r in pool._replicas}
    for idx, moved in gen_moves.items():
        if idx not in touched and idx in baseline_gen:
            assert moved == 0, \
                f"healthy replica {idx} was restarted ({moved}x)"

    # leak-free quiescence: the pool AND every corpse engine
    check_pool_quiesced(pool)
    for eng in all_engines:
        check_quiesced(eng)

    attainment = results["completed"] / admitted
    assert attainment >= attainment_floor, \
        f"attainment {attainment:.3f} below floor {attainment_floor}"

    # --------------------------------------------- flight recorder
    # Kill bundles were dumped by the dying engines' _fail_all and
    # the wedge bundle by the watchdog BEFORE its force-kill; close
    # the campaign with a pool-level postmortem, then assert the
    # bundles on disk EXPLAIN each injected fault.
    obs.dump_flight_bundle(flight_dir, "campaign-end", pool=pool,
                           watchdog=watchdog,
                           extra={"injected": counts})
    bundles = []
    for bdir in sorted(glob.glob(os.path.join(flight_dir, "*"))):
        if not os.path.isdir(bdir):
            continue
        try:
            b = obs.load_flight_bundle(bdir)
        except Exception:  # noqa: BLE001  half-written dir: skip
            continue
        eng_b = b.get("engine") or {}
        evs = eng_b.get("events") or []
        last = evs[-1] if evs else {}
        bundles.append({
            "path": os.path.basename(bdir),
            "reason": b.get("reason"),
            "heartbeat_gap_s": eng_b.get("heartbeat_gap_s"),
            "n_events": len(evs),
            "last_event": last.get("type"),
            "last_error": (last.get("data") or {}).get("error")
            if isinstance(last.get("data"), dict) else None,
        })
    # kill explained: the dying engine's event tail ends at the
    # injected death, naming the fault that took it down
    kills = [b for b in bundles
             if b["reason"] == "engine-fail-all"
             and b["last_event"] == "fail_all"
             and "ReplicaKilled" in (b["last_error"] or "")]
    assert kills, (
        "no flight bundle explains the injected kill (want an "
        "engine-fail-all bundle whose last event is fail_all "
        f"carrying ReplicaKilled); saw: {bundles}")
    # hang explained: the watchdog's pre-kill bundle records the
    # heartbeat gap that justified the hang->death escalation
    wedges = [b for b in bundles
              if str(b["reason"]).startswith("wedged")
              and isinstance(b["heartbeat_gap_s"], (int, float))
              and b["heartbeat_gap_s"] >= stall_deadline_s * 0.9]
    assert wedges, (
        "no flight bundle explains the injected hang (want a "
        "wedged-r* bundle whose heartbeat_gap_s >= "
        f"{stall_deadline_s * 0.9:.2f}s); saw: {bundles}")

    # -------------------------------------- KV migration fault drill
    # Fresh 2-replica pools (share_prefixes=True): kill the donor
    # mid-pull (requester falls back to plain prefill, token-
    # identical), then kill a replica whose session resumes token-
    # identically on a peer from MIGRATED prefix pages. Hard-asserts
    # inside; the artifact records the proof.
    migration = _run_migration_phases(model, params, flight_dir,
                                      seed, kv_dtype,
                                      max_new_tokens=8)

    # ------------------------------- disaggregation fault drill
    # Fresh role-split pools (1 prefill + 1 decode over the handoff
    # path): kill the prefill replica mid-handoff (decode side aborts
    # the pull typed and prefills in place, token-identical), then
    # kill the decode replica post-handoff (partial stream fails
    # typed; the resubmit lands decode-in-place on the prefill
    # replica through the handoff-fallback ladder, token-identical).
    # Hard-asserts inside; the artifact records the proof.
    disagg = _run_disagg_phases(model, params, flight_dir, seed,
                                kv_dtype)

    # ------------------------------- live weight-rollout fault drill
    # Fresh 2-replica auto-restart pool under pooled traffic: the
    # canary replica is killed with a drain-mode swap PENDING (the
    # controller retries onto the rebuilt incarnation and the fleet
    # converges), a bit-flipped checkpoint is refused typed before
    # any replica is touched, and a fresh controller resumes a
    # half-done rollout without re-swapping the converged replica.
    # Hard-asserts inside; the artifact records the proof.
    rollout_drill = _run_rollout_phases(model, params, flight_dir,
                                        seed, kv_dtype)

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001
        sha = None

    pool_stats = pool.pool_stats()
    artifact = {
        "notes": (
            "Seeded chaos against a live multi-replica serving pool "
            "under trace load: replica kill, dispatch hang escalated "
            "hang->death by the watchdog, slow-but-moving step "
            "(false-positive control), contained readback fault, "
            "capacity stockout mid-autoscale, and a kill-during-"
            "drain race. Invariants checked: zero admitted requests "
            "lost (complete token-identically or fail typed with an "
            "honest Retry-After), wedge detected within the stall "
            "deadline without restarting untouched replicas, "
            "leak-free pool quiescence including zombie corpses, "
            "attainment above the recorded floor. A KV-migration "
            "fault drill follows the campaign: the donor replica is "
            "killed mid-pull (requester falls back to plain prefill "
            "and completes token-identically) and a replica is "
            "killed after its prefix migrated to a peer (the session "
            "resumes on the peer hitting the migrated pages, token-"
            "identically); both faults are flight-explained. A "
            "disaggregation fault drill follows: against role-split "
            "1-prefill + 1-decode pools, the prefill replica is "
            "killed mid-handoff (the decode side aborts the pull "
            "typed and prefills in place, token-identically) and the "
            "decode replica is killed post-handoff (the partial "
            "stream fails typed; the resubmit lands decode-in-place "
            "on the prefill replica through the typed handoff-"
            "fallback ladder, token-identically); both "
            "flight-explained. A live weight-rollout fault drill "
            "closes the campaign: against a 2-replica auto-restart "
            "pool under pooled traffic, the canary replica is killed "
            "with a drain-mode hot weight swap PENDING (the rollout "
            "controller retries onto the rebuilt replica and the "
            "fleet converges on the new weights_id), a bit-flipped "
            "checkpoint is refused typed before any replica is "
            "touched, and a fresh controller resumes a half-done "
            "rollout without re-swapping the converged replica — "
            "token-identical traffic throughout, kill and completion "
            "flight-explained."),
        "seed": seed,
        "mesh": {"tp": 1, "replicas": replicas},
        "knobs": {
            "duration_s": duration_s, "clients": clients,
            "max_new_tokens": max_new_tokens,
            "stall_deadline_s": stall_deadline_s,
            "suspect_after_s": watchdog.suspect_after_s,
            "watchdog_poll_s": watchdog_poll_s,
            "drain_timeout_s": drain_timeout_s,
            # the replica engines ran the overlapped double-buffered
            # hot loop in eos-bounded mode (factory: eos_id=-1)
            "overlap": all(getattr(e, "overlap", False)
                           for e in all_engines),
            "eos_bounded": True,
            # int8 campaigns adjudicate against a same-knobs
            # reference ENGINE (quantized write history is replica-
            # deterministic), fp against dense greedy decode
            "kv_dtype": kv_dtype,
        },
        "schedule": [e.as_dict() for e in injector.schedule],
        "injected": counts,
        "requests": dict(results, admitted=admitted),
        "attainment": round(attainment, 4),
        "attainment_floor": attainment_floor,
        "wedge": {
            "detected": True,
            "detect_stall_age_s": round(detect_age, 4),
            "within_deadline": True,
        },
        "watchdog": wd,
        "counters": {
            "pool": {k: v for k, v in pool_stats.items()
                     if k not in ("watchdog", "autoscale")},
            "suspects_total": pool_stats.get("suspects", 0),
            "wedged_total": pool_stats.get("wedged", 0),
            "autoscaler": autoscaler.stats(),
            "provider_denied": provider.denied,
        },
        "flight_recorder": {
            "dir": flight_dir,
            "bundles": len(bundles),
            "reasons": sorted({str(b["reason"]) for b in bundles}),
            "kill_explained": True,
            "hang_explained": True,
            "summaries": bundles,
        },
        "kv_migration": migration,
        "disagg": disagg,
        "weight_rollout": rollout_drill,
        "quiesced": True,
        "wall_s": round(wall, 2),
        "git_sha": sha,
    }
    return artifact


def _spawn_fleet_proc(module_args, env, repo):
    return subprocess.Popen(
        [sys.executable, "-m"] + module_args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        env=env, cwd=repo)


def _wait_ready(proc, tag, timeout_s=180.0):
    """Block until the subprocess prints ``READY <port>``; raises if
    it exits or stalls first."""
    import select
    deadline = time.time() + timeout_s
    buf = []
    while time.time() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.2)
        if not r:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{tag} exited rc={proc.returncode} before "
                    f"READY; output: {''.join(buf[-20:])!r}")
            continue
        line = proc.stdout.readline().decode(errors="replace")
        if not line:
            raise RuntimeError(
                f"{tag} closed stdout before READY; output: "
                f"{''.join(buf[-20:])!r}")
        buf.append(line)
        if line.startswith("READY "):
            port = int(line.split()[1])
            # keep draining stdout so the child can never block on a
            # full pipe mid-campaign
            t = threading.Thread(
                target=lambda: [None for _ in iter(
                    lambda: proc.stdout.readline(), b"")],
                name=f"drain-{tag}", daemon=True)
            t.start()
            return port
    raise RuntimeError(f"{tag} not READY after {timeout_s}s")


def run_fleet_chaos(seed=47, agents=3, duration_s=4.0, clients=3,
                    max_new_tokens=10, lease_ttl_s=1.0,
                    partition_s=None, model="tiny",
                    token_delay_s=0.004,
                    attainment_floor=ATTAINMENT_FLOOR,
                    promote_after_s=None,
                    flight_dir=None):
    """Cross-process fleet chaos: the PR-5/9 availability contract
    re-proven with replicas as real OS processes behind the
    DURABLE + REPLICATED fleet control plane (serve/fleet/).

    Topology: a WAL-backed primary FleetDirectory streaming deltas to
    a hot-standby subprocess, ``agents`` ReplicaAgent subprocesses
    (each wrapping its own engine) holding the ordered endpoint list,
    trace load through a FleetRouter over the socket transport, and a
    supervisor restarting killed agents under bumped generations.
    The seeded ``FLEET_KINDS`` schedule fires: agent SIGKILL, two-way
    partition (self-fence on lease lapse), current-primary SIGKILL +
    same-port/same-data-dir restart (membership recovers from the
    WAL, not re-advertisement), PERMANENT primary kill (the standby
    must promote with the epoch bump folded into the fence counter;
    a post-failover canary must complete token-identically), a torn
    WAL tail injected between crash and restart (detected, truncated,
    never replayed), and autoscaler-driven churn (a
    FleetCapacityProvider spawns a real agent mid-campaign, the
    router harvests then drains + retires it under load).

    Gates: zero admitted requests lost, zero token mismatches,
    fencing tokens provably monotonic across failover (from the
    surviving directory's event log), every injected fault explained
    by a flight bundle, live agents quiesce leak-free at exit."""
    import glob
    import tempfile

    from ray_tpu.serve import chaos, obs
    from ray_tpu.serve.errors import (DeadlineExceeded,
                                      EngineDraining,
                                      EngineOverloaded,
                                      EngineShutdown,
                                      RequestCancelled,
                                      retry_after_s)
    from ray_tpu.serve.fleet.agent import (AgentClient,
                                           scripted_completion)
    from ray_tpu.serve.fleet.directory import DirectoryClient
    from ray_tpu.serve.fleet.router import FleetRouter
    from ray_tpu.serve.fleet.transport import (SocketTransport,
                                               TransportError)
    from ray_tpu.serve.fleet import wire

    if partition_s is None:
        partition_s = 2.5 * lease_ttl_s
    assert partition_s > lease_ttl_s, \
        "partition must outlive the lease or the victim never fences"
    if flight_dir is None:
        flight_dir = tempfile.mkdtemp(prefix="fleet-chaos-flight-")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    from ray_tpu.serve.fleet.provider import require_cpu_backend
    require_cpu_backend(env)

    # ground truth: one correct completion per prompt
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [10 + i, 20 + i] for i in range(8)]
    if model == "tiny":
        import jax
        import jax.numpy as jnp
        from ray_tpu.models.llama import Llama, llama_tiny
        cfg = llama_tiny(dtype=jnp.float32)
        ref_model = Llama(cfg)
        ref_params = ref_model.init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))
        want = {tuple(p): _reference_completion(
            ref_model, ref_params, p, max_new_tokens)
            for p in prompts}
    else:
        want = {tuple(p): scripted_completion(p, max_new_tokens)
                for p in prompts}

    # ------------------------------------------------- process fleet
    state_lock = threading.Lock()
    stop_all = threading.Event()
    procs = {}           # rid -> {"proc", "port", "generation"}
    spawned = []         # every Popen ever (teardown + pid stamp)
    killed = []          # {"rid", "member", "port", "t"}
    partitions = []      # {"rid", "port", "t", ...probe results}
    dir_restarts = []    # current-primary crash/restart (WAL proof)
    torn_restarts = []   # torn-tail crash/restart (truncation proof)
    churns = []          # autoscale_churn lifecycle records
    failover = {}        # the (single) permanent primary kill

    import socket as _socket

    def _free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    if promote_after_s is None:
        # must outlive a directory RESTART gap (READY in ~1s), or a
        # routine crash/recover would trigger a spurious failover
        promote_after_s = max(3.0, 3.0 * lease_ttl_s)
    dport, sport = _free_port(), _free_port()
    dirs = {
        "d1": {"port": dport,
               "data_dir": tempfile.mkdtemp(prefix="fleet-d1-"),
               "flags": ["--standby", f"127.0.0.1:{sport}"]},
        "d2": {"port": sport,
               "data_dir": tempfile.mkdtemp(prefix="fleet-d2-"),
               "flags": ["--role", "standby",
                         "--peer", f"127.0.0.1:{dport}",
                         "--promote-after-s",
                         str(promote_after_s)]},
    }
    endpoints = [f"127.0.0.1:{dport}", f"127.0.0.1:{sport}"]

    def start_directory(name):
        rec = dirs[name]
        p = _spawn_fleet_proc(
            ["ray_tpu.serve.fleet.directory",
             "--port", str(rec["port"]),
             "--lease-ttl-s", str(lease_ttl_s),
             "--data-dir", rec["data_dir"]] + rec["flags"],
            env, repo)
        spawned.append(p)
        _wait_ready(p, f"directory-{name}")
        rec["proc"] = p
        return p

    # standby FIRST: its monitor promotes only after seeing the
    # primary alive at least once, so boot order can't steal a throne
    start_directory("d2")
    start_directory("d1")

    def dir_client(name, timeout_s=2.0):
        return DirectoryClient(SocketTransport(
            ("127.0.0.1", dirs[name]["port"])), timeout_s)

    def current_primary():
        """Which directory process currently adjudicates (None
        mid-failover)."""
        for name in ("d1", "d2"):
            if dirs[name]["proc"].poll() is not None:
                continue
            try:
                if dir_client(name).ping()["role"] == "primary":
                    return name
            except Exception:   # noqa: BLE001
                continue
        return None

    def spawn_agent(rid, generation):
        cmd = ["ray_tpu.serve.fleet.agent", "--replica-id", rid,
               "--generation", str(generation),
               "--model", model, "--flight-dir", flight_dir]
        for ep in endpoints:
            cmd += ["--directory", ep]
        if model == "fake":
            cmd += ["--token-delay-s", str(token_delay_s)]
        p = _spawn_fleet_proc(cmd, env, repo)
        spawned.append(p)
        return p

    def start_agent(rid, generation):
        p = spawn_agent(rid, generation)
        port = _wait_ready(p, rid)
        with state_lock:
            procs[rid] = {"proc": p, "port": port,
                          "generation": generation}

    # boot the initial fleet in parallel (a tiny-model agent warms
    # its jitted paths before READY, which takes tens of seconds)
    boot = [(f"r{i}", spawn_agent(f"r{i}", 0))
            for i in range(agents)]
    for rid, p in boot:
        port = _wait_ready(p, rid)
        with state_lock:
            procs[rid] = {"proc": p, "port": port, "generation": 0}

    sup_errors = collections.deque(maxlen=32)

    def supervisor():
        """Restart SIGKILLed agents under a bumped generation (the
        fleet-manager role; the tombstoned old generation can never
        re-join)."""
        while not stop_all.is_set():
            with state_lock:
                dead = [(rid, info) for rid, info in procs.items()
                        if info["proc"].poll() is not None]
            for rid, info in dead:
                # the dead incarnation may have bumped its own
                # generation (self-fence -> rejoin) far past what we
                # spawned it with, and the tombstone burns everything
                # at or below it — ask the directory, don't guess
                gen = info["generation"] + 1
                try:
                    tomb = dc.stats()["tombstones"].get(rid)
                    if tomb is not None:
                        gen = max(gen, int(tomb) + 1)
                except Exception:   # noqa: BLE001
                    pass
                try:
                    start_agent(rid, gen)
                except Exception as e:   # noqa: BLE001 directory may
                    sup_errors.append(      # be mid-restart: retry
                        f"{rid} gen{gen}: "
                        f"{type(e).__name__}: {e}")
                    time.sleep(0.1)
            stop_all.wait(0.05)

    sup = threading.Thread(target=supervisor, name="fleet-supervisor",
                           daemon=True)
    sup.start()

    from ray_tpu.serve.fleet.replication import (
        FailoverDirectoryClient)
    dc = FailoverDirectoryClient(
        [SocketTransport(("127.0.0.1", dport)),
         SocketTransport(("127.0.0.1", sport))])
    router = FleetRouter(
        dc, lambda addr: SocketTransport((addr[1], addr[2])),
        seed=seed, snapshot_ttl_s=0.05, call_timeout_s=2.0,
        poll_interval_s=0.004, flight_dir=flight_dir)

    # cluster flight recorder: the telemetry collector scrapes every
    # role over the same transports the router routes on, aligns the
    # per-process event streams onto the router clock, and cuts ONE
    # cluster-wide bundle per fault (confirmed death via the router
    # hook; self-fence / promote / recover via the scraped streams)
    from ray_tpu.serve.fleet.telemetry import TelemetryCollector
    cluster_dir = os.path.join(flight_dir, "cluster")
    collector = TelemetryCollector(
        router, events_per_scrape=512, cluster_dir=cluster_dir,
        offset_bound_s=0.25).attach().run(interval_s=0.25)

    def router_member(rid):
        try:
            return router._snapshot().get(rid)
        except Exception:   # noqa: BLE001
            return None

    # --------------------------------------------------- fault ops
    reserved = set()     # rids already targeted by kill/partition
    canaries = []        # {"kind", "rid", "handle", "prompt"}

    def _pick_victim(kind, tries=25):
        """Plant one un-consumed canary request through the router
        and make WHEREVER it landed the fault's victim (skipping
        already-targeted or last-alive agents). With zero tokens
        delivered the canary MUST come back token-identically from
        another agent via the resubmit path — the at-most-once
        proof, planted deterministically on every victim."""
        for _ in range(tries):
            with state_lock:
                alive = sorted(
                    rid for rid, info in procs.items()
                    if info["proc"].poll() is None)
            eligible = [r for r in alive if r not in reserved]
            if len(alive) < 2 or not eligible:
                return None
            prompt = prompts[len(canaries) % len(prompts)]
            try:
                h = router.submit(prompt,
                                  max_new_tokens=max_new_tokens,
                                  trace_id=f"canary-{kind}")
            except Exception:   # noqa: BLE001 shed under load
                time.sleep(0.02)
                continue
            rid = h.replica_idx
            if rid in eligible:
                canaries.append({"kind": kind, "rid": rid,
                                 "incarnation": h.replica_tag,
                                 "handle": h, "prompt": prompt})
                return rid
            h.cancel()
            time.sleep(0.01)
        return None

    def op_kill(ev, rng):
        rid = _pick_victim("kill_agent")
        if rid is None:
            return None          # retry next tick
        mem = router_member(rid) or canaries[-1]["handle"]._member
        with state_lock:
            info = procs[rid]
        reserved.add(rid)
        info["proc"].kill()
        killed.append({"rid": rid, "member": mem,
                       "port": info["port"],
                       "generation": info["generation"]})
        return rid

    def _probe_fence(rec):
        """Hammer the partitioned agent with admission attempts
        through heal: while it is FENCED (lease lapsed, not yet
        re-registered) it must answer ``AgentFenced``."""
        client = AgentClient(
            SocketTransport(("127.0.0.1", rec["port"]),
                            connect_timeout_s=0.25),
            timeout_s=0.25)
        deadline = time.time() + partition_s + 3 * lease_ttl_s
        n = 0
        while time.time() < deadline:
            n += 1
            try:
                r = client.submit(f"fence-probe-{rec['rid']}-{n}",
                                  prompts[0], 1, fence=None)
                # admitted: the agent re-registered (gen bump) before
                # a probe landed in the FENCED window
                try:
                    client.cancel(r["rid"])
                except Exception:   # noqa: BLE001
                    pass
                rec["probe"] = "readmitted"
                return
            except wire.AgentFenced:
                rec["probe"] = "refused_fenced"
                rec["probe_attempts"] = n
                return
            except Exception:   # noqa: BLE001 partitioned/typed:
                time.sleep(0.005)   # keep probing
        rec["probe"] = "timeout"

    def op_partition(ev, rng):
        rid = _pick_victim("partition")
        if rid is None:
            return None
        with state_lock:
            info = procs[rid]
        try:
            AgentClient(SocketTransport(
                ("127.0.0.1", info["port"]))).inject_partition(
                    ev.duration_s)
        except Exception:   # noqa: BLE001 raced a concurrent fault
            canaries.pop()["handle"].cancel()   # withdraw: its
            return None      # victim was never actually faulted
        reserved.add(rid)
        rec = {"rid": rid, "port": info["port"],
               "generation_before": info["generation"],
               "probe": "pending"}
        partitions.append(rec)
        threading.Thread(target=_probe_fence, args=(rec,),
                         name=f"fence-probe-{rid}",
                         daemon=True).start()
        return rid

    def op_directory_restart(ev, rng):
        """Crash + same-port/same-data-dir restart of the CURRENT
        primary: membership must recover from the WAL — immediately,
        with no agent re-advertisement round."""
        name = current_primary()
        if name is None:
            return None          # mid-failover: retry next tick
        rec = dirs[name]
        rec["proc"].kill()
        rec["proc"].wait(timeout=10)
        t_down = time.time()
        start_directory(name)
        gap_s = time.time() - t_down
        with state_lock:
            expect = {rid for rid, info in procs.items()
                      if info["proc"].poll() is None}
        cl = dir_client(name)
        stats_after = cl.stats()
        got = {m["replica_id"]
               for m in cl.snapshot()["members"]}
        row = {
            "directory": name,
            "gap_s": round(gap_s, 3),
            # counted by _recover() in the NEW process, before any
            # agent could have re-registered
            "recovered_members":
                stats_after["counters"]["recovered_members"],
            "recovered_from_wal": expect <= got,
            "expected_members": sorted(expect),
            "members_at_probe": sorted(got),
            "registers_at_probe":
                stats_after["counters"]["registers"],
            "wal": stats_after.get("wal"),
        }
        dir_restarts.append(row)
        obs.dump_flight_bundle(
            flight_dir, "directory-restart", pool=router,
            extra=dict(row, directory_stats=stats_after))
        # the fresh process's "recover" event lives only in its
        # in-memory log, and the NEXT fault op may kill this process
        # before the periodic scrape lands — checkpoint the cluster
        # recorder while the op still holds it alive
        try:
            collector.scrape_once()
        except Exception:   # noqa: BLE001
            pass
        return name

    def op_torn_wal_restart(ev, rng):
        """Crash the current primary, append a TORN half-record to
        its WAL (crash-mid-write), restart: the tail must be detected
        and truncated — never replayed — and membership must still
        recover."""
        from ray_tpu.serve.fleet.wal import inject_torn_tail
        name = current_primary()
        if name is None:
            return None
        rec = dirs[name]
        try:
            fence_before = dir_client(name).stats()["fence_counter"]
        except Exception:   # noqa: BLE001
            fence_before = None
        rec["proc"].kill()
        rec["proc"].wait(timeout=10)
        inject_torn_tail(rec["data_dir"])
        t_down = time.time()
        start_directory(name)
        cl = dir_client(name)
        stats_after = cl.stats()
        row = {
            "directory": name,
            "gap_s": round(time.time() - t_down, 3),
            "torn_records_truncated":
                stats_after["counters"]["wal_torn_truncated"],
            "recovered_members":
                stats_after["counters"]["recovered_members"],
            "members_at_probe": sorted(
                m["replica_id"]
                for m in cl.snapshot()["members"]),
            "fence_before_crash": fence_before,
            "fence_after_recovery": stats_after["fence_counter"],
            "wal": stats_after.get("wal"),
        }
        torn_restarts.append(row)
        obs.dump_flight_bundle(
            flight_dir, "torn-wal-restart", pool=router,
            extra=dict(row, directory_stats=stats_after))
        # same as op_directory_restart: the torn-WAL "recover" event
        # (carrying torn_truncated >= 1) dies with this process if a
        # later primary_kill lands before the periodic scrape does
        try:
            collector.scrape_once()
        except Exception:   # noqa: BLE001
            pass
        return name

    def op_primary_kill(ev, rng):
        """PERMANENT primary death: nothing restarts d1. The standby
        must promote itself (epoch bump folded into the fence
        counter) and a post-failover canary must complete
        token-identically through the promoted directory."""
        if failover:
            return "noop-already-failed-over"
        if current_primary() != "d1":
            return "noop-already-failed-over"
        try:
            failover["fence_high_water_before"] = \
                dir_client("d1").stats()["fence_counter"]
        except Exception:   # noqa: BLE001
            failover["fence_high_water_before"] = None
        dirs["d1"]["proc"].kill()
        dirs["d1"]["proc"].wait(timeout=10)
        t_kill = time.time()
        deadline = t_kill + promote_after_s + 60.0
        promoted = False
        while time.time() < deadline:
            try:
                if dir_client("d2").ping()["role"] == "primary":
                    promoted = True
                    break
            except Exception:   # noqa: BLE001
                pass
            time.sleep(0.05)
        failover["promoted"] = promoted
        failover["promoted_in_s"] = round(time.time() - t_kill, 3)
        if promoted:
            st = dir_client("d2").stats()
            failover["epoch_after"] = st["epoch"]
            failover["fence_counter_after"] = st["fence_counter"]
            # post-failover canary: a FRESH request routed and
            # adjudicated entirely by the promoted directory. Right
            # after promotion the whole fleet may still be
            # self-fenced (leases lapsed while no primary answered
            # renews) — typed sheds here are correct behavior, so
            # retry until the agents re-register under the new
            # primary
            prompt = prompts[0]
            canary_deadline = time.time() + 60.0
            tries = 0
            while True:
                tries += 1
                try:
                    h = router.submit(
                        prompt, max_new_tokens=max_new_tokens,
                        trace_id="canary-post-failover")
                    toks = h.result()
                    failover["canary"] = {
                        "token_identical":
                            toks == want[tuple(prompt)],
                        "served_by": h.replica_tag,
                        "resubmits": h.resubmits,
                        "tries": tries}
                    break
                except Exception as e:   # noqa: BLE001
                    failover["canary"] = {
                        "token_identical": False,
                        "error": type(e).__name__,
                        "tries": tries}
                    if time.time() > canary_deadline:
                        break
                    time.sleep(0.1)
            # stash the promoted log NOW: a later crash/restart op
            # hitting d2 wipes its in-memory events (only durable
            # state rides the WAL)
            try:
                failover["d2_events"] = \
                    dir_client("d2").events()["events"]
            except Exception:   # noqa: BLE001
                failover["d2_events"] = []
        obs.dump_flight_bundle(
            flight_dir, "primary-failover", pool=router,
            extra=dict(failover))
        # capture the promoted standby's "promote" event before a
        # later restart op wipes its in-memory log
        try:
            collector.scrape_once()
        except Exception:   # noqa: BLE001
            pass
        return "d1"

    # ------------------------------------------- autoscaler churn
    from ray_tpu.serve.fleet.provider import FleetCapacityProvider
    provider = FleetCapacityProvider(
        endpoints, model=model, token_delay_s=token_delay_s,
        rid_prefix="churn", spawn_timeout_s=240.0, env=env)
    churn_threads = []

    def op_autoscale_churn(ev, rng):
        """The autoscaler's lifecycle, driven end-to-end: provider
        ticket -> real agent process (spawn -> register -> warm) ->
        router harvest -> serve under load -> health-gated drain +
        lease retirement + tombstone -> process reap. Churn agents
        are provider-owned, NOT in ``procs``, so the supervisor never
        resurrects a deliberately retired one."""
        ticket = provider.request()
        row = {"ticket": ticket, "state": "provisioning",
               "t_request": round(time.time() - t0, 3)}
        churns.append(row)

        def _lifecycle():
            t_spawn = time.time()
            ready = False
            while time.time() < t_spawn + 240.0 \
                    and not stop_all.is_set():
                try:
                    ready = provider.ready(ticket)
                except Exception as e:   # noqa: BLE001
                    row["state"] = \
                        f"spawn-failed:{type(e).__name__}"
                    return
                if ready:
                    break
                time.sleep(0.1)
            if not ready:
                row["state"] = "never-ready"
                return
            row["ready_in_s"] = round(time.time() - t_spawn, 3)
            row["eta_hint_s"] = round(provider.eta_s(ticket), 3)
            idx = router.add_replica_for_ticket(ticket)
            row["added_idx"] = idx
            row["state"] = "serving"
            # let it take real traffic before retiring it
            time.sleep(max(2.0 * lease_ttl_s, 1.0))
            # the drain may race a failover window in which the
            # agent is self-fenced (lease lapsed -> not routable):
            # keep retrying until it rejoins and drains cleanly
            retired = []
            retire_deadline = time.time() + 90.0
            while (not retired
                   and time.time() < retire_deadline
                   and not stop_all.is_set()):
                retired = router.scale_down(1, rids=[ticket])
                if not retired:
                    time.sleep(0.2)
            row["retired_idxs"] = retired
            provider.release(ticket)
            chk_deadline = time.time() + 30.0
            while time.time() < chk_deadline:
                try:
                    snap = dc.snapshot()
                    row["absent_after_retire"] = ticket not in {
                        m["replica_id"] for m in snap["members"]}
                    row["tombstoned"] = ticket in dc.stats()[
                        "tombstones"]
                    if (row.get("absent_after_retire")
                            and row.get("tombstoned")):
                        break
                except Exception:   # noqa: BLE001
                    pass
                time.sleep(0.2)
            row["state"] = "retired"

        th = threading.Thread(target=_lifecycle,
                              name=f"churn-{ticket}", daemon=True)
        churn_threads.append(th)
        th.start()
        return ticket

    schedule = chaos.make_fleet_schedule(seed, duration_s,
                                         partition_s=partition_s)
    injector = chaos.FleetChaosInjector(
        schedule, {"kill_agent": op_kill, "partition": op_partition,
                   "directory_restart": op_directory_restart,
                   "primary_kill": op_primary_kill,
                   "torn_wal_restart": op_torn_wal_restart,
                   "autoscale_churn": op_autoscale_churn},
        seed=seed)

    # -------------------------------------------------- trace load
    results = {"completed": 0, "failed_typed": 0, "lost": 0,
               "mismatched": 0, "shed": 0}
    failures = []
    resubmitted_ok = [0]     # completions that survived >=1 resubmit
    res_lock = threading.Lock()
    stop_load = threading.Event()
    typed = (RequestCancelled, DeadlineExceeded, EngineOverloaded,
             EngineDraining, EngineShutdown)

    def client(ci):
        import random as _random
        rng = _random.Random(seed * 1000 + ci)
        n = 0
        while not stop_load.is_set():
            n += 1
            prompt = prompts[rng.randrange(len(prompts))]
            trace = f"fleet-c{ci}-{n}"
            try:
                h = router.submit(prompt,
                                  max_new_tokens=max_new_tokens,
                                  trace_id=trace)
            except (EngineOverloaded, EngineShutdown) as e:
                with res_lock:
                    results["shed"] += 1
                    failures.append((type(e).__name__,
                                     retry_after_s(e, default=0.0)))
                time.sleep(0.05)
                continue
            try:
                toks = h.result()
            except typed as e:
                with res_lock:
                    results["failed_typed"] += 1
                    failures.append((type(e).__name__,
                                     retry_after_s(e, default=0.0)))
                continue
            except BaseException as e:   # noqa: BLE001
                with res_lock:
                    results["lost"] += 1
                    failures.append((type(e).__name__, None))
                continue
            with res_lock:
                if toks == want[tuple(prompt)]:
                    results["completed"] += 1
                    if h.resubmits:
                        resubmitted_ok[0] += 1
                else:
                    results["mismatched"] += 1

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"fleet-client-{i}",
                                daemon=True)
               for i in range(clients)]
    t0 = time.time()
    for t in threads:
        t.start()
    injector.start()

    # run until the whole schedule fired, then let partitions heal
    # and resubmissions settle on the survivors
    deadline = t0 + duration_s + partition_s + 60.0
    while time.time() < deadline and not injector.done():
        time.sleep(0.05)
    settle = t0 + duration_s + partition_s + 60.0
    while time.time() < settle:
        done_probes = all(p["probe"] != "pending"
                          for p in partitions)
        if injector.done() and done_probes:
            break
        time.sleep(0.05)
    time.sleep(2 * lease_ttl_s)   # fenced victims re-register
    # consume the canaries: each was in flight on a victim with zero
    # tokens delivered, so each must complete token-identically from
    # ANOTHER agent through the suspect -> directory-confirmed-dead
    # -> resubmit path (the at-most-once proof, per injected fault)
    for c in canaries:
        h = c["handle"]
        try:
            toks = h.result()
        except BaseException as e:   # noqa: BLE001
            c["outcome"] = f"failed:{type(e).__name__}"
            with res_lock:
                results["failed_typed"] += 1
            continue
        c["outcome"] = ("completed" if toks == want[tuple(c["prompt"])]
                        else "mismatched")
        c["resubmits"] = h.resubmits
        c["served_by"] = h.replica_tag
        with res_lock:
            if c["outcome"] == "completed":
                results["completed"] += 1
                if h.resubmits:
                    resubmitted_ok[0] += 1
            else:
                results["mismatched"] += 1
    # autoscale churn settles before load stops: the retired agent
    # must have drained while clients were still hammering the fleet
    for th in churn_threads:
        th.join(timeout=300)
    stop_load.set()
    for t in threads:
        t.join(timeout=60)
    injector.stop()

    # ------------------------------------------- post-hoc adjudication
    # every SIGKILLed incarnation must end directory-confirmed dead
    # with a router flight bundle explaining it; in the (unlikely)
    # case no client request ever touched the corpse, drive the same
    # suspect path the clients would have
    for k in killed:
        router._confirm_dead(
            k["member"],
            TransportError(f"harness probe: {k['rid']} was "
                           f"SIGKILLed by the campaign"))

    # ------------------------------------------------------- evidence
    wall = time.time() - t0
    counts = injector.injected_counts()
    for kind in chaos.FLEET_KINDS:
        assert counts.get(kind, 0) >= 1, \
            f"schedule never fired a {kind}"
    admitted = (results["completed"] + results["failed_typed"]
                + results["lost"] + results["mismatched"])
    assert admitted > 0, "campaign saw no admitted requests"
    assert results["lost"] == 0, (
        f"{results['lost']} admitted requests lost (untyped); "
        f"failure types: {[n for n, _ in failures]}")
    assert results["mismatched"] == 0, \
        f"{results['mismatched']} completions diverged from reference"
    for name, hint in failures:
        if name == "EngineOverloaded":
            assert hint and hint > 0, \
                "shed without a Retry-After hint"

    # the fleet recovered: every replica id serves again (a killed
    # tiny-model agent's replacement may still be warming its jitted
    # paths — give the supervisor time to finish the respawn)
    rec_deadline = time.time() + 180.0
    while time.time() < rec_deadline:
        with state_lock:
            live = {rid: info for rid, info in procs.items()
                    if info["proc"].poll() is None}
        if len(live) == agents:
            break
        time.sleep(0.2)
    assert len(live) == agents, (
        f"only {sorted(live)} of {agents} agents alive at exit; "
        f"supervisor errors: {list(sup_errors)}")

    agent_stats = {}
    for rid, info in sorted(live.items()):
        agent_stats[rid] = AgentClient(SocketTransport(
            ("127.0.0.1", info["port"]))).stats()

    # partition explained: the victim self-fenced IN ITS OWN PROCESS
    # (its lease lapsed while unreachable) and either refused an
    # admission probe while fenced or provably cycled through the
    # fenced state into a bumped generation
    for p in partitions:
        st = agent_stats.get(p["rid"])
        assert st is not None, f"partition victim {p['rid']} gone"
        assert st["counters"]["self_fences"] >= 1, (
            f"partitioned {p['rid']} never self-fenced: "
            f"{st['counters']}")
        gen_after = st["generation"]
        p["generation_after"] = gen_after
        assert (p["probe"] == "refused_fenced"
                or gen_after > p["generation_before"]), (
            f"no proof {p['rid']} refused admissions while fenced: "
            f"probe={p['probe']} gen {p['generation_before']} -> "
            f"{gen_after}")

    # quiesced at exit: no stuck requests on any live agent
    for rid, info in sorted(live.items()):
        q = AgentClient(SocketTransport(
            ("127.0.0.1", info["port"]))).quiesce()
        assert q.get("ok"), f"{rid} failed quiescence: {q}"

    # the planted canaries: in flight on a victim at fault time with
    # zero tokens delivered -> resubmitted token-identically (exactly
    # once unless a second fault also took the resubmit target)
    assert canaries, "no canary landed on any victim"
    for c in canaries:
        assert c["outcome"] == "completed", (
            f"canary on {c['kind']} victim {c['rid']} ended "
            f"{c['outcome']} (want token-identical completion via "
            f"resubmit)")
        assert c["resubmits"] >= 1, (
            f"canary on {c['kind']} victim {c['rid']} completed "
            f"without a resubmit (fault landed after completion?)")
        assert c["served_by"] != c["incarnation"], (
            f"canary resubmit landed back on the faulted incarnation "
            f"{c['served_by']}")
    assert resubmitted_ok[0] >= 1

    attainment = results["completed"] / admitted
    assert attainment >= attainment_floor, \
        f"attainment {attainment:.3f} below floor {attainment_floor}"

    # --------------------------------------------- flight recorder
    obs.dump_flight_bundle(
        flight_dir, "fleet-campaign-end", pool=router,
        extra={"injected": counts, "agent_stats": agent_stats})
    bundles = []
    for bdir in sorted(glob.glob(os.path.join(flight_dir, "*"))):
        if not os.path.isdir(bdir):
            continue
        try:
            b = obs.load_flight_bundle(bdir)
        except Exception:   # noqa: BLE001 half-written: skip
            continue
        bundles.append({
            "path": os.path.basename(bdir),
            "reason": b.get("reason"),
            "pid": b.get("pid"),
            "extra": b.get("extra"),
        })
    reasons = [str(b["reason"]) for b in bundles]
    for k in killed:
        assert f"agent-dead-{k['rid']}" in reasons, (
            f"no flight bundle explains the SIGKILL of {k['rid']}; "
            f"reasons on disk: {sorted(set(reasons))}")
    for p in partitions:
        fb = [b for b in bundles
              if b["reason"] == f"self-fenced-{p['rid']}"
              and (b["extra"] or {}).get("lease_overdue_s", -1) >= 0]
        assert fb, (
            f"no self-fence bundle from partitioned {p['rid']}; "
            f"reasons on disk: {sorted(set(reasons))}")
        # dumped by the agent's own process, not the harness
        assert fb[-1]["pid"] != os.getpid()
    for d in dir_restarts:
        assert d["recovered_from_wal"], (
            f"membership did not recover from the WAL after the "
            f"directory restart: {d}")
        assert d["recovered_members"] >= 1, (
            f"restarted directory recovered an empty table: {d}")
        assert "directory-restart" in reasons
    # torn WAL tail: detected, truncated, never replayed — and the
    # rest of the log still recovered membership
    assert torn_restarts, "schedule never fired a torn_wal_restart"
    for d in torn_restarts:
        assert d["torn_records_truncated"] >= 1, (
            f"torn WAL tail was not detected/truncated: {d}")
        assert d["recovered_members"] >= 1, (
            f"torn-tail recovery lost the whole table: {d}")
        assert (d["fence_before_crash"] is None
                or d["fence_after_recovery"]
                >= d["fence_before_crash"]), (
            f"fence counter regressed across torn-WAL recovery: {d}")
        assert "torn-wal-restart" in reasons
    # permanent primary loss: the standby promoted and adjudicated a
    # fresh token-identical canary
    assert failover.get("promoted"), (
        f"standby never promoted after the permanent primary kill: "
        f"{failover}")
    assert failover["canary"].get("token_identical"), (
        f"post-failover canary did not complete token-identically: "
        f"{failover['canary']}")
    assert "primary-failover" in reasons
    # fencing tokens are MONOTONIC across the failover, proven from
    # the promoted directory's own event log: every fence it saw
    # replicated, then the promote bump, then every fence it issued.
    # Prefer the live log (it has post-failover issuances too) but
    # fall back to the log stashed at promotion time — a later
    # crash/restart op on d2 wipes in-memory events.
    d2_events = failover.get("d2_events") or []
    try:
        live = dir_client("d2").events()["events"]
        if any(e["kind"] == "promote" for e in live):
            d2_events = live
    except Exception:   # noqa: BLE001
        pass
    promote_evs = [e for e in d2_events if e["kind"] == "promote"]
    assert promote_evs, "promoted directory logged no promote event"
    pi = d2_events.index(promote_evs[0])
    pre = [e["fence"] for e in d2_events[:pi]
           if e["kind"] in ("repl_member", "fence_issued")]
    post = [e["fence"] for e in d2_events[pi + 1:]
            if e["kind"] == "fence_issued"]
    bump = promote_evs[0]
    assert bump["fence_after"] > bump["fence_before"], bump
    assert bump["fence_after"] > max(pre, default=0), (
        f"promotion bump {bump} does not clear the replicated "
        f"high-water {max(pre, default=0)}")
    hw = failover.get("fence_high_water_before")
    if hw is not None:
        assert bump["fence_after"] > hw, (
            f"promotion bump {bump} does not clear the dead "
            f"primary's high-water {hw}")
    assert all(b > a for a, b in zip(post, post[1:])), (
        f"post-failover issued fences not strictly increasing: "
        f"{post}")
    assert all(f > bump["fence_before"] for f in post), (
        f"a post-failover fence fell below the pre-promotion "
        f"counter: {post} vs {bump}")
    # force one more issuance through the promoted directory so the
    # proof never rests on vacuous emptiness
    fr = dc.register("fence-canary", ["loopback", "fence-canary"],
                     0, page_size=0, min_fence=0)
    assert fr["fence"] > bump["fence_before"]
    assert fr["fence"] >= bump["fence_after"]
    if hw is not None:
        assert fr["fence"] > hw
    dc.deregister("fence-canary", fr["fence"])
    fence_monotonic = True
    # autoscaler churn: every provisioned agent served, then drained
    # + retired durably (tombstoned, absent from membership)
    assert churns, "schedule never fired an autoscale_churn"
    for c in churns:
        assert c["state"] == "retired", (
            f"churn agent never completed its lifecycle: {c}")
        assert c.get("absent_after_retire"), (
            f"retired churn agent still in membership: {c}")
        assert c.get("tombstoned"), (
            f"retired churn agent left no tombstone: {c}")
    assert provider.live_count() == 0, (
        f"provider leaked {provider.live_count()} agent processes")
    # the router bridged the directory outages from its stale cache
    assert router.counters["stale_snapshots"] >= 1, (
        "router never served from a stale snapshot during the "
        "directory outage")

    # ---------------------------------- cluster flight recorder
    # beyond the per-process bundles above, each injected fault must
    # be explained by ONE cluster bundle: merged offset-corrected
    # event stream + clock-offset table from every reachable role
    collector.stop()
    try:
        collector.scrape_once()   # drain events logged since the
    except Exception:             # noqa: BLE001 last periodic tick
        pass
    cbundles = list(collector.bundles)
    creasons = [str(b["reason"]) for b in cbundles]
    for k in killed:
        assert f"agent-dead-{k['rid']}" in creasons, (
            f"no cluster bundle explains the SIGKILL of "
            f"{k['rid']}; cluster reasons on disk: "
            f"{sorted(set(creasons))}")
    for p in partitions:
        assert f"self_fence-{p['rid']}" in creasons, (
            f"no cluster bundle explains the partition self-fence "
            f"of {p['rid']}; cluster reasons on disk: "
            f"{sorted(set(creasons))}")
    recover_cb = [b for b in cbundles
                  if str(b["reason"]).startswith("recover-")]
    assert recover_cb, (
        f"no cluster bundle explains any directory recovery; "
        f"cluster reasons on disk: {sorted(set(creasons))}")
    # torn-tail recovery is distinguishable in the trigger itself:
    # the restarted primary's recover event counts truncated records
    assert any(((b.get("trigger") or {}).get("data") or {})
               .get("torn_truncated", 0) >= 1 for b in recover_cb), (
        "no cluster bundle carries a recover trigger with a "
        "truncated torn WAL tail")
    assert any(r.startswith("promote-") for r in creasons), (
        f"no cluster bundle explains the standby promotion; "
        f"cluster reasons on disk: {sorted(set(creasons))}")
    # every bundle must round-trip from disk: manifest + offset
    # table + merged events (torn tails tolerated, never replayed)
    from ray_tpu.serve.fleet.telemetry import load_cluster_bundle
    for b in cbundles:
        cb = load_cluster_bundle(b["path"])
        assert cb["reason"] == b["reason"]
        assert cb["members"], f"bundle {b['path']} has no members"
    collector_health = collector.health()

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=10
        ).stdout.strip() or None
    except Exception:   # noqa: BLE001
        sha = None

    dirs_spawned = 2 + len(dir_restarts) + len(torn_restarts)
    artifact = {
        "schema_version": 2,
        "notes": (
            "Seeded cross-process fleet chaos over a DURABLE, "
            "REPLICATED control plane: replica agents as real OS "
            "processes behind a primary+standby directory pair, "
            "under trace load through the socket transport. Faults: "
            "agent SIGKILL (directory-confirmed death, "
            "token-identical resubmit), two-way network partition "
            "(victim self-fences on lease lapse, refuses admission, "
            "rejoins under a bumped generation), directory SIGKILL + "
            "same-port restart (membership recovers from the "
            "WAL/snapshot, not re-advertisement), torn-WAL-tail "
            "crash (tail truncated, never replayed, fence counter "
            "non-regressing), PERMANENT primary kill (standby "
            "promotes with an epoch-folded fence bump; clients fail "
            "over; fencing provably monotonic), and autoscaler "
            "churn (provider-spawned agent serves, then drains + "
            "retires tombstoned, mid-campaign). Gates: zero "
            "admitted requests lost, zero token mismatches, every "
            "fault explained by a flight bundle, live agents "
            "quiesce leak-free."),
        "seed": seed,
        "topology": {
            "agents": agents,
            "transport": "tcp-json-v1",
            "directories": ["primary", "standby"],
            "processes": {
                "directories_spawned": dirs_spawned,
                "agents_spawned": len(spawned) - dirs_spawned,
                "churn_agents_spawned": provider.stats["spawned"],
            },
            "model": model,
            "lease_ttl_s": lease_ttl_s,
            "promote_after_s": promote_after_s,
        },
        "knobs": {
            "duration_s": duration_s, "clients": clients,
            "max_new_tokens": max_new_tokens,
            "partition_s": partition_s,
            "token_delay_s": (token_delay_s if model == "fake"
                              else None),
        },
        "schedule": [e.as_dict() for e in injector.schedule],
        "injected": counts,
        "requests": dict(results, admitted=admitted,
                         resubmitted_ok=resubmitted_ok[0]),
        "attainment": round(attainment, 4),
        "attainment_floor": attainment_floor,
        "fleet": {
            "router": router.pool_stats(),
            "directory": dc.stats(),
            "agents": {
                rid: {"generation": st["generation"],
                      "counters": st["counters"]}
                for rid, st in agent_stats.items()},
            "kills": [{k2: v for k2, v in k.items()
                       if k2 != "member"} for k in killed],
            "partitions": partitions,
            "canaries": [{k2: v for k2, v in c.items()
                          if k2 not in ("handle", "prompt")}
                         for c in canaries],
        },
        "wal_recovery": {
            "directory_restarts": dir_restarts,
            "torn_wal_restarts": torn_restarts,
        },
        "failover": {k2: v for k2, v in failover.items()
                     if k2 != "d2_events"},
        "fence_monotonic": fence_monotonic,
        "autoscale_churn": {
            "churns": churns,
            "provider": provider.stats,
        },
        "flight_recorder": {
            "dir": flight_dir,
            "bundles": len(bundles),
            "reasons": sorted(set(reasons)),
            "kill_explained": True,
            "partition_explained": True,
            "directory_restart_explained": True,
            "torn_wal_explained": True,
            "failover_explained": True,
            "faults_explained": True,
        },
        "cluster_flight_recorder": {
            "dir": cluster_dir,
            "bundles": len(cbundles),
            "reasons": sorted(set(creasons)),
            "collector": collector_health,
            "kill_explained": True,
            "partition_explained": True,
            "recover_explained": True,
            "torn_wal_explained": True,
            "failover_explained": True,
            "faults_explained": True,
        },
        "quiesced": True,
        "wall_s": round(wall, 2),
        "git_sha": sha,
    }

    # ------------------------------------------------------ teardown
    stop_all.set()
    sup.join(timeout=30)
    router.shutdown()
    provider.stop_all()
    for p in spawned:
        if p.poll() is None:
            p.kill()
    for p in spawned:
        try:
            p.wait(timeout=10)
        except Exception:   # noqa: BLE001
            pass
    return artifact


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--stall-deadline", type=float, default=1.0)
    ap.add_argument("--fleet", action="store_true",
                    help="cross-process campaign: replicas as real "
                         "OS processes behind the fleet control "
                         "plane (serve/fleet/)")
    ap.add_argument("--model", choices=("tiny", "fake"),
                    default="tiny",
                    help="--fleet only: tiny = real llama_tiny "
                         "engines, fake = deterministic scripted "
                         "engines (fast smoke)")
    ap.add_argument("--lease-ttl", type=float, default=1.0)
    ap.add_argument("--kv-dtype", default=None,
                    choices=("fp", "int8"),
                    help="replica KV pool dtype (int8 = quantized "
                         "pages; references switch to a same-knobs "
                         "reference engine). In-process campaign "
                         "only; --fleet agents stay fp")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.fleet:
        artifact = run_fleet_chaos(
            seed=args.seed, agents=args.replicas,
            duration_s=args.duration, clients=args.clients,
            lease_ttl_s=args.lease_ttl, model=args.model)
    else:
        artifact = run_chaos(
            seed=args.seed, replicas=args.replicas,
            duration_s=args.duration, clients=args.clients,
            stall_deadline_s=args.stall_deadline,
            kv_dtype=args.kv_dtype)
    print(json.dumps(artifact, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        # Self-gate: the artifact must pass its own schema family.
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(args.out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        if problems:
            sys.exit(1)


if __name__ == "__main__":
    main()
