"""Live weight rollout tests (serve/weight_rollout.py + the engine/
pool fence hooks).

Three layers: the per-engine generation fence (swap under traffic is
token-identical, monotonic, cache-invalidating), the checkpoint
publish/load edge (torn payloads refused typed before any replica is
touched), and the fleet controller (canary -> advance -> done, parity-
probe rollback, resume-after-controller-death, rebuild re-stamping).
"""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.air import InvalidCheckpointError
from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.engine_pool import HEALTHY, EnginePool
from ray_tpu.serve.weight_rollout import (WeightRolloutController,
                                          load_weights, publish_weights)


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so greedy decode is bit-identical across replicas and
    # across a same-tensor weight swap (the parity proofs below)
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _reference_completion(model, params, prompt, n):
    from ray_tpu.models.llama import generate
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _slow_rounds(sleep_s=0.05):
    """Hold every scheduling round open (the chaos harness's delay
    hook): a tiny model otherwise finishes a whole request before a
    swap or shutdown issued "mid-request" can land."""
    from ray_tpu.serve.faults import FaultInjector
    inj = FaultInjector()
    inj.slow("step", sleep_s, times=10 ** 9)
    return inj


def _engine(model, params, **kw):
    args = dict(max_slots=2, page_size=8, n_pages=64, chunk=4,
                temperature=0.0, seed=0, prefix_cache=True)
    args.update(kw)
    eng = LLMEngine(model, params, **args)
    eng.start()
    return eng


def _perturb(params):
    return jax.tree_util.tree_map(lambda x: x + 0.25, params)


# ------------------------------------------------- engine-level fence


def test_preempt_swap_is_token_identical_and_fenced(tiny_model):
    """A preempt-mode swap mid-request: the straddling request
    resubmits through the replica-death path and still produces the
    reference completion (the swap installs the SAME tensors under a
    new id, so token identity is provable); the fence advances; the
    prefix cache is invalidated."""
    model, params = tiny_model
    eng = _engine(model, params, fault_injector=_slow_rounds())
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        want = _reference_completion(model, params, prompt, 24)
        # warm the prefix cache so invalidation is observable
        assert eng.submit(list(prompt), max_new_tokens=4).result() \
            == want[:4]
        assert eng.prefix_cache.cached_pages > 0
        h = eng.submit(list(prompt), max_new_tokens=24)
        # consume two tokens so the request provably OCCUPIES a slot
        # when the flip lands — the swap preempts it mid-decode
        it = h.stream()
        got = [next(it), next(it)]
        gen = eng.swap_weights(params, weights_id="same-bytes-v2")
        assert gen == 1
        assert eng.weight_generation == 1
        assert eng.weights_id == "same-bytes-v2"
        got.extend(it)
        assert got == want, \
            "request straddling a same-tensor swap must stay " \
            "token-identical"
        assert eng.stats["weight_swaps"] == 1
        rpt = eng.load_report()
        assert rpt["weight_generation"] == 1
        assert rpt["weights_id"] == "same-bytes-v2"
        swaps = [e for e in eng.events.snapshot()
                 if e[2] == "weight_swap"]
        assert swaps, "the flip must be evented"
        # the warmed old-weight KV was evicted AT the flip (pages
        # cached afterwards were computed under the new payload)
        assert swaps[0][5]["prefix_pages_evicted"] >= 1
        assert swaps[0][5]["preempted"] >= 1
    finally:
        eng.shutdown()


def test_fence_is_strictly_monotonic(tiny_model):
    model, params = tiny_model
    eng = _engine(model, params)
    try:
        assert eng.swap_weights(params, weights_id="a") == 1
        with pytest.raises(ValueError):
            eng.swap_weights(params, generation=1, weights_id="b")
        with pytest.raises(ValueError):
            eng.swap_weights(params, generation=0, weights_id="b")
        # rollback shape: OLD payload under a NEW generation
        assert eng.swap_weights(params, weights_id="a") == 2
        assert eng.weights_id == "a"
    finally:
        eng.shutdown()


def test_drain_mode_swap_waits_for_idle(tiny_model):
    """Drain mode: the flip waits for the engine to settle between
    rounds — the in-flight request finishes ON OLD WEIGHTS, then the
    swap applies."""
    model, params = tiny_model
    eng = _engine(model, params)
    try:
        prompt = [5, 3, 8, 13, 2]
        want = _reference_completion(model, params, prompt, 10)
        h = eng.submit(list(prompt), max_new_tokens=10)
        done = {}

        def swapper():
            done["gen"] = eng.swap_weights(
                params, weights_id="v2", mode="drain", timeout_s=60)

        t = threading.Thread(target=swapper, daemon=True)
        t.start()
        assert h.result() == want
        t.join(60)
        assert done.get("gen") == 1
        assert eng.weights_id == "v2"
        kinds = [e[2] for e in eng.events.snapshot()]
        assert "weight_swap_pending" in kinds and "weight_swap" in kinds
    finally:
        eng.shutdown()


def test_engine_handle_weights_tag(tiny_model):
    model, params = tiny_model
    eng = _engine(model, params)
    try:
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        h.result()
        assert h.weights_tag == "0:g0"
        eng.swap_weights(params, weights_id="abc")
        h2 = eng.submit([1, 2, 3], max_new_tokens=2)
        h2.result()
        assert h2.weights_tag == "1:abc"
    finally:
        eng.shutdown()


def test_shutdown_releases_pending_drain_swap(tiny_model):
    """An engine stopped with a drain swap pending must fail the
    waiter typed, not hang it."""
    from ray_tpu.serve.errors import EngineShutdown
    model, params = tiny_model
    eng = _engine(model, params, fault_injector=_slow_rounds(0.25))
    prompt = [7, 7, 7, 7]
    eng.submit(list(prompt), max_new_tokens=64, deadline_s=30)
    # the request must HOLD A SLOT before the swap is asked for: a drain
    # swap pauses admission, so one that lands while the request still
    # queues finds the engine settled, applies at once and the waiter
    # returns (one run in a few failed so, also before PR 42)
    deadline = time.monotonic() + 10
    while (all(s is None for s in eng.slots)
           and time.monotonic() < deadline):
        time.sleep(0.002)
    assert any(s is not None for s in eng.slots)
    err = {}

    def swapper():
        try:
            eng.swap_weights(params, weights_id="v2", mode="drain",
                             timeout_s=60)
        except BaseException as e:  # noqa: BLE001
            err["e"] = e

    t = threading.Thread(target=swapper, daemon=True)
    t.start()
    # the swap must be PENDING behind the still-decoding request when
    # the engine stops (16 rounds of >= 50 ms keep it decoding)
    deadline = time.monotonic() + 10
    while eng._pending_swap is None and time.monotonic() < deadline:
        time.sleep(0.002)
    assert eng._pending_swap is not None
    eng.shutdown()
    t.join(30)
    assert isinstance(err.get("e"), EngineShutdown)


# --------------------------------------------- checkpoint publish/load


def test_publish_load_roundtrip_and_payload_identity(tmp_path,
                                                     tiny_model):
    model, params = tiny_model
    p1, wid1 = publish_weights(params, str(tmp_path / "v1"), step=1)
    p2, wid2 = publish_weights(params, str(tmp_path / "v2"), step=2,
                               extra={"release": "v2"})
    assert wid1 != wid2, \
        "metadata must distinguish byte-identical tensor payloads"
    loaded, wid = load_weights(p1)
    assert wid == wid1, "weights_id derives from the manifest alone"
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(loaded)[0]),
        np.asarray(jax.tree_util.tree_leaves(params)[0]))


def test_torn_checkpoint_refused_typed(tmp_path, tiny_model):
    """A bit-flipped payload deep-fails its manifest hash and is
    refused InvalidCheckpointError before any replica is touched."""
    from ray_tpu.air.checkpoint import load_manifest
    model, params = tiny_model
    path, _wid = publish_weights(params, str(tmp_path / "bad"))
    rel = sorted(load_manifest(path)["files"])[0]
    full = os.path.join(path, rel)
    with open(full, "r+b") as f:
        first = f.read(1)
        f.seek(0)
        f.write(bytes([first[0] ^ 0xFF]))
    with pytest.raises(InvalidCheckpointError):
        load_weights(path)


def test_checkpoint_without_params_refused(tmp_path):
    from ray_tpu.air import Checkpoint
    out = Checkpoint.from_dict({"note": "no tensors"}).to_directory(
        str(tmp_path / "empty"))
    with pytest.raises(InvalidCheckpointError):
        load_weights(out)


# ------------------------------------------------ fleet controller


def _pool(model, params, n=3):
    return EnginePool(
        lambda i: LLMEngine(model, params, max_slots=2, page_size=8,
                            n_pages=64, chunk=4, temperature=0.0,
                            seed=i, prefix_cache=True),
        n)


def test_rollout_completes_and_serves_token_identically(
        tmp_path, tiny_model):
    model, params = tiny_model
    pool = _pool(model, params)
    try:
        _p2, wid2 = publish_weights(params, str(tmp_path / "v2"),
                                    extra={"release": "v2"})
        prompt = [2, 7, 1, 8, 2, 8]
        want = _reference_completion(model, params, prompt, 8)
        ctl = WeightRolloutController(
            pool, canary_fraction=0.3,      # ceil(0.9) = 1 canary of 3
            probes=[(prompt, want[:4])],
            flight_dir=str(tmp_path / "flight"))
        report = ctl.rollout(params, weights_id=wid2,
                             baseline_params=params,
                             baseline_weights_id="g0")
        assert report["status"] == "completed"
        assert report["generation"] >= 1
        assert len(report["canary"]) == 1
        assert sorted(sum(report["waves"], report["canary"])) \
            == [0, 1, 2]
        assert {wid for _g, wid in ctl.fleet_weights().values()} \
            == {wid2}
        # generation transitions are monotonic per replica
        seen = {}
        for tr in report["transitions"]:
            assert tr["to"] > tr["from"]
            assert tr["to"] > seen.get(tr["idx"], -1)
            seen[tr["idx"]] = tr["to"]
        # traffic after the rollout is still token-identical
        assert pool.submit(list(prompt),
                           max_new_tokens=8).result() == want
        assert pool.route_stats["weight_swaps"] == 3
        agg = pool.load_report()
        assert agg["weight_generation"] >= 1
        assert agg["weights_mixed"] is False
        # completion is flight-explained
        bundles = os.listdir(str(tmp_path / "flight"))
        assert any("weight-rollout-done" in b for b in bundles)
    finally:
        pool.shutdown()


def test_canary_parity_failure_auto_rolls_back(tmp_path, tiny_model):
    """An injected regression (perturbed tensors) fails the canary's
    output-parity probe; the controller rolls the fleet back onto the
    baseline payload and flight-explains the decision."""
    model, params = tiny_model
    pool = _pool(model, params)
    try:
        prompt = [3, 1, 4, 1, 5]
        want = _reference_completion(model, params, prompt, 6)
        bad = _perturb(params)
        flight = str(tmp_path / "flight")
        ctl = WeightRolloutController(
            pool, canary_fraction=0.34,
            probes=[(prompt, want)], flight_dir=flight)
        report = ctl.rollout(bad, weights_id="bad-widXXXX",
                             baseline_params=params,
                             baseline_weights_id="g0")
        assert report["status"] == "rolled_back"
        assert "parity" in report["rollback_reason"]
        assert report["probe_failures"]
        rb = report["rollback"]
        assert rb["converged"] is True
        assert rb["failed_replicas"] == []
        assert {wid for _g, wid in ctl.fleet_weights().values()} \
            == {"g0"}
        # the canary's fence still advanced (rollback = old payload
        # under a NEW generation; the fence never retreats)
        canary_idx = report["canary"][0]
        assert pool.replica(canary_idx).engine.weight_generation == 2
        # untouched replicas never swapped
        assert pool.route_stats["weight_rollbacks"] == 1
        # post-rollback traffic is token-identical to baseline
        assert pool.submit(list(prompt),
                           max_new_tokens=6).result() == want
        bundles = os.listdir(flight)
        assert any("weight-rollback" in b for b in bundles)
    finally:
        pool.shutdown()


def test_rollout_resumes_after_controller_death(tmp_path, tiny_model):
    """Controller killed mid-rollout: per-replica weights_id is the
    durable state. A fresh rollout() skips already-converged replicas
    and converges the rest."""
    model, params = tiny_model
    pool = _pool(model, params)
    try:
        _p2, wid2 = publish_weights(params, str(tmp_path / "v2"),
                                    extra={"release": "v2"})
        # the "dead" controller got exactly one replica swapped
        pool.swap_replica_weights(0, params, weights_id=wid2)
        ctl = WeightRolloutController(pool, canary_fraction=0.34,
                                      flight_dir=str(tmp_path / "f"))
        report = ctl.rollout(params, weights_id=wid2,
                             baseline_params=params,
                             baseline_weights_id="g0")
        assert report["status"] == "completed"
        assert report["resumed"] == [0]
        assert 0 not in sum(report["waves"], report["canary"]), \
            "already-converged replicas must not re-swap"
        assert {wid for _g, wid in ctl.fleet_weights().values()} \
            == {wid2}
    finally:
        pool.shutdown()


def test_rebuilt_and_added_replicas_are_restamped(tmp_path,
                                                  tiny_model):
    """The kill-mid-swap hole: a replica rebuilt (or added) AFTER a
    completed rollout must rejoin on the fleet's current payload, not
    the engine factory's generation-0 weights."""
    model, params = tiny_model
    pool = _pool(model, params, n=2)
    try:
        _p2, wid2 = publish_weights(params, str(tmp_path / "v2"),
                                    extra={"release": "v2"})
        ctl = WeightRolloutController(pool, canary_fraction=0.5)
        assert ctl.rollout(params, weights_id=wid2,
                           baseline_params=params,
                           baseline_weights_id="g0"
                           )["status"] == "completed"
        # rebuild path (drain -> factory -> restamp)
        assert pool.drain(0)
        rep = pool.replica(0)
        assert rep.state == HEALTHY and rep.generation == 1
        assert rep.engine.weights_id == wid2
        assert rep.engine.weight_generation >= 1
        # scale-up path
        idx = pool.add_replica()
        assert pool.replica(idx).engine.weights_id == wid2
        kinds = [e[2] for e in pool.events.snapshot()]
        assert "weight_restamp" in kinds
    finally:
        pool.shutdown()


def test_swap_refused_on_dead_replica(tiny_model):
    model, params = tiny_model
    pool = _pool(model, params, n=2)
    try:
        pool.replica(1).state = "dead"
        with pytest.raises(RuntimeError):
            pool.swap_replica_weights(1, params, weights_id="x")
        pool.replica(1).state = HEALTHY
    finally:
        pool.shutdown()


def test_pull_hint_respects_weight_fence(tiny_model):
    """Cross-replica fence half: a donor serving a DIFFERENT payload
    must never be picked as a KV-pull source — its pages were
    computed under weights the target does not run."""
    model, params = tiny_model
    pool = _pool(model, params, n=2)
    try:
        from ray_tpu.serve.prefix_cache import path_hashes
        prompt = [9, 8, 7, 6, 5, 4, 3, 2] * 4
        # replica 1 caches the prefix
        pool.replica(1).engine.submit(
            list(prompt), max_new_tokens=2).result()
        reports = {i: pool.replica(i).engine.load_report()
                   for i in (0, 1)}
        chain = path_hashes(prompt, pool.replica(0).engine.Pg)
        assert any(h in reports[1]["prefix_digest"] for h in chain)
        hint = pool._pull_hint(list(prompt), pool.replica(0), reports)
        assert hint is not None, "same payload: pull is offered"
        # now replica 1 is mid-rollout on a different payload
        pool.swap_replica_weights(1, params, weights_id="other")
        pool.replica(1).engine.submit(
            list(prompt), max_new_tokens=2).result()
        reports = {i: pool.replica(i).engine.load_report()
                   for i in (0, 1)}
        hint = pool._pull_hint(list(prompt), pool.replica(0), reports)
        assert hint is None, \
            "cross-payload KV pull must be fenced off"
    finally:
        pool.shutdown()


def test_pool_handle_weights_tag(tiny_model):
    model, params = tiny_model
    pool = _pool(model, params, n=1)
    try:
        h = pool.submit([1, 2, 3], max_new_tokens=2)
        h.result()
        assert h.weights_tag == "0:g0"
        pool.swap_replica_weights(0, params, weights_id="w2")
        h2 = pool.submit([1, 2, 3], max_new_tokens=2)
        h2.result()
        assert h2.weights_tag == "1:w2"
    finally:
        pool.shutdown()
