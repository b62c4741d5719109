"""Core task/object API tests (reference analogues:
python/ray/tests/test_basic.py, test_advanced.py)."""
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import (GetTimeoutError, TaskCancelledError,
                                TaskError)


def test_put_get(rt):
    ref = rt.put({"a": 1})
    assert rt.get(ref) == {"a": 1}


def test_put_objectref_rejected(rt):
    ref = rt.put(1)
    with pytest.raises(TypeError):
        rt.put(ref)


def test_simple_task(rt):
    @rt.remote
    def add(a, b):
        return a + b

    assert rt.get(add.remote(1, 2)) == 3


def test_task_with_kwargs_and_options(rt):
    @rt.remote(num_cpus=0.5)
    def f(a, b=10):
        return a * b

    assert rt.get(f.remote(3)) == 30
    assert rt.get(f.options(name="named").remote(2, b=4)) == 8


def test_task_dependency_chain(rt):
    @rt.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(9):
        ref = inc.remote(ref)
    assert rt.get(ref) == 10


def test_nested_tasks_no_deadlock(rt):
    # More nesting depth than CPU capacity: blocked parents must release
    # their resources (reference: worker leasing prevents this deadlock).
    @rt.remote(num_cpus=1)
    def fib(n):
        if n < 2:
            return n
        return sum(rt.get([fib.remote(n - 1), fib.remote(n - 2)]))

    assert rt.get(fib.remote(10)) == 55


def test_multiple_returns(rt):
    @rt.remote(num_returns=3)
    def three():
        return 1, 2, 3

    r1, r2, r3 = three.remote()
    assert rt.get([r1, r2, r3]) == [1, 2, 3]


def test_num_returns_mismatch_is_error(rt):
    @rt.remote(num_returns=2)
    def wrong():
        return (1, 2, 3)

    refs = wrong.remote()
    with pytest.raises(TaskError):
        rt.get(refs[0])


def test_task_exception_propagates(rt):
    @rt.remote
    def boom():
        raise ValueError("kapow")

    with pytest.raises(TaskError) as ei:
        rt.get(boom.remote())
    assert "kapow" in str(ei.value)
    assert isinstance(ei.value.cause, ValueError)


def test_get_timeout(rt):
    @rt.remote
    def slow():
        time.sleep(5)
        return 1

    with pytest.raises(GetTimeoutError):
        rt.get(slow.remote(), timeout=0.1)


def test_wait(rt):
    @rt.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.01)
    slow = sleepy.remote(2.0)
    ready, not_ready = rt.wait([fast, slow], num_returns=1, timeout=1.0)
    assert ready == [fast]
    assert not_ready == [slow]


def test_wait_timeout_returns_partial(rt):
    @rt.remote
    def never():
        time.sleep(30)

    ready, not_ready = rt.wait([never.remote()], num_returns=1,
                               timeout=0.05)
    assert ready == []
    assert len(not_ready) == 1


def test_object_ref_as_arg_resolved(rt):
    @rt.remote
    def double(x):
        return 2 * x

    assert rt.get(double.remote(rt.put(21))) == 42


def test_retry_on_exception(rt):
    import itertools
    counter = itertools.count()

    @rt.remote(max_retries=3, retry_exceptions=True)
    def flaky():
        if next(counter) < 2:
            raise RuntimeError("transient")
        return "ok"

    assert rt.get(flaky.remote()) == "ok"


def test_no_retry_by_default_on_app_error(rt):
    import itertools
    counter = itertools.count()

    @rt.remote(max_retries=5)
    def flaky():
        next(counter)
        raise RuntimeError("app error")

    with pytest.raises(TaskError):
        rt.get(flaky.remote())
    assert next(counter) == 1  # ran exactly once


def test_cancel_pending_task(rt):
    @rt.remote(num_cpus=8)
    def hog():
        time.sleep(3)

    @rt.remote(num_cpus=8)
    def victim():
        return 1

    h = hog.remote()
    v = victim.remote()   # queued behind the hog
    rt.cancel(v)
    with pytest.raises(TaskCancelledError):
        rt.get(v, timeout=5)
    del h


def test_infeasible_task_errors(rt):
    @rt.remote(num_cpus=10000)
    def big():
        return 1

    with pytest.raises(TaskError):
        rt.get(big.remote(), timeout=5)


def test_cluster_resources(rt):
    res = rt.cluster_resources()
    assert res["CPU"] == 8.0


def test_fractional_resources(rt):
    @rt.remote(num_cpus=0.25)
    def tiny(i):
        time.sleep(0.05)
        return i

    assert sorted(rt.get([tiny.remote(i) for i in range(32)])) == \
        list(range(32))


def test_custom_resources(rt):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, resources={"accel_slice": 2})

    @ray_tpu.remote(resources={"accel_slice": 1})
    def uses_slice():
        return "ok"

    assert ray_tpu.get(uses_slice.remote()) == "ok"


def test_lineage_reconstruction(rt):
    @rt.remote
    def produce():
        return list(range(100))

    ref = produce.remote()
    assert rt.get(ref) == list(range(100))
    runtime = ray_tpu._private.worker.global_worker().runtime
    runtime.simulate_object_loss(ref)
    assert runtime.reconstruct_object(ref)
    assert rt.get(ref, timeout=5) == list(range(100))


def test_timeline_records_tasks(rt):
    @rt.remote
    def traced():
        return 1

    rt.get(traced.remote())
    events = rt.timeline()
    assert any("traced" in e["name"] for e in events)


def test_runtime_context_surface(rt):
    """ray_tpu.get_runtime_context() (reference parity): identity is
    queryable from the driver AND inside tasks/actors."""
    ctx = ray_tpu.get_runtime_context()
    assert ctx.get_job_id()
    assert ctx.get_task_id() is None          # driver: no task

    @ray_tpu.remote
    def who():
        c = ray_tpu.get_runtime_context()
        return {"task": c.get_task_id(), "job": c.get_job_id(),
                "node": c.get_node_id()}

    info = ray_tpu.get(who.remote())
    assert info["task"]
    assert info["job"]


def test_runtime_context_in_multiprocess_worker():
    import ray_tpu
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=1, resources_per_worker={"CPU": 2}):
        @ray_tpu.remote
        def who():
            c = ray_tpu.get_runtime_context()
            return c.get_task_id(), c.get_worker_id(), c.get_node_id()

        tid, wid, nid = ray_tpu.get(who.remote())
        assert tid and len(tid) == 40        # 20-byte task id hex
        assert nid


def test_request_resources_demand_floor():
    """autoscaler.sdk.request_resources pins a standing demand the
    load snapshot carries even with an empty queue."""
    import ray_tpu
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.autoscaler import request_resources
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=1, resources_per_worker={"CPU": 2}):
        from ray_tpu._private.worker import global_worker
        head = global_worker().runtime.head
        request_resources(bundles=[{"CPU": 4.0}, {"TPU": 8.0}])
        snap = head.call("load_metrics_snapshot")
        assert {"CPU": 4.0} in snap["pending_demands"]
        assert {"TPU": 8.0} in snap["pending_demands"]
        request_resources(bundles=[])         # clears the floor
        snap = head.call("load_metrics_snapshot")
        assert {"TPU": 8.0} not in snap["pending_demands"]


def test_runtime_context_in_actor():
    import ray_tpu
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=1, resources_per_worker={"CPU": 2}):
        @ray_tpu.remote
        class A:
            def ident(self):
                c = ray_tpu.get_runtime_context()
                return c.get_actor_id(), c.get_task_id()

        a = A.remote()
        aid, tid = ray_tpu.get(a.ident.remote())
        assert aid == a.actor_id.hex()
        assert tid


def test_the_task_table_keeps_a_bounded_ring_of_finished_tasks(
        rt, monkeypatch):
    """Finished tasks stay listed for the state API up to a cap, oldest
    out first (as the multiprocess head keeps them); tasks and actor
    tasks alike; what is still pending is never dropped. A serve
    handle polls a stream with an actor task a poll: unbounded, the
    table grew by 565 specs a second behind 128 slots (PERF.md
    section 6, PR 39)."""
    from ray_tpu import state
    from ray_tpu._private.worker import global_worker
    runtime = global_worker().runtime
    monkeypatch.setattr(type(runtime), "_DONE_TASKS_CAP", 50)

    @rt.remote
    def one(i):
        return i

    @rt.remote
    class Counter:
        def bump(self, i):
            return i + 1

    assert rt.get([one.remote(i) for i in range(120)]) == list(range(120))
    c = Counter.remote()
    assert rt.get([c.bump.remote(i) for i in range(120)])[-1] == 120
    listed = state.list_tasks()
    assert 0 < len(listed) <= 50 + 2
    assert len(runtime._tasks_by_id) == len(runtime._task_states) <= 52
    assert {t["state"] for t in listed} <= {"FINISHED", "RUNNING",
                                            "PENDING", "PENDING_ACTOR"}
    # the newest are the ones kept
    assert any(t["name"].endswith("bump") for t in listed)
