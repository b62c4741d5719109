"""Multiprocess runtime tests (reference analogues:
python/ray/tests/test_multiprocessing-era basic tests with
ray_start_cluster, test_failure.py worker-death cases)."""
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import NodeDiedError, TaskError
from ray_tpu.runtime import Cluster


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu._private.worker as worker_mod
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    c = Cluster(num_workers=2, resources_per_worker={"CPU": 2})
    yield c
    c.shutdown()


def test_cross_process_task(cluster):
    import os
    driver_pid = os.getpid()

    @ray_tpu.remote
    def whoami():
        import os
        import time as _t
        _t.sleep(0.3)   # overlap so tasks spread across workers
        return os.getpid()

    pids = set(ray_tpu.get([whoami.remote() for _ in range(8)]))
    assert driver_pid not in pids        # ran in worker processes
    assert len(pids) >= 2                # spread across both workers


def test_put_get_across_processes(cluster):
    import numpy as np
    arr = np.arange(100000, dtype=np.float32)
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def total(a):
        return float(a.sum())

    assert ray_tpu.get(total.remote(ref)) == pytest.approx(
        float(arr.sum()))


def test_task_error_propagates(cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("distributed kapow")

    with pytest.raises(TaskError) as ei:
        ray_tpu.get(boom.remote())
    assert "distributed kapow" in str(ei.value)


def test_nested_tasks(cluster):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(10)) == 21


def test_actor_on_worker_process(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def pid(self):
            import os
            return os.getpid()

    import os
    c = Counter.remote()
    assert ray_tpu.get(c.pid.remote()) != os.getpid()
    for _ in range(5):
        c.inc.remote()
    assert ray_tpu.get(c.inc.remote()) == 6


def test_named_actor_across_processes(cluster):
    @ray_tpu.remote
    class Registry:
        def ping(self):
            return "pong"

    Registry.options(name="dist-registry").remote()
    h = ray_tpu.get_actor("dist-registry")
    assert ray_tpu.get(h.ping.remote()) == "pong"


def test_actor_handle_passed_to_task(cluster):
    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v
            return "set"

        def get(self):
            return self.v

    @ray_tpu.remote
    def writer(store):
        return ray_tpu.get(store.set.remote("from-other-process"))

    s = Store.remote()
    assert ray_tpu.get(writer.remote(s)) == "set"
    assert ray_tpu.get(s.get.remote()) == "from-other-process"


def test_cluster_resources(cluster):
    res = cluster.runtime.cluster_resources()
    assert res["CPU"] == 4.0


def test_worker_death_fails_running_task(cluster):
    @ray_tpu.remote(max_retries=0)
    def hang_forever():
        import time as _t
        _t.sleep(60)

    ref = hang_forever.remote()
    task_id = ref.id.task_id().hex()
    deadline = time.time() + 10
    victim = None
    while victim is None and time.time() < deadline:
        for w in cluster.workers():
            if w["alive"] and task_id in w.get("running_tasks", []):
                victim = w["worker_id"]
        time.sleep(0.05)
    assert victim is not None
    cluster.kill_worker(victim)
    with pytest.raises((NodeDiedError, TaskError)):
        ray_tpu.get(ref, timeout=15)
    # Replace the dead worker so later tests keep full capacity.
    cluster.add_worker()


def test_actor_restart_after_worker_death(cluster):
    @ray_tpu.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def pid(self):
            import os
            return os.getpid()

    # Headroom so the restart can be placed (other module tests' actors
    # hold CPUs on the surviving workers).
    cluster.add_worker()
    p = Phoenix.remote()
    assert ray_tpu.get(p.bump.remote(), timeout=15) == 1
    pid = ray_tpu.get(p.pid.remote(), timeout=15)
    # Kill the process hosting the actor (matched by pid).
    victim = None
    for wid, proc in list(cluster.node.procs.items()):
        if proc.pid == pid:
            victim = wid
    assert victim is not None
    cluster.kill_worker(victim)
    deadline = time.time() + 20
    value = None
    last_exc = None
    while time.time() < deadline:
        try:
            value = ray_tpu.get(p.bump.remote(), timeout=5)
            break
        except Exception as e:  # noqa: BLE001
            last_exc = e
            time.sleep(0.2)
    if value is None:
        print("last exception while retrying:", repr(last_exc))
    # Restarted fresh on another worker: state reset.
    assert value == 1
    new_pid = ray_tpu.get(p.pid.remote(), timeout=10)
    assert new_pid != pid
    cluster.add_worker()


def test_placement_group_distributed(cluster):
    from ray_tpu.util import placement_group, remove_placement_group

    # Fresh capacity (earlier tests' actors hold CPUs on old workers).
    cluster.add_worker(resources={"CPU": 4})
    before = cluster.runtime.available_resources()["CPU"]
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.wait(10)
    after = cluster.runtime.available_resources()["CPU"]
    assert before - after == pytest.approx(2.0)
    remove_placement_group(pg)
    deadline = time.time() + 5
    while time.time() < deadline and \
            cluster.runtime.available_resources()["CPU"] != \
            pytest.approx(before):
        time.sleep(0.05)
    assert cluster.runtime.available_resources()["CPU"] == \
        pytest.approx(before)


def test_driver_attach_by_address(cluster):
    """connect_to_cluster: a second driver attaches by address and its
    shutdown must not take the cluster down (Ray Client parity, P9)."""
    from ray_tpu.runtime.client import connect_to_cluster
    rt2 = connect_to_cluster(cluster.node.head_address)
    ref = rt2.put({"k": 1})
    assert rt2.get(ref) == {"k": 1}
    rt2.shutdown()   # must be a no-op for the shared cluster
    assert cluster.runtime.head.call("ping") == "pong"


def test_pg_actor_no_double_deduct(cluster):
    """ADVICE r1: a PG-pinned actor must consume the PG's reservation,
    not deduct from the worker a second time (which drove availability
    negative and blocked unrelated scheduling on that worker)."""
    from ray_tpu.util import (PlacementGroupSchedulingStrategy,
                              placement_group, remove_placement_group)

    cluster.add_worker(resources={"CPU": 4})
    before = cluster.runtime.available_resources()["CPU"]
    pg = placement_group([{"CPU": 2}], strategy="PACK")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=2)
    class A:
        def ping(self):
            return "pong"

    a = A.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=0)).remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    # PG already reserved 2 CPUs; the actor must not deduct 2 more.
    avail = cluster.runtime.available_resources()["CPU"]
    assert before - avail == pytest.approx(2.0)
    ray_tpu.kill(a)
    remove_placement_group(pg)
    deadline = time.time() + 5
    while time.time() < deadline and \
            cluster.runtime.available_resources()["CPU"] != \
            pytest.approx(before):
        time.sleep(0.05)
    assert cluster.runtime.available_resources()["CPU"] == \
        pytest.approx(before)


def test_pg_bundle_capacity_bounds_actors(cluster):
    """A bundle's reservation bounds how many actors pack into it —
    over-subscription must block (and unblock when an actor dies)."""
    from ray_tpu.util import (PlacementGroupSchedulingStrategy,
                              placement_group, remove_placement_group)

    cluster.add_worker(resources={"CPU": 4})
    pg = placement_group([{"CPU": 2}], strategy="PACK")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=2)
    class A:
        def ping(self):
            return "pong"

    strat = PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=0)
    a1 = A.options(scheduling_strategy=strat).remote()
    assert ray_tpu.get(a1.ping.remote()) == "pong"
    # Second 2-CPU actor exceeds the 2-CPU bundle: creation must BLOCK
    # (not overcommit). Free the bundle shortly after; the blocked
    # creation must then proceed on the freed capacity.
    import threading

    def free_soon():
        time.sleep(1.0)
        ray_tpu.kill(a1)

    t = threading.Thread(target=free_soon, daemon=True)
    start = time.time()
    t.start()
    a2 = A.options(scheduling_strategy=strat).remote()
    assert ray_tpu.get(a2.ping.remote(), timeout=10) == "pong"
    assert time.time() - start >= 0.9, "second actor scheduled into a full bundle"
    t.join()
    ray_tpu.kill(a2)
    remove_placement_group(pg)


def test_resource_syncer_pushes_view(cluster):
    """N6 resource-syncer role: the head pushes resource snapshots
    over pub/sub; resource queries serve from the cached view and a
    membership change shows up push-fast WITHOUT a polling RPC."""
    import time as _t
    rt = cluster.runtime
    # wait for the first push
    deadline = _t.time() + 20
    while rt._resource_view is None and _t.time() < deadline:
        _t.sleep(0.05)
    assert rt._resource_view is not None, "no resource push arrived"
    base_cpus = rt.cluster_resources().get("CPU", 0)
    assert base_cpus > 0

    # wait until the pushed view is FRESH (a loaded machine can stall
    # the subscriber past the TTL, which would legitimately fall back
    # to an RPC and flake the no-RPC assertion)
    deadline = _t.time() + 20
    while _t.time() - rt._resource_view_ts > 4 and \
            _t.time() < deadline:
        _t.sleep(0.1)
    calls_before = getattr(rt.head, "_rid", None)
    rt.cluster_resources()          # served from the pushed cache
    # no RPC was issued for the query
    assert getattr(rt.head, "_rid", None) == calls_before

    # membership change propagates by push
    wid = cluster.add_worker({"CPU": 3})
    deadline = _t.time() + 20
    while _t.time() < deadline and \
            rt.cluster_resources().get("CPU", 0) < base_cpus + 3:
        _t.sleep(0.05)
    assert rt.cluster_resources()["CPU"] == base_cpus + 3
    cluster.node.kill_worker(wid)
    deadline = _t.time() + 30
    while _t.time() < deadline and \
            rt.cluster_resources().get("CPU", 0) > base_cpus:
        _t.sleep(0.05)
    assert rt.cluster_resources()["CPU"] == base_cpus


def test_concurrency_groups_distributed(cluster):
    """Concurrency groups hold across the process boundary: group
    parallelism on a worker-process actor."""
    import time as _time

    import threading as _threading

    @ray_tpu.remote(concurrency_groups={"io": 2})
    class W:
        def __init__(self):
            self.active = 0
            self.peak = 0
            self.lock = _threading.Lock()

        @ray_tpu.method(concurrency_group="io")
        def slow(self):
            import time
            with self.lock:
                self.active += 1
                self.peak = max(self.peak, self.active)
            time.sleep(0.3)
            with self.lock:
                self.active -= 1
            return "ok"

        def quick(self):
            return "q"

        def peak_seen(self):
            return self.peak

    w = W.remote()
    ray_tpu.get(w.quick.remote(), timeout=60)   # actor up
    t0 = _time.time()
    refs = [w.slow.remote() for _ in range(2)]
    # default group is NOT blocked behind the io group: quick returns
    # before the two 0.3s io calls drain
    assert ray_tpu.get(w.quick.remote(), timeout=10) == "q"
    quick_dt = _time.time() - t0
    assert ray_tpu.get(refs, timeout=30) == ["ok", "ok"]
    assert quick_dt < _time.time() - t0   # quick beat the group drain
    # group parallelism proven by the peak-concurrency counter
    assert ray_tpu.get(w.peak_seen.remote(), timeout=10) == 2


def test_state_api_lists_tasks_and_objects():
    """list_tasks/list_objects on the multiprocess runtime (were
    empty stubs; reference: experimental/state/api.py)."""
    import time
    import numpy as np
    import ray_tpu
    from ray_tpu import state
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=1,
                 resources_per_worker={"CPU": 2, "n0": 10}) as c:
        c.add_node(num_workers=1,
                   resources_per_worker={"CPU": 2, "n1": 10})

        @ray_tpu.remote
        def work(x):
            return x + 1

        refs = [work.remote(i) for i in range(5)]
        assert ray_tpu.get(refs) == [1, 2, 3, 4, 5]
        deadline = time.time() + 10
        finished = []
        while time.time() < deadline:
            finished = [t for t in state.list_tasks()
                        if t["state"] == "FINISHED"
                        and t["name"].endswith("work")]
            if len(finished) >= 5:
                break
            time.sleep(0.2)
        assert len(finished) >= 5, finished[:3]
        # objects: a registered multinode object shows its location
        ref = ray_tpu.put(np.ones((1 << 20) // 8))

        @ray_tpu.remote(resources={"n1": 1})
        def touch(a):
            return a.nbytes
        assert ray_tpu.get(touch.remote(ref)) == 1 << 20
        deadline = time.time() + 10
        objs = []
        while time.time() < deadline:
            objs = state.list_objects()
            if any(o["object_id"] == ref.id.hex() for o in objs):
                break
            time.sleep(0.2)
        mine = [o for o in objs if o["object_id"] == ref.id.hex()]
        assert mine and mine[0]["locations"], objs[:3]


def test_cancel_queued_and_force_running():
    """ray_tpu.cancel on the multiprocess runtime (was a no-op stub):
    queued tasks fail fast with TaskCancelledError; force=True
    interrupts a RUNNING task by killing its worker (reference:
    ray.cancel force_kill semantics)."""
    import time
    import pytest
    import ray_tpu
    from ray_tpu.exceptions import TaskCancelledError
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=2, resources_per_worker={"CPU": 1}):
        @ray_tpu.remote(num_cpus=1)
        def sleeper(sec):
            # interruption-friendly wait: the async cancel exception
            # lands between bytecodes, i.e. every 50ms here
            t0 = time.time()
            while time.time() - t0 < sec:
                time.sleep(0.05)
            return "done"

        # occupy BOTH CPUs, then queue a third task and cancel it
        running = [sleeper.remote(30), sleeper.remote(4)]
        time.sleep(0.5)
        queued = sleeper.remote(0)
        time.sleep(0.3)
        ray_tpu.cancel(queued)
        with pytest.raises(TaskCancelledError):
            ray_tpu.get(queued, timeout=15)
        # non-force cancel of a RUNNING task is a no-op ("running")
        ray_tpu.cancel(running[0])
        # force-cancel: async TaskCancelledError in the executing
        # THREAD — the task fails promptly, the worker survives, and
        # nothing co-resident is touched
        t0 = time.time()
        res = ray_tpu.cancel(running[0], force=True)
        assert res == "interrupted", res
        with pytest.raises(Exception) as ei:
            ray_tpu.get(running[0], timeout=20)
        assert "cancel" in repr(ei.value).lower(), ei.value
        assert time.time() - t0 < 15       # prompt, not wait-it-out
        # the other task completes; BOTH workers still serve
        assert ray_tpu.get(running[1], timeout=60) == "done"
        assert ray_tpu.get(
            [sleeper.remote(0) for _ in range(4)], timeout=60) == \
            ["done"] * 4


def test_cancel_rejects_non_task_refs():
    import pytest
    import ray_tpu
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=1, resources_per_worker={"CPU": 2}):
        with pytest.raises(TypeError, match="put"):
            ray_tpu.cancel(ray_tpu.put(1))

        @ray_tpu.remote
        class A:
            def f(self):
                return 1

        a = A.remote()
        ref = a.f.remote()
        with pytest.raises(TypeError, match="actor"):
            ray_tpu.cancel(ref)
        assert ray_tpu.get(ref) == 1
