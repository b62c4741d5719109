"""Local (single-process) runtime: the full task/actor/object semantics of the
framework executed with threads in one process.

This is the analogue of the reference's local-mode runtime
(cpp/src/ray/runtime/task/local_mode_task_submitter.cc) grown to full
capability: resource-gated scheduling (reference semantics:
src/ray/raylet/scheduling/cluster_task_manager.cc +
local_task_manager.cc), ordered/async/threaded actors with restart
(src/ray/core_worker/transport/direct_actor_task_submitter.cc,
gcs_actor_manager.cc:1037 ReconstructActor), task retries + lineage
reconstruction (src/ray/core_worker/task_manager.h:135,
object_recovery_manager.h:41), placement-group reservation
(gcs_placement_group_scheduler.h 2PC), named actors, cancellation, chaos
delay injection (common/asio/asio_chaos.cc), and a task timeline
(core_worker/profiling.h).

It doubles as the in-process test fake for every library layer, exactly the
role local mode plays in the reference.
"""
from __future__ import annotations

import asyncio
import collections
import inspect
import logging
import os
import queue
import random
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import profiling
from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import (ActorID, JobID, ObjectID,
                                  PlacementGroupID, TaskID)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.object_store import MemoryStore, ReferenceCounter
from ray_tpu._private.task_spec import (ActorCreationSpec, Bundle,
                                        PlacementGroupSchedulingStrategy,
                                        PlacementGroupSpec, TaskSpec)
from ray_tpu.exceptions import (ActorDiedError, ObjectLostError,
                                PendingCallsLimitExceeded,
                                TaskCancelledError, TaskError)

logger = logging.getLogger(__name__)

_exec_ctx = threading.local()


def current_task_context():
    return getattr(_exec_ctx, "ctx", None)


class _TaskContext:
    __slots__ = ("spec", "runtime", "resources_held")

    def __init__(self, spec, runtime):
        self.spec = spec
        self.runtime = runtime
        self.resources_held = True


class ResourcePool:
    """Node resource accounting with fractional amounts (the reference uses
    fixed-point arithmetic, scheduling/fixed_point.h; floats + epsilon here)."""

    EPS = 1e-9

    def __init__(self, total: Dict[str, float]):
        self.total = dict(total)
        self.available = dict(total)
        self._cv = threading.Condition()

    def fits(self, req: Dict[str, float]) -> bool:
        return all(self.available.get(k, 0.0) + self.EPS >= v
                   for k, v in req.items())

    def can_ever_fit(self, req: Dict[str, float]) -> bool:
        return all(self.total.get(k, 0.0) + self.EPS >= v
                   for k, v in req.items())

    def try_acquire(self, req: Dict[str, float]) -> bool:
        with self._cv:
            if not self.fits(req):
                return False
            for k, v in req.items():
                self.available[k] = self.available.get(k, 0.0) - v
            return True

    def acquire(self, req: Dict[str, float],
                timeout: Optional[float] = None) -> bool:
        """Block until the request fits (or timeout). Returns success."""
        deadline = None if timeout is None else time.time() + timeout
        with self._cv:
            while not self.fits(req):
                remaining = None if deadline is None else \
                    deadline - time.time()
                if remaining is not None and remaining <= 0:
                    return False
                self._cv.wait(timeout=remaining if remaining is not None
                              else 1.0)
            for k, v in req.items():
                self.available[k] = self.available.get(k, 0.0) - v
            return True

    def release(self, req: Dict[str, float]):
        with self._cv:
            for k, v in req.items():
                self.available[k] = min(self.total.get(k, 0.0),
                                        self.available.get(k, 0.0) + v)
            self._cv.notify_all()

    def add_capacity(self, extra: Dict[str, float]):
        with self._cv:
            for k, v in extra.items():
                self.total[k] = self.total.get(k, 0.0) + v
                self.available[k] = self.available.get(k, 0.0) + v
            self._cv.notify_all()

    def remove_capacity(self, extra: Dict[str, float]):
        with self._cv:
            for k, v in extra.items():
                self.total[k] = self.total.get(k, 0.0) - v
                self.available[k] = self.available.get(k, 0.0) - v
            self._cv.notify_all()


class _ActorState:
    def __init__(self, spec: ActorCreationSpec, runtime: "LocalRuntime"):
        self.spec = spec
        self.runtime = runtime
        self.instance: Any = None
        self.dead = False
        self.death_reason = ""
        self.num_restarts = 0
        self.restarting = False
        from ray_tpu._private.concurrency_groups import GroupMailboxes
        self.gm = GroupMailboxes(spec.concurrency_groups,
                                 max(1, spec.max_concurrency))
        self.pending_count = 0
        self.lock = threading.RLock()
        self.threads: List[threading.Thread] = []
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.created = threading.Event()
        self.init_error: Optional[BaseException] = None

    # --- lifecycle ---------------------------------------------------------

    def start(self):
        if self.spec.is_async:
            t = threading.Thread(target=self._async_loop, daemon=True,
                                 name=f"actor-{self.spec.actor_id.hex()[:8]}")
            t.start()
            self.threads = [t]
        else:
            self.threads = []
            for group, box in self.gm.items():
                for i in range(self.gm.size(group)):
                    t = threading.Thread(
                        target=self._thread_loop, args=(box,),
                        daemon=True,
                        name=f"actor-{self.spec.actor_id.hex()[:8]}"
                             f"-{group}-{i}")
                    t.start()
                    self.threads.append(t)

    def _instantiate(self):
        try:
            profiling.record("actor_init", self.spec.cls.__name__)
            from ray_tpu._private.runtime_env import runtime_env_context
            with runtime_env_context(self.spec.runtime_env):
                self.instance = self.spec.cls(*self.spec.args,
                                              **self.spec.kwargs)
            self.init_error = None
        except BaseException as e:  # noqa: BLE001
            self.init_error = e
            self.dead = True
            self.death_reason = f"__init__ failed: {e!r}"
        finally:
            self.created.set()

    def _thread_loop(self, box: "queue.Queue"):
        # First thread instantiates.
        if not self.created.is_set():
            with self.lock:
                if not self.created.is_set():
                    self._instantiate()
        self.created.wait()
        while True:
            item = box.get()
            if item is None:
                return
            spec, ctx_runtime = item
            with self.lock:
                self.pending_count -= 1
            if self.dead:
                ctx_runtime._store_error(
                    spec, ActorDiedError(self.spec.actor_id,
                                         self.death_reason))
                continue
            ctx_runtime._execute_actor_task(self, spec)

    def _async_loop(self):
        loop = asyncio.new_event_loop()
        self.loop = loop
        asyncio.set_event_loop(loop)
        # The loop's DEFAULT executor sizes to min(32, cpus + 4) —
        # on a small host that silently caps run_in_executor offloads
        # (serve replicas run sync user methods there) far below the
        # actor's declared max_concurrency. Size it to the actor's
        # own concurrency; threads spawn lazily.
        # + one thread per group: each group's pump parks a blocking
        # box.get in this same pool while idle
        from concurrent.futures import ThreadPoolExecutor
        loop.set_default_executor(ThreadPoolExecutor(
            max_workers=self.gm.max_concurrency + len(self.gm.boxes),
            thread_name_prefix="actor-exec"))
        self._instantiate()
        # per-group semaphores bound concurrency independently
        sems = {g: asyncio.Semaphore(self.gm.size(g))
                for g, _ in self.gm.items()}

        async def pump(box, sem):
            while True:
                item = await loop.run_in_executor(None, box.get)
                if item is None:
                    return
                spec, ctx_runtime = item
                with self.lock:
                    self.pending_count -= 1
                if self.dead:
                    ctx_runtime._store_error(
                        spec, ActorDiedError(self.spec.actor_id,
                                             self.death_reason))
                    continue

                async def run_one(spec=spec):
                    async with sem:
                        await ctx_runtime._execute_actor_task_async(
                            self, spec)

                loop.create_task(run_one())

        async def pump_all():
            await asyncio.gather(*[
                pump(box, sems[g])
                for g, box in self.gm.items()])

        try:
            loop.run_until_complete(pump_all())
            pending = [t for t in asyncio.all_tasks(loop)
                       if not t.done()]
            if pending:
                loop.run_until_complete(
                    asyncio.gather(*pending, return_exceptions=True))
        finally:
            loop.close()

    def submit(self, spec: TaskSpec, runtime: "LocalRuntime"):
        with self.lock:
            if self.dead and not self.restarting:
                # dead-actor contract first: callers uniformly get an
                # ActorDiedError via the ref, even with a bad group
                runtime._store_error(
                    spec, ActorDiedError(self.spec.actor_id,
                                         self.death_reason))
                return
            box = self.gm.route(
                getattr(spec, "concurrency_group", None))
            limit = self.spec.max_pending_calls
            if limit and limit > 0 and self.pending_count >= limit:
                raise PendingCallsLimitExceeded(
                    f"actor {self.spec.actor_id.hex()[:8]} has "
                    f"{self.pending_count} pending calls (limit {limit})")
            self.pending_count += 1
        box.put((spec, runtime))

    def stop(self):
        if self.spec.is_async:
            self.gm.stop_one_per_group()
        else:
            self.gm.stop()


class PlacementGroup:
    """User-facing placement group handle (reference:
    python/ray/util/placement_group.py)."""

    def __init__(self, spec: PlacementGroupSpec, runtime: "LocalRuntime"):
        self.spec = spec
        self._runtime = runtime
        self._ready_event = threading.Event()
        self._removed = False
        self._state_lock = threading.Lock()

    @property
    def id(self) -> PlacementGroupID:
        return self.spec.pg_id

    @property
    def bundle_specs(self) -> List[Dict[str, float]]:
        return [dict(b.resources) for b in self.spec.bundles]

    def ready(self) -> ObjectRef:
        """An ObjectRef resolving when all bundles are reserved."""
        oid = ObjectID.from_random()
        ref = ObjectRef(oid)

        def _wait():
            self._ready_event.wait()
            self._runtime.store.put(oid, self)

        threading.Thread(target=_wait, daemon=True).start()
        return ref

    def wait(self, timeout_seconds: float = 30) -> bool:
        return self._ready_event.wait(timeout_seconds)

    def is_ready(self) -> bool:
        return self._ready_event.is_set()


class LocalRuntime:
    """Single-process runtime implementing the full API surface."""

    def __init__(self, resources: Dict[str, float],
                 job_id: Optional[JobID] = None):
        self.job_id = job_id or JobID.next()
        self.store = MemoryStore()
        self.ref_counter = ReferenceCounter(
            on_object_released=self._on_object_released)
        self.pool = ResourcePool(resources)
        self._lock = threading.RLock()
        self._actors: Dict[ActorID, _ActorState] = {}
        self._named_actors: Dict[Tuple[str, str], ActorID] = {}
        self._actor_handles: Dict[ActorID, Any] = {}
        self._pending: collections.deque = collections.deque()
        self._cancelled: set = set()
        self._tasks_by_id: Dict[TaskID, TaskSpec] = {}
        self._task_states: Dict[TaskID, str] = {}
        self._done_tasks: collections.deque = collections.deque()
        self._lineage: Dict[ObjectID, TaskSpec] = {}
        self._lineage_bytes = 0
        self._pgs: Dict[PlacementGroupID, PlacementGroup] = {}
        self._shutdown = False
        self._sched_cv = threading.Condition()
        self._memory_monitor = None
        if GlobalConfig.memory_monitor_threshold > 0:
            from ray_tpu._private.memory_monitor import MemoryMonitor
            self._memory_monitor = MemoryMonitor(
                threshold=GlobalConfig.memory_monitor_threshold,
                check_interval_s=(
                    GlobalConfig.memory_monitor_interval_ms / 1000.0),
                on_threshold=lambda f: logger.warning(
                    "Memory pressure: %.0f%% used — pausing task "
                    "dispatch (reference: raylet MemoryMonitor OOM "
                    "prevention)", f * 100),
                on_recovered=lambda f: self._kick_scheduler(),
            ).start()
        self._sched_thread = threading.Thread(
            target=self._scheduler_loop, daemon=True, name="local-scheduler")
        self._sched_thread.start()

    # --- chaos -------------------------------------------------------------

    def _chaos_delay(self):
        from ray_tpu._private.config import chaos_delay
        chaos_delay()

    # --- objects -----------------------------------------------------------

    def put(self, value: Any) -> ObjectRef:
        self._chaos_delay()
        oid = ObjectID.from_random()
        self.store.put(oid, value)
        return ObjectRef(oid)

    def object_future(self, oid: ObjectID) -> Future:
        return self.store.future(oid)

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        for r in ref_list:
            if not isinstance(r, ObjectRef):
                raise TypeError(
                    f"get() expects ObjectRef(s), got {type(r).__name__}")
        ctx = current_task_context()
        # Release held resources while blocked (prevents nested-task
        # deadlock; the reference achieves this by leasing new workers).
        released = False
        if ctx is not None and ctx.resources_held and any(
                not self.store.contains(r.id) for r in ref_list):
            self.pool.release(ctx.spec.resources)
            ctx.resources_held = False
            released = True
            self._kick_scheduler()
        try:
            # One overall deadline across all refs, not per-ref.
            deadline = None if timeout is None else time.time() + timeout
            values = []
            for r in ref_list:
                remaining = None if deadline is None else \
                    max(0.0, deadline - time.time())
                values.append(self.store.get(r.id, remaining))
        finally:
            if released:
                # Resume immediately even if the resources were taken in
                # the meantime (temporary oversubscription, matching the
                # reference's unblocked-worker semantics). resources_held
                # tracks whether re-acquisition succeeded so the ledger
                # stays balanced: release at task end only if held.
                ctx.resources_held = self.pool.try_acquire(
                    ctx.spec.resources)
        return values[0] if single else values

    def wait(self, refs: List[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None):
        if num_returns > len(refs):
            raise ValueError("num_returns > len(refs)")
        id_map = {r.id: r for r in refs}
        ready_ids, rest_ids = self.store.wait(
            [r.id for r in refs], num_returns, timeout)
        return ([id_map[i] for i in ready_ids],
                [id_map[i] for i in rest_ids])

    # Finished tasks kept for the state API: a bounded ring, as the
    # multiprocess head keeps (runtime/head.py ``_DONE_TASKS_CAP``).
    # Every task a process ever ran used to stay in the table, and a
    # serve handle polls a stream's chunks with one actor task a poll:
    # 565 a second behind 128 slots, six containers each, all of them
    # walked by every full pass of the collector (PERF.md section 6,
    # PR 39).
    _DONE_TASKS_CAP = 2000

    def _task_done(self, task_id: TaskID, state: str) -> None:
        with self._lock:
            self._task_states[task_id] = state
            self._done_tasks.append(task_id)
            while len(self._done_tasks) > self._DONE_TASKS_CAP:
                old = self._done_tasks.popleft()
                self._tasks_by_id.pop(old, None)
                self._task_states.pop(old, None)

    def _on_object_released(self, oid: ObjectID):
        # Out-of-scope objects are evicted (distributed GC capability).
        self.store.delete(oid)
        with self._lock:
            self._lineage.pop(oid, None)

    # --- normal tasks ------------------------------------------------------

    def submit_task(self, spec: TaskSpec) -> List[ObjectRef]:
        self._chaos_delay()
        refs = []
        for oid in spec.return_ids:
            refs.append(ObjectRef(oid))
            self.ref_counter.set_lineage(oid, spec.task_id)
        with self._lock:
            self._tasks_by_id[spec.task_id] = spec
            self._task_states[spec.task_id] = "PENDING"
            for oid in spec.return_ids:
                self._lineage[oid] = spec
        for a in list(spec.args) + list(spec.kwargs.values()):
            if isinstance(a, ObjectRef):
                self.ref_counter.add_submitted_task_ref(a.id)
        with self._sched_cv:
            self._pending.append(spec)
            self._sched_cv.notify_all()
        profiling.record("task_submitted", spec.name)
        return refs

    def _kick_scheduler(self):
        with self._sched_cv:
            self._sched_cv.notify_all()

    def _scheduler_loop(self):
        while not self._shutdown:
            with self._sched_cv:
                dispatched = self._try_dispatch()
                if not dispatched:
                    self._sched_cv.wait(timeout=0.05)

    def _try_dispatch(self) -> bool:
        """Dispatch every queued task whose resources fit. Returns True if
        any dispatch happened."""
        any_dispatched = False
        if self._memory_monitor is not None and \
                self._memory_monitor.above_threshold:
            # Above the watermark: stop starting new work until usage
            # drops (on_recovered kicks the scheduler).
            return False
        still_pending = collections.deque()
        while self._pending:
            spec = self._pending.popleft()
            if spec.task_id in self._cancelled:
                self._store_error(spec, TaskCancelledError(spec.task_id))
                continue
            req = self._effective_resources(spec)
            if req is None:
                # PG not ready yet.
                still_pending.append(spec)
                continue
            if self.pool.try_acquire(req):
                self._task_states[spec.task_id] = "RUNNING"
                t = threading.Thread(target=self._run_task,
                                     args=(spec, req), daemon=True,
                                     name=f"task-{spec.name[:24]}")
                t.start()
                any_dispatched = True
            else:
                if not self.pool.can_ever_fit(req):
                    self._store_error(spec, ValueError(
                        f"Task {spec.name} requires {req} but the cluster "
                        f"total is {self.pool.total} (infeasible)"))
                    continue
                still_pending.append(spec)
        self._pending = still_pending
        return any_dispatched

    def _effective_resources(self, spec: TaskSpec) -> Optional[Dict]:
        strat = spec.scheduling_strategy
        if isinstance(strat, PlacementGroupSchedulingStrategy) and \
                strat.placement_group is not None:
            pg = strat.placement_group
            if not pg.is_ready():
                return None
            # Resources were pre-reserved by the PG: the task runs inside
            # the reservation, so the node pool sees zero demand.
            return {}
        return spec.resources

    def _resolve_args(self, spec: TaskSpec):
        args = []
        for a in spec.args:
            args.append(self.store.get(a.id) if isinstance(a, ObjectRef)
                        else a)
        kwargs = {}
        for k, v in spec.kwargs.items():
            kwargs[k] = self.store.get(v.id) if isinstance(v, ObjectRef) \
                else v
        return args, kwargs

    def _release_task_arg_refs(self, spec: TaskSpec):
        for a in list(spec.args) + list(spec.kwargs.values()):
            if isinstance(a, ObjectRef):
                self.ref_counter.remove_submitted_task_ref(a.id)

    def _run_task(self, spec: TaskSpec, acquired: Dict[str, float]):
        ctx = _TaskContext(spec, self)
        _exec_ctx.ctx = ctx
        self._chaos_delay()
        profiling.record_span_start("task_run", spec.name, spec.task_id)
        try:
            args, kwargs = self._resolve_args(spec)
            if spec.task_id in self._cancelled:
                raise TaskCancelledError(spec.task_id)
            from ray_tpu._private.runtime_env import runtime_env_context
            from ray_tpu.util.tracing import execution_span
            with runtime_env_context(spec.runtime_env), \
                    execution_span(spec.name, "task", spec.trace_ctx):
                result = spec.func(*args, **kwargs)
            self._store_returns(spec, result)
            self._task_done(spec.task_id, "FINISHED")
        except TaskCancelledError as e:
            self._store_error(spec, e, wrap=False)
            self._task_done(spec.task_id, "CANCELLED")
        except BaseException as e:  # noqa: BLE001
            self._handle_task_failure(spec, e)
        finally:
            self._release_task_arg_refs(spec)
            profiling.record_span_end("task_run", spec.name, spec.task_id)
            _exec_ctx.ctx = None
            if ctx.resources_held:
                self.pool.release(acquired)
            self._kick_scheduler()

    def _should_retry(self, spec: TaskSpec, exc: BaseException) -> bool:
        max_retries = spec.max_retries
        if spec.attempt >= max_retries:
            return False
        re = spec.retry_exceptions
        if re is True:
            return True
        if isinstance(re, (list, tuple)):
            return isinstance(exc, tuple(re))
        # retry_exceptions=False: only system failures retry; application
        # exceptions do not (reference semantics). Local runtime models
        # system failure as NodeDiedError/ObjectLostError.
        return isinstance(exc, (ObjectLostError,))

    def _handle_task_failure(self, spec: TaskSpec, exc: BaseException):
        if self._should_retry(spec, exc):
            delay = GlobalConfig.task_retry_delay_ms / 1000.0
            spec.attempt += 1
            logger.warning("Retrying task %s (attempt %d/%d) after %r",
                           spec.name, spec.attempt, spec.max_retries, exc)
            self._task_states[spec.task_id] = "PENDING_RETRY"

            def _resubmit():
                if delay:
                    time.sleep(delay)
                with self._sched_cv:
                    self._pending.append(spec)
                    self._sched_cv.notify_all()
            threading.Thread(target=_resubmit, daemon=True).start()
        else:
            self._store_error(spec, exc)
            self._task_done(spec.task_id, "FAILED")

    def _put_return(self, oid: ObjectID, value: Any,
                    is_exception: bool = False):
        self.store.put(oid, value, is_exception=is_exception)
        # Fire-and-forget: if every ref to this return was already
        # dropped, evict immediately instead of leaking the entry.
        if self.ref_counter.ref_count(oid) == 0:
            self.store.delete(oid)
            with self._lock:
                self._lineage.pop(oid, None)

    def _store_returns(self, spec: TaskSpec, result: Any):
        n = spec.num_returns
        if n == 0:
            return
        if n == 1:
            self._put_return(spec.return_ids[0], result)
            return
        try:
            values = list(result)
        except TypeError:
            raise TypeError(
                f"Task {spec.name} declared num_returns={n} but returned "
                f"non-iterable {type(result).__name__}") from None
        if len(values) != n:
            raise ValueError(
                f"Task {spec.name} declared num_returns={n} but returned "
                f"{len(values)} values")
        for oid, v in zip(spec.return_ids, values):
            self._put_return(oid, v)

    def _store_error(self, spec: TaskSpec, exc: BaseException,
                     wrap: bool = True):
        if wrap and not isinstance(exc, (TaskError, ActorDiedError,
                                         TaskCancelledError,
                                         ObjectLostError)):
            exc = TaskError(exc, task_name=spec.name)
        for oid in spec.return_ids:
            self._put_return(oid, exc, is_exception=True)

    # --- lineage reconstruction -------------------------------------------

    def reconstruct_object(self, ref: ObjectRef) -> bool:
        """Re-execute the creating task of a lost object (reference:
        object_recovery_manager.h). Returns False if lineage is gone."""
        with self._lock:
            spec = self._lineage.get(ref.id)
        if spec is None:
            return False
        self.store.mark_lost(ref.id)
        clone = TaskSpec(**{f.name: getattr(spec, f.name)
                            for f in spec.__dataclass_fields__.values()})
        clone.attempt = 0
        with self._sched_cv:
            self._pending.append(clone)
            self._sched_cv.notify_all()
        return True

    def simulate_object_loss(self, ref: ObjectRef):
        """Test/chaos hook: drop the stored value (keeps lineage)."""
        self.store.mark_lost(ref.id)

    # --- cancellation ------------------------------------------------------

    def cancel(self, ref: ObjectRef, force: bool = False,
               recursive: bool = True):
        tid = ref.id.task_id()
        self._cancelled.add(tid)
        with self._lock:
            spec = self._tasks_by_id.get(tid)
        if spec is not None and self._task_states.get(tid) in (
                "PENDING", "PENDING_RETRY"):
            self._store_error(spec, TaskCancelledError(tid), wrap=False)
        self._kick_scheduler()

    # --- actors ------------------------------------------------------------

    def create_actor(self, spec: ActorCreationSpec) -> "_ActorState":
        self._chaos_delay()
        if spec.name:
            key = (spec.namespace or "default", spec.name)
            with self._lock:
                if key in self._named_actors:
                    existing = self._actors.get(self._named_actors[key])
                    if existing is not None and not existing.dead:
                        if spec.get_if_exists:
                            return existing
                        raise ValueError(
                            f"Actor name {spec.name!r} already taken")
        if not self.pool.try_acquire(spec.resources):
            if not self.pool.can_ever_fit(spec.resources):
                raise ValueError(
                    f"Actor requires {spec.resources}, cluster total "
                    f"{self.pool.total} (infeasible)")
            # Block until resources free (actors queue like tasks). If the
            # caller is itself a task holding resources, release them while
            # blocked — same nested-deadlock avoidance as get().
            ctx = current_task_context()
            released = False
            if ctx is not None and ctx.resources_held:
                self.pool.release(ctx.spec.resources)
                ctx.resources_held = False
                released = True
                self._kick_scheduler()
            try:
                if not self.pool.acquire(spec.resources, timeout=300):
                    raise TimeoutError(
                        f"Timed out acquiring {spec.resources} for actor")
            finally:
                if released:
                    # Same oversubscription semantics as get() above.
                    ctx.resources_held = self.pool.try_acquire(
                        ctx.spec.resources)
        state = _ActorState(spec, self)
        with self._lock:
            self._actors[spec.actor_id] = state
            if spec.name:
                self._named_actors[(spec.namespace or "default",
                                    spec.name)] = spec.actor_id
        state.start()
        return state

    def get_actor_state(self, actor_id: ActorID) -> _ActorState:
        with self._lock:
            st = self._actors.get(actor_id)
        if st is None:
            raise ActorDiedError(actor_id, "unknown actor")
        return st

    def lookup_named_actor(self, name: str,
                           namespace: Optional[str]) -> ActorID:
        with self._lock:
            key = (namespace or "default", name)
            if key not in self._named_actors:
                raise ValueError(f"No actor named {name!r}")
            return self._named_actors[key]

    def submit_actor_task(self, actor_id: ActorID,
                          spec: TaskSpec) -> List[ObjectRef]:
        self._chaos_delay()
        refs = [ObjectRef(oid) for oid in spec.return_ids]
        with self._lock:
            self._tasks_by_id[spec.task_id] = spec
            self._task_states[spec.task_id] = "PENDING_ACTOR"
        st = self.get_actor_state(actor_id)
        try:
            st.submit(spec, self)
        except BaseException:
            # rejected at submit (unknown concurrency group, pending
            # limit): drop the phantom task record
            with self._lock:
                self._tasks_by_id.pop(spec.task_id, None)
                self._task_states.pop(spec.task_id, None)
            raise
        return refs

    def _execute_actor_task(self, st: _ActorState, spec: TaskSpec):
        ctx = _TaskContext(spec, self)
        ctx.resources_held = False   # actor holds its own resources
        _exec_ctx.ctx = ctx
        profiling.record_span_start("actor_task", spec.name, spec.task_id)
        try:
            if st.init_error is not None:
                raise ActorDiedError(st.spec.actor_id, st.death_reason)
            args, kwargs = self._resolve_args(spec)
            method = getattr(st.instance, spec.method_name)
            from ray_tpu._private.runtime_env import runtime_env_context
            from ray_tpu.util.tracing import execution_span
            with runtime_env_context(st.spec.runtime_env), \
                    execution_span(spec.name, "actor_task",
                                   spec.trace_ctx):
                result = method(*args, **kwargs)
            self._store_returns(spec, result)
            self._task_done(spec.task_id, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            self._handle_actor_task_failure(st, spec, e)
        finally:
            profiling.record_span_end("actor_task", spec.name, spec.task_id)
            _exec_ctx.ctx = None

    async def _execute_actor_task_async(self, st: _ActorState,
                                        spec: TaskSpec):
        profiling.record_span_start("actor_task", spec.name, spec.task_id)
        try:
            if st.init_error is not None:
                raise ActorDiedError(st.spec.actor_id, st.death_reason)
            args, kwargs = self._resolve_args(spec)
            method = getattr(st.instance, spec.method_name)
            from ray_tpu._private.runtime_env import runtime_env_context
            with runtime_env_context(st.spec.runtime_env):
                result = method(*args, **kwargs)
                if inspect.isawaitable(result):
                    result = await result
            self._store_returns(spec, result)
            self._task_done(spec.task_id, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            self._handle_actor_task_failure(st, spec, e)
        finally:
            profiling.record_span_end("actor_task", spec.name, spec.task_id)

    def _handle_actor_task_failure(self, st: _ActorState, spec: TaskSpec,
                                   exc: BaseException):
        # Application exceptions do not kill the actor (reference
        # semantics); they are returned to the caller.
        if isinstance(exc, ActorDiedError):
            # Actor is dead: honor max_task_retries by re-submitting to
            # the (possibly restarted) actor.
            if spec.attempt < st.spec.max_task_retries and not (
                    st.dead and not st.restarting):
                spec.attempt += 1
                st.submit(spec, self)
                return
        self._store_error(spec, exc)
        self._task_done(spec.task_id, "FAILED")

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        """Kill an actor. With no_restart=False this models a *crash* —
        the restart policy (max_restarts) applies, pending calls see
        ActorDiedError or are retried per max_task_retries."""
        st = self.get_actor_state(actor_id)
        with st.lock:
            st.dead = True
            st.death_reason = ("killed via kill()" if no_restart
                               else "worker crashed")
            can_restart = (not no_restart and
                           (st.spec.max_restarts == -1 or
                            st.num_restarts < st.spec.max_restarts))
            st.restarting = can_restart
        if can_restart:
            backoff = GlobalConfig.actor_restart_backoff_ms / 1000.0

            def _restart():
                if backoff:
                    time.sleep(backoff)
                with st.lock:
                    st.num_restarts += 1
                    st.dead = False
                    st.restarting = False
                    st.created.clear()
                    st.instance = None
                # Threads keep draining the mailbox; the next task
                # triggers re-instantiation.
                with st.lock:
                    if not st.created.is_set():
                        st._instantiate()
            threading.Thread(target=_restart, daemon=True).start()
        else:
            self.pool.release(st.spec.resources)
            st.stop()
            with self._lock:
                if st.spec.name:
                    self._named_actors.pop(
                        (st.spec.namespace or "default", st.spec.name),
                        None)

    # --- placement groups --------------------------------------------------

    def create_placement_group(self, spec: PlacementGroupSpec
                               ) -> PlacementGroup:
        pg = PlacementGroup(spec, self)
        with self._lock:
            self._pgs[spec.pg_id] = pg
        total: Dict[str, float] = {}
        for b in spec.bundles:
            for k, v in b.resources.items():
                total[k] = total.get(k, 0.0) + v

        def _reserve():
            deadline = time.time() + 300
            while True:
                if pg._removed:
                    return
                if self.pool.try_acquire(total):
                    break
                if not self.pool.can_ever_fit(total):
                    return  # infeasible: never ready (caller times out)
                if time.time() > deadline:
                    return
                time.sleep(0.005)
            with pg._state_lock:
                if pg._removed:
                    # Removed while we were acquiring: give it back.
                    self.pool.release(total)
                    return
                pg._ready_event.set()
        threading.Thread(target=_reserve, daemon=True).start()
        return pg

    def remove_placement_group(self, pg: PlacementGroup):
        with self._lock:
            self._pgs.pop(pg.id, None)
        with pg._state_lock:
            pg._removed = True
            was_ready = pg.is_ready()
            pg._ready_event.clear()
        if was_ready:
            total: Dict[str, float] = {}
            for b in pg.spec.bundles:
                for k, v in b.resources.items():
                    total[k] = total.get(k, 0.0) + v
            self.pool.release(total)

    # --- introspection -----------------------------------------------------

    def cluster_resources(self) -> Dict[str, float]:
        return dict(self.pool.total)

    def available_resources(self) -> Dict[str, float]:
        return dict(self.pool.available)

    def list_actors(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = []
            for aid, st in self._actors.items():
                out.append({
                    "actor_id": aid.hex(),
                    "class_name": st.spec.cls.__name__,
                    "state": ("DEAD" if st.dead else
                              "RESTARTING" if st.restarting else "ALIVE"),
                    "name": st.spec.name or "",
                    "num_restarts": st.num_restarts,
                    "pending_tasks": st.pending_count,
                })
            return out

    def list_tasks(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{"task_id": tid.hex(),
                     "name": spec.name,
                     "state": self._task_states.get(tid, "UNKNOWN")}
                    for tid, spec in self._tasks_by_id.items()]

    def list_objects(self) -> List[Dict[str, Any]]:
        out = []
        for oid in self.store.keys():
            out.append({"object_id": oid.hex(),
                        "ref_count": self.ref_counter.ref_count(oid),
                        "ready": self.store.contains(oid)})
        return out

    # --- shutdown ----------------------------------------------------------

    def shutdown(self):
        self._shutdown = True
        if self._memory_monitor is not None:
            self._memory_monitor.stop()
        self._kick_scheduler()
        with self._lock:
            actors = list(self._actors.values())
        for st in actors:
            st.stop()
        self.ref_counter.enabled = False
