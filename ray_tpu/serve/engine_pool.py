"""EnginePool: N LLMEngine replicas behaving as ONE logical engine.

After chunked prefill, the radix prefix cache, spec decode, and
lifecycle hardening, the serving stack still terminated in a single
``LLMEngine`` — one replica was both the throughput ceiling and the
blast radius. This module is the data-parallel control plane that
removes that ceiling the way the reference runtime scales serving:
many identical accelerator-bound workers behind a thin, load-aware
router (Ray's replica sets + power-of-two-choices; Podracer-style
TPU fleets).

Routing policy, in precedence order (``_route``):

1. **Session stickiness** — a ``session_id`` keeps hitting the
   replica that served it last (its KV prefix lives there), unless
   that replica is gone or saturated.
2. **Longest-prefix affinity** — each replica's ``load_report()``
   carries a digest of its radix prefix cache (rolling path hashes,
   ``prefix_cache.path_hashes``). The prompt is hashed once and the
   replica holding its longest cached prefix wins, so the PR-2 radix
   cache COMPOUNDS across the fleet instead of fragmenting: without
   affinity, a shared system prompt gets re-prefilled on every
   replica it happens to land on.
3. **Spill** — when the affinity target is saturated (bounded queue
   full), the request spills to the least-loaded healthy replica
   instead of queueing behind its hot spot. The spill target then
   caches the prefix too, so sustained hot prefixes replicate
   themselves exactly as wide as their load requires.
4. **Power-of-two-choices** on least outstanding tokens — the
   classic load-balancing result: sampling two replicas and taking
   the lighter one gets within a constant of optimal at O(1) cost.

Replica lifecycle, owned by the pool:

- **Draining** (``drain(idx)``): the replica admits nothing new
  (direct submits fail typed ``EngineDraining``), finishes in-flight
  work, shuts down, and is rebuilt from the factory — a rolling
  config update with zero failed requests when work fits the drain
  budget.
- **Failure recovery**: when a replica dies (device loss, injected
  ``ReplicaKilled``, any global ``_fail_all``), requests that have
  not streamed a single token resubmit transparently to a healthy
  replica (at-most-once delivery holds: nothing was observed, so
  the retry cannot duplicate). Requests that already streamed fail
  TYPED with ``EngineShutdown`` — replaying a partial greedy stream
  exactly-once cannot be guaranteed, so the pool refuses to guess.
- **Aggregate shed**: when every healthy replica sheds, the pool
  raises one ``EngineOverloaded`` whose ``retry_after_s`` is the MAX
  over replicas — an honest Retry-After even when only the slowest
  replica is the bottleneck (the proxy maps it to 429).

The pool mirrors the single-engine surface the deployment layer uses
(``submit``/``stats``/``ttfts_s``/``prefix_stats``/``spec_stats``/
``lifecycle_stats``/``shutdown``), so ``num_engine_replicas=N`` is a
one-knob change in ``serve/llm.py``.
"""
from __future__ import annotations

import collections
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ray_tpu.serve import kv_migration, obs
from ray_tpu.serve.errors import (DeadlineExceeded, EngineDraining,
                                  EngineOverloaded, EngineShutdown,
                                  PoolDegraded, RequestCancelled,
                                  RequestError)
from ray_tpu.serve.fleet.routing import (Candidate, ResubmitPolicy,
                                         select_candidate)
from ray_tpu.serve.prefix_cache import path_hashes
from ray_tpu.serve.scheduler import (LANE_BATCH, LANE_ONLINE,
                                     REPLICA_ROLES, ROLE_DECODE,
                                     ROLE_PREFILL, ROLE_UNIFIED)

ROUTED = "serve_pool_routed_total"
AFFINITY_HITS = "serve_pool_affinity_hits_total"
STICKY_HITS = "serve_pool_sticky_hits_total"
SPILLS = "serve_pool_spills_total"
REQUEUES = "serve_pool_requeues_total"
REPLICA_DEATHS = "serve_pool_replica_deaths_total"
DRAINS = "serve_pool_drains_total"
RESTARTS = "serve_pool_restarts_total"
ALL_SHED = "serve_pool_all_shed_total"
FREE_SLOTS = "serve_pool_replica_free_slots"
QUEUE_DEPTH = "serve_pool_replica_queue_depth"
BATCH_QUEUE_DEPTH = "serve_pool_replica_batch_queue_depth"
CAPACITY_HINT_ERRORS = "serve_pool_capacity_hint_errors_total"
SUSPECTS = "serve_pool_suspect_total"
WEDGED = "serve_pool_wedged_total"
WEDGE_LATENCY = "serve_pool_wedge_detect_latency_s"
DISAGG_HANDOFFS = "serve_disagg_handoffs_total"
DISAGG_FALLBACKS = "serve_disagg_handoff_fallbacks_total"

# Role sets the disaggregated router selects over: new prompts land
# on the prefill side, handed-off streams on the decode side. UNIFIED
# replicas serve both — they are the bridge that keeps a half-rolled
# (or degraded) disaggregated pool available.
_PREFILL_SIDE = (ROLE_PREFILL, ROLE_UNIFIED)
_DECODE_SIDE = (ROLE_DECODE, ROLE_UNIFIED)

_METRICS: Optional[dict] = None


def _metrics() -> dict:
    """Lazy module-level metric singletons, re-created if a test's
    ``clear_registry()`` dropped them (same pattern as the engine and
    prefix-cache modules)."""
    global _METRICS
    from ray_tpu.util import metrics
    if (_METRICS is None
            or metrics.registry().get(ROUTED)
            is not _METRICS["routed"]):
        _METRICS = {
            "routed": metrics.Counter(
                ROUTED, "Requests routed by the engine pool"),
            "affinity_hits": metrics.Counter(
                AFFINITY_HITS, "Routes landing on a replica already "
                "holding a prefix of the prompt"),
            "sticky_hits": metrics.Counter(
                STICKY_HITS, "Routes resolved by session stickiness"),
            "spills": metrics.Counter(
                SPILLS, "Affinity targets saturated; request spilled "
                "to another replica"),
            "requeues": metrics.Counter(
                REQUEUES, "Unstreamed requests resubmitted after a "
                "replica death"),
            "replica_deaths": metrics.Counter(
                REPLICA_DEATHS, "Replica engines observed dead"),
            "drains": metrics.Counter(
                DRAINS, "Replica drains started"),
            "restarts": metrics.Counter(
                RESTARTS, "Replica engines rebuilt from the factory"),
            "all_shed": metrics.Counter(
                ALL_SHED, "Pool-aggregate sheds (every healthy "
                "replica refused admission)"),
            "free_slots": metrics.Gauge(
                FREE_SLOTS, "Free decode slots per replica",
                tag_keys=("replica",)),
            "queue_depth": metrics.Gauge(
                QUEUE_DEPTH, "Admission queue depth per replica "
                "(ONLINE lane — the saturation/autoscaling signal)",
                tag_keys=("replica",)),
            "batch_queue_depth": metrics.Gauge(
                BATCH_QUEUE_DEPTH, "BATCH-lane queue depth per "
                "replica (preemptible backlog; excluded from "
                "saturation and autoscaling signals)",
                tag_keys=("replica",)),
            "capacity_hint_errors": metrics.Counter(
                CAPACITY_HINT_ERRORS, "capacity_hint_fn raised; the "
                "pool fell back to the pending-backoff ETA"),
            "suspects": metrics.Counter(
                SUSPECTS, "Replicas quarantined SUSPECT by the "
                "watchdog (stale heartbeat with work pending)"),
            "wedged": metrics.Counter(
                WEDGED, "Replicas declared WEDGED and force-killed "
                "by the watchdog"),
            "wedge_latency": metrics.Histogram(
                WEDGE_LATENCY, "Seconds from last observed progress "
                "to the WEDGED declaration",
                boundaries=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                            10.0, 30.0)),
            "disagg_handoffs": metrics.Counter(
                DISAGG_HANDOFFS, "Prefill->decode stream handoffs "
                "submitted over the KV-migration path"),
            "disagg_fallbacks": metrics.Counter(
                DISAGG_FALLBACKS, "Handoffs aborted typed and fallen "
                "back to decoding in place"),
        }
    return _METRICS


HEALTHY = "healthy"
DRAINING = "draining"
DEAD = "dead"
# Watchdog quarantine (serve/watchdog.py): the replica's progress
# heartbeat went stale WITH work pending. Routing, capacity counts,
# and the autoscaler's healthy_replicas signal all skip it for free
# (everything filters on HEALTHY); the watchdog either clears it back
# to HEALTHY on probed progress or escalates to the death path.
SUSPECT = "suspect"
# Scale-down tombstone: the replica was drained and shut down ON
# PURPOSE and will not be rebuilt; its slot index may be reused by a
# later scale-up. Kept in the table so pool-wide quiescence checks
# still cover its engine.
RETIRED = "retired"
# Crash-loop terminal state: the replica died ``max_restarts`` times
# and the pool stopped rebuilding it. Routing skips it; a human (or
# ``restart_dead()``) has to intervene.
DEGRADED = "degraded"


class _Replica:
    """One pool slot: a live engine plus its lifecycle state.
    ``generation`` counts factory rebuilds (drain restarts + failure
    restarts) so tests can assert a replica was actually replaced."""

    __slots__ = ("idx", "engine", "state", "deaths", "generation",
                 "role")

    def __init__(self, idx: int, engine, state: str = HEALTHY,
                 deaths: int = 0, generation: int = 0,
                 role: str = ROLE_UNIFIED):
        self.idx = idx
        self.engine = engine
        self.state = state
        self.deaths = deaths
        self.generation = generation
        self.role = role


class PoolRequestHandle(ResubmitPolicy):
    """Client-side view of a pooled request. Mirrors the engine's
    ``RequestHandle`` surface (stream/result/cancel/done/error/
    ttft_s) and adds the recovery loop: iterating ``stream()`` (or
    ``result()``) transparently resubmits the request to a healthy
    replica when its replica dies BEFORE any token was delivered;
    after first delivery a replica death fails typed
    ``EngineShutdown`` — never a silent hang, never a duplicated
    token. The at-most-once guard itself (budget, deadline carry,
    partial-stream refusal) is ``fleet.routing.ResubmitPolicy``,
    shared with the process-separated ``FleetRouter``."""

    def __init__(self, pool: "EnginePool", prompt: List[int],
                 max_new_tokens: int, deadline_s: Optional[float],
                 session_id: Optional[str],
                 trace_id: Optional[str] = None,
                 priority: str = LANE_ONLINE):
        super().__init__(prompt, max_new_tokens, deadline_s,
                         session_id, trace_id,
                         max_resubmits=pool.max_resubmits)
        self._pool = pool
        self._priority = priority
        self._rep: Optional[_Replica] = None
        self._inner = None
        # Disaggregated two-leg service (set by the pool at submit
        # when the request was split): leg 1 streams ONE bridging
        # token from the prefill pool, leg 2 resumes the stream on a
        # decode replica over the KV-migration handoff path.
        self._disagg = False

    # ------------------------------------------------------- consuming

    def stream(self):
        """Yield generated token ids; recover across replica deaths
        while the at-most-once guard allows (zero tokens delivered)."""
        if self._disagg:
            yield from self._stream_disagg()
            return
        while True:
            rep, inner = self._rep, self._inner
            try:
                for tok in inner.stream():
                    self._note_token(tok)
                    yield tok
                self._finished = True
                return
            except GeneratorExit:
                # consumer closed the stream (disconnect): not a
                # failure, and certainly not a resubmission trigger
                raise
            except (RequestCancelled, DeadlineExceeded,
                    EngineOverloaded, EngineDraining) as e:
                # request-level outcomes: the pool never second-
                # guesses an explicit cancel/deadline/shed
                self._fail(e)
                raise
            except BaseException as e:
                # EngineShutdown, a contained-fault wrapper, or the
                # RAW global error a _fail_all delivered (e.g.
                # ReplicaKilled). Replica death is judged by the
                # engine, not the exception type.
                if not self._pool._note_replica_death(rep):
                    self._fail(e)
                    raise
                if self._generated or self._cancelled:
                    raise self._partial_stream_error(
                        str(rep.idx), e) from e
                self._resubmit(e)      # raises typed when impossible

    def _stream_disagg(self):
        """Two-leg disaggregated stream. Leg 1 (already submitted by
        the pool): one bridging token on the prefill side — the
        engine retires the slot after it, publishing the prompt's
        full KV pages into the donor's prefix cache. Leg 2: the rest
        of the stream on the decode side, admitted with a
        finished-prefill push hint so its KV lands over
        ``kv_migration.pull_prefix`` (mid-offset resume at full
        prompt length) instead of recomputing. Greedy fp32 decoding
        is deterministic, so the stitched stream is token-identical
        to single-replica service.

        Failure contract (the tentpole's "cost time, never
        correctness"): every way leg 2 can fail BEFORE its first
        token is one typed abort that falls back to decoding in
        place on the prefill replica (then, if the donor itself is
        gone, to any healthy replica via plain prefill). After leg 2
        streams, a death fails typed exactly like the base loop —
        per-leg at-most-once."""
        pool = self._pool
        first: Optional[int] = None
        # ---- leg 1: bridging token from the prefill pool
        while True:
            rep, inner = self._rep, self._inner
            try:
                for tok in inner.stream():
                    self._note_token(tok)
                    first = tok
                break
            except GeneratorExit:
                raise
            except (RequestCancelled, DeadlineExceeded,
                    EngineOverloaded, EngineDraining) as e:
                self._fail(e)
                raise
            except BaseException as e:
                if not pool._note_replica_death(rep):
                    self._fail(e)
                    raise
                if first is not None:
                    break     # token landed; only the donor is gone
                if self._cancelled:
                    raise self._partial_stream_error(
                        str(rep.idx), e) from e
                deadline = self._check_resubmit(e)
                pool._count_requeue(trace_id=self._trace_id)
                try:
                    self._rep, self._inner = pool._submit_leg(
                        self._prompt, 1, deadline, None,
                        trace_id=self._trace_id, roles=_PREFILL_SIDE,
                        fallback_any=True)
                except BaseException as e2:
                    self._fail(e2)
                    raise
        if first is None:
            # engine contract: a non-failing stream emits >= 1 token
            err = EngineShutdown(
                "prefill leg closed without a token")
            self._fail(err)
            raise err
        yield first
        if self._mnt <= 1 or self._cancelled:
            self._finished = True
            return
        # ---- handoff: decode leg resumes at full prompt length
        donor = self._rep
        prompt2 = self._prompt + [first]
        mnt2 = self._mnt - 1
        self._rep = self._inner = None
        self._hand_off(donor, prompt2, mnt2)
        # ---- leg 2: stream on the decode side
        leg2_tokens = 0
        while True:
            rep, inner = self._rep, self._inner
            try:
                for tok in inner.stream():
                    if leg2_tokens == 0:
                        pool._note_handoff_first_token(
                            rep, trace_id=self._trace_id)
                    leg2_tokens += 1
                    self._note_token(tok)
                    yield tok
                self._finished = True
                return
            except GeneratorExit:
                raise
            except (RequestCancelled, DeadlineExceeded,
                    EngineOverloaded, EngineDraining) as e:
                self._fail(e)
                raise
            except BaseException as e:
                if not pool._note_replica_death(rep):
                    self._fail(e)
                    raise
                if leg2_tokens or self._cancelled:
                    raise self._partial_stream_error(
                        str(rep.idx), e) from e
                self._check_resubmit(e)
                pool._count_requeue(trace_id=self._trace_id)
                self._hand_off(donor, prompt2, mnt2)

    def _hand_off(self, donor: Optional[_Replica],
                  prompt2: List[int], mnt2: int) -> None:
        """Submit the decode leg: decode-side route with the
        finished-prefill push hint, then the typed-abort fallback
        ladder — decode in place on the donor, then any healthy
        replica (plain prefill). Raises typed only when no replica
        at all can take the stream."""
        pool = self._pool
        deadline = self._remaining_deadline(None) \
            if self._deadline_s is not None else None
        donor_live = (donor is not None
                      and not getattr(donor.engine, "_stopped", True))
        hint = None
        if donor_live:
            hint = kv_migration.prefill_push_hint(
                self._prompt, getattr(donor.engine, "Pg", 0),
                replica_idx=donor.idx)
        try:
            self._rep, self._inner = pool._submit_leg(
                prompt2, mnt2, deadline, self._session_id,
                trace_id=self._trace_id, roles=_DECODE_SIDE,
                pull=hint,
                exclude={donor.idx} if donor_live else None)
            pool._note_handoff(donor, self._rep,
                               trace_id=self._trace_id)
            return
        except (RequestCancelled, DeadlineExceeded) as e:
            self._fail(e)
            raise
        except BaseException as e:
            cause = e
        # Typed abort -> decode in place on the prefill replica: its
        # prefix cache already holds the prompt's pages, so this is a
        # local-hit residual prefill, not a recompute.
        pool._note_handoff_fallback(donor, cause,
                                    trace_id=self._trace_id)
        if donor_live:
            try:
                self._rep, self._inner = pool._submit_once(
                    prompt2, mnt2, deadline, None,
                    trace_id=self._trace_id, target_idx=donor.idx,
                    record_sticky=False)
                return
            except (RequestCancelled, DeadlineExceeded) as e:
                self._fail(e)
                raise
            except BaseException:
                pass          # donor died under us: last rung below
        # Donor gone too: any healthy replica, plain prefill.
        try:
            self._rep, self._inner = pool._submit_once(
                prompt2, mnt2, deadline, self._session_id,
                trace_id=self._trace_id)
        except BaseException as e:
            self._fail(e)
            raise

    # ------------------------------------------------------- lifecycle

    def cancel(self) -> bool:
        self._cancelled = True
        inner = self._inner
        return inner.cancel() if inner is not None else False

    @property
    def replica_idx(self) -> Optional[int]:
        return self._rep.idx if self._rep is not None else None

    @property
    def replica_tag(self) -> Optional[str]:
        """``idx:generation`` of the serving replica incarnation —
        a resubmit that lands on a rebuilt replica of the SAME idx
        still shows a different tag (the X-Replica header value)."""
        rep = self._rep
        return (f"{rep.idx}:{rep.generation}"
                if rep is not None else None)

    @property
    def weights_tag(self) -> Optional[str]:
        """``generation:weights_id`` of the serving replica's engine
        (the X-Model-Generation header value). A resubmit that lands
        mid-rollout on a replica serving a different payload shows a
        different tag."""
        rep = self._rep
        eng = getattr(rep, "engine", None) if rep is not None else None
        gen = getattr(eng, "weight_generation", None)
        if gen is None:
            return None
        return f"{gen}:{getattr(eng, 'weights_id', None)}"

    @property
    def logprobs(self) -> Optional[List[float]]:
        """Per-token sampling logprobs from the serving replica's
        handle (engines built with ``capture_logprobs=True``; None
        otherwise). A death-triggered resubmit regenerates from
        scratch on the new replica, so the list always reflects one
        engine's aligned token stream — never a stitched mix."""
        inner = self._inner
        if inner is None:
            return None
        return getattr(inner, "logprobs", None)

    # -------------------------------------------------------- internal

    def _resubmit(self, cause: BaseException) -> None:
        deadline = self._check_resubmit(cause)
        self._pool._count_requeue(trace_id=self._trace_id)
        try:
            self._rep, self._inner = self._pool._submit_once(
                self._prompt, self._mnt, deadline, self._session_id,
                trace_id=self._trace_id, priority=self._priority)
        except BaseException as e:
            self._fail(e)
            raise

    def _attach(self, rep: _Replica, inner) -> None:
        self._rep, self._inner = rep, inner


class EnginePool:
    """N ``LLMEngine`` replicas as one logical engine (module
    docstring has the full routing + lifecycle contract).

    Parameters
    ----------
    engine_factory: ``f(replica_idx) -> LLMEngine`` building ONE
        replica (not started; the pool starts it). Called again on
        drain-restart and failure-restart, so config changes in the
        factory roll out via ``rolling_restart``.
    num_replicas: pool width.
    auto_restart: rebuild dead replicas in the background. Off by
        default so tests (and capacity accounting) see deterministic
        pool shapes; deployments turn it on.
    max_resubmits: per-request cap on death-triggered resubmissions
        (default ``num_replicas``): a request that outlives that many
        replicas fails typed instead of looping.
    restart_backoff_s / restart_backoff_max_s: exponential backoff
        between auto-restarts of a dying replica (base doubles per
        death, capped). Without it a crash-looping factory rebuilds
        hot in a tight loop.
    max_restarts: per-replica death cap; once exceeded the replica
        parks in ``DEGRADED`` instead of rebuilding, and a pool with
        no healthy replicas left raises typed ``PoolDegraded``.
        ``None`` = unlimited (the pre-backoff behavior).
    seed: P2C sampling seed (deterministic tests).
    """

    def __init__(self, engine_factory: Callable[[int], Any],
                 num_replicas: int, *,
                 auto_restart: bool = False,
                 max_resubmits: Optional[int] = None,
                 max_sticky_sessions: int = 4096,
                 restart_backoff_s: float = 0.05,
                 restart_backoff_max_s: float = 5.0,
                 max_restarts: Optional[int] = 5,
                 share_prefixes: bool = False,
                 roles: Optional[Sequence[str]] = None,
                 kv_pull_deadline_s: Optional[float] = None,
                 kv_pull_backoff_s: Optional[float] = None,
                 seed: int = 0):
        if num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        if roles is None:
            roles = [ROLE_UNIFIED] * num_replicas
        else:
            roles = list(roles)
            if len(roles) != num_replicas:
                raise ValueError(
                    f"roles must name every replica: got "
                    f"{len(roles)} roles for {num_replicas} replicas")
            for role in roles:
                if role not in REPLICA_ROLES:
                    raise ValueError(
                        f"unknown replica role {role!r}; expected "
                        f"one of {sorted(REPLICA_ROLES)}")
            if (any(r != ROLE_UNIFIED for r in roles)
                    and not share_prefixes):
                # the handoff path IS the share_prefixes KV wiring;
                # a disaggregated pool without it would re-prefill
                # every handed-off stream from scratch
                raise ValueError(
                    "role-disaggregated pools require "
                    "share_prefixes=True (the KV handoff path)")
        self._factory = engine_factory
        # Requester-side KV pull knob overrides (None = pull_prefix
        # defaults), validated typed here at construction
        self._kv_pull_knobs = kv_migration.validate_pull_knobs(
            kv_pull_deadline_s, kv_pull_backoff_s)
        self._lock = threading.Lock()
        self._rng = random.Random(seed)
        self._auto_restart = auto_restart
        self.max_resubmits = (max_resubmits if max_resubmits
                              is not None else num_replicas)
        self._max_sticky = max_sticky_sessions
        self.restart_backoff_s = max(0.0, float(restart_backoff_s))
        self.restart_backoff_max_s = max(
            self.restart_backoff_s, float(restart_backoff_max_s))
        self.max_restarts = max_restarts
        # installed by an attached PoolAutoscaler: returns the ETA (s)
        # until in-flight provisioned capacity joins the pool, so an
        # all-shed Retry-After never invites a client back BEFORE the
        # capacity that would serve it exists
        self.capacity_hint_fn: Optional[Callable[[], float]] = None
        self._autoscaler = None      # attached PoolAutoscaler, if any
        self._watchdog = None        # attached PoolWatchdog, if any
        self._sticky: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        # pool-level routing/lifecycle counters (the engines keep
        # their own ``stats``; ``EnginePool.stats`` aggregates those)
        self.route_stats: Dict[str, int] = collections.Counter()
        # typed pool event log (serve/obs.py): routing decisions,
        # resubmits, drains, SUSPECT/WEDGED transitions, replica
        # deaths/restarts, autoscaler decisions — one ring per pool,
        # merged with engine rings by the trace exporter
        self.events = obs.EventLog(2048, name="pool")
        self._stopped = False
        # Global prefix cache (share_prefixes=True): per-replica KV
        # donors so a route landing on a cold replica PULLS the hot
        # prefix's pages from the replica that already holds them
        # instead of recomputing. Donors go through
        # ``kv_migration.loopback_call`` — the JSON+b64 wire toll is
        # paid even in-process, so the pool and the fleet share one
        # transfer contract.
        self._share_prefixes = bool(share_prefixes)
        self._kv_donors: Dict[int, kv_migration.KVDonor] = {}
        # role -> RolePoolView, registered by the views themselves:
        # per-role autoscaler attachment points + pool_stats blocks
        self._role_views: Dict[str, Any] = {}
        # Current-weights source (live rollout, serve/weight_rollout):
        # the factory closes over the ORIGINAL params, so without this
        # a replica rebuilt after a mid-rollout death would rejoin the
        # fleet on stale weights. ``set_weight_source`` records the
        # payload every rebuild/add must be re-stamped to.
        self._weight_source: Optional[Dict[str, Any]] = None
        self._replicas: List[_Replica] = []
        for i in range(num_replicas):
            eng = engine_factory(i)
            self._stamp_role(eng, roles[i])
            self._stamp_replica_tag(eng, i)
            eng.start()
            rep = _Replica(i, eng, role=roles[i])
            self._replicas.append(rep)
            self._wire_kv(rep)

    @staticmethod
    def _stamp_role(engine, role: str) -> None:
        """Stamp a replica's role onto its engine AFTER the factory
        built it — one ``f(idx)`` factory serves both pools, and the
        role only steers dynamic decisions (planner caps via
        ``role_plan_caps``, load_report stamp). Engines without the
        attribute (test fakes) are left alone: routing treats a
        missing role as unified."""
        try:
            engine.role = role
        except Exception:
            pass

    @staticmethod
    def _stamp_replica_tag(engine, idx: int) -> None:
        """Stamp the pool index onto the engine so its per-replica
        metrics (the ``serve_weight_generation`` gauge) are
        attributable. Same best-effort contract as ``_stamp_role``."""
        try:
            engine.replica_tag = str(idx)
        except Exception:
            pass

    def _restamp_weights(self, rep: _Replica) -> None:
        """Bring a freshly built replica onto the pool's CURRENT
        weights. The engine factory closes over the original params;
        when a rollout has moved the fleet past them, a rebuilt or
        added replica must not rejoin on generation 0 — that is the
        kill-mid-swap hole. Best-effort: a failure leaves the replica
        serving factory weights and is evented (the rollout
        controller's convergence check will see the lagging
        weights_id)."""
        src = self._weight_source
        eng = rep.engine
        if src is None or not hasattr(eng, "swap_weights"):
            return
        try:
            eng.swap_weights(src["params"],
                             generation=src["generation"],
                             weights_id=src["weights_id"])
            self.events.append("weight_restamp", sid=rep.idx,
                               data={"generation": src["generation"],
                                     "weights_id": src["weights_id"]})
        except Exception as e:  # noqa: BLE001
            self.events.append("weight_restamp_failed", sid=rep.idx,
                               data={"error": repr(e)})

    def set_weight_source(self, params, *, weights_id: str,
                          generation: int) -> None:
        """Record the payload every future rebuild/add re-stamps to
        (``None``-free contract: call after each completed rollout or
        rollback so replica churn converges on the fleet's current
        weights, not the factory's)."""
        with self._lock:
            self._weight_source = {"params": params,
                                   "weights_id": weights_id,
                                   "generation": int(generation)}
        self.events.append("weight_source", data={
            "generation": int(generation), "weights_id": weights_id})

    def swap_replica_weights(self, idx: int, params, *,
                             weights_id: Optional[str] = None,
                             generation: Optional[int] = None,
                             mode: str = "preempt") -> int:
        """Hot-swap ONE replica's weights through the engine's
        generation fence (``LLMEngine.swap_weights``). The staged
        rollout controller drives canary waves through this. Returns
        the generation now serving on that replica."""
        with self._lock:
            rep = self._replicas[idx]
            if rep.state not in (HEALTHY, SUSPECT):
                raise RuntimeError(
                    f"replica {idx} is {rep.state}; only live "
                    f"replicas can swap weights")
        try:
            gen = rep.engine.swap_weights(params,
                                          generation=generation,
                                          weights_id=weights_id,
                                          mode=mode)
        except BaseException:
            # A swap that dies WITH its replica is how the rollout
            # controller meets a corpse. Deaths are otherwise noted
            # lazily, by routed traffic: without this the replica
            # still reads HEALTHY and the controller's bounded retry
            # burns every attempt on the corpse within a millisecond
            # instead of waiting for the rebuild.
            self._note_replica_death(rep)
            raise
        with self._lock:
            self.route_stats["weight_swaps"] += 1
        self.events.append("weight_swap", sid=idx,
                           data={"generation": gen,
                                 "weights_id": rep.engine.weights_id,
                                 "mode": mode})
        return gen

    # --------------------------------------------------------- public

    @property
    def n_replicas(self) -> int:
        return len(self._replicas)

    def engines(self) -> List[Any]:
        """Every replica engine, regardless of state (quiescence
        checks cover dead replicas too — a crash must not leak)."""
        return [r.engine for r in self._replicas]

    def replica(self, idx: int) -> _Replica:
        return self._replicas[idx]

    def healthy_count(self) -> int:
        with self._lock:
            return sum(1 for r in self._replicas
                       if r.state == HEALTHY)

    def active_count(self) -> int:
        """Replicas currently holding capacity (anything but a
        scale-down tombstone) — the autoscaler's notion of pool
        size, and the bench's chip-count at any instant."""
        with self._lock:
            return sum(1 for r in self._replicas
                       if r.state != RETIRED)

    @property
    def degraded(self) -> bool:
        """True when any replica burned through its restart cap."""
        with self._lock:
            return any(r.state == DEGRADED for r in self._replicas)

    def disaggregated(self) -> bool:
        """True while a healthy prefill-role replica exists — the
        condition under which new online requests split into the
        two-leg prefill -> decode service. Recomputed per submit on
        purpose: when the last prefill replica dies, the pool
        degrades to unified service instead of stranding traffic."""
        with self._lock:
            return any(r.role == ROLE_PREFILL and r.state == HEALTHY
                       for r in self._replicas)

    def role_counts(self) -> Dict[str, int]:
        """Active (non-retired) replica count per role."""
        out: Dict[str, int] = collections.Counter()
        with self._lock:
            for r in self._replicas:
                if r.state != RETIRED:
                    out[r.role] += 1
        return dict(out)

    def submit(self, prompt_ids: Sequence[int],
               max_new_tokens: int = 64,
               deadline_s: Optional[float] = None,
               session_id: Optional[str] = None,
               trace_id: Optional[str] = None,
               priority: str = LANE_ONLINE) -> PoolRequestHandle:
        """Route and queue one request (engine ``submit`` signature
        plus ``session_id`` for stickiness and ``trace_id`` for
        request-scope tracing — the id survives replica-death
        resubmits because the handle re-sends it). Raises exactly
        like a single engine: validation ``RequestError``
        immediately, pool-aggregate ``EngineOverloaded`` when every
        healthy replica sheds, ``EngineShutdown`` when none is
        left.

        ``priority=LANE_BATCH`` routes through the batch spill path:
        least batch-backlog replica, skipping session stickiness and
        prefix affinity entirely — batch work soaks whatever replica
        is emptiest and NEVER claims (or pollutes) the sticky/affinity
        placement online traffic depends on. The lane rides replica-
        death resubmits unchanged."""
        if self._stopped:
            raise EngineShutdown("engine pool stopped")
        prompt = [int(t) for t in prompt_ids]
        handle = PoolRequestHandle(self, prompt, max_new_tokens,
                                   deadline_s, session_id, trace_id,
                                   priority=priority)
        if (priority == LANE_ONLINE and max_new_tokens > 1
                and self.disaggregated()):
            # Two-leg disaggregated service: leg 1 takes ONE token
            # on the prefill side (session stickiness deliberately
            # unused — a sticky entry must never pin a session to a
            # prefill replica). If the prefill side cannot admit at
            # all, serve unified below — disaggregation degrades,
            # availability doesn't.
            try:
                rep, inner = self._submit_once(
                    prompt, 1, deadline_s, None, trace_id=trace_id,
                    roles=_PREFILL_SIDE)
                handle._disagg = True
                handle._attach(rep, inner)
                return handle
            except (EngineShutdown, PoolDegraded):
                pass
        rep, inner = self._submit_once(prompt, max_new_tokens,
                                       deadline_s, session_id,
                                       trace_id=trace_id,
                                       priority=priority)
        handle._attach(rep, inner)
        return handle

    def submit_rollout_batch(self, prompts: Sequence[Sequence[int]],
                             max_new_tokens: int = 64,
                             deadline_s: Optional[float] = None,
                             trace_id: Optional[str] = None
                             ) -> List[PoolRequestHandle]:
        """Rollout-batch submit surface (ray_tpu/rl): one BATCH-lane
        request per prompt, routed through the batch spill path
        (least-backlog replica, no stickiness/affinity claims), in
        order. Mirrors ``LLMEngine.submit_rollout_batch`` so the RL
        generator drives a single engine and a pool through one
        interface; per-token logprobs ride the handles when the
        replica engines were built with ``capture_logprobs=True``."""
        return [self.submit(list(p), max_new_tokens=max_new_tokens,
                            deadline_s=deadline_s,
                            trace_id=(f"{trace_id}:{i}"
                                      if trace_id else None),
                            priority=LANE_BATCH)
                for i, p in enumerate(prompts)]

    def _submit_leg(self, prompt: List[int], max_new_tokens: int,
                    deadline_s: Optional[float],
                    session_id: Optional[str], *,
                    trace_id: Optional[str] = None,
                    roles: Optional[Sequence[str]] = None,
                    pull: Optional[Dict[str, Any]] = None,
                    exclude: Optional[set] = None,
                    fallback_any: bool = False):
        """One leg of a disaggregated request: a role-filtered
        ``_submit_once``, optionally degrading to an unrestricted
        route when the whole role side is gone (leg-1 resubmits —
        a dead prefill pool must not strand a request a decode
        replica could still serve, slowly, via plain prefill)."""
        try:
            return self._submit_once(prompt, max_new_tokens,
                                     deadline_s, session_id,
                                     trace_id=trace_id, roles=roles,
                                     pull=pull, exclude=exclude)
        except (EngineShutdown, PoolDegraded):
            if not fallback_any:
                raise
            return self._submit_once(prompt, max_new_tokens,
                                     deadline_s, session_id,
                                     trace_id=trace_id)

    # -------------------------------------------- handoff bookkeeping

    def _note_handoff(self, donor: Optional[_Replica],
                      target: _Replica,
                      trace_id: Optional[str] = None) -> None:
        with self._lock:
            self.route_stats["disagg_handoffs"] += 1
        self.events.append(
            "handoff", sid=target.idx,
            data={"from": donor.idx if donor is not None else None,
                  "to": target.idx, "trace_id": trace_id})
        _metrics()["disagg_handoffs"].inc()

    def _note_handoff_first_token(self, target: _Replica,
                                  trace_id: Optional[str] = None
                                  ) -> None:
        """First decode token on the new replica — the closing edge
        of the handoff-latency interval tools/trace_report.py
        derives (prefill-done is the ``handoff`` event above)."""
        self.events.append("handoff_first_token", sid=target.idx,
                           data={"to": target.idx,
                                 "trace_id": trace_id})

    def _note_handoff_fallback(self, donor: Optional[_Replica],
                               cause: BaseException,
                               trace_id: Optional[str] = None
                               ) -> None:
        with self._lock:
            self.route_stats["disagg_handoff_fallbacks"] += 1
        self.events.append(
            "handoff_fallback",
            sid=donor.idx if donor is not None else None,
            data={"error": repr(cause), "trace_id": trace_id})
        _metrics()["disagg_fallbacks"].inc()

    def shutdown(self) -> None:
        """Stop every replica; queued/in-flight requests fail typed
        ``EngineShutdown`` (per-engine contract). Idempotent."""
        self._stopped = True
        for rep in self._replicas:
            try:
                rep.engine.shutdown()
            except Exception:
                pass
            rep.state = DEAD

    # ------------------------------------------------------- lifecycle

    def drain(self, idx: int, timeout_s: float = 30.0) -> bool:
        """Gracefully restart replica ``idx``: stop admitting, let
        in-flight work finish (up to ``timeout_s``), shut down, and
        rebuild from the factory. Returns True when the drain
        completed with no work left (nobody failed); False when the
        budget expired and stragglers were axed — those fail typed
        and unstreamed ones recover via resubmission, so the restart
        still converges."""
        clean = self._drain_out(idx, timeout_s)
        self._rebuild(idx)
        return clean

    def _drain_out(self, idx: int, timeout_s: float) -> bool:
        """The health-gated half of a drain: stop admitting, wait for
        in-flight work (bounded), shut down. Shared by ``drain``
        (which rebuilds after) and ``retire`` (which doesn't)."""
        with self._lock:
            rep = self._replicas[idx]
            if rep.state != HEALTHY:
                raise RuntimeError(
                    f"replica {idx} is {rep.state}; only a healthy "
                    f"replica can drain")
            rep.state = DRAINING
            self.route_stats["drains"] += 1
            self._drop_sticky_locked(idx)
        self.events.append("drain", sid=idx)
        _metrics()["drains"].inc()
        eng = rep.engine
        eng.drain()
        clean = eng.wait_idle(timeout_s)
        try:
            eng.shutdown()
        except Exception:
            pass
        return clean

    # -------------------------------------------------------- scaling

    def add_replica(self, role: str = ROLE_UNIFIED) -> int:
        """Scale up by one: build a fresh engine from the factory,
        reusing a retired slot index when one exists (its generation
        bumps) or appending a new one. ``role`` places the new
        capacity in a disaggregated pool's prefill or decode side
        (default unified). Returns the replica index."""
        if self._stopped:
            raise EngineShutdown("engine pool stopped")
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"unknown replica role {role!r}; expected one of "
                f"{sorted(REPLICA_ROLES)}")
        with self._lock:
            retired = [r for r in self._replicas
                       if r.state == RETIRED]
            idx = retired[0].idx if retired else len(self._replicas)
            if retired:
                retired[0].role = role  # _rebuild carries it over
        if retired:
            self._rebuild(idx)
        else:
            eng = self._factory(idx)
            self._stamp_role(eng, role)
            self._stamp_replica_tag(eng, idx)
            eng.start()
            rep = _Replica(idx, eng, role=role)
            with self._lock:
                self._replicas.append(rep)
            self._wire_kv(rep)
            self._restamp_weights(rep)
        with self._lock:
            self.route_stats["replicas_added"] += 1
        return idx

    def retire(self, idx: int, timeout_s: float = 30.0) -> bool:
        """Scale down replica ``idx`` through the SAME health-gated
        drain path as a rolling restart — admit nothing new, finish
        in-flight work, shut down — but leave a ``RETIRED`` tombstone
        instead of rebuilding. In-flight requests either complete
        normally (clean drain) or fail typed / resubmit under the
        at-most-once rule (budget expired), exactly like ``drain``.
        Returns the drain's cleanliness."""
        with self._lock:
            healthy = sum(1 for r in self._replicas
                          if r.state == HEALTHY)
            if healthy <= 1 and self._replicas[idx].state == HEALTHY:
                raise RuntimeError(
                    "refusing to retire the last healthy replica")
        clean = self._drain_out(idx, timeout_s)
        with self._lock:
            self._replicas[idx].state = RETIRED
            self.route_stats["replicas_retired"] += 1
        return clean

    def scale_down(self, n: int = 1, timeout_s: float = 30.0,
                   role: Optional[str] = None) -> List[int]:
        """Retire the ``n`` least-loaded healthy replicas (by
        outstanding tokens), never going below one healthy replica —
        per ROLE when ``role`` is given (a per-role autoscaler must
        never retire its side's last replica, even when the other
        side has plenty). Returns the retired indices."""
        with self._lock:
            candidates = [r for r in self._replicas
                          if r.state == HEALTHY
                          and (role is None or r.role == role)]
        n = min(n, len(candidates) - 1)
        if n <= 0:
            return []
        load = []
        for r in candidates:
            try:
                rpt = r.engine.load_report()
                load.append((rpt.get("outstanding_tokens", 0), r.idx))
            except Exception:
                load.append((0, r.idx))
        load.sort()
        out = []
        for _, idx in load[:n]:
            try:
                self.retire(idx, timeout_s)
            except RuntimeError:
                continue       # raced a death; replica count moved
            out.append(idx)
        return out

    def scale_to(self, n: int, timeout_s: float = 30.0) -> int:
        """Converge the pool to ``n`` active replicas (adds via the
        factory, removes via ``scale_down``'s drain path). Returns
        the resulting active count."""
        if n < 1:
            raise ValueError("scale_to target must be >= 1")
        while self.active_count() < n:
            self.add_replica()
        excess = self.active_count() - n
        if excess > 0:
            self.scale_down(excess, timeout_s)
        return self.active_count()

    def rolling_restart(self, timeout_s: float = 30.0) -> bool:
        """Drain-restart every replica in sequence (a config rollout
        when the factory closes over new knobs). True iff every
        drain was clean."""
        clean = True
        for idx in range(len(self._replicas)):
            clean = self.drain(idx, timeout_s) and clean
        return clean

    def restart_dead(self) -> int:
        """Rebuild every DEAD (and crash-loop DEGRADED — this is the
        manual override) replica now. Returns how many were
        rebuilt."""
        with self._lock:
            dead = [r.idx for r in self._replicas
                    if r.state in (DEAD, DEGRADED)]
        for idx in dead:
            self._rebuild(idx)
        return len(dead)

    def _rebuild(self, idx: int) -> None:
        old = self._replicas[idx]
        eng = self._factory(idx)
        self._stamp_role(eng, old.role)
        self._stamp_replica_tag(eng, idx)
        eng.start()
        with self._lock:
            self._replicas[idx] = _Replica(
                idx, eng, HEALTHY, deaths=old.deaths,
                generation=old.generation + 1, role=old.role)
            self.route_stats["restarts"] += 1
        self._wire_kv(self._replicas[idx])
        # kill-mid-swap closure: the factory built the engine on the
        # ORIGINAL params; converge it onto the pool's current weights
        self._restamp_weights(self._replicas[idx])
        self.events.append("restart", sid=idx,
                           data={"generation": old.generation + 1})
        _metrics()["restarts"].inc()

    # -------------------------------------------------- watchdog hooks

    def mark_suspect(self, rep: _Replica) -> bool:
        """HEALTHY -> SUSPECT (watchdog quarantine). The replica
        immediately stops counting as capacity everywhere — routing,
        ``healthy_count``, scale-down candidacy, autoscaler signals —
        because they all filter on HEALTHY. Returns False when the
        replica moved on (died, drained, replaced) since observed."""
        with self._lock:
            if (self._replicas[rep.idx] is not rep
                    or rep.state != HEALTHY):
                return False
            rep.state = SUSPECT
            self.route_stats["suspects"] += 1
            self._drop_sticky_locked(rep.idx)
        self.events.append("suspect", sid=rep.idx)
        _metrics()["suspects"].inc()
        return True

    def clear_suspect(self, rep: _Replica) -> bool:
        """SUSPECT -> HEALTHY: the probe saw progress (heartbeat
        advanced or work drained) — a long-but-moving dispatch, not a
        wedge. The replica resumes taking traffic."""
        with self._lock:
            if (self._replicas[rep.idx] is not rep
                    or rep.state != SUSPECT):
                return False
            rep.state = HEALTHY
        self.events.append("suspect_cleared", sid=rep.idx)
        return True

    def mark_wedged(self, rep: _Replica,
                    err: Optional[BaseException] = None,
                    stalled_for_s: Optional[float] = None) -> bool:
        """Declare a silent replica WEDGED and drive the EXISTING
        death path: ``force_kill`` the engine out-of-band (lock-free —
        the wedged scheduler thread holds the engine lock), which
        unblocks every consumer typed so unstreamed requests resubmit
        token-identically, then ``_note_replica_death`` marks it DEAD,
        counts the death, and schedules the backoff rebuild with a
        generation bump. Healthy replicas are never touched."""
        with self._lock:
            if (self._replicas[rep.idx] is not rep
                    or rep.state not in (HEALTHY, SUSPECT)):
                return False
            self.route_stats["wedged"] += 1
        self.events.append("wedged", sid=rep.idx,
                           data={"stalled_for_s": stalled_for_s,
                                 "error": repr(err) if err else None})
        m = _metrics()
        m["wedged"].inc()
        if stalled_for_s is not None:
            m["wedge_latency"].observe(stalled_for_s)
        try:
            rep.engine.force_kill(err)
        except Exception:
            pass
        return self._note_replica_death(rep)

    def _note_replica_death(self, rep: _Replica) -> bool:
        """Judge (and record) a replica death. True iff ``rep``'s
        engine has globally stopped — the discriminator between
        request-level failures (engine alive; not the pool's
        business) and replica-level ones (recoverable by routing
        around the corpse)."""
        if not getattr(rep.engine, "_stopped", False):
            return False
        restart = False
        transitioned = False
        with self._lock:
            if (self._replicas[rep.idx] is rep
                    and rep.state not in (DEAD, DEGRADED, RETIRED)):
                rep.state = DEAD
                rep.deaths += 1
                transitioned = True
                self.route_stats["replica_deaths"] += 1
                self._drop_sticky_locked(rep.idx)
                restart = self._auto_restart and not self._stopped
                if (restart and self.max_restarts is not None
                        and rep.deaths > self.max_restarts):
                    # crash loop: stop feeding the factory — park the
                    # replica DEGRADED until a human (or restart_dead)
                    # intervenes
                    restart = False
                    rep.state = DEGRADED
                    self.route_stats["crash_loops"] += 1
        if transitioned:
            self.events.append("replica_death", sid=rep.idx,
                               data={"deaths": rep.deaths,
                                     "state": rep.state})
            _metrics()["replica_deaths"].inc()
        # idempotent: unblocks every remaining consumer typed and
        # frees whatever the dead scheduler left behind
        try:
            rep.engine.shutdown()
        except Exception:
            pass
        if restart:
            # exponential backoff before the rebuild: first death
            # restarts after backoff_s, each further death doubles it
            # (capped), so a crash-looping factory cannot spin hot
            backoff = min(self.restart_backoff_max_s,
                          self.restart_backoff_s
                          * (2 ** (rep.deaths - 1)))
            threading.Thread(target=self._backoff_rebuild,
                             args=(rep, backoff),
                             name=f"pool-restart-{rep.idx}",
                             daemon=True).start()
        return True

    def _backoff_rebuild(self, rep: _Replica, backoff_s: float
                         ) -> None:
        if backoff_s > 0:
            time.sleep(backoff_s)
        with self._lock:
            # the world may have moved during the backoff: pool
            # stopped, replica replaced, or manually rebuilt already
            if (self._stopped or self._replicas[rep.idx] is not rep
                    or rep.state != DEAD):
                return
        self._rebuild(rep.idx)

    def _restart_eta_s(self) -> float:
        """Honest Retry-After for a pool with no healthy replica: the
        max of any in-flight provisioning ETA (autoscaler hint) and
        the longest pending auto-restart backoff — the soonest moment
        a retry could plausibly find capacity."""
        eta = 0.0
        if self.capacity_hint_fn is not None:
            try:
                eta = max(eta, float(self.capacity_hint_fn()))
            except Exception:
                # a raising provider hint must not poison the ETA:
                # fall back to the pending-backoff estimate below
                _metrics()["capacity_hint_errors"].inc()
        return max(eta, self._pending_backoff_eta_s())

    def _pending_backoff_eta_s(self) -> float:
        """Longest pending auto-restart backoff — the capacity ETA
        the pool can always compute from its own state, used as the
        fallback whenever ``capacity_hint_fn`` raises."""
        eta = 0.0
        if self._auto_restart:
            with self._lock:
                dead_deaths = [r.deaths for r in self._replicas
                               if r.state == DEAD]
            for deaths in dead_deaths:
                eta = max(eta, min(
                    self.restart_backoff_max_s,
                    self.restart_backoff_s
                    * (2 ** max(0, deaths - 1))))
        return eta

    def _drop_sticky_locked(self, idx: int) -> None:
        for k in [k for k, v in self._sticky.items() if v == idx]:
            del self._sticky[k]

    def _count_requeue(self, trace_id: Optional[str] = None) -> None:
        with self._lock:
            self.route_stats["requeues"] += 1
        self.events.append("resubmit",
                           data={"trace_id": trace_id}
                           if trace_id is not None else None)
        _metrics()["requeues"].inc()

    # ---------------------------------------------- prefix sharing

    def _wire_kv(self, rep: _Replica) -> None:
        """Register ``rep``'s engine as a KV donor and hand it a
        fetcher that pulls from its siblings. Re-run on every
        rebuild: the donor table must always point at the LIVE
        engine for each slot (a transfer begun against the old
        incarnation aborts typed on the fresh donor's empty
        table)."""
        if not self._share_prefixes:
            return
        eng = rep.engine
        if not hasattr(eng, "kv_migration_stats"):
            return
        with self._lock:
            self._kv_donors[rep.idx] = kv_migration.KVDonor(eng)
        eng.kv_fetcher = lambda pull, e=eng: self._kv_fetch(e, pull)

    def _kv_fetch(self, requester_engine,
                  pull: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        with self._lock:
            donor = self._kv_donors.get(pull.get("replica_idx"))
        if donor is None:
            return None
        try:
            return kv_migration.pull_prefix(
                kv_migration.loopback_call(donor),
                pull.get("hashes") or [],
                stats=requester_engine.kv_migration_stats,
                **self._kv_pull_knobs)
        except Exception:
            return None

    def _pull_hint(self, prompt: List[int], rep: _Replica,
                   reports: Dict[int, Dict[str, Any]]
                   ) -> Optional[Dict[str, Any]]:
        """When a sibling replica advertises a strictly longer
        cached prefix of this prompt than the routed target does,
        name it as the donor — the target pulls instead of
        recomputing. A hint only: any staleness degrades to plain
        prefill on the target."""
        Pg = getattr(rep.engine, "Pg", 0)
        if Pg <= 0 or len(prompt) < Pg:
            return None
        chain = path_hashes(prompt, Pg)
        # weight-generation fence, cross-replica half: a donor serving
        # a DIFFERENT weight payload holds KV computed under weights
        # the target does not run — matching it would decode new
        # tokens against foreign-generation pages. Mid-rollout, pulls
        # simply stay within each side of the fleet.
        my_wid = reports.get(rep.idx, {}).get("weights_id")

        def cover(idx: int) -> int:
            rpt = reports.get(idx, {})
            if rpt.get("weights_id") != my_wid:
                return 0
            have = rpt.get("prefix_digest") or frozenset()
            n = 0
            for h in chain:
                if h not in have:
                    break
                n += 1
            return n

        best_idx, best_n = None, cover(rep.idx)
        for idx in reports:
            if idx == rep.idx:
                continue
            n = cover(idx)
            if n > best_n:
                best_idx, best_n = idx, n
        if best_idx is None:
            return None
        with self._lock:
            self.route_stats["pull_hints"] += 1
        return {"hashes": chain[:best_n], "replica_idx": best_idx}

    def kv_migration_stats(self) -> Optional[Dict[str, Any]]:
        """Summed cross-replica KV migration counters (pulls, pages,
        wire bytes, aborts, fallbacks) — the ``kv_migration`` block
        in pool stats, bench artifacts, and flight bundles."""
        per = [getattr(r.engine, "kv_migration_stats", None)
               for r in self._replicas]
        return self._agg_numeric(per)

    # --------------------------------------------------------- routing

    def _submit_once(self, prompt: List[int], max_new_tokens: int,
                     deadline_s: Optional[float],
                     session_id: Optional[str],
                     trace_id: Optional[str] = None,
                     priority: str = LANE_ONLINE,
                     roles: Optional[Sequence[str]] = None,
                     pull: Optional[Dict[str, Any]] = None,
                     exclude: Optional[set] = None,
                     target_idx: Optional[int] = None,
                     record_sticky: bool = True):
        """Route + submit until one replica accepts. Replicas that
        shed/die/drain between the snapshot and the submit are
        excluded and routing retries; when nothing accepts, the
        failure is typed and aggregated (module docstring).

        Disaggregation extras: ``roles`` restricts routing to those
        replica roles; ``pull`` attaches an explicit KV pull hint
        (the finished-prefill push hint) overriding the routed one;
        ``target_idx`` bypasses routing entirely and submits to ONE
        named healthy replica (the decode-in-place fallback);
        ``record_sticky=False`` keeps a route from writing session
        placement state."""
        batch = priority == LANE_BATCH
        exclude = set(exclude) if exclude else set()
        shed: List[EngineOverloaded] = []
        while True:
            if target_idx is not None:
                rep, decision = self._route_direct(target_idx)
            else:
                rep, decision = self._route(prompt, session_id,
                                            exclude, batch=batch,
                                            roles=roles)
            if rep is not None and pull is not None:
                decision = dict(decision, pull=pull)
            if rep is None:
                hints = decision.get("hints", [])
                hints += [e.retry_after_s for e in shed]
                if hints:
                    with self._lock:
                        self.route_stats["all_shed"] += 1
                    _metrics()["all_shed"].inc()
                    # Retry-After honesty under autoscaling: when
                    # capacity is already provisioning, the hint must
                    # cover its remaining ETA — never invite a client
                    # back before a replica exists to serve it
                    if self.capacity_hint_fn is not None:
                        try:
                            eta = float(self.capacity_hint_fn())
                        except Exception:
                            # broken hint provider: fall back to the
                            # pool's own pending-backoff ETA rather
                            # than silently dropping the signal
                            _metrics()["capacity_hint_errors"].inc()
                            eta = self._pending_backoff_eta_s()
                        if eta > 0:
                            hints.append(eta)
                    err = EngineOverloaded(
                        f"all healthy replicas shed (retry hints "
                        f"{sorted(set(round(h, 3) for h in hints))})",
                        retry_after_s=max(hints))
                    if shed:
                        raise err from shed[-1]
                    raise err
                # No healthy replica and nobody shed: a bare 503
                # would tell the client nothing — attach the honest
                # restart/provisioning ETA so the proxy can emit
                # Retry-After on the degraded path too.
                eta = self._restart_eta_s()
                if self.degraded:
                    raise PoolDegraded(
                        "no healthy replicas: the pool burned through "
                        "its crash-loop restart budget "
                        f"(max_restarts={self.max_restarts})",
                        retry_after_s=eta if eta > 0 else None)
                err = EngineShutdown("no healthy replicas in pool")
                if eta > 0:
                    err.retry_after_s = eta
                raise err
            try:
                # trace_id only when set: fake engines in tests (and
                # older engine builds) take the bare 3-arg signature
                kw: Dict[str, Any] = dict(
                    max_new_tokens=max_new_tokens,
                    deadline_s=deadline_s)
                if trace_id is not None:
                    kw["trace_id"] = trace_id
                if decision.get("pull") is not None:
                    kw["pull"] = decision["pull"]
                if batch:
                    # only when non-default: fake engines in tests
                    # (and older builds) lack the priority kwarg
                    kw["priority"] = priority
                inner = rep.engine.submit(prompt, **kw)
            except EngineOverloaded as e:
                if target_idx is not None:
                    raise       # the named target shed: no retry loop
                shed.append(e)
                exclude.add(rep.idx)
                continue
            except (EngineShutdown, EngineDraining) as e:
                # raced a death/drain after the snapshot
                self._note_replica_death(rep)
                if target_idx is not None:
                    raise
                exclude.add(rep.idx)
                continue
            self._record_route(rep, decision,
                               session_id if record_sticky else None,
                               trace_id=trace_id)
            return rep, inner

    def _route_direct(self, idx: int):
        """Directly target replica ``idx`` (decode-in-place
        fallback): no routing policy, no sticky write — just a
        health check shaped like a route decision."""
        with self._lock:
            rep = (self._replicas[idx]
                   if 0 <= idx < len(self._replicas) else None)
            if rep is None or rep.state != HEALTHY:
                rep = None
        if rep is None:
            raise EngineShutdown(
                f"replica {idx} is not healthy; cannot decode in "
                f"place")
        return rep, {"kind": "direct", "pages": 0}

    def _route(self, prompt: List[int], session_id: Optional[str],
               exclude: set, *, batch: bool = False,
               roles: Optional[Sequence[str]] = None):
        """Pick a replica (or ``(None, {"hints": [...]})`` when none
        can admit). Lock discipline: the replica table is read under
        the pool lock; ``load_report()`` calls happen OUTSIDE it (they
        briefly take each engine's lock).

        ``batch=True`` bypasses the sticky -> affinity -> P2C policy
        entirely: the batch lane routes to the replica with the least
        batch backlog (ties on outstanding tokens), reads — never
        writes — placement state, and respects each replica's
        ``max_queued_batch`` bound. Batch never lands on a
        prefill-only replica: backlog spills only into the
        decode/unified pool, whose admission knobs can actually run
        long decode streams.

        ``roles`` (disaggregation) restricts candidates to those
        replica roles."""
        with self._lock:
            reps = [r for r in self._replicas
                    if r.state == HEALTHY and r.idx not in exclude
                    and (roles is None or r.role in roles)
                    and not (batch and r.role == ROLE_PREFILL)]
            sticky_idx = (self._sticky.get(session_id)
                          if session_id is not None else None)
            if sticky_idx is not None:
                srep = (self._replicas[sticky_idx]
                        if sticky_idx < len(self._replicas) else None)
                if srep is not None and srep.role == ROLE_PREFILL:
                    # A sticky entry must never pin a session to a
                    # prefill-only replica (e.g. written before the
                    # replica was re-roled): drop it, don't follow it.
                    del self._sticky[session_id]
                    sticky_idx = None
        if not reps:
            return None, {"hints": []}
        reports = {r.idx: r.engine.load_report() for r in reps}
        m = _metrics()
        for r in reps:
            rep_report = reports[r.idx]
            tags = {"replica": str(r.idx)}
            m["free_slots"].set(rep_report["free_slots"], tags=tags)
            m["queue_depth"].set(rep_report["queue_depth"],
                                 tags=tags)
            m["batch_queue_depth"].set(
                rep_report.get("queue_depth_batch", 0), tags=tags)
        # A replica can die while IDLE — engine thread gone with no
        # in-flight handle around to trip the death path. Routing is
        # the other place a corpse becomes visible: note the death
        # here so auto-restart/crash-loop accounting fires instead of
        # the replica sitting "healthy" in the table forever while
        # every route skips it.
        for r in reps:
            if reports[r.idx]["stopped"]:
                self._note_replica_death(r)
        # selection itself is the shared fleet.routing core: the same
        # sticky -> affinity/spill -> P2C policy the FleetRouter runs
        # over the directory's advertised reports
        by_key = {r.idx: r for r in reps}
        live = [r for r in reps
                if not reports[r.idx]["stopped"]
                and not reports[r.idx]["draining"]]
        if batch:
            return self._route_batch(live, reports)
        cands = [Candidate(r.idx, reports[r.idx],
                           getattr(r.engine, "Pg", 0))
                 for r in live]
        pick, decision = select_candidate(
            cands, prompt, sticky_key=sticky_idx, rng=self._rng)
        if pick is None:
            return None, decision
        rep = by_key[pick.key]
        if self._share_prefixes:
            hint = self._pull_hint(prompt, rep, reports)
            if hint is not None:
                decision = dict(decision, pull=hint)
        return rep, decision

    def _route_batch(self, live: List[_Replica],
                     reports: Dict[int, Dict[str, Any]]):
        """Batch-lane spill routing: least batch backlog first, ties
        on least outstanding token work — the lane flows wherever
        capacity is idlest. Replicas whose batch lane is at its
        ``max_queued_batch`` bound contribute a retry hint instead of
        a queue position; when every replica is bound, the caller
        aggregates those hints into one pool-level shed. Sticky and
        affinity state is untouched: batch never claims a placement
        online traffic could want."""
        hints: List[float] = []
        open_reps: List[_Replica] = []
        for r in live:
            rpt = reports[r.idx]
            bound = rpt.get("max_queued_batch")
            if (bound is not None
                    and rpt.get("queue_depth_batch", 0) >= bound):
                hints.append(rpt.get("shed_retry_after_s", 1.0))
                continue
            open_reps.append(r)
        if not open_reps:
            return None, {"hints": hints}
        pick = min(open_reps,
                   key=lambda r: (
                       reports[r.idx].get("queue_depth_batch", 0),
                       reports[r.idx].get("outstanding_tokens", 0),
                       r.idx))
        return pick, {"kind": "batch", "pages": 0, "spilled": False}

    def _record_route(self, rep: _Replica, decision: Dict[str, Any],
                      session_id: Optional[str],
                      trace_id: Optional[str] = None) -> None:
        self.events.append(
            "route", sid=rep.idx,
            data={"kind": decision["kind"],
                  "pages": decision.get("pages", 0),
                  "spilled": bool(decision.get("spilled")),
                  "trace_id": trace_id})
        m = _metrics()
        with self._lock:
            self.route_stats["routed"] += 1
            self.route_stats[f"route_{decision['kind']}"] += 1
            if decision.get("pages", 0) > 0:
                # an affinity HIT is a route landing on a replica
                # that already holds >= 1 page of this prompt's
                # prefix — whichever rule picked it
                self.route_stats["affinity_hits"] += 1
                self.route_stats["affinity_hit_pages"] += \
                    decision["pages"]
            if decision["kind"] == "sticky":
                self.route_stats["sticky_hits"] += 1
            if decision.get("spilled"):
                self.route_stats["spills"] += 1
            if (session_id is not None
                    and decision["kind"] != "batch"):
                # batch routes never write placement state: a batch
                # job naming a session must not steal (or evict, via
                # the LRU bound) the sticky entry online traffic
                # relies on
                self._sticky[session_id] = rep.idx
                self._sticky.move_to_end(session_id)
                while len(self._sticky) > self._max_sticky:
                    self._sticky.popitem(last=False)
        m["routed"].inc()
        if decision.get("pages", 0) > 0:
            m["affinity_hits"].inc()
        if decision["kind"] == "sticky":
            m["sticky_hits"].inc()
        if decision.get("spilled"):
            m["spills"].inc()

    # ---------------------------------------------------- aggregation

    @property
    def stats(self) -> Dict[str, int]:
        """Summed engine counters across replicas (the single-engine
        ``stats`` surface, fleet-wide)."""
        total: Dict[str, int] = collections.Counter()
        for rep in self._replicas:
            total.update(rep.engine.stats)
        return total

    @property
    def ttfts_s(self) -> List[float]:
        out: List[float] = []
        for rep in self._replicas:
            out.extend(rep.engine.ttfts_s)
        return out

    def load_reports(self, role: Optional[str] = None
                     ) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            reps = [r for r in self._replicas
                    if r.state in (HEALTHY, DRAINING)
                    and (role is None or r.role == role)]
        return {r.idx: r.engine.load_report() for r in reps}

    def load_report(self, role: Optional[str] = None
                    ) -> Dict[str, Any]:
        """Pool-aggregate load snapshot (the single-engine
        ``load_report`` surface, summed over live replicas — what the
        serve controller's replica table stores for cross-replica
        routing hints). No digest: prefix affinity is an intra-pool
        decision; the deployment-level router only needs pressure.
        ``role`` restricts the aggregate to one disaggregated side —
        the view a per-role autoscaler senses."""
        reports = list(self.load_reports(role).values())
        with self._lock:
            n = sum(1 for r in self._replicas
                    if role is None or r.role == role)
            active = sum(1 for r in self._replicas
                         if r.state != RETIRED
                         and (role is None or r.role == role))
            healthy = sum(1 for r in self._replicas
                          if r.state == HEALTHY
                          and (role is None or r.role == role))
            role_counts: Dict[str, int] = collections.Counter(
                r.role for r in self._replicas
                if r.state != RETIRED)
        agg = {"free_slots": 0, "free_pages": 0, "queue_depth": 0,
               "queue_depth_batch": 0,
               "outstanding_tokens": 0, "draining": False,
               "stopped": not reports, "max_queued": None,
               "shed_retry_after_s": 1.0,
               "total_slots": 0, "shed_total": 0,
               "ttft_ewma_s": None,
               "itl_ewma_s": None,
               "role": role if role is not None else ROLE_UNIFIED,
               "roles": dict(role_counts),
               "n_replicas": n,
               "active_replicas": active,
               "healthy_replicas": healthy,
               # 2-D scale-out stamp: tp devices per replica x
               # n_replicas slices — uniform across a pool (replicas
               # are interchangeable), so the max IS the value
               "tp": max((rpt.get("tp", 1) for rpt in reports),
                         default=1)}
        for rpt in reports:
            agg["free_slots"] += rpt["free_slots"]
            agg["free_pages"] += rpt["free_pages"]
            agg["queue_depth"] += rpt["queue_depth"]
            agg["queue_depth_batch"] += rpt.get(
                "queue_depth_batch", 0)
            agg["outstanding_tokens"] += rpt["outstanding_tokens"]
            agg["shed_retry_after_s"] = max(
                agg["shed_retry_after_s"], rpt["shed_retry_after_s"])
            agg["total_slots"] += rpt.get("total_slots", 0)
            agg["shed_total"] += rpt.get("shed_total", 0)
            # worst replica wins: the SLO is violated if ANY replica's
            # first-token latency drifted, and routing can only
            # partially steer around a slow one
            ewma = rpt.get("ttft_ewma_s")
            if ewma is not None:
                agg["ttft_ewma_s"] = ewma if agg["ttft_ewma_s"] \
                    is None else max(agg["ttft_ewma_s"], ewma)
            itl = rpt.get("itl_ewma_s")
            if itl is not None:
                agg["itl_ewma_s"] = itl if agg["itl_ewma_s"] \
                    is None else max(agg["itl_ewma_s"], itl)
        # rollout visibility: the newest generation serving anywhere
        # in the pool, and whether the fleet is mid-rollout (mixed
        # payloads across live replicas)
        agg["weight_generation"] = max(
            (rpt.get("weight_generation", 0) for rpt in reports),
            default=0)
        wids = {rpt.get("weights_id") for rpt in reports
                if rpt.get("weights_id") is not None}
        agg["weights_mixed"] = len(wids) > 1
        return agg

    def pool_stats(self) -> Dict[str, Any]:
        """Routing/lifecycle counters + per-replica snapshot — the
        pool block in serve stats and bench artifacts."""
        with self._lock:
            counters = dict(self.route_stats)
            reps = [{"idx": r.idx, "state": r.state,
                     "deaths": r.deaths,
                     "generation": r.generation,
                     "role": r.role,
                     # weight fence state (pool incarnation
                     # "generation" above is a DIFFERENT counter:
                     # restarts, not rollouts)
                     "weight_generation": getattr(
                         r.engine, "weight_generation", 0),
                     "weights_id": getattr(
                         r.engine, "weights_id", None)}
                    for r in self._replicas]
            role_views = dict(self._role_views)
        routed = counters.get("routed", 0)
        counters["affinity_hit_rate"] = round(
            counters.get("affinity_hits", 0) / routed, 4) \
            if routed else 0.0
        counters["spill_rate"] = round(
            counters.get("spills", 0) / routed, 4) if routed else 0.0
        counters["n_replicas"] = len(reps)
        counters["active_replicas"] = sum(
            1 for r in reps if r["state"] != RETIRED)
        counters["suspect_replicas"] = sum(
            1 for r in reps if r["state"] == SUSPECT)
        counters["degraded"] = any(
            r["state"] == DEGRADED for r in reps)
        counters["roles"] = dict(collections.Counter(
            r["role"] for r in reps if r["state"] != RETIRED))
        counters["replicas"] = reps
        kv = self.kv_migration_stats()
        if kv is not None:
            counters["kv_migration"] = kv
        scaler = self._autoscaler
        if scaler is not None:
            counters["autoscale"] = scaler.stats()
        # per-role autoscalers (disaggregation): one block per side,
        # so both roles' scale decisions are visible in one snapshot
        by_role = {}
        for role, view in role_views.items():
            vs = getattr(view, "_autoscaler", None)
            if vs is not None:
                by_role[role] = vs.stats()
        if by_role:
            counters["autoscale_by_role"] = by_role
        wd = self._watchdog
        if wd is not None:
            counters["watchdog"] = wd.stats()
        return counters

    def _agg_numeric(self, per_replica: List[Optional[Dict[str, Any]]]
                     ) -> Optional[Dict[str, Any]]:
        dicts = [d for d in per_replica if d]
        if not dicts:
            return None
        out: Dict[str, Any] = {}
        for d in dicts:
            for k, v in d.items():
                if isinstance(v, bool) or not isinstance(
                        v, (int, float)):
                    out.setdefault(k, v)
                else:
                    out[k] = out.get(k, 0) + v
        return out

    def prefix_stats(self) -> Optional[Dict[str, Any]]:
        out = self._agg_numeric(
            [r.engine.prefix_stats() for r in self._replicas])
        if out:
            total = out.get("hit_tokens", 0) + out.get(
                "miss_tokens", 0)
            out["hit_rate"] = round(
                out.get("hit_tokens", 0) / total, 4) if total else 0.0
        return out

    def spec_stats(self) -> Optional[Dict[str, Any]]:
        out = self._agg_numeric(
            [r.engine.spec_stats() for r in self._replicas])
        if out:
            proposed = out.get("proposed", 0)
            out["accept_rate"] = round(
                out.get("accepted", 0) / proposed, 4) \
                if proposed else 0.0
            disp = out.get("dispatches", 0)
            if "tokens_per_dispatch" in out:
                out["tokens_per_dispatch"] = round(
                    (out.get("accepted", 0) + disp) / disp, 4) \
                    if disp else 0.0
        return out

    def lifecycle_stats(self) -> Dict[str, Any]:
        per = [r.engine.lifecycle_stats() for r in self._replicas]
        out = self._agg_numeric(per) or {}
        # knobs are per-replica config, not summable: report rep 0's
        for knob in ("max_queued", "max_retries", "retry_backoff_s"):
            if per:
                out[knob] = per[0].get(knob)
        return out

    def _role_capacity_eta_s(self) -> float:
        """Max in-flight provisioning ETA over the per-role
        autoscalers — the pool-wide ``capacity_hint_fn`` when role
        views are attached (either side's provisioning capacity can
        end an all-shed)."""
        eta = 0.0
        for view in list(self._role_views.values()):
            scaler = getattr(view, "_autoscaler", None)
            if scaler is None:
                continue
            try:
                eta = max(eta, float(scaler.capacity_eta_s()))
            except Exception:
                _metrics()["capacity_hint_errors"].inc()
        return eta


class _RoleEventLog:
    """Event seam a RolePoolView hands its autoscaler: appends land
    in the POOL's ring with the view's role injected into the data,
    so both sides' scale decisions interleave in one log and stay
    attributable."""

    def __init__(self, log: obs.EventLog, role: str):
        self._log = log
        self._role = role

    def append(self, etype: str, rid: Any = None, sid: Any = None,
               data: Any = None, t: Optional[float] = None) -> None:
        d = dict(data) if isinstance(data, dict) else (
            {"data": data} if data is not None else {})
        d["role"] = self._role
        self._log.append(etype, rid=rid, sid=sid, data=d, t=t)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._log, name)


class RolePoolView:
    """One disaggregated side of an EnginePool, shaped like a pool.

    ``PoolAutoscaler`` attaches to whatever it is given — ctor
    side-effects (``pool._autoscaler``, ``pool.capacity_hint_fn``)
    included — so two per-role scalers pointed at the SAME pool would
    clobber each other. Each scaler instead gets a view: load_report
    and counts filter to the role, ``add_replica``/``scale_down``
    scale only this side, events are tagged with the role, and the
    view registers itself on the pool so ``pool_stats`` shows both
    sides' decisions (``autoscale_by_role``) and the pool's own
    capacity hint becomes the max over the attached scalers' ETAs."""

    def __init__(self, pool: EnginePool, role: str):
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"unknown replica role {role!r}; expected one of "
                f"{sorted(REPLICA_ROLES)}")
        self._pool = pool
        self.role = role
        # PoolAutoscaler ctor attachment points land HERE, per view
        self._autoscaler = None
        self.capacity_hint_fn: Optional[Callable[[], float]] = None
        self.events = _RoleEventLog(pool.events, role)
        pool._role_views[role] = self
        pool.capacity_hint_fn = pool._role_capacity_eta_s

    # pool surface the autoscaler senses -----------------------------

    @property
    def _stopped(self) -> bool:
        return self._pool._stopped

    @property
    def add_replica_for_ticket(self):
        # provider-harvest override, honored pool-wide if installed
        return getattr(self._pool, "add_replica_for_ticket", None)

    def load_report(self) -> Dict[str, Any]:
        return self._pool.load_report(role=self.role)

    def active_count(self) -> int:
        with self._pool._lock:
            return sum(1 for r in self._pool._replicas
                       if r.state != RETIRED and r.role == self.role)

    def healthy_count(self) -> int:
        with self._pool._lock:
            return sum(1 for r in self._pool._replicas
                       if r.state == HEALTHY and r.role == self.role)

    # pool surface the autoscaler actuates ---------------------------

    def add_replica(self) -> int:
        return self._pool.add_replica(role=self.role)

    def scale_down(self, n: int = 1,
                   timeout_s: float = 30.0) -> List[int]:
        return self._pool.scale_down(n, timeout_s, role=self.role)
