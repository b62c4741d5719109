"""LLM serving: Llama replicas behind serve deployments.

The build's serving north star (BASELINE.md: "Serve Llama-2-7B JAX
replicas autoscaled on v5e"): a deployment class whose every request
goes through the continuous-batching engine (serve/engine.py: paged
KV, chunked prefill beside decode), with an optional device mesh per
replica (tensor-parallel serving = a replica whose mesh has a
nontrivial `tensor` axis; cf. serve/_private/replica.py in the
reference for the replica wrapper shape)."""
from __future__ import annotations

from typing import Any, Dict, List, Optional


class LlamaDeployment:
    """Deployment-ready Llama wrapper: __init__ builds/loads the model,
    __call__ generates. Wrap with @serve.deployment at use site so
    num_replicas/autoscaling stay caller-controlled."""

    def __init__(self, config=None, params=None, max_new_tokens: int = 64,
                 temperature: float = 0.0, max_slots: int = 16,
                 page_size: int = 64, n_pages: Optional[int] = None,
                 decode_chunk: int = 8,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 prefix_cache: bool = False,
                 spec_len: int = 0, spec_ngram: int = 3,
                 deadline_s: Optional[float] = None,
                 max_queued: Optional[int] = None,
                 max_retries: int = 2,
                 retry_backoff_s: float = 0.02,
                 batch_wait_timeout_s: float = 0.0,
                 num_engine_replicas: int = 1,
                 pool_auto_restart: bool = True,
                 tensor_parallel: int = 1,
                 expert_parallel: int = 1,
                 autoscale: bool = False,
                 autoscale_max_replicas: Optional[int] = None,
                 autoscale_policy: Optional[Dict[str, Any]] = None,
                 autoscale_interval_s: float = 0.5,
                 autoscale_provider=None,
                 engine_stall_deadline_s: Optional[float] = None,
                 watchdog_interval_s: Optional[float] = None,
                 overlap: bool = True,
                 fleet: int = 0,
                 fleet_lease_ttl_s: float = 2.0,
                 kv_dtype: Optional[str] = None,
                 disaggregate: bool = False,
                 prefill_replicas: Optional[int] = None,
                 decode_replicas: Optional[int] = None,
                 kv_pull_deadline_s: Optional[float] = None,
                 kv_pull_backoff_s: Optional[float] = None):
        import jax
        from ray_tpu.models.llama import llama_tiny
        self.cfg = config or llama_tiny()
        # any family serves through the same decode stack: its config
        # says which class it builds (models/llama.py ``model_class``)
        self.model = self.cfg.model_class(self.cfg)
        if params is None:
            import jax.numpy as jnp
            # jitted: un-jitted, flax runs every initialiser op by op
            params = jax.jit(self.model.init)(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))
        self.params = params
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.mesh = None
        # Continuous batching (serve/engine.py): requests join/leave
        # the decode batch at token granularity instead of riding
        # whole-call batches (supersedes @serve.batch for LLMs).
        self._engine = None
        import threading
        self._engine_lock = threading.Lock()
        # Request-lifecycle defaults (serve/engine.py hardening):
        # deadline_s is the deployment-wide per-request deadline
        # (per-call dict payloads can override); max_queued bounds
        # admission so overload sheds fast (EngineOverloaded -> 429
        # at the proxy) instead of silently collapsing TTFT.
        self.deadline_s = deadline_s
        # Data-parallel engine pool (serve/engine_pool.py): N engines
        # behind prefix-affinity routing behave as one logical
        # engine. 1 = plain single engine, no pool in the path.
        if num_engine_replicas < 1:
            raise ValueError("num_engine_replicas must be >= 1")
        self.num_engine_replicas = num_engine_replicas
        self.pool_auto_restart = pool_auto_restart
        # Tensor/expert parallelism WITHIN a replica
        # (serve/sharding.py EngineSharding): each engine shards its
        # weights + head-sharded KV pool over tp*ep devices.
        # Composes orthogonally with num_engine_replicas — 2-D
        # scale-out: shard within a slice x replicate across slices
        # (replica_device_groups hands each pool member its own
        # device group). Validated eagerly so a non-dividing config
        # fails at deployment construction, not first request.
        if tensor_parallel < 1 or expert_parallel < 1:
            raise ValueError("tensor_parallel/expert_parallel must "
                             "be >= 1")
        self.tensor_parallel = int(tensor_parallel)
        self.expert_parallel = int(expert_parallel)
        # a model whose layers keep another kind of request state
        # than K/V pages (models/kv_cache.py KIND_REFUSALS says what
        # each kind cannot do, and why)
        from ray_tpu.models.kv_cache import refuse_unsupported
        refuse_unsupported(
            self.cfg, prefix_cache=prefix_cache, spec_len=spec_len,
            kv_dtype=kv_dtype == "int8" and kv_dtype,
            kv_migration=disaggregate and "disaggregate",
            sharding=(self.tensor_parallel,
                      self.expert_parallel) != (1, 1))
        if self.tensor_parallel > 1 or self.expert_parallel > 1:
            from ray_tpu.serve.sharding import validate_tp
            validate_tp(self.cfg, self.tensor_parallel,
                        self.expert_parallel)
        # SLO-driven pool autoscaling (serve/pool_autoscaler.py):
        # num_engine_replicas becomes the FLOOR, autoscale_max_replicas
        # the ceiling, and a PoolAutoscaler drives the pool between
        # them on queue/shed/TTFT pressure. autoscale_policy overrides
        # individual SLOPolicy fields (e.g. {"ttft_slo_s": 0.2});
        # autoscale_provider supplies the capacity backend (default:
        # ImmediateCapacityProvider — capacity already on the host).
        self.autoscale = autoscale
        self.autoscale_max_replicas = (
            autoscale_max_replicas
            if autoscale_max_replicas is not None
            else max(num_engine_replicas, 4))
        if self.autoscale and \
                self.autoscale_max_replicas < num_engine_replicas:
            raise ValueError("autoscale_max_replicas must be >= "
                             "num_engine_replicas")
        self.autoscale_policy = dict(autoscale_policy or {})
        self.autoscale_interval_s = autoscale_interval_s
        self.autoscale_provider = autoscale_provider
        self._autoscaler = None
        # Pool watchdog (serve/watchdog.py): a replica whose scheduler
        # stops making progress for engine_stall_deadline_s (with work
        # pending) is quarantined (SUSPECT), probed, then force-killed
        # and rebuilt through the pool's death path. None = watchdog
        # off (single-engine deployments have no survivor to resubmit
        # to, so the per-request deadline is the only backstop there).
        if engine_stall_deadline_s is not None \
                and engine_stall_deadline_s <= 0:
            raise ValueError(
                "engine_stall_deadline_s must be > 0 (or None)")
        self.engine_stall_deadline_s = engine_stall_deadline_s
        self.watchdog_interval_s = watchdog_interval_s
        self._watchdog = None
        # Fleet control plane (serve/fleet/): fleet=N swaps the
        # in-process EnginePool for a loopback fleet — a
        # FleetDirectory, N lease-renewing ReplicaAgents (one engine
        # each), and a FleetRouter as the deployment's engine
        # object. Same routing/resubmit core as the pool, but every
        # replica sits behind the transport seam and the
        # lease/fencing state machine, so deployment-level tests
        # exercise exactly the control plane the cross-process
        # harness (tools/chaos_serve.py --fleet) kills for real.
        if fleet < 0:
            raise ValueError("fleet must be >= 0")
        if fleet and num_engine_replicas > 1:
            raise ValueError(
                "fleet= and num_engine_replicas>1 are exclusive — "
                "the fleet IS the replica set")
        if fleet and autoscale:
            # the autoscaler drives the ROUTER here: tickets
            # provision loopback ReplicaAgents (fleet/provider.py),
            # so the provider must be ours — tickets ARE replica ids
            if autoscale_provider is not None:
                raise ValueError(
                    "fleet+autoscale builds its own "
                    "LoopbackAgentProvider (tickets provision fleet "
                    "agents); autoscale_provider is not accepted")
            if self.autoscale_max_replicas < fleet:
                raise ValueError("autoscale_max_replicas must be "
                                 ">= fleet")
        self.fleet = int(fleet)
        self.fleet_lease_ttl_s = float(fleet_lease_ttl_s)
        self._fleet_agents: Dict[str, Any] = {}
        self._fleet_directory = None
        # Prefill/decode disaggregation (serve/engine_pool.py roles):
        # the pool splits into a prefill pool (new requests, TTFT)
        # and a decode pool (streams resumed over the KV-migration
        # handoff) that scale independently. Junk knobs fail HERE,
        # at construction, not on the first pulled page.
        from ray_tpu.serve.kv_migration import validate_pull_knobs
        validate_pull_knobs(kv_pull_deadline_s, kv_pull_backoff_s)
        self.kv_pull_deadline_s = kv_pull_deadline_s
        self.kv_pull_backoff_s = kv_pull_backoff_s
        self.disaggregate = bool(disaggregate)
        if not disaggregate and (prefill_replicas is not None
                                 or decode_replicas is not None):
            raise ValueError(
                "prefill_replicas/decode_replicas require "
                "disaggregate=True")
        if disaggregate:
            if fleet:
                raise ValueError(
                    "disaggregate=True and fleet= are exclusive — "
                    "fleet members carry role metadata but the "
                    "router serves them unified")
            if not prefix_cache:
                raise ValueError(
                    "disaggregate=True requires prefix_cache=True "
                    "(the handoff pulls the prefill replica's "
                    "published pages)")
            p = (int(prefill_replicas)
                 if prefill_replicas is not None else 1)
            d = (int(decode_replicas)
                 if decode_replicas is not None else 1)
            if p < 1 or d < 1:
                raise ValueError("prefill_replicas and "
                                 "decode_replicas must be >= 1")
            if num_engine_replicas not in (1, p + d):
                raise ValueError(
                    f"num_engine_replicas={num_engine_replicas} "
                    f"conflicts with prefill_replicas+decode_"
                    f"replicas={p + d}; omit it (the role split "
                    f"determines pool width)")
            self.num_engine_replicas = p + d
            self.prefill_replicas: Optional[int] = p
            self.decode_replicas: Optional[int] = d
        else:
            self.prefill_replicas = None
            self.decode_replicas = None
        self._engine_opts = dict(
            max_slots=max_slots, page_size=page_size,
            # decode steps per device round trip: each chunk pays
            # one host-sync latency, so bigger chunks raise
            # steady-state tok/s at the cost of burstier delivery
            n_pages=n_pages, chunk=decode_chunk,
            prefill_chunk=prefill_chunk, eos_id=eos_id,
            prefix_cache=prefix_cache,
            spec_len=spec_len, spec_ngram=spec_ngram,
            max_queued=max_queued, max_retries=max_retries,
            retry_backoff_s=retry_backoff_s,
            # an idle engine's first admission waits this long for
            # the prefill call's rows to fill (engine.py); 0: at once
            batch_wait_timeout_s=batch_wait_timeout_s,
            # with a watchdog guarding the pool, a submit racing a
            # wedged scheduler sheds-and-reroutes instead of parking
            # on the wedged engine's lock
            admit_timeout_s=engine_stall_deadline_s,
            # overlapped hot loop (engine.py); False is the lockstep one
            overlap=overlap,
            # KV storage dtype ("fp"/"int8"): int8 halves page bytes
            # at tolerance-gated parity; every replica/fleet engine
            # built from these opts inherits the same pool format
            kv_dtype=kv_dtype)

    def setup_mesh(self, mesh):
        """Called by the serve replica when cfg.mesh is set: shard the
        params over the replica's mesh (tensor-parallel; for Mixtral
        also expert-parallel)."""
        from ray_tpu.mesh.sharding import shard_params
        from ray_tpu.serve.sharding import family_sharding_rules
        self.mesh = mesh
        self.params = shard_params(
            self.params, family_sharding_rules(self.cfg), mesh)

    def engine(self):
        """The replica's continuous-batching engine (lazy: params may
        be resharded by setup_mesh after __init__). Locked: replicas
        run sync handlers on an executor, so two first requests race
        here — an unlocked check would double-allocate the KV pool."""
        with self._engine_lock:
            if self._engine is None:
                from ray_tpu.serve.engine import LLMEngine
                opts = dict(self._engine_opts)
                # max_slots/n_pages are PER-REPLICA: each pool member
                # is a full engine, so num_engine_replicas=N scales
                # aggregate slots and KV pages N-fold (data-parallel
                # replication adds capacity; it does not reshard one
                # engine's budget).
                if opts["n_pages"] is None:
                    # full residency by default: every slot can reach
                    # prompt+completion without preemption
                    per_seq = -(-self.cfg.max_seq_len
                                // opts["page_size"])
                    opts["n_pages"] = opts["max_slots"] * per_seq + 1
                per = self.tensor_parallel * self.expert_parallel
                single = not (self.fleet or self.autoscale
                              or self.disaggregate
                              or self.num_engine_replicas > 1)

                def _replica_sharding(idx):
                    # One EngineSharding per replica over its own
                    # device group (2-D scale-out). Recomputed on
                    # restart/scale-up for whatever idx the pool
                    # hands us — the group assignment is pure
                    # arithmetic, so a rebuilt replica idx lands on
                    # the same devices its predecessor used. A
                    # one-device replica still gets its (degenerate)
                    # mesh: without one, every replica's params and
                    # KV pool would pile onto the default device.
                    # Only the lone unsharded engine stays there.
                    if per == 1 and single:
                        return None
                    from ray_tpu.serve.sharding import (
                        EngineSharding, replica_device_groups)
                    group = replica_device_groups(idx + 1, per)[idx]
                    return EngineSharding.build(
                        self.cfg, tp=self.tensor_parallel,
                        ep=self.expert_parallel, devices=group)

                if self.fleet:
                    from ray_tpu.serve.fleet.agent import ReplicaAgent
                    from ray_tpu.serve.fleet.directory import (
                        DirectoryClient, FleetDirectory)
                    from ray_tpu.serve.fleet.router import FleetRouter
                    from ray_tpu.serve.fleet.transport import (
                        LoopbackTransport)
                    self._fleet_directory = FleetDirectory(
                        lease_ttl_s=self.fleet_lease_ttl_s)
                    dc = DirectoryClient(LoopbackTransport(
                        self._fleet_directory.handle))
                    agents = self._fleet_agents

                    def tf(addr):
                        # loopback addr = ["loopback", replica_id]
                        return LoopbackTransport(agents[addr[1]].handle)

                    for i in range(self.fleet):
                        rid = f"r{i}"

                        def factory(gen, _i=i, _opts=opts):
                            return LLMEngine(
                                self.model, self.params,
                                temperature=self.temperature,
                                seed=_i,
                                sharding=_replica_sharding(_i),
                                **_opts)

                        agents[rid] = ReplicaAgent(
                            rid, factory, dc,
                            stall_deadline_s=(
                                self.engine_stall_deadline_s)).start()
                    self._engine = FleetRouter(dc, tf)
                    if self.autoscale:
                        import itertools

                        from ray_tpu.serve.fleet.provider import (
                            LoopbackAgentProvider)
                        from ray_tpu.serve.pool_autoscaler import (
                            PoolAutoscaler, SLOPolicy)
                        seq = itertools.count(self.fleet)

                        def spawn_agent(rid, _opts=opts):
                            # provisioning == building + starting a
                            # loopback agent; inserted in the
                            # transport map BEFORE start() so the
                            # router can route the moment the
                            # directory advertises it
                            n = next(seq)

                            def f(gen, _n=n):
                                return LLMEngine(
                                    self.model, self.params,
                                    temperature=self.temperature,
                                    seed=_n,
                                    sharding=_replica_sharding(_n),
                                    **_opts)

                            a = ReplicaAgent(
                                rid, f, dc,
                                stall_deadline_s=(
                                    self.engine_stall_deadline_s))
                            agents[rid] = a
                            return a.start()

                        policy = SLOPolicy(
                            min_replicas=self.fleet,
                            max_replicas=self.autoscale_max_replicas,
                            **self.autoscale_policy)
                        self._autoscaler = PoolAutoscaler(
                            self._engine, policy,
                            LoopbackAgentProvider(spawn_agent)).run(
                                self.autoscale_interval_s)
                elif (self.num_engine_replicas > 1 or self.autoscale
                      or self.disaggregate):
                    from ray_tpu.serve.engine_pool import EnginePool

                    def factory(idx, _opts=opts):
                        return LLMEngine(
                            self.model, self.params,
                            temperature=self.temperature,
                            seed=idx,
                            sharding=_replica_sharding(idx),
                            **_opts)

                    pool_kw: Dict[str, Any] = dict(
                        auto_restart=self.pool_auto_restart,
                        kv_pull_deadline_s=self.kv_pull_deadline_s,
                        kv_pull_backoff_s=self.kv_pull_backoff_s)
                    if self.disaggregate:
                        from ray_tpu.serve.scheduler import (
                            ROLE_DECODE, ROLE_PREFILL)
                        pool_kw.update(
                            share_prefixes=True,
                            roles=([ROLE_PREFILL]
                                   * self.prefill_replicas
                                   + [ROLE_DECODE]
                                   * self.decode_replicas))
                    self._engine = EnginePool(
                        factory, self.num_engine_replicas,
                        **pool_kw)
                    if self.autoscale and self.disaggregate:
                        # one scaler per role over role-filtered pool
                        # views, one shared capacity provider: the
                        # prefill pool chases TTFT/queue, the decode
                        # pool chases ITL/free slots, and they reach
                        # DIFFERENT sizes on the same trace
                        from ray_tpu.serve.engine_pool import (
                            RolePoolView)
                        from ray_tpu.serve.pool_autoscaler import (
                            ImmediateCapacityProvider,
                            PoolAutoscaler, SLOPolicy)
                        ap = dict(self.autoscale_policy)
                        pre_over = dict(ap.pop("prefill", {}))
                        dec_over = dict(ap.pop("decode", {}))
                        provider = (self.autoscale_provider
                                    or ImmediateCapacityProvider())
                        self._autoscaler = {}
                        for role, floor, over in (
                                (ROLE_PREFILL, self.prefill_replicas,
                                 pre_over),
                                (ROLE_DECODE, self.decode_replicas,
                                 dec_over)):
                            policy = SLOPolicy(
                                min_replicas=floor,
                                max_replicas=(
                                    self.autoscale_max_replicas),
                                **{**ap, **over})
                            self._autoscaler[role] = PoolAutoscaler(
                                RolePoolView(self._engine, role),
                                policy, provider).run(
                                    self.autoscale_interval_s)
                    elif self.autoscale:
                        from ray_tpu.serve.pool_autoscaler import (
                            PoolAutoscaler, SLOPolicy)
                        policy = SLOPolicy(
                            min_replicas=self.num_engine_replicas,
                            max_replicas=self.autoscale_max_replicas,
                            **self.autoscale_policy)
                        self._autoscaler = PoolAutoscaler(
                            self._engine, policy,
                            self.autoscale_provider).run(
                                self.autoscale_interval_s)
                    if self.engine_stall_deadline_s is not None:
                        from ray_tpu.serve.watchdog import PoolWatchdog
                        self._watchdog = PoolWatchdog(
                            self._engine,
                            stall_deadline_s=(
                                self.engine_stall_deadline_s),
                            poll_interval_s=(
                                self.watchdog_interval_s)).run()
                else:
                    self._engine = LLMEngine(
                        self.model, self.params,
                        temperature=self.temperature,
                        sharding=_replica_sharding(0),
                        **opts).start()
                # a serving process now: its start-up heap stays, and
                # what a stream allocates dies young
                from ray_tpu.serve.obs import tune_collector_for_serving
                tune_collector_for_serving()
            return self._engine

    def start_trace(self, log_dir: str) -> float:
        """Trace this replica's chip from outside: start
        ``jax.profiler`` in the process that holds it (the engine's
        ``start_trace``: device planes plus the ``engine.*`` host
        annotations; it starts between two rounds with nothing in
        flight, and ``trace_start`` in the event log carries the round
        and the dispatch counts that join the trace's executions to
        rounds). Reachable through the serve handle
        (``handle.start_trace.remote(dir)``). One engine only: a pool
        of replicas shares the process and the profiler, so trace it
        with ``ray_tpu._private.profiling.start_device_trace``."""
        return self._trace_engine().start_trace(log_dir)

    def stop_trace(self):
        """Stop the trace and write it; returns its span (t0, t1) on
        time.monotonic(). RuntimeError when none is running."""
        return self._trace_engine().stop_trace()

    def _trace_engine(self):
        from ray_tpu.serve.engine import LLMEngine
        eng = self.engine()
        if not isinstance(eng, LLMEngine):
            raise RuntimeError(
                "start_trace/stop_trace drive one engine; this "
                f"deployment runs a {type(eng).__name__}")
        return eng

    def autoscaler(self):
        """The attached PoolAutoscaler (None until the lazy engine is
        built or when autoscale=False). Disaggregated deployments
        return a ``{"prefill": ..., "decode": ...}`` dict — one
        scaler per role."""
        return self._autoscaler

    def watchdog(self):
        """The attached PoolWatchdog (None until the lazy engine is
        built or when engine_stall_deadline_s is None)."""
        return self._watchdog

    def serve_stats(self) -> dict:
        """Replica metrics hook (merged into Replica.stats() under
        "user"): engine counters plus live slot occupancy, without
        forcing a lazy engine into existence."""
        if self._engine is None:
            return {"engine": None}
        eng = self._engine
        if self.fleet:
            # FleetRouter: members are behind the transport seam, so
            # the aggregate comes from their ADVERTISED reports (the
            # directory snapshot), not from reaching into engine
            # locks — the same information a remote router would
            # have.
            out = dict(eng.load_report())
            out.update(consistent=False,
                       max_queued=self._engine_opts["max_queued"],
                       fleet=eng.pool_stats())
            return {"engine": out}
        from ray_tpu.serve.engine_pool import EnginePool
        if isinstance(eng, EnginePool):
            out: dict = dict(eng.stats)
            slots_live = slots_total = 0
            pages_free = pages_total = 0
            for rep_eng in eng.engines():
                locked = rep_eng._lock.acquire(timeout=0.05)
                try:
                    slots_live += sum(1 for s in rep_eng.slots
                                      if s is not None)
                    slots_total += rep_eng.S
                    pages_free += rep_eng.alloc.n_free
                    pages_total += rep_eng.alloc.n_pages - 1
                finally:
                    if locked:
                        rep_eng._lock.release()
            out.update(slots_live=slots_live,
                       slots_total=slots_total,
                       pages_free=pages_free,
                       pages_total=pages_total,
                       consistent=False,
                       max_queued=self._engine_opts["max_queued"],
                       max_retries=self._engine_opts["max_retries"],
                       retry_backoff_s=self._engine_opts[
                           "retry_backoff_s"],
                       pool=eng.pool_stats())
            ps = eng.prefix_stats()
            if ps:
                out["prefix_cache"] = ps
            return {"engine": out}
        # Best-effort lock: the scheduler holds eng._lock across
        # dispatch AND blocking readbacks (seconds under load), and
        # this runs as a sync method ON the replica event loop —
        # waiting here would stall request handling and make the
        # controller's 2s-timeout stats polls misread a busy replica
        # as idle. Lock-free reads of these ints/lists are safe
        # (GIL), just possibly torn across fields.
        locked = eng._lock.acquire(timeout=0.05)
        try:
            live = sum(1 for s in eng.slots if s is not None)
            out = dict(eng.stats)
            free, total = eng.alloc.n_free, eng.alloc.n_pages - 1
        finally:
            if locked:
                eng._lock.release()
        out.update(slots_live=live, slots_total=eng.S,
                   pages_free=free, pages_total=total,
                   consistent=locked,
                   max_queued=eng.max_queued,
                   max_retries=eng.max_retries,
                   retry_backoff_s=eng.retry_backoff_s)
        if eng.prefix_cache is not None:
            out["prefix_cache"] = eng.prefix_cache.stats()
        return {"engine": out}

    def load_report(self) -> Optional[dict]:
        """Compact load snapshot for the controller's replica table
        (engine or pool-aggregate; None before the lazy engine
        exists — an idle replica carries no load)."""
        if self._engine is None:
            return None
        rpt = dict(self._engine.load_report())
        # the digest is an intra-pool affinity signal, not something
        # the deployment-level replica table needs to carry around
        rpt.pop("prefix_digest", None)
        return rpt

    def _request_args(self, payload):
        """(prompt_ids, max_new_tokens, deadline_s, session_id,
        trace_id): a request is a plain token-id list, or a dict
        carrying per-request lifecycle/routing overrides
        ({"prompt_ids": [...], "max_new_tokens": n, "deadline_s": s,
        "session_id": "u123", "trace_id": "ab12..."}) — what the
        HTTP proxy posts through. session_id drives engine-pool
        stickiness and is ignored by a single engine; trace_id is
        the proxy-minted request-scope id stamped into the engine
        event log (serve/obs.py)."""
        if isinstance(payload, dict):
            prompt_ids = payload.get("prompt_ids",
                                     payload.get("prompt"))
            if prompt_ids is None:
                raise ValueError(
                    "request dict needs a 'prompt_ids' key")
            mnt = int(payload.get("max_new_tokens",
                                  self.max_new_tokens))
            dl = payload.get("deadline_s", self.deadline_s)
            sid = payload.get("session_id")
            tid = payload.get("trace_id")
            return list(prompt_ids), mnt, (
                float(dl) if dl is not None else None), (
                str(sid) if sid is not None else None), (
                str(tid) if tid is not None else None)
        return (list(payload), self.max_new_tokens, self.deadline_s,
                None, None)

    def _submit(self, ids, mnt, dl, sid=None, tid=None):
        kw: Dict[str, Any] = dict(max_new_tokens=mnt, deadline_s=dl)
        if sid is not None and (self.num_engine_replicas > 1
                                or self.fleet):
            kw["session_id"] = sid
        if tid is not None:
            kw["trace_id"] = tid
        return self.engine().submit(ids, **kw)

    def _weights_tag(self, h) -> str:
        """``generation:weights_id`` of whatever served ``h`` (the
        X-Model-Generation header value). Handle-first: the pool/
        engine handles know their serving replica; fall back to the
        deployment's own engine surface (single engine), then to the
        never-swapped default."""
        tag = getattr(h, "weights_tag", None)
        if tag:
            return tag
        eng = self.engine()
        gen = getattr(eng, "weight_generation", None)
        if gen is not None:
            return f"{gen}:{getattr(eng, 'weights_id', None)}"
        return "0:g0"

    def _echo(self, payload, h) -> Dict[str, Any]:
        """What a dict payload asked to have echoed about the handle
        that serves it: ``replica`` (``echo_replica``) and
        ``generation`` (``echo_generation``); empty for a plain
        request."""
        echo: Dict[str, Any] = {}
        if isinstance(payload, dict):
            if payload.get("echo_replica"):
                echo["replica"] = getattr(
                    h, "replica_tag", None) or "0:0"
            if payload.get("echo_generation"):
                echo["generation"] = self._weights_tag(h)
        return echo

    def __call__(self, prompt_ids: List[int]) -> List[int]:
        """One request: token ids in, prompt+generated ids out.

        A dict payload with ``"echo_replica": true`` (injected by the
        HTTP proxy when the client sends an ``X-Replica`` request
        header) gets ``{"ids": [...], "replica": "<id>:<gen>"}``
        back instead of the bare list — the tag names which replica
        incarnation actually served the request (pool ``idx:gen``,
        fleet ``replica_id:generation``, single engine ``0:0``), so
        a client can see a failover land on a different
        incarnation."""
        ids, mnt, dl, sid, tid = self._request_args(prompt_ids)
        h = self._submit(ids, mnt, dl, sid, tid)
        out = list(ids) + h.result()
        echo = self._echo(prompt_ids, h)
        return {"ids": out, **echo} if echo else out

    def stream(self, prompt_ids: List[int]):
        """Streaming request: yields each generated token id as soon
        as it is sampled (token-at-a-time decode; serve wraps this
        generator in a StreamingResponse and the HTTP proxy in a
        chunked ndjson response).

        ``"echo_replica": true`` in a dict payload makes the FIRST
        yield ``{"replica": "<id>:<gen>"}`` instead of a token — the
        proxy pops it into the ``X-Replica`` response header before
        committing the chunked response, so streaming clients get
        the same which-incarnation-served-me signal unary clients
        do."""
        ids, mnt, dl, sid, tid = self._request_args(prompt_ids)
        h = self._submit(ids, mnt, dl, sid, tid)
        echo = self._echo(prompt_ids, h)
        if echo:
            yield echo
        try:
            yield from h.stream()
        except GeneratorExit:
            # The client disconnected: the replica abandons the
            # stream and garbage-collects this generator
            # (controller.py _drain_sync), which closes it here.
            # Cancel so the slot and its KV pages free NOW — an
            # abandoned stream must not decode to completion.
            h.cancel()
            raise

    def generate_batch(self, prompts: List[List[int]]) -> List[List[int]]:
        """Batched generation for throughput serving: every prompt is
        submitted to the engine at once and joins its decode batch as
        slots free up (continuous batching: prompts of any lengths
        share a step, and each completion equals its unbatched
        call's). Returns the generated ids only, in prompt order."""
        eng = self.engine()
        hs = [eng.submit(p, max_new_tokens=self.max_new_tokens)
              for p in prompts]
        return [h.result() for h in hs]
