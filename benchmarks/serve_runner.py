"""The one runner for every configuration of ``kind: serve``.

ray_tpu.init() -> serve.run() of a deployment wrapping LlamaDeployment
(the continuous-batching engine), in this process, as a user deploys it;
clients are threads that call the serve handle and read the stream.
Everything that differs between cells comes from the configuration file
(model sizes, deployment arguments, chips), its family's file
(benchmarks/families/: the model's classes, seeded weights and plain
reference, as ``ctx.family``) and the traffic file (loop, rate or
clients, lengths). An open loop's end-to-end metrics are its
requests' times at the clients; a closed loop's is the tokens a second
its clients received over a window of whole engine rounds.

``--trace 2`` measures first and traces afterwards: up to the moment
the window's numbers are taken it does what ``--trace 0`` does, then
``traced_phase`` traces a few seconds of the same traffic through the
program's own control (``LlamaDeployment.start_trace``/``stop_trace``).
"""
from __future__ import annotations

import itertools
import threading
import time
import types
import uuid
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import parity, trafficgen, weights
from benchmarks.common import (Timer, Tracer, cache_report, log,
                               percentile, prepare_trace,
                               whole_rounds_rate)

TRACE_SECONDS = 4.0            # a few seconds of the steady window
# a closed loop's clients start this far apart, so that the first
# requests enter the engine in the population's order whatever the
# threads' scheduling (64 clients racing gave two trajectories, 3-6 %
# apart in tokens/s, under one seed: PERF.md, PR 24)
CLIENT_STAGGER_S = 0.015
# a closed loop's window is one of whole rounds (common.whole_rounds_rate):
# its clients read this long past the nominal end so that the closing
# burst is whole (a round is 0.16 s since PR 26, 0.5-0.7 s before), and
# a burst may take the guard to reach all of them
EDGE_TAIL_S = 3.0
BURST_GUARD_S = 0.1


class _Client:
    """One request's life as its client saw it (time.monotonic())."""
    __slots__ = ("req", "due", "sent", "token_times", "done", "error",
                 "trace_id", "abandoned")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = None
        self.token_times: List[float] = []
        self.done = None
        self.error = None
        self.abandoned = False
        self.trace_id = uuid.uuid4().hex


def _stream_one(handle, c: _Client, prompt: List[int],
                stop_at: Optional[List[float]]) -> None:
    """Send one request through the serve handle and read its stream.
    ``stop_at``: [instant] at which the client abandons the stream (a
    closed loop's end; a list so that --trace 2 can move it out past
    its traced phase)."""
    payload = {"prompt_ids": prompt,
               "max_new_tokens": c.req.output_len,
               "trace_id": c.trace_id}
    c.sent = time.monotonic()
    try:
        for _tok in handle.stream.options(stream=True).remote(payload):
            now = time.monotonic()
            c.token_times.append(now)
            if stop_at is not None and now >= stop_at[0]:
                c.abandoned = True
                break
        c.done = time.monotonic()
    except Exception as e:            # noqa: BLE001 — counted as failed
        c.error = repr(e)
        c.done = time.monotonic()


class _Sampler(threading.Thread):
    """Once a second: load_report() and the engine's new events (its
    log is a ring of 8192; a window outlasts it)."""

    def __init__(self, eng, after: Optional["_Sampler"] = None):
        super().__init__(name="bench-sampler", daemon=True)
        self.eng, self.samples, self.events = eng, [], []
        # ``after``: carry on where a stopped sampler left off
        self._cursor = after._cursor if after is not None else 0
        self._halt = threading.Event()
        self.dropped = 0

    def drain(self):
        evs = [e for e in self.eng.events.snapshot()
               if e[0] >= self._cursor]
        if evs:
            self.dropped += max(0, evs[0][0] - self._cursor)
            self._cursor = evs[-1][0] + 1
            self.events.extend(evs)

    def run(self):
        while not self._halt.wait(1.0):
            rep = self.eng.load_report()
            self.samples.append({
                "t": time.monotonic(),
                "kv_bytes_in_use": rep["kv_bytes_in_use"],
                "kv_bytes_total": rep["kv_bytes_total"],
                "queue_depth": rep["queue_depth"],
                "free_slots": rep["free_slots"]})
            self.drain()

    def stop(self):
        self._halt.set()
        self.join(timeout=5)
        self.drain()


def traced_phase(dep, trace_dir: str, seconds: float, ramp_s: float,
                 arrivals) -> tuple:
    """--trace 2, after the window: one start and stop of the profiler
    whose trace is thrown away (the first start's cost falls into no
    number), then ``seconds`` of the traffic traced into ``trace_dir``
    through the deployment's own control. An open loop hands
    ``arrivals(t_zero)``, which replays its schedule around ``t_zero``
    (``ramp_s`` of it before); a closed loop's clients are simply still
    running. Returns the traced span on time.monotonic()."""
    prepare_trace(trace_dir, dep.start_trace, dep.stop_trace)
    t_zero = time.monotonic() + ramp_s
    if arrivals is not None:
        threading.Thread(target=arrivals, args=(t_zero,), daemon=True,
                         name="bench-replay").start()
    time.sleep(max(0.0, t_zero - time.monotonic()))
    dep.start_trace(trace_dir)
    time.sleep(seconds)
    return dep.stop_trace()


def run(ctx) -> types.SimpleNamespace:
    """ctx: args, cell, cfg, family, traffic, chips, meter, t_process,
    peaks, trace_dir, rate (sweep override or None)."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.mesh.sharding import infer_sharding
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.serve.sharding import EngineSharding
    from ray_tpu.util.compile_cache import enable_compile_cache

    cfg, tr, args, meter = ctx.cfg, ctx.traffic, ctx.args, ctx.meter
    fam = ctx.family
    dep_args = dict(cfg["deployment"])
    tp = int(dep_args.pop("tensor_parallel", 1))
    # stays in dep_args: it reaches LlamaDeployment only where the file
    # sets it
    ep = int(dep_args.get("expert_parallel", 1))
    if tp * ep != ctx.chips and not ctx.rehearse:
        raise SystemExit(f"benchmarks: cell asks {ctx.chips} chips, its "
                         f"configuration shards over tensor_parallel "
                         f"{tp} x expert_parallel {ep}")
    cache_dir = enable_compile_cache()
    log(f"[cache] {cache_dir} before: {cache_report(cache_dir)}")

    pcfg = fam.program_config(cfg)
    shapes = weights.param_shapes(fam.model(pcfg))
    shardings = None
    if tp * ep > 1:
        es = EngineSharding.build(pcfg, tp=tp, ep=ep,
                                  devices=jax.devices()[:tp * ep])
        shardings = infer_sharding(shapes, es.rules, es.mesh)
    with Timer("weights from the seed, one call", meter):
        params = fam.init_params(shapes, args.seed, shardings)

    # ---- the served path -------------------------------------------
    holder: Dict[str, Any] = {}
    closed = tr["loop"] == "closed"
    n_clients = (max(1, int(tr["clients_per_slot"]
                            * dep_args["max_slots"])) if closed else 0)
    prefix_cache = bool(tr.get("prefix_cache", False))

    @serve.deployment(max_ongoing_requests=max(
        4 * dep_args["max_slots"], n_clients + 8))
    class Llm:
        def __init__(self):
            self.inner = LlamaDeployment(
                config=pcfg, params=params, tensor_parallel=tp,
                prefix_cache=prefix_cache, **dep_args)
            holder["dep"] = self.inner

        def __call__(self, payload):
            return self.inner(payload)

        def stream(self, payload):
            yield from self.inner.stream(payload)

    ray_tpu.init()
    handle = serve.run(Llm.bind(), timeout_s=900)
    vocab = cfg["vocab_size"]

    # ---- warm-up: this cell's prefill widths and its decode program --
    eng = holder["dep"].engine()
    page, chunk = eng.Pg, eng.PC
    widths, w = [], page
    while w < chunk:
        widths.append(w)
        w *= 2
    widths.append(chunk)
    with Timer(f"warm-up: prefill widths {widths} + decode", meter):
        for i, w in enumerate(widths):
            n = max(2, w - 3)
            out = ray_tpu.get(handle.remote({
                "prompt_ids": trafficgen.prompt_tokens(
                    args.seed, 10_000_000 + i, n, vocab),
                "max_new_tokens": 4}), timeout=1100)
            if len(out) != n + 4:
                raise SystemExit("benchmarks: warm-up request returned "
                                 f"{len(out) - n} tokens, asked 4")

    # ---- correct: greedy tokens of the served path vs the reference --
    par = cfg["parity"]
    P, G = par["prompt_len"], par["new_tokens"]
    prompts = [trafficgen.prompt_tokens(args.seed, 20_000_000 + i, P,
                                         vocab)
               for i in range(par["prompts"])]
    with Timer("parity: served path", meter):
        refs = [handle.remote({"prompt_ids": p, "max_new_tokens": G})
                for p in prompts]
        outs = [ray_tpu.get(r, timeout=600) for r in refs]
    ids = np.asarray(outs, np.int32)
    if ids.shape != (len(prompts), P + G):
        raise SystemExit(f"benchmarks: parity output shape {ids.shape}")
    with Timer("parity: plain float32 reference", meter):
        rw = fam.reference_weights(params, pcfg)
        dev_ids = jnp.asarray(ids)
        if shardings is not None:
            dev_ids = jax.device_put(dev_ids, es.replicated)
        logits = np.asarray(fam.reference_logits(rw, dev_ids, pcfg))
    check = parity.margin_rule(logits, ids, P)
    log(f"[correct] margin rule: {check}")
    del logits, rw

    # ---- traffic, made before the window ----------------------------
    shared = int(tr.get("shared_prefix_tokens", 0))
    if closed:
        population = trafficgen.closed_population(tr)
    else:
        population = trafficgen.open_schedule(tr, args.seconds, ctx.rate)
    prompts_by_index = {r.index: trafficgen.prompt_tokens(
        args.seed, r.index, r.prompt_len, vocab, shared)
        for r in population}
    ramp = float(tr.get("ramp_s", 0.0))
    drain_s = float(tr.get("drain_s", 0.0))

    sampler = _Sampler(eng)
    sampler.start()
    stats0 = dict(eng.stats)
    clients: List[_Client] = []
    lock = threading.Lock()

    t_stream = time.monotonic()
    t_open = t_stream + ramp
    t_close = t_open + float(args.seconds)
    tracer = None
    if args.trace == 1:
        tracer = Tracer(ctx.trace_dir, t_open, args.seconds,
                        TRACE_SECONDS)
        tracer.start()
    window_meter = None
    # a closed loop's clients take requests until loop_until and leave
    # their streams at stream_until; --trace 2 keeps them going through
    # its traced phase and cuts them when the trace stops
    after = args.trace == 2
    loop_until = [float("inf") if after else t_close]
    stream_until = [float("inf") if after else t_close + EDGE_TAIL_S]

    if closed:
        counter = itertools.count()

        def client_loop():
            while time.monotonic() < loop_until[0]:
                with lock:
                    r = population[next(counter) % len(population)]
                    c = _Client(r, time.monotonic())
                    clients.append(c)
                _stream_one(handle, c, prompts_by_index[r.index],
                            stream_until)

        for i in range(n_clients):
            threading.Thread(target=client_loop, daemon=True,
                             name=f"bench-client-{i}").start()
            time.sleep(CLIENT_STAGGER_S)
        time.sleep(max(0.0, t_open - time.monotonic()))
        window_meter = meter.snapshot()
        time.sleep(max(0.0, t_close + EDGE_TAIL_S - time.monotonic()))
        in_window = meter.since(window_meter)
        # the clients are cut here by design: a thread leaves its
        # stream at its next token, and nothing below waits for it
    else:
        threads: List[threading.Thread] = []
        for r in population:
            due = t_open + r.due_s
            if window_meter is None and due >= t_open:
                window_meter = meter.snapshot()
            time.sleep(max(0.0, due - time.monotonic()))
            c = _Client(r, due)
            clients.append(c)
            th = threading.Thread(
                target=_stream_one, daemon=True,
                args=(handle, c, prompts_by_index[r.index], None))
            threads.append(th)
            th.start()
        time.sleep(max(0.0, t_close - time.monotonic()))
        in_window = meter.since(window_meter)
        deadline = t_close + drain_s
        for th in threads:
            th.join(timeout=max(0.0, deadline - time.monotonic()))
    t_end = time.monotonic()
    if tracer is not None:
        tracer.join(timeout=120)
    sampler.stop()
    stats = {k: v - stats0.get(k, 0) for k, v in dict(eng.stats).items()}
    log(f"[window] {args.seconds} s + {t_end - t_close:.1f} s drain; "
        f"programs built or loaded inside the window: "
        f"{in_window['programs']:.0f} (must be 0); engine counters "
        f"{ {k: stats[k] for k in sorted(stats) if stats[k]} }; "
        f"events dropped by the ring {sampler.dropped}")

    # ---- what the clients saw ----------------------------------------
    measured = [c for c in clients if t_open <= c.due < t_close]
    failed = 0
    ttft, itl, lateness = [], [], []
    tokens_in_window = 0
    for c in clients:
        tokens_in_window += sum(1 for t in c.token_times
                                if t_open <= t < t_close)
    for c in measured:
        finished = (c.error is None and c.done is not None
                    and len(c.token_times) == c.req.output_len)
        if closed:
            # cut at the window's end by design; only errors fail
            if c.error is not None:
                failed += 1
            continue
        if not finished:
            failed += 1
            continue
        lateness.append(c.sent - c.due)
        ttft.append(c.token_times[0] - c.due)
        if len(c.token_times) > 1:
            itl.append((c.token_times[-1] - c.token_times[0])
                       / (len(c.token_times) - 1))
    e2e: Dict[str, float] = {}
    if closed:
        whole = whole_rounds_rate(
            [t for c in clients for t in c.token_times], t_open, t_close,
            BURST_GUARD_S)
        if whole is None:
            raise SystemExit("benchmarks: no token reached a client "
                             f"within {EDGE_TAIL_S} s of the window's end")
        e2e["serve_tokens_per_s"], span, n_tok, n_first = whole
        log(f"[clients] {n_tok} tokens in {span:.4f} s of whole rounds "
            f"(after an opening burst of {n_first}); between the fixed "
            f"instants {tokens_in_window} in {args.seconds} s = "
            f"{tokens_in_window / float(args.seconds):.3f} tokens/s")
    else:
        for name, xs in (("ttft", ttft), ("itl", itl)):
            for q in (50, 95):
                if xs:
                    e2e[f"{name}_p{q}_ms"] = 1e3 * percentile(xs, q)

        def pcts(xs):
            return ("/".join(f"{1e3 * percentile(xs, q):.1f}"
                             for q in (50, 75, 90, 95))
                    + f" mean {1e3 * float(np.mean(xs)):.1f}"
                    if xs else "none")
        log(f"[clients] {len(measured)} measured, {failed} failed; ttft "
            f"p50/75/90/95 {pcts(ttft)} ms over {len(ttft)}; itl "
            f"p50/75/90/95 {pcts(itl)} ms over {len(itl)}; generator "
            f"lateness p95 "
            f"{1e3 * percentile(lateness, 95) if lateness else 0:.2f} max "
            f"{1e3 * max(lateness, default=0):.2f} ms;"
            f" completed tokens/s in window "
            f"{tokens_in_window / float(args.seconds):.1f}")
    in_win = [s_ for s_ in sampler.samples if t_open <= s_["t"] < t_close]
    if in_win:
        mid = in_win[len(in_win) // 2]
        log(f"[queue] depth at the window's middle {mid['queue_depth']} "
            f"(free slots {mid['free_slots']}), at its end "
            f"{in_win[-1]['queue_depth']} (free slots "
            f"{in_win[-1]['free_slots']}); offered output tokens/s "
            f"{sum(c.req.output_len for c in measured) / args.seconds:.1f}")
    e2e["setup_s"] = t_open - ctx.t_process
    log(f"[cache] after: {cache_report(cache_dir, top=6)}")

    run_ = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, traffic=tr,
        deployment=cfg["deployment"],
        chips=ctx.chips, peaks=ctx.peaks, seconds=float(args.seconds),
        window=(t_open, t_close), clients=clients, measured=measured,
        events=sampler.events, samples=sampler.samples,
        engine_stats=stats, e2e=e2e, attempted=len(measured),
        failed=failed, correct=bool(check["ok"]),
        compiles_in_window=int(in_window["programs"]),
        tokens_in_window=tokens_in_window, ttft_s=ttft, itl_s=itl,
        trace_span=tracer.span if tracer else None,
        trace=None, closed=closed)

    if after:
        # the window is closed and its numbers are taken (everything
        # above): now trace a few seconds of the same traffic
        with lock:
            seen = len(clients)
        if not closed:
            # the drain left the engine empty: replay the head of the
            # same draw, its ramp unmeasured, and trace what follows
            replay = trafficgen.open_schedule(tr, TRACE_SECONDS, ctx.rate)
            for r in replay:       # (a window shorter than the trace)
                if r.index not in prompts_by_index:
                    prompts_by_index[r.index] = trafficgen.prompt_tokens(
                        args.seed, r.index, r.prompt_len, vocab, shared)

            def arrivals(t_zero):
                for r in replay:
                    time.sleep(max(0.0, t_zero + r.due_s
                                   - time.monotonic()))
                    if time.monotonic() >= stream_until[0]:
                        return
                    c = _Client(r, t_zero + r.due_s)
                    with lock:
                        clients.append(c)
                    threading.Thread(
                        target=_stream_one, daemon=True,
                        args=(handle, c, prompts_by_index[r.index],
                              stream_until)).start()
        else:
            arrivals = None
        sampler2 = _Sampler(eng, after=sampler)
        sampler2.start()
        run_.trace_span = traced_phase(
            holder["dep"], ctx.trace_dir, TRACE_SECONDS,
            ramp if not closed else 0.0, arrivals)
        # cut the clients: each leaves its stream at its next token
        loop_until[0] = stream_until[0] = 0.0
        sampler2.stop()
        run_.events = sampler.events + sampler2.events
        run_.samples = sampler.samples + sampler2.samples
        run_.trace_dir = ctx.trace_dir
        t0_, t1_ = run_.trace_span
        times = [t for c in list(clients) for t in list(c.token_times)]
        n_tok = sum(1 for t in times if t0_ <= t < t1_)
        # whole rounds too (the trace is still being written while the
        # clients read on, so the closing burst is there)
        whole = whole_rounds_rate(times, t0_, t1_ - 1.0, BURST_GUARD_S)
        log(f"[traced phase] {t1_ - t0_:.2f} s after the window: "
            f"{n_tok} tokens reached clients = "
            f"{n_tok / (t1_ - t0_):.1f} tokens/s with the profiler on"
            + (f", {whole[0]:.3f} over {whole[1]:.3f} s of whole rounds"
               if whole else "")
            + f" ({len(clients) - seen} requests sent after the window);"
            f" events dropped by the ring {sampler2.dropped}")

    def shutdown():
        serve.shutdown()
        ray_tpu.shutdown()
    run_.shutdown = shutdown
    return run_
