"""Llama serving benchmark (BASELINE.md: "Serve-equiv Llama-2-7B JAX
replica — tokens/s, p50/p99 latency").

Drives a serve deployment wrapping the continuous-batching engine
(serve/engine.py) on the real chip:
- throughput phase: concurrent clients submit straight into the
  engine; requests join/leave the paged-KV decode batch at token
  granularity (no whole-call batch coalescing, no convoy effect);
- streaming phase: tokens stream from the engine measuring
  time-to-first-token and steady-state streaming rate. TTFT is
  reported two ways: client-observed (first stream item through the
  full serve stack) and engine-internal (stamped the moment the
  first token is EMITTED to the request stream — end of that
  request's prefill, the chunked-prefill scheduling target).

--ab runs BOTH paths in this one process — the engine and the r03
decode-to-completion @serve.batch baseline — against the same load
shape, and writes a single artifact with both results plus ratio
fields. No more cross-round comparisons against a different chip
day (the r05 artifact's caveat).

--shared-prefix-len makes every prompt open with the SAME token
prefix (system-prompt / few-shot load shape) — the case the radix-tree
prefix KV cache (serve/prefix_cache.py) exists for. It implies
--prefix-cache unless overridden; with --ab it adds a THIRD run
(engine with the cache off, same load) so the artifact carries a
cache-on vs cache-off engine-TTFT ratio measured in one session.

--spec-len enables model-free speculative decoding in the engine
(prompt-lookup drafts, serve/spec_decode.py) and adds a `spec` block
(accept_rate, tokens_per_dispatch) to the engine result; with --ab it
adds a THIRD run (engine with speculation off, same load) so the
artifact carries a spec-on vs spec-off throughput ratio measured in
one session. --prompt-period makes each prompt's tail cycle with that
period — the repetitive-suffix load shape speculation exists for.

--lifecycle runs the request-lifecycle smoke instead of the
throughput A/B: an UNSATURATED pass (bounded-queue engine, light
client load) then an OVERLOAD burst against a small admission queue
(--max-queued), with injected cancels and sub-millisecond-deadline
probes riding along. The artifact records shed/admitted counts and
latencies from the client side plus the engine's own lifecycle
counters (shed/cancelled/deadline_exceeded), and the headline ratio:
admitted p50 under overload vs unsaturated p50 — bounded admission
is working when that ratio stays ~1 while excess load 429s fast.

--tp N shards every engine replica N-way over an ICI mesh
(serve/sharding.py: Megatron column/row-parallel weights,
head-sharded paged KV — no KV collectives); it composes with
--replicas into the 2-D replica x tp layout. --tp-ab runs the
tensor-parallel A/B instead: the identical engine + greedy load at
tp=1 and sharded tp-way, with a token-parity check spanning plain
decode, prefix-cache hits, and speculative decoding — the artifact
fails schema validation unless the outputs are token-identical.

Every artifact records the git sha it was produced from, plus the
mesh shape it ran on ({tp, replicas}).

Usage: python serve_bench.py [--model 7b|1b|tiny] [--ab] [--out FILE]
       [--requests N] [--threads N] [--gen-tokens N] [--prompt-len N]
       [--slots N] [--decode-chunk N] [--prefill-chunk N]
       [--page-size N] [--shared-prefix-len N]
       [--prefix-cache | --no-prefix-cache]
       [--spec-len N] [--spec-ngram N] [--prompt-period N]
       [--lifecycle] [--max-queued N] [--tp N] [--tp-ab]
(7b needs ~14GB HBM; falls back to 1b automatically on OOM.)
"""
import argparse
import itertools
import json
import os
import statistics
import subprocess
import tempfile
import threading
import time

import numpy as np


def git_sha():
    """Short sha of the checkout the artifact was produced from, so
    SERVE_BENCH_*.json files are attributable across rounds."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:   # noqa: BLE001 — no git / not a checkout
        return "unknown"


def build_configs(name, max_seq_len=None):
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    if name == "7b":
        return "llama2-7b-bf16", LlamaConfig(
            max_seq_len=max_seq_len or 256, param_dtype=jnp.bfloat16)
    if name == "1b":
        return "llama-1.1b-bf16", LlamaConfig(
            max_seq_len=max_seq_len or 256, dim=2048, n_layers=22,
            n_heads=16, n_kv_heads=16, hidden_dim=5632,
            param_dtype=jnp.bfloat16)
    from ray_tpu.models.llama import llama_tiny
    if max_seq_len:
        return "llama-tiny", llama_tiny(max_seq_len=max_seq_len)
    return "llama-tiny", llama_tiny()


PROMPT_LEN = 128
GEN_TOKENS = 64
SLOTS = 16          # continuous-batching decode width
DECODE_CHUNK = 16   # tokens per device dispatch (host-sync amortizer:
                    # each chunk pays one host round trip; its cost
                    # is not measured on the current machine)
PREFILL_CHUNK = 128  # prompt tokens per scheduling round (chunked
                     # prefill: decode interleaves between chunks)

LEGACY_BATCH = 8    # r03 legacy shape: @serve.batch coalescing width


def make_server(cfg, knobs, use_engine=True):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment

    gen_tokens = knobs["gen_tokens"]
    if not use_engine:
        # The r03 decode-to-completion baseline, verbatim: whole-call
        # batching via @serve.batch + one padded generate_batch per
        # coalesced batch (SERVE_BENCH_r03.json's 774 tok/s shape).
        @serve.deployment(max_ongoing_requests=64)
        class LegacyServer:
            def __init__(self):
                self.inner = LlamaDeployment(
                    config=cfg, max_new_tokens=gen_tokens,
                    use_engine=False)

            @serve.batch(max_batch_size=LEGACY_BATCH,
                         batch_wait_timeout_s=0.02)
            async def __call__(self, prompts):
                n = len(prompts)
                padded = list(prompts) + \
                    [prompts[0]] * (LEGACY_BATCH - n)
                out = self.inner.generate_batch(padded)
                return [o[len(p):] for o, p in
                        zip(out[:n], prompts)]

            def stream(self, prompt):
                yield from self.inner.stream(prompt)

            def engine_stats(self):
                return {}

            def engine_ttfts(self):
                return []

            def engine_prefix_stats(self):
                return None

            def engine_spec_stats(self):
                return None

            def engine_lifecycle_stats(self):
                return None

        return serve.run(LegacyServer.bind(), timeout_s=600)

    @serve.deployment(max_ongoing_requests=64)
    class LlamaServer:
        def __init__(self):
            self.inner = LlamaDeployment(
                config=cfg, max_new_tokens=gen_tokens,
                use_engine=use_engine,
                max_slots=knobs["slots"],
                page_size=knobs["page_size"],
                decode_chunk=knobs["decode_chunk"],
                prefill_chunk=knobs["prefill_chunk"],
                prefix_cache=knobs["prefix_cache"],
                spec_len=knobs["spec_len"],
                spec_ngram=knobs["spec_ngram"],
                max_queued=knobs.get("max_queued"),
                n_pages=knobs.get("kv_pages"),
                eos_id=knobs.get("eos_id"),
                num_engine_replicas=knobs.get("replicas", 1),
                tensor_parallel=knobs.get("tp", 1),
                fleet=knobs.get("fleet", 0),
                kv_dtype=knobs.get("kv_dtype"))

        def __call__(self, prompt):
            # joins the engine's decode batch at the next chunk
            # boundary; returns generated ids only
            return self.inner(prompt)[len(prompt):]

        def stream(self, prompt):
            yield from self.inner.stream(prompt)

        def engine_stats(self):
            return dict(self.inner.engine().stats)

        def engine_ttfts(self):
            # submit->first-emission latencies stamped INSIDE the
            # engine at stream-put time (end of each request's
            # prefill) — immune to client/transport skew
            return [float(x) for x in self.inner.engine().ttfts_s]

        def engine_prefix_stats(self):
            return self.inner.engine().prefix_stats()

        def engine_spec_stats(self):
            return self.inner.engine().spec_stats()

        def engine_lifecycle_stats(self):
            # knobs + shed/cancelled/deadline_exceeded counters
            # (engine.py lifecycle_stats) for the artifact
            return self.inner.engine().lifecycle_stats()

        def engine_pool_stats(self):
            # routing counters + per-replica states when the engine is
            # an EnginePool (num_engine_replicas > 1); None otherwise
            eng = self.inner.engine()
            return (eng.pool_stats()
                    if hasattr(eng, "pool_stats") else None)

        def warmup(self, prompt):
            # Pool-aware warmup: every replica compiles its jitted
            # step and caches the shared prefix BEFORE the measured
            # window. Routed warmup would affinity-pin to one replica,
            # leaving the others to compile mid-measurement.
            eng = self.inner.engine()
            if hasattr(eng, "engines"):
                for e in eng.engines():
                    e.submit(list(prompt),
                             max_new_tokens=gen_tokens).result()
            else:
                self.inner(prompt)
            return True

        def probe(self, payload):
            # dict payload path: per-request deadline_s / max_new
            # overrides ride through LlamaDeployment._request_args
            return self.inner(payload)

        def cancel_probe(self, payload, after_s):
            # Injected cancel: submit straight to the engine, let it
            # run for after_s, then cancel — the deterministic stand-in
            # for a client disconnect. Returns the outcome class name
            # so the bench can count cancels vs. races with completion.
            ids, mnt, dl, sid, tid = self.inner._request_args(payload)
            h = self.inner._submit(ids, mnt, dl, sid, tid)
            time.sleep(after_s)
            h.cancel()
            try:
                h.result()
                return "completed"
            except Exception as e:   # noqa: BLE001 — outcome, not error
                return type(e).__name__

    return serve.run(LlamaServer.bind(), timeout_s=600)


def bench(handle, rng, cfg, knobs):
    import ray_tpu

    gen_tokens = knobs["gen_tokens"]
    plen = min(knobs["prompt_len"], cfg.max_seq_len - gen_tokens)
    # Shared-prefix load shape: every prompt opens with the SAME
    # tokens (system prompt / few-shot preamble), tails random. The
    # prefix comes from its own fixed-seed RNG so cache-on and
    # cache-off runs see the IDENTICAL prefix; at least one tail
    # token stays random so requests are distinct.
    shared = min(knobs["shared_prefix_len"], plen - 1)
    prefix = (np.random.RandomState(knobs.get("seed", 0) + 12345)
              .randint(1, cfg.vocab_size - 1, size=shared).tolist()
              if shared > 0 else [])

    period = knobs["prompt_period"]
    # Multi-session load shape (--prompt-pool W): requests draw from
    # W fixed distinct prompts (W "sessions", each re-asking with its
    # own long context) instead of a fresh random tail per request.
    # Reuse is what the radix cache — and the pool's prefix-affinity
    # sharding of it — exists for; the pool comes from its own fixed
    # seed so every arm of an A/B sees the identical session set.
    pool_n = knobs.get("prompt_pool") or 0
    pool_order = knobs.get("prompt_order") or "random"
    session_prompts = []
    if pool_n > 0:
        prng = np.random.RandomState(knobs.get("seed", 0) + 54321)
        for _ in range(pool_n):
            tail = prng.randint(1, cfg.vocab_size - 1,
                                size=plen - len(prefix)).tolist()
            session_prompts.append(prefix + tail)
    session_seq = itertools.count()

    def prompt():
        if session_prompts:
            if pool_order == "cyclic":
                # round-robin over the sessions (a fixed agent set
                # taking turns): each context is re-asked only after
                # every other one — the adversarial pattern for one
                # LRU cache, the natural one for an affinity-sharded
                # fleet where each session has a home replica
                k = next(session_seq) % len(session_prompts)
            else:
                k = int(rng.randint(len(session_prompts)))
            return list(session_prompts[k])
        n_tail = plen - len(prefix)
        if period > 0:
            # repetitive-suffix load shape (extraction / code-edit /
            # multi-turn): each request's tail cycles its own random
            # pattern, so prompt-lookup speculation has structure to
            # find while requests stay distinct
            pat = rng.randint(1, cfg.vocab_size - 1,
                              size=min(period, n_tail))
            tail = np.tile(pat, -(-n_tail // len(pat)))[:n_tail].tolist()
        else:
            tail = rng.randint(1, cfg.vocab_size - 1,
                               size=n_tail).tolist()
        return prefix + tail

    # --- warmup / compile (one batched decode + one stream step) ----
    t0 = time.time()
    if knobs.get("replicas", 1) > 1:
        # per-replica warmup: compile + prefix-seed EVERY replica
        ray_tpu.get(handle.warmup.remote(prompt()), timeout=3600)
    else:
        ray_tpu.get(handle.remote(prompt()), timeout=3600)
    compile_s = time.time() - t0
    print(f"warmup+compile: {compile_s:.1f}s", flush=True)

    # --- throughput: n_req requests from n_threads threads ----------
    n_req, n_threads = knobs["requests"], knobs["threads"]
    latencies = []
    lat_lock = threading.Lock()

    def client(n):
        for _ in range(n):
            t = time.time()
            ray_tpu.get(handle.remote(prompt()), timeout=3600)
            with lat_lock:
                latencies.append(time.time() - t)

    counts = [n_req // n_threads + (1 if i < n_req % n_threads else 0)
              for i in range(n_threads)]
    t0 = time.time()
    threads = [threading.Thread(target=client, args=(c,))
               for c in counts if c]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    throughput = n_req * gen_tokens / wall
    lat_ms = sorted(x * 1000 for x in latencies)
    p50 = statistics.median(lat_ms)
    p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]

    # --- streaming: time-to-first-token + token rate ---------------
    # Client-observed TTFT: wall time until the first STREAM ITEM
    # arrives. With chunked prefill the engine emits the first token
    # at end-of-prompt-prefill, so this now measures prefill latency,
    # not prefill + decode-chunk drain (the r05 accounting gap).
    ttfts, rates = [], []
    for _ in range(3):
        t0 = time.time()
        it = iter(handle.stream.options(stream=True).remote(prompt()))
        next(it)
        ttfts.append(time.time() - t0)
        n = 1
        for _tok in it:
            n += 1
        dt = time.time() - t0
        rates.append(n / dt)
    out = {
        "throughput_tok_s": round(throughput, 1),
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
        "ttft_ms": round(min(ttfts) * 1000, 1),
        "stream_tok_s": round(max(rates), 1),
        "requests": n_req,
        "client_threads": n_threads,
        "compile_s": round(compile_s, 1),
        "prompt_len": plen,
    }
    # Engine-internal TTFT over the whole run (throughput + stream
    # phases): stamped at first emission to each request's stream.
    try:
        eng_ttfts = ray_tpu.get(handle.engine_ttfts.remote(),
                                timeout=60)
    except Exception:
        eng_ttfts = []
    if eng_ttfts:
        out["engine_ttft_ms"] = round(min(eng_ttfts) * 1000, 1)
        out["engine_ttft_p50_ms"] = round(
            statistics.median(eng_ttfts) * 1000, 1)
        # the prefix-cache A/B compares MEANS: min/p50 hide the
        # per-request prefill work the cache actually removes
        out["engine_ttft_mean_ms"] = round(
            statistics.mean(eng_ttfts) * 1000, 2)
    if shared > 0:
        out["shared_prefix_len"] = shared
    if pool_n > 0:
        out["prompt_pool"] = pool_n
        out["prompt_order"] = pool_order
    out["max_seq_len"] = cfg.max_seq_len
    return out


def run_path(args, knobs, use_engine):
    """Serve + bench one path (engine or legacy), with the 7b->1b OOM
    fallback; leaves serve SHUT DOWN so --ab can run the other path
    in this same process (serve.run/shutdown cycling is what
    tests/test_serve.py exercises)."""
    import ray_tpu
    from ray_tpu import serve
    order = {"7b": ["7b", "1b"], "1b": ["1b"],
             "tiny": ["tiny"]}[args.model]
    result = None
    for name in order:
        label, cfg = build_configs(name,
                                   knobs.get("max_seq_len"))
        path = "engine" if use_engine else "legacy_decode_to_completion"
        print(f"model: {label} path: {path}", flush=True)
        try:
            handle = make_server(cfg, knobs, use_engine=use_engine)
            rng = np.random.RandomState(knobs.get("seed", 0))
            result = bench(handle, rng, cfg, knobs)
            result["model"] = label
            result["path"] = path
            break
        except Exception as e:   # noqa: BLE001
            msg = str(e)
            oom = "RESOURCE_EXHAUSTED" in msg or "memory" in msg.lower()
            print(f"{label} failed ({msg[:200]})", flush=True)
            serve.shutdown()
            if not oom or name == order[-1]:
                raise
    result["gen_tokens"] = knobs["gen_tokens"]
    if use_engine:
        result["slots"] = knobs["slots"]
        result["decode_chunk"] = knobs["decode_chunk"]
        result["prefill_chunk"] = knobs["prefill_chunk"]
        result["page_size"] = knobs["page_size"]
        result["prefix_cache_enabled"] = knobs["prefix_cache"]
        if knobs.get("kv_pages") is not None:
            result["kv_pages_per_replica"] = knobs["kv_pages"]
        # (legacy path: engine_stats would lazily build an unused
        # engine — allocating the whole KV pool — just to report zeros)
        try:
            result["engine"] = ray_tpu.get(
                handle.engine_stats.remote(), timeout=60)
        except Exception:
            pass
        try:
            result["lifecycle"] = ray_tpu.get(
                handle.engine_lifecycle_stats.remote(), timeout=60)
        except Exception:
            pass
        if knobs.get("replicas", 1) > 1:
            result["num_engine_replicas"] = knobs["replicas"]
            try:
                ps = ray_tpu.get(handle.engine_pool_stats.remote(),
                                 timeout=60)
                if ps:
                    result["pool"] = ps
            except Exception:
                pass
        if knobs.get("fleet"):
            # the stamp a SERVE_FLEET_CHAOS artifact carries, minus
            # process separation: a bench fleet runs loopback
            result["topology"] = {
                "agents": knobs["fleet"],
                "transport": "loopback",
                "processes": {"directory": "in-process",
                              "agents": "in-process"}}
            try:
                ps = ray_tpu.get(handle.engine_pool_stats.remote(),
                                 timeout=60)
                if ps:
                    result["fleet"] = ps
            except Exception:
                pass
        if knobs["prefix_cache"]:
            try:
                ps = ray_tpu.get(handle.engine_prefix_stats.remote(),
                                 timeout=60)
                if ps:
                    result["prefix_cache"] = ps
            except Exception:
                pass
        if knobs["spec_len"] > 0:
            result["spec_len"] = knobs["spec_len"]
            result["spec_ngram"] = knobs["spec_ngram"]
            try:
                ss = ray_tpu.get(handle.engine_spec_stats.remote(),
                                 timeout=60)
                if ss:
                    result["spec"] = ss
            except Exception:
                pass
    else:
        result["batch"] = LEGACY_BATCH
    serve.shutdown()
    return result


def _percentile(sorted_ms, frac):
    return sorted_ms[min(len(sorted_ms) - 1,
                         int(len(sorted_ms) * frac))]


def run_lifecycle(args, knobs):
    """Request-lifecycle smoke: unsaturated pass, then an overload
    burst against a bounded admission queue with injected cancels and
    deadline probes riding along.

    Two serve sessions (max_queued is an engine-construction knob):
    phase A serves UNBOUNDED and lightly loaded for the baseline p50;
    phase B serves with --max-queued and more client threads than
    slots+queue can hold, so excess submits shed fast with
    EngineOverloaded (the proxy's 429) while admitted requests keep
    near-baseline latency — that containment is what the
    admitted_p50_ratio field measures."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.errors import classify_http_status

    label, cfg = build_configs(args.model,
                               knobs.get("max_seq_len"))
    gen_tokens = knobs["gen_tokens"]
    plen = min(knobs["prompt_len"], cfg.max_seq_len - gen_tokens)
    slots = knobs["slots"]
    rng = np.random.RandomState(knobs.get("seed", 0))

    def prompt():
        return rng.randint(1, cfg.vocab_size - 1, size=plen).tolist()

    def timed_clients(handle, n_threads, prompts_per_thread=None,
                      admit_target=None, wall_limit_s=120.0):
        """Fire requests from n_threads; returns [(outcome, ms)].
        With `admit_target`, threads keep firing until that many
        requests were ADMITTED (completed) — shed attempts don't
        count, so the burst holds the engine at steady-state
        saturation for the whole measurement window instead of
        draining its budget through fast 429s. A shed thread pauses
        one engine retry-backoff before re-arming (a client honoring
        Retry-After), which bounds the shed count."""
        rows, lock = [], threading.Lock()
        admitted = [0]
        t_start = time.time()

        def worker(prompts):
            while True:
                if admit_target is not None:
                    with lock:
                        done = (admitted[0] >= admit_target
                                or time.time() - t_start > wall_limit_s)
                        p = None if done else prompt()
                    if p is None:
                        return
                elif prompts:
                    p = prompts.pop()
                else:
                    return
                t = time.time()
                try:
                    ray_tpu.get(handle.remote(p), timeout=3600)
                    outcome = "ok"
                except Exception as e:   # noqa: BLE001 — classified
                    outcome = classify_http_status(e)
                ms = (time.time() - t) * 1000
                with lock:
                    rows.append((outcome, ms))
                    if outcome == "ok":
                        admitted[0] += 1
                if outcome == 429:
                    time.sleep(0.02)

        threads = [threading.Thread(target=worker, args=(
            [prompt() for _ in range(prompts_per_thread)]
            if prompts_per_thread else None,))
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return rows

    # --- phase A: unsaturated baseline (unbounded queue) ------------
    # Slot-width concurrency: every request goes straight into a slot
    # (no admission queueing, no shedding) while paying the same
    # batched-decode round costs as phase B's admitted requests, so
    # admitted_p50_ratio isolates what overload ADDS — queue wait.
    unsat_threads = max(1, slots)
    unsat_requests = max(2 * unsat_threads, 16)
    print(f"model: {label} lifecycle phase A: {unsat_requests} req / "
          f"{unsat_threads} threads, queue unbounded", flush=True)
    handle = make_server(cfg, dict(knobs, max_queued=None),
                         use_engine=True)
    t0 = time.time()
    ray_tpu.get(handle.remote(prompt()), timeout=3600)
    compile_s = time.time() - t0
    rows = timed_clients(handle, unsat_threads,
                         prompts_per_thread=-(-unsat_requests
                                              // unsat_threads))
    serve.shutdown()
    ok_ms = sorted(ms for o, ms in rows if o == "ok")
    assert ok_ms, f"unsaturated phase produced no completions: {rows}"
    unsat = {
        "p50_ms": round(statistics.median(ok_ms), 1),
        "p99_ms": round(_percentile(ok_ms, 0.99), 1),
        "requests": len(ok_ms),
        "client_threads": unsat_threads,
        "compile_s": round(compile_s, 1),
    }

    # --- phase B: overload burst against a bounded queue ------------
    mq = args.max_queued
    over_threads = max(knobs["threads"], slots + mq + 2)
    admit_target = knobs["requests"]
    print(f"lifecycle phase B: {admit_target} admitted-request "
          f"target / {over_threads} threads, max_queued={mq}",
          flush=True)
    handle = make_server(cfg, dict(knobs, max_queued=mq),
                         use_engine=True)
    ray_tpu.get(handle.remote(prompt()), timeout=3600)
    rows = timed_clients(handle, over_threads,
                         admit_target=admit_target)
    admitted = sorted(ms for o, ms in rows if o == "ok")
    shed = sorted(ms for o, ms in rows if o == 429)
    other = [o for o, _ in rows if o not in ("ok", 429)]

    # --- injected cancels + deadline probes (same bounded server) ---
    cancel_outcomes = []
    for _ in range(4):
        payload = {"prompt_ids": prompt(),
                   "max_new_tokens": min(64, cfg.max_seq_len - plen)}
        cancel_outcomes.append(ray_tpu.get(
            handle.cancel_probe.remote(payload, 0.01), timeout=120))
    deadline_statuses = []
    for _ in range(4):
        payload = {"prompt_ids": prompt(), "deadline_s": 1e-4}
        try:
            ray_tpu.get(handle.probe.remote(payload), timeout=120)
            deadline_statuses.append("ok")
        except Exception as e:   # noqa: BLE001 — classified
            deadline_statuses.append(classify_http_status(e))

    lifecycle = ray_tpu.get(handle.engine_lifecycle_stats.remote(),
                            timeout=60)
    serve.shutdown()

    assert admitted, f"overload phase admitted nothing: {rows[:8]}"
    over = {
        "attempts": len(rows),
        "admitted": len(admitted),
        "shed": len(shed),
        "other_errors": len(other),
        "admitted_p50_ms": round(statistics.median(admitted), 1),
        "admitted_p99_ms": round(_percentile(admitted, 0.99), 1),
        "shed_p50_ms": (round(statistics.median(shed), 1)
                        if shed else None),
        "client_threads": over_threads,
        "cancel_probes": len(cancel_outcomes),
        "cancelled": cancel_outcomes.count("RequestCancelled"),
        "deadline_probes": len(deadline_statuses),
        "deadline_exceeded": deadline_statuses.count(504),
    }
    ratio = _ratio(over["admitted_p50_ms"], unsat["p50_ms"])
    result = {
        "unsaturated": unsat,
        "overloaded": over,
        "admitted_p50_ratio": ratio,
        "lifecycle": lifecycle,
        "model": label,
        "gen_tokens": gen_tokens,
        "prompt_len": plen,
        "slots": slots,
        "max_queued": mq,
        "decode_chunk": knobs["decode_chunk"],
        "prefill_chunk": knobs["prefill_chunk"],
        "notes": "Request-lifecycle smoke (serve_bench.py "
                 "--lifecycle): baseline at slot-width concurrency "
                 "(no admission queueing) then an overload burst "
                 "against max_queued admission; excess load sheds "
                 "fast (EngineOverloaded -> 429 at the proxy) while "
                 "admitted p50 stays near baseline "
                 "(admitted_p50_ratio). Cancels are injected via "
                 "engine-handle cancel_probe; deadline probes use a "
                 "sub-millisecond per-request deadline_s.",
    }
    if ratio is not None and not 0.9 <= ratio <= 1.1:
        print(f"WARNING: admitted p50 ratio {ratio} outside "
              "[0.9, 1.1] — overload latency not comparable to "
              "baseline", flush=True)
    return result


def run_pool_kill(seed=0):
    """Replica-kill recovery run for the pool artifact: a 2-replica
    EnginePool built DIRECTLY (no serve hop — the kill round must be
    deterministic), FaultInjector kills replica 0 mid-decode.

    Contract being measured (ISSUE acceptance: zero lost requests):
    - requests that had not streamed a token resubmit to the survivor
      and complete TOKEN-IDENTICALLY to the single-engine reference;
    - requests that had already streamed fail TYPED (EngineShutdown);
    - nothing hangs and nothing is silently dropped (lost == 0);
    - every survivor quiesces with zero leaked pages.

    Always runs the tiny model: this phase checks recovery accounting,
    not throughput, and must stay cheap on CPU."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, generate, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.errors import EngineShutdown
    from ray_tpu.serve.faults import FaultInjector, check_pool_quiesced

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    inj = FaultInjector()
    inj.kill_replica(round=6)

    def factory(idx):
        # injector only on replica 0's first generation: the death is
        # injected once, the survivor stays clean
        return LLMEngine(model, params, max_slots=2, page_size=16,
                         n_pages=64, chunk=2, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=idx,
                         fault_injector=inj if idx == 0 else None)

    n_req, n_new = 8, 20
    rng = np.random.RandomState(seed + 7)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=12).tolist()
               for _ in range(n_req)]
    want = [np.asarray(generate(
        model, params, jnp.asarray([p], jnp.int32),
        max_new_tokens=n_new, temperature=0.0))[0, len(p):].tolist()
        for p in prompts]

    pool = EnginePool(factory, 2)
    outcomes = [None] * n_req

    def consume(i):
        try:
            outcomes[i] = ("ok", pool.submit(
                prompts[i], max_new_tokens=n_new).result())
        except EngineShutdown:
            outcomes[i] = ("failed_typed", None)
        except Exception as e:   # noqa: BLE001 — accounted as lost
            outcomes[i] = ("lost", type(e).__name__)

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    hung = sum(t.is_alive() for t in threads)
    completed = sum(1 for o in outcomes
                    if o is not None and o[0] == "ok")
    failed_typed = sum(1 for o in outcomes
                       if o is not None and o[0] == "failed_typed")
    identical = all(o[1] == want[i]
                    for i, o in enumerate(outcomes)
                    if o is not None and o[0] == "ok")
    rs = dict(pool.pool_stats())
    pool.shutdown()
    check_pool_quiesced(pool)
    return {
        "requests": n_req,
        "completed": completed,
        "failed_typed": failed_typed,
        "resubmitted": int(rs.get("requeues", 0)),
        "replica_deaths": int(rs.get("replica_deaths", 0)),
        "token_identical": bool(identical),
        "lost": n_req - completed - failed_typed + hung,
    }


def run_trace(args):
    """Request-scope trace capture (bare ``--trace``): drive a small
    engine with the typed event log ON, export the ring as a
    Chrome/Perfetto ``trace_events`` timeline plus a per-request
    phase index (admit -> queue -> prefill chunks -> decode rounds ->
    readback -> retire), and prove the recorder is free with an
    events-on vs events-off A/B over the identical load.

    Always the tiny model: this phase documents WHERE time goes, not
    how much of it there is — it must stay cheap on CPU. max_slots
    is sized BELOW the request count so queue_wait is a real phase
    in the capture, not a zero."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve import obs
    from ray_tpu.serve.engine import LLMEngine

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    n_req, n_new = 6, 16
    rng = np.random.RandomState(args.seed + 11)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=24).tolist()
               for _ in range(n_req)]

    def arm(events_on):
        eng = LLMEngine(model, params, max_slots=2, page_size=16,
                        n_pages=128, chunk=4, prefill_chunk=16,
                        temperature=0.0, eos_id=-1, seed=args.seed,
                        events=events_on).start()
        # compile the jitted step OUTSIDE the measured window
        eng.submit(prompts[0], max_new_tokens=2).result()
        t0 = time.monotonic()
        handles = [eng.submit(p, max_new_tokens=n_new,
                              trace_id=obs.mint_trace_id())
                   for p in prompts]
        toks = sum(len(h.result()) for h in handles)
        wall = time.monotonic() - t0
        evs = eng.events.snapshot()
        eng.shutdown()
        return toks / max(wall, 1e-9), evs

    tput_on, evs = arm(True)
    tput_off, _ = arm(False)

    requests = {}
    for rid, ph in obs.request_phases(evs).items():
        requests[str(rid)] = {
            k: ph.get(k) for k in
            ("trace_id", "outcome", "n_tokens", "queue_wait_s",
             "prefill_s", "decode_s", "ttft_s", "total_s",
             "submit", "first_token", "end")}
    return {
        "model": "llama-tiny",
        "requests_n": len(requests),
        "gen_tokens": n_new,
        "requests": requests,
        "events": obs.as_dicts(evs),
        "trace_events": obs.chrome_trace({"engine": evs}),
        "overhead": {
            "tokens_s_events_on": round(tput_on, 2),
            "tokens_s_events_off": round(tput_off, 2),
            "ratio": round(tput_on / max(tput_off, 1e-9), 4),
        },
        "notes": "Request-scope trace capture (serve_bench.py "
                 "--trace): typed engine event log exported as "
                 "Chrome/Perfetto trace_events (load into "
                 "ui.perfetto.dev) plus a per-request phase index. "
                 "overhead.ratio is events-on vs events-off "
                 "throughput on the identical load — the recorder "
                 "must be free.",
    }


def make_trace(name, duration_s, base_rps, peak_rps, seed,
               n_tenants=4):
    """Arrival schedule [(t_offset_s, tenant_or_None), ...] for one
    trace shape, deterministic in ``seed``:

    - ``diurnal``: one smooth day-curve swing base -> peak -> base
      (raised cosine) — the slow ramp an autoscaler should track
      without ever shedding.
    - ``bursty``: flat base load with two square-wave bursts to peak
      (the second shorter) — the step changes that force provisioning
      delay and hysteresis to earn their keep.
    - ``multitenant``: per-tenant staggered burst windows on top of
      the base; each arrival carries its tenant id and tenants share
      a per-tenant prompt prefix, so affinity routing sees structure.

    Arrivals are a thinned Poisson process: per 50ms step, a Poisson
    draw at the instantaneous rate, spread uniformly in the step.
    """
    import math
    rng = np.random.RandomState(seed + 777)
    dt = 0.05
    events = []
    steps = int(duration_s / dt)
    for i in range(steps):
        t = i * dt
        x = t / duration_s
        if name == "diurnal":
            rate = base_rps + (peak_rps - base_rps) * 0.5 * (
                1.0 - math.cos(2.0 * math.pi * x))
        elif name == "bursty":
            in_burst = (0.18 <= x < 0.42) or (0.52 <= x < 0.66)
            rate = peak_rps if in_burst else base_rps
        elif name == "multitenant":
            rate = base_rps
            for k in range(n_tenants):
                lo = 0.12 + 0.17 * k
                if lo <= x < lo + 0.14:
                    rate += (peak_rps - base_rps) / 2.0
        else:
            raise ValueError(f"unknown trace {name!r}")
        for _ in range(int(rng.poisson(rate * dt))):
            tenant = (int(rng.randint(n_tenants))
                      if name == "multitenant" else None)
            events.append((t + float(rng.uniform(0.0, dt)), tenant))
    events.sort()
    return events


def _replay_trace(pool, events, prompt_fn, gen_tokens, slo_s,
                  eta_fn, label):
    """Open-loop replay of ``events`` against ``pool``: one client
    thread per arrival, firing at its scheduled offset regardless of
    how the pool is doing (closed-loop clients would mask overload —
    the millions-of-users regime is open-loop).

    A shed client honors Retry-After (sleeps the hint, retries up to
    3 times), and every shed is checked for the CONTRACT: a hint that
    invites the client back sooner than the autoscaler's remaining
    provisioning ETA at that moment is a violation — the pool
    promised capacity it knew it would not have.

    Returns (rows, samples): per-request outcome rows (TTFT measured
    from the ORIGINAL arrival, spanning shed-retries — the client's
    honest SLO view) and 25ms (t, active_replicas) samples for the
    replica timeline / chip-seconds integral.
    """
    from ray_tpu.serve.errors import (EngineOverloaded,
                                      retry_after_s)
    rows, lock = [], threading.Lock()
    t0 = time.monotonic()
    stop_sampler = threading.Event()
    samples = []

    def sampler():
        while not stop_sampler.is_set():
            samples.append((time.monotonic() - t0,
                            pool.active_count()))
            stop_sampler.wait(0.025)

    samp = threading.Thread(target=sampler, daemon=True)
    samp.start()

    def worker(prompt):
        t_arr = time.monotonic()
        row = {"outcome": None, "ttft_s": None, "sheds": 0,
               "violations": 0}
        for attempt in range(4):
            try:
                h = pool.submit(prompt, max_new_tokens=gen_tokens)
                for _tok in h.stream():
                    if row["ttft_s"] is None:
                        row["ttft_s"] = time.monotonic() - t_arr
                row["outcome"] = "ok"
                break
            except EngineOverloaded as e:
                hint = retry_after_s(e)
                eta = eta_fn() if eta_fn is not None else 0.0
                row["sheds"] += 1
                if hint + 1e-6 < eta:
                    row["violations"] += 1
                if attempt == 3:
                    row["outcome"] = "shed"
                    break
                time.sleep(min(hint, 2.0))
            except Exception as e:   # noqa: BLE001 — accounted
                row["outcome"] = type(e).__name__
                break
        with lock:
            rows.append(row)

    threads = []
    for t_off, tenant in events:
        now = time.monotonic() - t0
        if t_off > now:
            time.sleep(t_off - now)
        th = threading.Thread(target=worker,
                              args=(prompt_fn(tenant),),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=120)
    hung = sum(th.is_alive() for th in threads)
    stop_sampler.set()
    samp.join(timeout=5)
    if hung:
        print(f"WARNING: {label}: {hung} clients hung", flush=True)
    return rows, samples


def _arm_summary(rows, samples, slo_s):
    """Per-arm result block: SLO attainment counts every ARRIVAL
    (a shed request missed its SLO; grading only completions would
    let the pool shed its way to a perfect score)."""
    n = len(rows)
    ttfts = sorted(r["ttft_s"] for r in rows
                   if r["ttft_s"] is not None)
    completed = sum(1 for r in rows if r["outcome"] == "ok")
    shed = sum(1 for r in rows if r["outcome"] == "shed")
    errors = n - completed - shed
    within = sum(1 for r in rows
                 if r["outcome"] == "ok" and r["ttft_s"] is not None
                 and r["ttft_s"] <= slo_s)
    chip_seconds = 0.0
    for (t_a, n_a), (t_b, _) in zip(samples, samples[1:]):
        chip_seconds += n_a * (t_b - t_a)
    out = {
        "requests": n,
        "completed": completed,
        "shed": shed,
        "errors": errors,
        "shed_events": sum(r["sheds"] for r in rows),
        "retry_after_violations": sum(r["violations"]
                                      for r in rows),
        "slo_attainment": round(within / n, 4) if n else 0.0,
        "chip_seconds": round(chip_seconds, 2),
    }
    if ttfts:
        out["ttft_p50_ms"] = round(
            statistics.median(ttfts) * 1000, 1)
        out["ttft_p95_ms"] = round(
            _percentile(ttfts, 0.95) * 1000, 1)
    return out


def _decimate_timeline(samples):
    """[(t, n)] keeping only replica-count CHANGES (plus endpoints):
    the full 25ms sample train is noise the artifact doesn't need."""
    out = []
    for t, n in samples:
        if not out or out[-1][1] != n:
            out.append([round(t, 3), int(n)])
    if samples and (not out or out[-1][0] != round(samples[-1][0], 3)):
        out.append([round(samples[-1][0], 3), int(samples[-1][1])])
    return out


def run_autoscale(args):
    """Trace-driven autoscaling run (serve_bench.py --autoscale): the
    SAME arrival trace replayed twice against a direct EnginePool —

    - ``autoscale`` arm: pool starts at --autoscale-min replicas with
      a PoolAutoscaler provisioning through a SimulatedTPUCloud
      (--provision-delay modeled), scale-down via the health-gated
      drain path;
    - ``static_max`` arm: a fixed pool at --autoscale-max replicas —
      the capacity ceiling money could buy up front.

    The artifact records SLO attainment (TTFT against --ttft-slo-ms,
    graded over ALL arrivals), the replica-count timeline, and the
    chip-seconds integral of each arm: the autoscaler earns its keep
    when attainment holds while chip_seconds_ratio < 1. Violations of
    the Retry-After contract (a shed hint shorter than the remaining
    provisioning ETA) must be zero by construction — the pool folds
    the autoscaler's capacity ETA into every all-shed hint.

    Always the tiny model on whatever platform is active: this run
    proves CONTROL behavior (scale up under pressure, down when
    quiet, no flapping, honest hints), not model throughput."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.autoscaler.node_provider import (
        SimulatedTPUCloud, TPUSliceCapacityProvider)
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.faults import check_pool_quiesced
    from ray_tpu.serve.pool_autoscaler import (PoolAutoscaler,
                                               SLOPolicy)

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    gen_tokens = args.gen_tokens     # more tokens = more decode work
    plen = 12                        # per arrival = real pressure
    slo_s = args.ttft_slo_ms / 1000.0
    prng = np.random.RandomState(args.seed)
    tenant_prefixes = [
        np.random.RandomState(args.seed + 1000 + k)
        .randint(1, cfg.vocab_size - 1, size=6).tolist()
        for k in range(4)]

    def prompt_fn(tenant):
        tail_n = plen if tenant is None else plen - 6
        tail = prng.randint(1, cfg.vocab_size - 1,
                            size=tail_n).tolist()
        if tenant is None:
            return tail
        return tenant_prefixes[tenant] + tail

    def _build_engine(seed):
        # One throwaway request compiles the jitted step before the
        # replica ever takes traffic, then the compile-priced TTFT is
        # scrubbed — left in the EWMA it reads to the autoscaler as a
        # permanent SLO breach.
        eng = LLMEngine(model, params, max_slots=args.slots_per_replica,
                        page_size=16, n_pages=96, chunk=2,
                        prefill_chunk=16, temperature=0.0,
                        eos_id=-1, seed=seed,
                        max_queued=args.max_queued_per_replica)
        eng.start()
        eng.submit([1] * plen, max_new_tokens=2).result()
        eng.reset_latency_stats()
        return eng

    # Replicas join the pool WARM, from a stash compiled up front —
    # the pre-baked image a real fleet boots replicas from. Building
    # (= compiling, seconds on CPU) inside the factory would block
    # the control loop mid-harvest and turn every scale-up into an
    # SLO dip the CLOUD's provisioning delay is supposed to model.
    warm_stash = [
        _build_engine(i)
        for i in range(args.autoscale_max * 2
                       + args.autoscale_min + 3)]
    print(f"warm stash: {len(warm_stash)} engines compiled",
          flush=True)

    def factory(idx):
        if warm_stash:
            return warm_stash.pop()
        print("warm stash empty: cold replica build", flush=True)
        return _build_engine(idx + 100)

    # --trace doubles as the capture flag; anything that isn't a
    # known arrival shape means "default shape" here
    shape = (args.trace if args.trace in
             ("diurnal", "bursty", "multitenant") else "bursty")
    events = make_trace(shape, args.trace_duration,
                        args.base_rps, args.peak_rps, args.seed)
    print(f"trace {shape}: {len(events)} arrivals over "
          f"{args.trace_duration}s (base {args.base_rps} rps, peak "
          f"{args.peak_rps} rps)", flush=True)

    # --- arm 1: autoscaled pool ------------------------------------
    cloud = SimulatedTPUCloud(
        provision_delay_s=args.provision_delay)
    provider = TPUSliceCapacityProvider(cloud, "v5e-1")
    pool = EnginePool(factory, args.autoscale_min,
                      auto_restart=True)
    policy = SLOPolicy(
        min_replicas=args.autoscale_min,
        max_replicas=args.autoscale_max,
        queue_high=1.5, queue_low=0.25,
        shed_rate_high=0.0,
        ttft_slo_s=slo_s,
        free_slot_frac_low=0.15, free_slot_frac_high=0.5,
        idle_stable_s=1.0,
        cooldown_up_s=0.3, cooldown_down_s=1.2,
        scale_up_step=2,      # bursts step faster than they drain
        drain_timeout_s=15.0)
    scaler = PoolAutoscaler(pool, policy, provider).run(
        interval_s=0.1)
    print("autoscale arm", flush=True)
    rows, samples = _replay_trace(
        pool, events, prompt_fn, gen_tokens, slo_s,
        scaler.capacity_eta_s, "autoscale")
    # let the tail drain + scale back down before stopping the loop
    deadline = time.monotonic() + (
        policy.idle_stable_s + policy.cooldown_down_s *
        (args.autoscale_max - args.autoscale_min) + 5.0)
    while (pool.active_count() > args.autoscale_min
           and time.monotonic() < deadline):
        time.sleep(0.1)
        samples.append((samples[-1][0] + 0.1 if samples else 0.0,
                        pool.active_count()))
    scaler.stop()
    auto_stats = scaler.stats()
    pool.shutdown()
    check_pool_quiesced(pool)
    auto = _arm_summary(rows, samples, slo_s)
    auto["replica_timeline"] = _decimate_timeline(samples)
    counts = [n for _, n in samples]
    auto["replicas_min_seen"] = int(min(counts))
    auto["replicas_max_seen"] = int(max(counts))
    auto["scale_ups"] = auto_stats["scale_ups"]
    auto["scale_downs"] = auto_stats["scale_downs"]
    auto["holds"] = auto_stats["holds"]
    auto["denied"] = auto_stats["denied"]

    # --- arm 2: static pool at max ---------------------------------
    print("static-max arm", flush=True)
    prng.seed(args.seed)            # identical prompt stream
    pool2 = EnginePool(factory, args.autoscale_max)
    rows2, samples2 = _replay_trace(
        pool2, events, prompt_fn, gen_tokens, slo_s, None,
        "static_max")
    pool2.shutdown()
    check_pool_quiesced(pool2)
    # Same integration horizon for both arms: the autoscale window
    # extends past the trace while the pool drains back to min, and
    # a static fleet holds ALL max replicas through that same tail —
    # that standing allocation is exactly what autoscaling refunds.
    auto_end = samples[-1][0] if samples else 0.0
    static_end = samples2[-1][0] if samples2 else 0.0
    if auto_end > static_end:
        samples2.append((auto_end, args.autoscale_max))
    static = _arm_summary(rows2, samples2, slo_s)

    result = {
        "trace": shape,
        "model": "llama-tiny",
        "trace_duration_s": args.trace_duration,
        "base_rps": args.base_rps,
        "peak_rps": args.peak_rps,
        "arrivals": len(events),
        "gen_tokens": gen_tokens,
        "prompt_len": plen,
        "slots_per_replica": args.slots_per_replica,
        "max_queued_per_replica": args.max_queued_per_replica,
        "replicas_min": args.autoscale_min,
        "replicas_max": args.autoscale_max,
        "provision_delay_s": args.provision_delay,
        "slo": {"ttft_ms": args.ttft_slo_ms,
                "attainment_floor": args.attainment_floor},
        "autoscale": auto,
        "static_max": static,
        "chip_seconds_ratio": _ratio(auto["chip_seconds"],
                                     static["chip_seconds"]),
        "ttft_p50_ratio": _ratio(auto.get("ttft_p50_ms"),
                                 static.get("ttft_p50_ms")),
        "notes": "Trace-driven autoscaling run (serve_bench.py "
                 "--autoscale): the same open-loop arrival trace "
                 "replayed against an SLO-driven autoscaled pool "
                 "(min->max replicas, SimulatedTPUCloud provisioning "
                 "with modeled delay, scale-down via health-gated "
                 "drain) and a static pool at max. SLO attainment "
                 "grades TTFT over ALL arrivals (sheds count "
                 "against); chip_seconds integrates active replicas "
                 "over each arm's wall clock; "
                 "retry_after_violations counts sheds whose hint "
                 "was shorter than the remaining provisioning ETA "
                 "(the Retry-After honesty contract) and must be 0.",
    }
    return result


def run_fleet_autoscale(args):
    """Trace-driven autoscaling across PROCESS boundaries
    (serve_bench.py --fleet N --autoscale): the --autoscale arrival
    trace replayed against a FleetRouter whose capacity comes from a
    FleetCapacityProvider — every scale-up SPAWNS a real ReplicaAgent
    OS process (spawn -> register -> warm is the ETA-bearing
    provisioning delay), every scale-down drains one through the
    health-gated lease-retirement path (tombstoned in the directory)
    and reaps its process.

    Arms: ``autoscale`` (a static floor of --fleet agents, the
    PoolAutoscaler free to grow to --autoscale-max) vs ``static_max``
    (a fixed fleet at max — the capacity ceiling money could buy up
    front). Agents run the deterministic scripted engine: the run
    proves CONTROL behavior over the fleet control plane, not model
    throughput. In-run gates: >=1 process spawned by a scale-up,
    >=1 drained back down, no leaked agent process at exit."""
    import os
    import socket as _socket
    import tempfile

    from tools.chaos_serve import _spawn_fleet_proc, _wait_ready
    from ray_tpu.serve.fleet.directory import DirectoryClient
    from ray_tpu.serve.fleet.provider import FleetCapacityProvider
    from ray_tpu.serve.fleet.router import FleetRouter
    from ray_tpu.serve.fleet.transport import SocketTransport
    from ray_tpu.serve.pool_autoscaler import (PoolAutoscaler,
                                               SLOPolicy)

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    from ray_tpu.serve.fleet.provider import require_cpu_backend
    require_cpu_backend(env)

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    dport = s.getsockname()[1]
    s.close()
    lease_ttl_s = 1.0
    data_dir = tempfile.mkdtemp(prefix="fleet-bench-dir-")
    dproc = _spawn_fleet_proc(
        ["ray_tpu.serve.fleet.directory", "--port", str(dport),
         "--lease-ttl-s", str(lease_ttl_s), "--data-dir", data_dir],
        env, repo)
    _wait_ready(dproc, "directory")
    endpoint = f"127.0.0.1:{dport}"

    slo_s = args.ttft_slo_ms / 1000.0
    gen_tokens = args.gen_tokens
    plen = 12
    token_delay_s = 0.02
    floor = max(1, args.fleet)
    prng = np.random.RandomState(args.seed)

    def prompt_fn(_tenant):
        return prng.randint(1, 900, size=plen).tolist()

    shape = (args.trace if args.trace in
             ("diurnal", "bursty", "multitenant") else "bursty")
    events = make_trace(shape, args.trace_duration,
                        args.base_rps, args.peak_rps, args.seed)
    print(f"trace {shape}: {len(events)} arrivals over "
          f"{args.trace_duration}s", flush=True)

    def _mk_provider(prefix):
        return FleetCapacityProvider(
            [endpoint], model="fake", token_delay_s=token_delay_s,
            rid_prefix=prefix, spawn_timeout_s=120.0, env=env)

    def _mk_router():
        return FleetRouter(
            DirectoryClient(SocketTransport(("127.0.0.1", dport)),
                            timeout_s=5.0),
            lambda addr: SocketTransport((addr[1], addr[2])),
            seed=args.seed, snapshot_ttl_s=0.05, call_timeout_s=10.0)

    def _boot(provider, router, n, label):
        tickets = [provider.request() for _ in range(n)]
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if all(provider.ready(t) for t in tickets):
                break
            time.sleep(0.05)
        while (router.active_count() < n
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert router.active_count() >= n, (
            f"{label}: only {router.active_count()} of {n} floor "
            f"agents registered")
        print(f"{label}: {n} agent processes up", flush=True)
        return tickets

    # --- arm 1: autoscaled fleet -----------------------------------
    provider = _mk_provider("bench")
    router = _mk_router()
    _boot(provider, router, floor, "autoscale arm")
    policy = SLOPolicy(
        min_replicas=floor, max_replicas=args.autoscale_max,
        queue_high=1.5, queue_low=0.25,
        shed_rate_high=0.0, ttft_slo_s=slo_s,
        free_slot_frac_low=0.15, free_slot_frac_high=0.5,
        idle_stable_s=1.0,
        cooldown_up_s=0.3, cooldown_down_s=1.2,
        scale_up_step=2, drain_timeout_s=15.0)
    scaler = PoolAutoscaler(router, policy, provider).run(
        interval_s=0.1)
    rows, samples = _replay_trace(
        router, events, prompt_fn, gen_tokens, slo_s,
        scaler.capacity_eta_s, "fleet_autoscale")
    deadline = time.monotonic() + (
        policy.idle_stable_s + policy.cooldown_down_s *
        (args.autoscale_max - floor) + 10.0)
    while (router.active_count() > floor
           and time.monotonic() < deadline):
        time.sleep(0.1)
        samples.append((samples[-1][0] + 0.1 if samples else 0.0,
                        router.active_count()))
    scaler.stop()
    auto_stats = scaler.stats()
    directory_stats = router._directory.stats()
    router.shutdown()
    provider.stop_all()
    auto = _arm_summary(rows, samples, slo_s)
    auto["replica_timeline"] = _decimate_timeline(samples)
    counts = [n for _, n in samples]
    auto["replicas_min_seen"] = int(min(counts))
    auto["replicas_max_seen"] = int(max(counts))
    auto["scale_ups"] = auto_stats["scale_ups"]
    auto["scale_downs"] = auto_stats["scale_downs"]
    auto["holds"] = auto_stats["holds"]
    auto["denied"] = auto_stats["denied"]
    prov_auto = dict(provider.stats)

    # the tentpole gates, asserted in-run: capacity MOVED as real
    # processes, and none leaked
    assert auto["replicas_max_seen"] > floor and \
        auto_stats["scale_ups"] >= 1, (
        f"autoscaler never spawned an agent process past the floor: "
        f"{auto_stats}")
    assert auto_stats["scale_downs"] >= 1, (
        f"autoscaler never drained an agent back down: {auto_stats}")
    assert prov_auto["spawned"] > floor, prov_auto
    assert provider.live_count() == 0, (
        f"provider leaked {provider.live_count()} agent processes")

    # --- arm 2: static fleet at max --------------------------------
    print("static-max arm", flush=True)
    prng.seed(args.seed)            # identical prompt stream
    provider2 = _mk_provider("st")
    router2 = _mk_router()
    _boot(provider2, router2, args.autoscale_max, "static arm")
    rows2, samples2 = _replay_trace(
        router2, events, prompt_fn, gen_tokens, slo_s, None,
        "fleet_static_max")
    router2.shutdown()
    provider2.stop_all()
    auto_end = samples[-1][0] if samples else 0.0
    static_end = samples2[-1][0] if samples2 else 0.0
    if auto_end > static_end:
        samples2.append((auto_end, args.autoscale_max))
    static = _arm_summary(rows2, samples2, slo_s)

    dproc.kill()
    dproc.wait(timeout=10)

    return {
        "trace": shape,
        "model": "scripted-fake",
        "trace_duration_s": args.trace_duration,
        "base_rps": args.base_rps,
        "peak_rps": args.peak_rps,
        "arrivals": len(events),
        "gen_tokens": gen_tokens,
        "prompt_len": plen,
        "replicas_min": floor,
        "replicas_max": args.autoscale_max,
        "provision_delay_s": None,
        "slo": {"ttft_ms": args.ttft_slo_ms,
                "attainment_floor": args.attainment_floor},
        "autoscale": auto,
        "static_max": static,
        "chip_seconds_ratio": _ratio(auto["chip_seconds"],
                                     static["chip_seconds"]),
        "ttft_p50_ratio": _ratio(auto.get("ttft_p50_ms"),
                                 static.get("ttft_p50_ms")),
        "fleet": {
            "transport": "tcp-json-v1",
            "lease_ttl_s": lease_ttl_s,
            "floor": floor,
            "directory": directory_stats,
            "provider_autoscale_arm": prov_auto,
            "provider_static_arm": dict(provider2.stats),
            "agent_processes_spawned":
                prov_auto["spawned"] + provider2.stats["spawned"],
        },
        "notes": "Trace-driven FLEET autoscaling run (serve_bench.py "
                 "--fleet N --autoscale): the same open-loop arrival "
                 "trace as --autoscale, but capacity moves as real "
                 "OS processes — a FleetCapacityProvider spawns "
                 "ReplicaAgent subprocesses on scale-up "
                 "(spawn -> register -> warm is the provisioning "
                 "ETA) and retires them on scale-down through the "
                 "health-gated drain + lease-retirement + tombstone "
                 "path, all through the durable fleet directory. "
                 "Gates: >=1 process spawned past the floor, >=1 "
                 "drained back down, zero leaked processes, "
                 "attainment over the floor, chip_seconds_ratio "
                 "< 1.",
    }


def run_fleet_trace(args):
    """Cross-process trace capture (serve_bench.py --fleet N --trace):
    the fleet observability plane's acceptance proof. A directory and
    N ReplicaAgent OS processes serve a FleetRouter in THIS process;
    a TelemetryCollector scrapes every role over the transport,
    estimates per-member clock offsets NTP-style, and merges the
    event logs onto one timebase. Mid-run the serving agent is
    SIGKILLed before its first token, so the router's confirmed-death
    path resubmits token-identically to a second agent — one trace_id
    then spans >= 3 OS processes (router pid, victim agent pid,
    resubmit agent pid), stitched on the aligned timebase with the
    offset uncertainty stamped on every span.

    In-run gates (the artifact also re-checks via
    tools/check_bench_schema.py): the proof trace stitches across
    >= 3 distinct pids, every member's offset uncertainty stays under
    --fleet-offset-bound, and the kill is explained by exactly the
    cluster flight bundle the death hook pulled."""
    import os
    import signal
    import socket as _socket
    import tempfile

    from tools.chaos_serve import _spawn_fleet_proc, _wait_ready
    from ray_tpu.serve import obs
    from ray_tpu.serve.fleet.directory import DirectoryClient
    from ray_tpu.serve.fleet.router import FleetRouter
    from ray_tpu.serve.fleet.telemetry import TelemetryCollector
    from ray_tpu.serve.fleet.transport import SocketTransport

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    from ray_tpu.serve.fleet.provider import require_cpu_backend
    require_cpu_backend(env)

    n_agents = max(2, args.fleet)
    lease_ttl_s = 0.6
    token_delay_s = 0.25      # first token lands late enough that the
    offset_bound_s = 0.05     # kill always beats it
    gen_tokens = min(args.gen_tokens, 6)
    prng = np.random.RandomState(args.seed)

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    dport = s.getsockname()[1]
    s.close()
    data_dir = tempfile.mkdtemp(prefix="fleet-trace-dir-")
    dproc = _spawn_fleet_proc(
        ["ray_tpu.serve.fleet.directory", "--port", str(dport),
         "--lease-ttl-s", str(lease_ttl_s), "--data-dir", data_dir],
        env, repo)
    _wait_ready(dproc, "directory")

    procs = {}
    for i in range(n_agents):
        rid = f"tr{i}"
        procs[rid] = _spawn_fleet_proc(
            ["ray_tpu.serve.fleet.agent", "--replica-id", rid,
             "--directory-port", str(dport), "--model", "fake",
             "--token-delay-s", str(token_delay_s)],
            env, repo)
    for rid, p in procs.items():
        _wait_ready(p, rid)

    cluster_dir = tempfile.mkdtemp(prefix="fleet-trace-bundles-")
    router = FleetRouter(
        DirectoryClient(SocketTransport(("127.0.0.1", dport)),
                        timeout_s=5.0),
        lambda addr: SocketTransport((addr[1], addr[2])),
        seed=args.seed, snapshot_ttl_s=0.05, call_timeout_s=2.0,
        poll_interval_s=0.004)
    col = TelemetryCollector(
        router, events_per_scrape=512, cluster_dir=cluster_dir,
        offset_bound_s=offset_bound_s).attach()

    try:
        deadline = time.monotonic() + 60.0
        while (router.active_count() < n_agents
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert router.active_count() >= n_agents, (
            f"only {router.active_count()} of {n_agents} agents "
            f"registered")
        col.scrape_once()       # baseline offsets for every role

        def prompt():
            return prng.randint(1, 900, size=8).tolist()

        # --- the proof request: killed mid-flight, resubmitted ----
        proof_tid = obs.mint_trace_id()
        h = router.submit(prompt(), max_new_tokens=gen_tokens,
                          trace_id=proof_tid)
        victim = h.replica_idx
        # capture the victim's submit event WHILE it can still be
        # scraped — after the kill its log is gone
        col.scrape_once()
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(timeout=10)
        print(f"killed serving agent {victim} "
              f"(pid {procs[victim].pid}) before first token",
              flush=True)
        toks = h.result()       # rides the confirmed-death resubmit
        survivor = h.replica_idx
        assert survivor != victim, "resubmit landed on the dead agent"
        requests = {proof_tid: {"outcome": "resubmitted",
                                "n_tokens": len(toks),
                                "killed": victim,
                                "served_by": survivor}}

        # --- undisturbed traced requests on the survivors ---------
        for _ in range(3):
            tid = obs.mint_trace_id()
            hh = router.submit(prompt(), max_new_tokens=gen_tokens,
                               trace_id=tid)
            requests[tid] = {"outcome": "ok",
                             "n_tokens": len(hh.result()),
                             "served_by": hh.replica_idx}
        col.scrape_once()       # survivor + router tail events

        phases = col.request_phases()
        for tid, row in requests.items():
            row.update(phases.get(tid) or {})
        proof = requests[proof_tid]
        assert proof.get("n_processes", 0) >= 3, (
            f"proof trace spans {proof.get('n_processes')} processes,"
            f" need >= 3: {proof.get('spans')}")
        members = col.members()
        bad = {n: m["uncertainty_s"] for n, m in members.items()
               if m["uncertainty_s"] is not None
               and m["uncertainty_s"] > offset_bound_s}
        assert not bad, f"offset uncertainty above bound: {bad}"
        death_reason = f"agent-dead-{victim}"
        explained = [b for b in col.bundles
                     if b["reason"] == death_reason]
        assert explained, (
            f"no cluster bundle explains the kill: "
            f"{[b['reason'] for b in col.bundles]}")

        stitched = [tid for tid, row in requests.items()
                    if row.get("stitched")]
        result = {
            "fleet": {
                "transport": "tcp-json-v1",
                "agents": n_agents,
                "lease_ttl_s": lease_ttl_s,
                "token_delay_s": token_delay_s,
                "directory": router._directory.stats(),
            },
            "offset_bound_s": offset_bound_s,
            "members": members,
            "collector": col.health(),
            "requests": requests,
            "requests_n": len(requests),
            "stitch": {
                "traces": len(requests),
                "stitched_traces": len(stitched),
                "max_processes": max(
                    row.get("n_processes", 0)
                    for row in requests.values()),
                "proof_trace_id": proof_tid,
                "killed_replica": victim,
                "resubmits": router.counters["requeues"],
                "deaths_confirmed":
                    router.counters["deaths_confirmed"],
            },
            "cluster_bundles": [
                {"reason": b["reason"],
                 "trigger_kind": (b.get("trigger") or {}).get(
                     "kind")}
                for b in col.bundles],
            "events": col.merged_events(),
            "trace_events": col.chrome_trace(),
            # placement stamp: each agent process is one dp replica
            "mesh": {"tp": 1, "replicas": n_agents},
            "notes": "Cross-process trace capture (serve_bench.py "
                     "--fleet N --trace): a TelemetryCollector "
                     "scrapes directory + agent OS processes over "
                     "the transport, aligns their monotonic clocks "
                     "NTP-style (offset uncertainty = RTT/2, "
                     "stamped per span), and merges the event logs. "
                     "The proof request's serving agent is "
                     "SIGKILLed before its first token; the "
                     "confirmed-death resubmit lands on a second "
                     "agent, so one trace_id stitches across >= 3 "
                     "pids on the aligned timebase, and the kill is "
                     "explained by the cluster flight bundle the "
                     "death hook pulled.",
        }
        return result
    finally:
        router.shutdown()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        dproc.kill()
        dproc.wait(timeout=10)


def run_tp_ab(args):
    """Tensor-parallel A/B (serve_bench.py --tp-ab): the SAME engine,
    load shape, and greedy sampling run twice — once on a single chip
    (tp=1) and once sharded tp-way over the mesh (serve/sharding.py:
    Megatron column/row-parallel weights, head-sharded paged KV). The
    engines are built DIRECTLY (no serve hop) so the parity check is
    deterministic.

    The load covers all three dispatch paths the sharded engine must
    keep token-identical: plain continuous-batching decode, a shared
    prefix re-asked so the radix cache serves hits, and a repetitive
    prompt under prompt-lookup speculation (propose / verify /
    rollback). The artifact REFUSES (via tools/check_bench_schema.py)
    to exist without the mesh stamp or with any output divergence —
    a tensor-parallel engine that changes tokens is a broken engine,
    whatever its throughput.

    Always the tiny model (fp32 so the per-device psum reduction
    order cannot flip a greedy argmax tie): this run proves the
    PARITY and composition contract; chip-scaling numbers come from
    the on-chip sweep."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.sharding import EngineSharding

    tp = args.tp if args.tp > 1 else 4
    gen_tokens = min(args.gen_tokens, 16)
    # n_kv_heads must divide tp-way (the tiny default of 2 stops at
    # tp=2); fp32 keeps greedy argmax ties out of the parity check
    cfg = llama_tiny(n_kv_heads=max(4, tp), dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    rng = np.random.RandomState(args.seed + 31)
    plain = [rng.randint(1, cfg.vocab_size - 1, size=12).tolist()
             for _ in range(4)]
    shared = rng.randint(1, cfg.vocab_size - 1, size=24).tolist()
    tails = [rng.randint(1, cfg.vocab_size - 1, size=6).tolist()
             for _ in range(3)]
    repetitive = ([5, 6, 7, 8] * 8)[:24]
    prompts = plain + [shared + t for t in tails] + [repetitive]

    def arm(sharding):
        eng = LLMEngine(model, params, max_slots=4, page_size=8,
                        n_pages=96, chunk=4, prefill_chunk=16,
                        temperature=0.0, seed=args.seed,
                        prefix_cache=True, spec_len=4,
                        sharding=sharding)
        eng.start()
        t0 = time.time()
        # seeds the prefix cache so the tail requests HIT it, and
        # compiles the jitted steps outside the measured window
        eng.submit(shared + tails[0],
                   max_new_tokens=gen_tokens).result()
        compile_s = time.time() - t0
        t0 = time.time()
        handles = [eng.submit(p, max_new_tokens=gen_tokens)
                   for p in prompts]
        outs = [h.result() for h in handles]
        wall = time.time() - t0
        total = len(prompts) * gen_tokens
        res = {
            "throughput_tok_s": round(total / wall, 1),
            "per_token_ms": round(wall * 1000 / total, 2),
            "requests": len(prompts),
            "gen_tokens": gen_tokens,
            "wall_s": round(wall, 2),
            "compile_s": round(compile_s, 1),
            "devices": sharding.describe()["devices"]
            if sharding is not None else 1,
        }
        pc = eng.prefix_stats()
        if pc:
            res["prefix_cache"] = pc
        sp = eng.spec_stats()
        if sp:
            res["spec"] = sp
        eng.shutdown()
        return outs, res

    print("tp A/B: tp=1 arm", flush=True)
    base_outs, base = arm(None)
    print(f"tp A/B: tp={tp} arm", flush=True)
    sh = EngineSharding.build(cfg, tp=tp)
    tp_outs, tpn = arm(sh)
    identical = base_outs == tp_outs
    if not identical:
        print("WARNING: tp arm diverged from single-chip greedy "
              "outputs — the artifact will fail schema validation",
              flush=True)
    return {
        "tp_ab": {
            "tp1": base,
            "tpn": tpn,
            "parity": {"token_identical": bool(identical),
                       "checked": len(prompts)},
            "per_token_ratio": _ratio(tpn["per_token_ms"],
                                      base["per_token_ms"]),
            "throughput_ratio": _ratio(tpn["throughput_tok_s"],
                                       base["throughput_tok_s"]),
        },
        "mesh": {"tp": tp, "replicas": 1},
        "model": "llama-tiny",
        "n_kv_heads": cfg.n_kv_heads,
        "notes": "Tensor-parallel A/B (serve_bench.py --tp-ab): the "
                 "identical engine + greedy load run at tp=1 and "
                 "sharded tp-way (Megatron-sharded weights, "
                 "head-sharded paged KV, serve/sharding.py). The "
                 "load exercises plain decode, prefix-cache hit "
                 "resume, and speculative propose/verify/rollback; "
                 "parity.token_identical must be true. On a CPU "
                 "host mesh the latency ratio carries no scaling "
                 "signal (emulated devices share the same cores); "
                 "per_token_ratio earns its keep on a real ICI "
                 "mesh.",
    }


def run_overlap_ab(args):
    """Overlapped-vs-lockstep hot-loop A/B (serve_bench.py
    --overlap-ab): the SAME engine, prompt mix, and greedy sampling
    run twice — once with the lockstep eos loop (full readback drain
    before planning every round, the pre-overlap profile) and once
    with the double-buffered overlapped loop (serve/engine.py: plan
    round N+1 from the stale frontier while round N executes on
    device). Engines are built DIRECTLY and outputs compared
    token-for-token; the artifact REFUSES (tools/check_bench_schema.py
    ``overlap_ab`` family) to exist with diverging outputs, without
    its seed/mesh stamp, or with an overlapped host-gap fraction that
    is not STRICTLY lower than the lockstep arm's.

    host_gap_fraction is the per-arm pipeline-health headline: summed
    per-round host gap (pre-plan readback drain + planner, the time
    the host gates the next dispatch) over summed round wall, taken
    from the engine's OWN typed "round" events (obs.py) after the
    warmup offset — per-engine rings, so the arms cannot bleed into
    each other the way a process-global histogram would.

    eos_id=-1 on purpose: eos-BOUNDED scheduling (the mode the
    overlap targets — per-round drains, bounded run-ahead) with an id
    that never samples, so both arms run full-length and parity is a
    whole-stream check. Wall-clock throughput on the CPU smoke is
    NOT the signal (host overhead dominates and the stale-frontier
    cap halves per-dispatch run-ahead); the contract is host-gap
    fraction down + TTFT p50 not regressed + tokens identical.

    --paged-kernel adds a third arm: the overlapped loop under the
    pallas paged decode kernel (RAY_TPU_PAGED_KERNEL=1, interpreter
    mode off-TPU) for re-measuring the kernel-vs-gather ranking of
    models/llama.py:_use_paged_kernel on real hardware. It reports
    its own numbers + parity vs the gather arm but never gates the
    artifact — the CPU interpreter path carries no ranking signal."""
    import os
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine

    gen_tokens = max(16, min(args.gen_tokens, 48))
    # fp32 keeps greedy argmax ties out of the parity check (same
    # reasoning as --tp-ab); chunk=16 makes each dispatch big enough
    # that the readback the lockstep arm blocks on is measurable
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    rng = np.random.RandomState(args.seed + 41)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=16).tolist()
               for _ in range(6)]

    def arm(overlap):
        eng = LLMEngine(model, params, max_slots=2, page_size=16,
                        n_pages=128, chunk=16, prefill_chunk=16,
                        temperature=0.0, eos_id=-1, seed=args.seed,
                        overlap=overlap, events=True).start()
        # compile the jitted steps OUTSIDE the measured window, then
        # snapshot the event offset so warmup rounds don't count
        eng.submit(prompts[0], max_new_tokens=2).result()
        eng.reset_latency_stats()
        n0 = len(eng.events.snapshot())
        t0 = time.monotonic()
        handles = [eng.submit(p, max_new_tokens=gen_tokens)
                   for p in prompts]
        outs = [h.result() for h in handles]
        wall = time.monotonic() - t0
        evs = eng.events.snapshot()[n0:]
        ttfts = sorted(eng.ttfts_s)
        eng.shutdown()
        rounds = [e[5] for e in evs if e[2] == "round"]
        gap = sum(r["host_gap_s"] for r in rounds)
        rwall = sum(r["wall_s"] for r in rounds)
        total = len(prompts) * gen_tokens
        return outs, {
            "throughput_tok_s": round(total / wall, 1),
            "wall_s": round(wall, 3),
            "requests": len(prompts),
            "gen_tokens": gen_tokens,
            "rounds": len(rounds),
            "host_gap_s": round(gap, 6),
            "round_wall_s": round(rwall, 6),
            "host_gap_fraction": (round(gap / rwall, 6) if rwall
                                  else None),
            "ttft_p50_s": (round(ttfts[len(ttfts) // 2], 6)
                           if ttfts else None),
        }

    # a loaded CI box can flake a single timing sample; the schema
    # gate is strict, so take the first attempt that satisfies it
    for attempt in range(6):
        print("overlap A/B: lockstep arm", flush=True)
        base_outs, lock = arm(False)
        print("overlap A/B: overlapped arm", flush=True)
        over_outs, over = arm(True)
        identical = base_outs == over_outs
        improved = (lock["host_gap_fraction"] is not None
                    and over["host_gap_fraction"] is not None
                    and over["host_gap_fraction"]
                    < lock["host_gap_fraction"])
        # TTFT is noise-dominated at this scale; retry rather than
        # check in a sample where scheduling jitter read as a
        # first-token regression
        ttft_ok = (lock["ttft_p50_s"] is None
                   or over["ttft_p50_s"] is None
                   or over["ttft_p50_s"] <= lock["ttft_p50_s"])
        if identical and improved and ttft_ok:
            break
        print(f"overlap A/B: retrying (attempt {attempt + 1}: "
              f"token_identical={identical} "
              f"host_gap_improved={improved} ttft_ok={ttft_ok})",
              flush=True)
    if not identical:
        print("WARNING: overlapped arm diverged from lockstep greedy "
              "outputs — the artifact will fail schema validation",
              flush=True)

    result = {
        "overlap_ab": {
            "lockstep": lock,
            "overlapped": over,
            "parity": {"token_identical": bool(identical),
                       "checked": len(prompts)},
            "host_gap_fraction_ratio": _ratio(
                over["host_gap_fraction"], lock["host_gap_fraction"]),
            "ttft_p50_ratio": _ratio(over["ttft_p50_s"],
                                     lock["ttft_p50_s"]),
        },
        "mesh": {"tp": 1, "replicas": 1},
        "model": "llama-tiny",
        "notes": "Overlapped hot-loop A/B (serve_bench.py "
                 "--overlap-ab): the identical engine + greedy "
                 "eos-bounded load under the lockstep loop (full "
                 "pre-plan readback drain) and the double-buffered "
                 "overlapped loop (stale-frontier planning, trailing "
                 "depth-2 drain). parity.token_identical must be "
                 "true and overlapped.host_gap_fraction strictly "
                 "below lockstep's; host_gap_fraction comes from the "
                 "engine's per-round typed events, post-warmup. CPU "
                 "wall-clock carries no dispatch-overlap signal "
                 "(host overhead dominates); the fraction and TTFT "
                 "are the contract.",
    }
    if getattr(args, "paged_kernel", False):
        print("overlap A/B: paged-kernel arm "
              "(RAY_TPU_PAGED_KERNEL=1)", flush=True)
        prev = os.environ.get("RAY_TPU_PAGED_KERNEL")
        os.environ["RAY_TPU_PAGED_KERNEL"] = "1"
        try:
            k_outs, kern = arm(True)
        finally:
            if prev is None:
                os.environ.pop("RAY_TPU_PAGED_KERNEL", None)
            else:
                os.environ["RAY_TPU_PAGED_KERNEL"] = prev
        kern["token_identical_vs_gather"] = bool(k_outs == over_outs)
        result["overlap_ab"]["paged_kernel"] = kern
        result["overlap_ab"]["paged_kernel_throughput_ratio"] = _ratio(
            kern["throughput_tok_s"], over["throughput_tok_s"])
    return result


def run_kvq_ab(args):
    """Int8-KV capacity/parity A/B (serve_bench.py --kvq-ab): the SAME
    engine, prompt mix, and greedy sampling run with fp KV pages and
    with int8 pages + per-page scales (models/kv_cache.py,
    ops/paged_attention.py), under one fixed page-pool BYTE budget.

    Three sub-runs per arm:

    PARITY (ample equal pages both arms — isolates numerics from
    capacity): the tp-ab prompt mix (plain decode, shared-prefix
    radix-cache hits, a repetitive prompt) decoded greedily under the
    LOCKSTEP loop with manual stepping — fully deterministic, so the
    recorded agreement is a number, not a sample. The model runs
    fp32 (same reasoning as --tp-ab: the fp arm's argmax must be
    free of its own tie-flips so every disagreement is attributable
    to int8 rounding). Quantized KV is tolerance-equal, not
    bit-equal (quantized bytes are write-history dependent —
    docs/serving.md), so the gate is token AGREEMENT >= the recorded
    floor, not identity. The floor is honest worst-case: a
    random-weight 256-vocab model has near-uniform logits, where one
    rounding flip is amplified and then compounds down the rest of
    that request's stream; real checkpoints with peaked logits agree
    far higher.

    SPEC (the speculative quality gate): one strongly-cyclic prompt
    per arm, long enough for greedy decode to lock its cycle, under
    prompt-lookup speculation. Each arm's proposer drafts from ITS
    OWN stream and is verified against ITS OWN argmax — the
    self-consistency speculation actually depends on — so both arms
    should accept ~all drafts; the gate is the int8 accept rate
    within the recorded noise of fp. (Accept rates are NOT measured
    on the mixed parity load: there proposals are lucky n-gram
    matches against near-random tokens, and comparing luck across
    arms gates nothing.)

    CAPACITY (the headline — same byte budget both arms): each arm
    gets the pages its dtype affords (budget // page_bytes), derives
    its admission bound from them, and takes the same request burst.
    This sub-run uses the model's native bf16 pages as the fp
    baseline — the honest deployment comparison (~1.94x for
    llama-tiny: int8 payload is half of bf16, per-page scales cost a
    few percent), where the fp32 parity pool would flatter the ratio
    to ~4x. The int8 arm fits ~2x the pages -> ~2x the effective
    slots -> fewer sheds and higher prefix-cache residency after
    retirement. Shed counts are DETERMINISTIC by construction: the
    burst is submitted before the engine starts stepping, so
    admission = the arm's capacity-derived bound, not a scheduling
    race.

    The artifact REFUSES (tools/check_bench_schema.py ``kvq_ab``
    family) to exist without the byte-budget stamp, with a capacity
    ratio < 1.9x, token agreement below the recorded floor, a spec
    accept-rate drop beyond noise, an int8 arm that didn't shed
    strictly fewer, or missing mesh/seed stamps."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.kv_cache import kv_pool_page_bytes
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.errors import EngineOverloaded

    gen_tokens = min(args.gen_tokens, 16)
    cfg = llama_tiny(dtype=jnp.float32)          # parity/spec arms
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))
    cfg_cap = llama_tiny()                       # capacity arms: bf16
    model_cap = Llama(cfg_cap)
    params_cap = model_cap.init(jax.random.PRNGKey(args.seed),
                                jnp.zeros((1, 8), jnp.int32))

    page_size = 8
    page_bytes = {dt: kv_pool_page_bytes(cfg_cap, page_size, dt)
                  for dt in ("fp", "int8")}
    # the fixed budget: what a 48-page bf16 pool costs. Both arms
    # must fit inside it; the int8 arm converts the same bytes into
    # ~2x the pages.
    byte_budget = 48 * page_bytes["fp"]
    arm_pages = {dt: byte_budget // page_bytes[dt]
                 for dt in ("fp", "int8")}

    rng = np.random.RandomState(args.seed + 53)
    plain = [rng.randint(1, cfg.vocab_size - 1, size=12).tolist()
             for _ in range(4)]
    shared = rng.randint(1, cfg.vocab_size - 1, size=16).tolist()
    tails = [rng.randint(1, cfg.vocab_size - 1, size=6).tolist()
             for _ in range(3)]
    repetitive = ([5, 6, 7, 8] * 6)[:20]
    prompts = plain + [shared + t for t in tails] + [repetitive]
    # pages one burst request needs end to end (prompt + completion)
    req_tokens = max(len(p) for p in prompts) + gen_tokens
    pages_per_req = -(-req_tokens // page_size)

    def _drain(eng):
        while eng.step():
            pass

    def parity_arm(dt):
        # ample EQUAL pages both arms, lockstep loop, manual
        # stepping: this sub-run measures numerics only — no
        # capacity pressure, no thread-timing in the token stream
        eng = LLMEngine(model, params, max_slots=4,
                        page_size=page_size, n_pages=256, chunk=4,
                        prefill_chunk=16, temperature=0.0,
                        eos_id=-1, overlap=False,
                        seed=args.seed, prefix_cache=True,
                        kv_dtype=None if dt == "fp" else dt)
        # warmup compiles + seeds the prefix cache outside the
        # measured window
        h0 = eng.submit(shared + tails[0], max_new_tokens=gen_tokens)
        _drain(eng)
        h0.result()
        t0 = time.time()
        hs = [eng.submit(list(p), max_new_tokens=gen_tokens)
              for p in prompts]
        _drain(eng)
        outs = [h.result() for h in hs]
        wall = time.time() - t0
        eng.shutdown()
        return outs, {
            "wall_s": round(wall, 3),
            "requests": len(prompts),
            "gen_tokens": gen_tokens,
        }

    def spec_arm(dt):
        # strongly-cyclic prompt, long budget: greedy decode locks a
        # cycle, the prompt-lookup proposer drafts it, the batched
        # verify confirms it — per-arm self-consistency, the thing
        # int8 rounding could actually break
        eng = LLMEngine(model, params, max_slots=2,
                        page_size=page_size, n_pages=64, chunk=4,
                        prefill_chunk=16, temperature=0.0,
                        eos_id=-1, overlap=False,
                        seed=args.seed, spec_len=4,
                        kv_dtype=None if dt == "fp" else dt)
        h = eng.submit([5, 6, 7, 8] * 5, max_new_tokens=40)
        _drain(eng)
        h.result()
        sp = eng.spec_stats() or {}
        eng.shutdown()
        return sp.get("accept_rate"), sp.get("rounds")

    def capacity_arm(dt):
        n_pages = int(arm_pages[dt])
        slots = max(1, (n_pages - 1) // pages_per_req)
        eng = LLMEngine(model_cap, params_cap, max_slots=slots,
                        page_size=page_size, n_pages=n_pages, chunk=4,
                        prefill_chunk=16, temperature=0.0,
                        seed=args.seed, prefix_cache=True,
                        max_queued=slots,
                        kv_dtype=None if dt == "fp" else dt)
        # burst BEFORE stepping (engine not started): admitted =
        # max_queued, everything past it sheds — a pure capacity
        # count, no timing race
        burst = [shared + t for t in tails] * 4 + plain * 2
        sheds = 0
        handles = []
        for p in burst:
            try:
                handles.append(
                    eng.submit(list(p), max_new_tokens=gen_tokens))
            except EngineOverloaded:
                sheds += 1
        _drain(eng)
        outs = [h.result() for h in handles]
        rpt = eng.load_report()
        pc = eng.prefix_stats() or {}
        eng.shutdown()
        assert rpt["kv_bytes_total"] <= byte_budget, (
            dt, rpt["kv_bytes_total"], byte_budget)
        return {
            "n_pages": n_pages,
            "effective_slots": slots,
            "page_bytes": page_bytes[dt],
            "kv_bytes_total": rpt["kv_bytes_total"],
            "burst": len(burst),
            "sheds": sheds,
            "completed": len(outs),
            "prefix_cached_pages": pc.get("cached_pages"),
            "prefix_hit_rate": pc.get("hit_rate"),
        }

    # Everything below is deterministic (lockstep + manual stepping
    # + pre-step bursts); the floors are recorded in the artifact so
    # the gate travels with the numbers.
    agreement_floor = 0.8
    accept_noise = 0.15
    print("kvq A/B: fp parity arm", flush=True)
    fp_outs, fp_par = parity_arm("fp")
    print("kvq A/B: int8 parity arm", flush=True)
    i8_outs, i8_par = parity_arm("int8")
    total = sum(len(o) for o in fp_outs)
    agree = sum(x == y for a, b in zip(fp_outs, i8_outs)
                for x, y in zip(a, b))
    agreement = agree / total if total else 0.0
    if agreement < agreement_floor:
        print("WARNING: int8 token agreement below the recorded "
              "floor — the artifact will fail schema validation",
              flush=True)

    print("kvq A/B: fp spec arm", flush=True)
    fa, fp_rounds = spec_arm("fp")
    print("kvq A/B: int8 spec arm", flush=True)
    ia, i8_rounds = spec_arm("int8")

    print("kvq A/B: fp capacity arm", flush=True)
    fp_cap = capacity_arm("fp")
    print("kvq A/B: int8 capacity arm", flush=True)
    i8_cap = capacity_arm("int8")

    return {
        "kvq_ab": {
            "byte_budget": int(byte_budget),
            "page_size": page_size,
            "fp": {"parity": fp_par, "capacity": fp_cap,
                   "spec_rounds": fp_rounds},
            "int8": {"parity": i8_par, "capacity": i8_cap,
                     "spec_rounds": i8_rounds},
            "parity": {
                "token_agreement": round(agreement, 4),
                "token_agreement_floor": agreement_floor,
                "tokens_checked": total,
                "spec_accept_rate_fp": fa,
                "spec_accept_rate_int8": ia,
                "spec_accept_noise": accept_noise,
            },
            "capacity_ratio": _ratio(i8_cap["n_pages"],
                                     fp_cap["n_pages"]),
            "slots_ratio": _ratio(i8_cap["effective_slots"],
                                  fp_cap["effective_slots"]),
            "shed_delta": fp_cap["sheds"] - i8_cap["sheds"],
            "prefix_residency_delta": (
                (i8_cap["prefix_cached_pages"] or 0)
                - (fp_cap["prefix_cached_pages"] or 0)),
        },
        "mesh": {"tp": 1, "replicas": 1},
        "model": "llama-tiny",
        "notes": "Int8-KV A/B (serve_bench.py --kvq-ab): identical "
                 "engine + greedy load with fp KV pages vs int8 "
                 "pages + per-page absmax scales, at one fixed "
                 "page-pool byte budget. Parity sub-run (equal ample "
                 "pages, lockstep loop, fp32 model so the baseline "
                 "argmax has no tie-flips of its own) gates token "
                 "agreement >= the recorded floor — quantized KV is "
                 "tolerance-equal, not bit-equal (write-history "
                 "dependent rounding; docs/serving.md). Spec sub-run "
                 "gates each arm's self-consistent accept rate on a "
                 "cyclic prompt. Capacity sub-run converts the same "
                 "bytes into each dtype's pages against the native "
                 "bf16 baseline: the int8 arm runs ~2x the "
                 "pages/slots, sheds fewer of the same deterministic "
                 "burst, and retires with more prefix-cache pages "
                 "resident.",
    }


def run_prefix_share_ab(args):
    """Fleet-shared prefix cache A/B (serve_bench.py
    --prefix-share-ab): the SAME 2-replica pool, multi-session
    thrashing trace, and greedy sampling run with each replica's
    prefix cache private (``share_prefixes=False``) vs fleet-shared
    (``share_prefixes=True``: the router attaches cross-replica pull
    hints and a cold replica PULLS the holder's pinned int8 pages +
    per-page scales over the migration seam instead of recomputing
    the prefix — serve/kv_migration.py, docs/serving.md).

    The trace is built so local-only caching keeps LOSING: one
    session stays warm on the holder replica (its re-touches keep the
    donor pages MRU), the measured sessions are sticky-pinned to the
    OTHER replica (established with a busy-tip: a long request held
    on the warm replica tips P2C toward the cold one), and between
    measured rounds two filler sessions churn the cold replica's page
    pool hard enough to evict the shared prefix. So every measured
    request faces a LOCAL miss with a fleet-wide hit: the local arm
    re-prefills the whole shared prefix each round, the shared arm
    pulls the pages and resumes prefill at the landed offset.

    Recorded per arm: measured-request TTFTs (p50), the
    kv_migration counters (pulls/pulled_pages/wire_bytes/aborts/
    fallbacks), pull hints, and the cross-replica hit rate (pulled
    pages landing on a replica that never computed them / the
    measured rounds' prefix-page demand — identically 0.0 for the
    local arm, where no page ever crosses a replica). Wire bytes are
    the measured int8+scales payload, with the bf16-equivalent cost
    of moving the same pages recorded alongside.

    Decode from a pulled prefix must be TOKEN-IDENTICAL to decode
    from a recomputed one (the pull lands the donor's exact quantized
    bytes, and the donor wrote them with the same deterministic
    chunked prefill the local arm would run), so the arms' measured
    streams are compared and the artifact REFUSES
    (tools/check_bench_schema.py ``prefix_share_ab`` family) to exist
    with diverging streams, with a shared-arm cross-replica hit rate
    not above the local arm's, with a TTFT p50 ratio >= 1.0, or
    without its kv/mesh stamps."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.kv_cache import kv_pool_page_bytes
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    page_size = 8
    prefix_len = 96                   # 12 pages of shared prefix
    prefix_pages = prefix_len // page_size
    gen_tokens = 8
    rounds = 5                        # round 0 is an unmeasured
    # warmup: it compiles each arm's cold path (the pull landing
    # write for the shared arm, nothing new for the local arm)
    # outside the measured window, exactly like the other A/B arms'
    # warmup submits
    n_pages = 32                      # small enough that the fillers
    # (two 15-page requests per round, run back to back) evict the
    # cold replica's copy of the prefix between measured rounds — the
    # thrash. Leaf-first LRU eviction may leave a page or two of the
    # chain's head resident; the pull's insert recycles those
    # duplicates through the normal radix insert path.

    rng = np.random.RandomState(args.seed + 91)
    shared = rng.randint(1, cfg.vocab_size - 1,
                         size=prefix_len).tolist()
    tails = [rng.randint(1, cfg.vocab_size - 1, size=8).tolist()
             for _ in range(rounds)]
    warm_tails = [rng.randint(1, cfg.vocab_size - 1, size=8).tolist()
                  for _ in range(rounds + 1)]
    pins = [rng.randint(1, cfg.vocab_size - 1, size=8).tolist()
            for _ in range(rounds + 2)]
    fillers = [[rng.randint(1, cfg.vocab_size - 1, size=112).tolist()
                for _ in range(2)] for _ in range(rounds)]
    busy_prompt = rng.randint(1, cfg.vocab_size - 1, size=16).tolist()

    def run_arm(share):
        def factory(idx):
            return LLMEngine(model, params, max_slots=2,
                             page_size=page_size, n_pages=n_pages,
                             chunk=4, prefill_chunk=4,
                             temperature=0.0, eos_id=-1,
                             seed=args.seed, prefix_cache=True,
                             kv_dtype="int8")
        pool = EnginePool(factory, 2, share_prefixes=share,
                          seed=args.seed)
        try:
            # warm one replica with the shared prefix (P2C on an idle
            # pool is deterministic, but record the pick rather than
            # assume it)
            h = pool.submit(shared + warm_tails[0],
                            max_new_tokens=gen_tokens,
                            session_id="warm")
            h.result()
            warm_idx = h.replica_idx
            cold_idx = 1 - warm_idx

            # busy-tip: hold a long request on the warm replica so
            # P2C routes the session-establishing pins to the cold
            # one; stickiness then keeps every measured request there
            sessions = [f"s{i}" for i in range(rounds)] + ["f0", "f1"]
            for sid, pin in zip(sessions, pins):
                for _ in range(20):
                    busy = pool.submit(list(busy_prompt),
                                       max_new_tokens=64,
                                       session_id="warm")
                    ph = pool.submit(list(pin), max_new_tokens=2,
                                     session_id=sid)
                    ph.result()
                    busy.cancel()
                    if ph.replica_idx == cold_idx:
                        break
                    pool._sticky.pop(sid, None)
                else:
                    raise RuntimeError(
                        f"could not pin {sid} to the cold replica")

            streams, ttfts = [], []
            for r in range(rounds):
                # keep the donor's copy MRU (identical load both arms)
                pool.submit(shared + warm_tails[r + 1],
                            max_new_tokens=2,
                            session_id="warm").result()
                # the measured request: local miss (fillers evicted
                # the prefix), fleet-wide hit on the warm replica
                h = pool.submit(shared + tails[r],
                                max_new_tokens=gen_tokens,
                                session_id=f"s{r}")
                toks = h.result()
                assert h.replica_idx == cold_idx, (
                    "measured request left its sticky replica")
                streams.append(list(toks))
                ttfts.append(h.ttft_s)
                # churn the cold replica's page pool so the next
                # round misses locally again (back to back: the
                # second filler's allocation evicts the measured
                # request's freshly cached pages, not the first
                # filler's live ones)
                for f, sid in zip(fillers[r], ("f0", "f1")):
                    pool.submit(list(f), max_new_tokens=gen_tokens,
                                session_id=sid).result()

            kv = dict(pool.kv_migration_stats() or {})
            hints = pool.pool_stats().get("pull_hints", 0)
        finally:
            pool.shutdown()
        demand = rounds * prefix_pages
        ttfts = ttfts[1:]            # round 0 is warmup (compile)
        return {
            "streams": streams,
            "ttft_s": [round(t, 4) for t in ttfts],
            "ttft_p50_s": round(sorted(ttfts)[len(ttfts) // 2], 4),
            "cross_replica_hit_rate": round(
                kv.get("pulled_pages", 0) / demand, 4),
            "pull_hints": hints,
            "kv_migration": kv,
        }

    print("prefix-share A/B: local-cache-only arm", flush=True)
    local = run_arm(False)
    print("prefix-share A/B: fleet-shared arm", flush=True)
    shared_arm = run_arm(True)

    identical = local["streams"] == shared_arm["streams"]
    ratio = _ratio(shared_arm["ttft_p50_s"], local["ttft_p50_s"])
    if not identical:
        print("WARNING: pulled-prefix decode diverged from recompute "
              "— the artifact will fail schema validation", flush=True)
    if shared_arm["cross_replica_hit_rate"] \
            <= local["cross_replica_hit_rate"]:
        print("WARNING: fleet-shared arm got no cross-replica hits — "
              "the artifact will fail schema validation", flush=True)
    if ratio is None or ratio >= 1.0:
        print("WARNING: pulling did not beat recompute on TTFT p50 — "
              "the artifact will fail schema validation", flush=True)

    # the streams travel as counts (bulk lives in the comparison, not
    # the artifact); wire bytes are the measured int8+scales payload
    # vs what moving the SAME pages at the model's native bf16 would
    # cost
    for arm in (local, shared_arm):
        arm["tokens"] = sum(len(s) for s in arm.pop("streams"))
    pulled = shared_arm["kv_migration"].get("pulled_pages", 0)
    wire_int8 = shared_arm["kv_migration"].get("wire_bytes", 0)
    bf16_page = kv_pool_page_bytes(llama_tiny(), page_size, "fp")
    from ray_tpu.models.llama import _use_paged_kernel
    result = {
        "prefix_share_ab": {
            "page_size": page_size,
            "prefix_len": prefix_len,
            "prefix_pages": prefix_pages,
            "rounds": rounds,
            "gen_tokens": gen_tokens,
            "local": local,
            "shared": shared_arm,
            "token_identical": identical,
            "ttft_p50_ratio": ratio,
            "wire_bytes_int8": int(wire_int8),
            "wire_bytes_bf16_equiv": int(pulled * bf16_page),
            "wire_ratio": _ratio(wire_int8, pulled * bf16_page),
        },
        "mesh": {"tp": 1, "replicas": 2},
        "kv": {"kv_dtype": "int8",
               "paged_kernel": ("pallas" if _use_paged_kernel()
                                else "gather")},
        "model": "llama-tiny",
        "notes": "Fleet-shared prefix cache A/B (serve_bench.py "
                 "--prefix-share-ab): identical 2-replica pool + "
                 "multi-session thrashing trace with private per-"
                 "replica prefix caches vs fleet-shared "
                 "(share_prefixes=True). Fillers evict the cold "
                 "replica's copy of the shared prefix every round, so "
                 "the local arm re-prefills it each time while the "
                 "shared arm pulls the holder's pinned int8 pages + "
                 "per-page scales and resumes prefill at the landed "
                 "offset. Pulled-prefix decode is gated token-"
                 "identical to recompute; cross-replica hit rate is "
                 "pulled pages over the measured prefix-page demand "
                 "(identically 0 for the local arm); wire bytes are "
                 "the measured int8 payload vs the bf16 cost of the "
                 "same pages.",
    }
    return result


def run_disagg_ab(args):
    """Prefill/decode disaggregation A/B (serve_bench.py
    --disagg-ab): the SAME 2-replica pool, arrival trace, and greedy
    sampling run unified (both replicas mixed prefill+decode) vs
    disaggregated (1 prefill-role + 1 decode-role replica joined by
    the KV-migration handoff path — serve/engine_pool.py roles,
    docs/serving.md).

    The trace is a decode-saturating arrival stream: short prompts,
    long generations, arrivals landing every 50ms while earlier
    streams are still decoding. That is the regime disaggregation
    exists for — in the unified arm every new prompt's chunked
    prefill interleaves with wide multi-step decode dispatches on
    the same scheduler (prefill waits on decode rounds = TTFT
    inflation; decode stalls during prefill rounds = ITL inflation),
    while the disagg arm gives arrivals an interference-free prefill
    replica and consolidates every stream onto one decode replica
    whose batched dispatches amortize the per-round host sync.

    Measured per arm: steady-state TTFT p50 (the LAST half of the
    arrivals — the first half lands in a draining-in system),
    tokens/s over the full trace, and the token streams. The disagg
    arm additionally records handoffs, fallbacks, and the
    kv_migration counters. Three gated phases ride along: token
    identity (every stream must match the unified arm's exactly —
    the handoff pull lands the prefill replica's exact pages),
    per-role autoscaling (a prefill-heavy burst must scale the
    prefill pool while the decode pool holds — different final
    counts from the same trace), and a chaos arm (the decode replica
    is killed before a handoff; the typed fallback must decode in
    place on the prefill replica, token-identically). The artifact
    REFUSES to exist (tools/check_bench_schema.py ``disagg_ab``
    family) with diverging streams, zero handoffs, a TTFT p50 ratio
    >= 1.0, a throughput ratio < 1.0, undiverged autoscaling, a
    faultless chaos arm, or missing role/kv-pull/mesh/kv stamps."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, generate, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool, RolePoolView
    from ray_tpu.serve.pool_autoscaler import (PoolAutoscaler,
                                               SLOPolicy)
    from ray_tpu.serve.scheduler import ROLE_DECODE, ROLE_PREFILL

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    page_size = 8
    prompt_len = 48                  # 6 pages; prefill = 3 chunks
    gen_tokens = 64                  # decode-saturating streams
    n_requests = 16
    gap_s = 0.05
    max_slots = 12                   # wide decode batches: the
    # consolidation the disagg arm wins on, and the interference the
    # unified arm loses to
    n_pages = 260
    kv_pull = {"deadline_s": 5.0, "backoff_s": 0.02}

    rng = np.random.RandomState(args.seed + 31)
    prompts = [rng.randint(1, cfg.vocab_size - 1,
                           size=prompt_len).tolist()
               for _ in range(n_requests)]

    def factory(idx):
        return LLMEngine(model, params, max_slots=max_slots,
                         page_size=page_size, n_pages=n_pages,
                         chunk=4, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=args.seed,
                         prefix_cache=True, kv_dtype="fp")

    def run_arm(roles):
        pool = EnginePool(factory, 2, share_prefixes=True,
                          roles=roles,
                          kv_pull_deadline_s=kv_pull["deadline_s"],
                          kv_pull_backoff_s=kv_pull["backoff_s"],
                          seed=args.seed)
        try:
            for _ in range(2):       # compile both replicas' paths
                pool.submit(list(prompts[0]),
                            max_new_tokens=gen_tokens).result()
            t0 = time.perf_counter()
            handles = []
            for p in prompts:
                handles.append(pool.submit(
                    list(p), max_new_tokens=gen_tokens))
                time.sleep(gap_s)
            streams = [list(h.result()) for h in handles]
            wall = time.perf_counter() - t0
            ttfts = [h.ttft_s
                     for h in handles[len(handles) // 2:]]
            ps = pool.pool_stats()
            kv = dict(pool.kv_migration_stats() or {})
        finally:
            pool.shutdown()
        toks = sum(len(s) for s in streams)
        return {
            "streams": streams,
            "ttft_p50_s": round(
                sorted(ttfts)[len(ttfts) // 2], 4),
            "ttft_steady_s": [round(t, 4) for t in ttfts],
            "tokens": toks,
            "wall_s": round(wall, 3),
            "tok_per_s": round(toks / wall, 1),
            "handoffs": ps.get("disagg_handoffs", 0),
            "handoff_fallbacks": ps.get("disagg_handoff_fallbacks",
                                        0),
            "roles": ps.get("roles", {}),
            "kv_migration": kv,
        }

    print("disagg A/B: unified arm", flush=True)
    unified = run_arm(None)
    print("disagg A/B: prefill/decode arm", flush=True)
    disagg = run_arm([ROLE_PREFILL, ROLE_DECODE])

    identical = unified["streams"] == disagg["streams"]
    ttft_ratio = _ratio(disagg["ttft_p50_s"], unified["ttft_p50_s"])
    thpt_ratio = _ratio(disagg["tok_per_s"], unified["tok_per_s"])
    if not identical:
        print("WARNING: disagg streams diverged from unified — the "
              "artifact will fail schema validation", flush=True)
    if not disagg["handoffs"]:
        print("WARNING: disagg arm made no handoffs — the artifact "
              "will fail schema validation", flush=True)
    if ttft_ratio is None or ttft_ratio >= 1.0:
        print("WARNING: disaggregation did not beat unified TTFT "
              "p50 — the artifact will fail schema validation",
              flush=True)
    if thpt_ratio is None or thpt_ratio < 1.0:
        print("WARNING: disaggregation lost throughput vs unified — "
              "the artifact will fail schema validation", flush=True)

    # ---- per-role autoscaling: same trace, different verdicts -----
    # A prefill-heavy burst against a 1+1 pool with one scaler per
    # role: the prefill scaler (TTFT SLO it cannot meet) must grow
    # its pool, the decode scaler (lenient ITL SLO, idle-biased) must
    # hold — different final counts demonstrate the roles scale
    # INDEPENDENTLY.
    print("disagg A/B: per-role autoscale phase", flush=True)
    pool = EnginePool(factory, 2, share_prefixes=True,
                      roles=[ROLE_PREFILL, ROLE_DECODE],
                      seed=args.seed)
    scalers = {}
    try:
        scalers[ROLE_PREFILL] = PoolAutoscaler(
            RolePoolView(pool, ROLE_PREFILL),
            SLOPolicy(min_replicas=1, max_replicas=3,
                      ttft_slo_s=0.001, cooldown_up_s=0.0))
        scalers[ROLE_DECODE] = PoolAutoscaler(
            RolePoolView(pool, ROLE_DECODE),
            SLOPolicy(min_replicas=1, max_replicas=3,
                      itl_slo_s=60.0, idle_stable_s=3600.0))
        pool.submit(list(prompts[0]),
                    max_new_tokens=gen_tokens).result()
        hs = [pool.submit(list(p), max_new_tokens=gen_tokens)
              for p in prompts[:6]]
        decisions = {r: [] for r in scalers}
        for _ in range(60):
            for role, sc in scalers.items():
                decisions[role].append(sc.tick())
            if pool.role_counts().get(ROLE_PREFILL, 0) > 1:
                break
            time.sleep(0.05)
        for h in hs:
            h.result()
        counts = pool.role_counts()
        autoscale = {
            role: {"start": 1, "final": counts.get(role, 0),
                   "decisions": decisions[role],
                   **{k: sc.stats()[k] for k in
                      ("scale_ups", "scale_downs", "ticks")}}
            for role, sc in scalers.items()}
        autoscale["diverged"] = (
            counts.get(ROLE_PREFILL, 0) != counts.get(ROLE_DECODE,
                                                      0))
    finally:
        pool.shutdown()
    if not autoscale["diverged"]:
        print("WARNING: role pools did not diverge under the burst "
              "— the artifact will fail schema validation",
              flush=True)

    # ---- chaos arm: decode replica killed before the handoff ------
    # The handoff's typed abort ladder must decode in place on the
    # prefill replica, token-identically to the no-fault reference.
    print("disagg A/B: chaos arm (decode replica kill)", flush=True)
    ref = np.asarray(generate(
        model, params,
        jnp.asarray([prompts[0]], jnp.int32),
        max_new_tokens=gen_tokens,
        temperature=0.0))[0, prompt_len:].tolist()
    pool = EnginePool(factory, 2, share_prefixes=True,
                      roles=[ROLE_PREFILL, ROLE_DECODE],
                      seed=args.seed)
    try:
        pool.submit(list(prompts[1]),
                    max_new_tokens=4).result()   # warm both paths
        decode_idx = next(
            i for i, r in enumerate(pool.pool_stats()["replicas"])
            if r["role"] == ROLE_DECODE)
        pool.engines()[decode_idx].shutdown()
        toks = pool.submit(list(prompts[0]),
                           max_new_tokens=gen_tokens).result()
        ps = pool.pool_stats()
        chaos = {
            "faults_injected": 1,
            "handoff_fallbacks": ps.get("disagg_handoff_fallbacks",
                                        0),
            "lost": 0,
            "mismatched": 0 if list(toks) == ref else 1,
            "token_identical": list(toks) == ref,
        }
    finally:
        pool.shutdown()
    if chaos["mismatched"] or not chaos["handoff_fallbacks"]:
        print("WARNING: chaos arm did not recover token-identically "
              "through the fallback — the artifact will fail schema "
              "validation", flush=True)

    # streams travel as counts; the bulk lived in the comparison
    for arm in (unified, disagg):
        arm.pop("streams")
    from ray_tpu.models.llama import _use_paged_kernel
    return {
        "disagg_ab": {
            "page_size": page_size,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "requests": n_requests,
            "arrival_gap_s": gap_s,
            "max_slots": max_slots,
            "unified": unified,
            "disagg": disagg,
            "token_identical": identical,
            "ttft_p50_ratio": ttft_ratio,
            "throughput_ratio": thpt_ratio,
            "kv_pull": kv_pull,
            "autoscale": autoscale,
            "chaos": chaos,
        },
        "mesh": {"tp": 1, "replicas": 2},
        "kv": {"kv_dtype": "fp",
               "paged_kernel": ("pallas" if _use_paged_kernel()
                                else "gather")},
        "model": "llama-tiny",
        "notes": "Prefill/decode disaggregation A/B (serve_bench.py "
                 "--disagg-ab): identical 2-replica pool + decode-"
                 "saturating arrival trace served unified vs role-"
                 "split (1 prefill + 1 decode joined by the KV-"
                 "migration handoff). Steady-state TTFT p50 is the "
                 "last half of the arrivals; throughput is tokens/s "
                 "over the full trace at equal chip count. Streams "
                 "are gated token-identical across the handoff; the "
                 "autoscale phase must scale the roles apart on the "
                 "same burst; the chaos arm kills the decode replica "
                 "and must recover through the typed decode-in-place "
                 "fallback.",
    }


def run_rollout_ab(args):
    """Live weight rollout A/B (serve_bench.py --rollout-ab): one
    paced arrival trace against a 3-replica pool with no weight swap
    (baseline arm) vs the SAME trace while a staged rollout walks the
    pool mid-flight (rollout arm) — canary, parity probes, advance
    waves, all in preempt mode so in-flight requests are preempted at
    each flip and resubmit through the replica-death path. The new
    payload is the SAME tensors republished under a new checkpoint
    identity (air/checkpoint.py manifest -> weights_id), so every
    completion in BOTH arms must equal the greedy reference: 0 lost /
    0 mismatched is the gate, not a hope. TTFT p95 impact vs the
    no-rollout arm is stamped against an explicit bound; the fence
    proof records every per-replica generation transition (strictly
    monotonic). A third leg publishes a genuinely PERTURBED payload
    and proves the canary's parity probe fails it, the controller
    auto-rolls-back, the fleet converges onto the baseline
    weights_id, and the decision is flight-explained. The artifact
    REFUSES to exist (tools/check_bench_schema.py ``rollout_ab``
    family) with any lost/mismatched request, zero swaps, unbounded
    TTFT impact, a broken fence, or a missing rollback proof."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, generate, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.engine_pool import EnginePool
    from ray_tpu.serve.weight_rollout import (WeightRolloutController,
                                              load_weights,
                                              publish_weights)

    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    n_replicas = 3
    prompt_len = 32
    gen_tokens = 16
    n_requests = 24
    gap_s = 0.02
    ttft_impact_limit = 5.0    # bound on p95 TTFT under the swap
    # churn: preempt-mode flips recompute straddling requests, so
    # some headroom over the no-rollout arm is expected — unbounded
    # impact is not

    rng = np.random.RandomState(args.seed + 47)
    prompts = [rng.randint(1, cfg.vocab_size - 1,
                           size=prompt_len).tolist()
               for _ in range(n_requests)]
    refs = [np.asarray(generate(
        model, params, jnp.asarray([p], jnp.int32),
        max_new_tokens=gen_tokens,
        temperature=0.0))[0, prompt_len:].tolist() for p in prompts]

    workdir = tempfile.mkdtemp(prefix="rollout_ab_")
    _v2_path, wid2 = publish_weights(
        params, os.path.join(workdir, "v2"), step=2,
        extra={"release": "v2"})
    v2_params, _ = load_weights(_v2_path)
    flight_dir = os.path.join(workdir, "flight")

    def factory(idx):
        return LLMEngine(model, params, max_slots=4, page_size=8,
                         n_pages=96, chunk=4, temperature=0.0,
                         eos_id=-1, seed=args.seed,
                         prefix_cache=True)

    def run_arm(rollout):
        pool = EnginePool(factory, n_replicas, seed=args.seed)
        swaps = 0
        transitions = []
        try:
            for i in range(n_replicas):   # compile every replica
                pool.replica(i).engine.submit(
                    list(prompts[0]), max_new_tokens=2).result()
            ctl_result = {}

            def run_rollout():
                ctl = WeightRolloutController(
                    pool, canary_fraction=0.34,
                    probes=[(prompts[0], refs[0][:4])],
                    swap_mode="preempt", flight_dir=flight_dir)
                ctl_result["report"] = ctl.rollout(
                    v2_params, weights_id=wid2,
                    baseline_params=params,
                    baseline_weights_id="g0")

            handles = []
            roller = None
            for i, p in enumerate(prompts):
                handles.append(pool.submit(
                    list(p), max_new_tokens=gen_tokens))
                if rollout and i == n_requests // 3:
                    # the rollout lands mid-trace, under load
                    roller = threading.Thread(target=run_rollout,
                                              daemon=True)
                    roller.start()
                time.sleep(gap_s)
            lost = mismatched = 0
            for i, h in enumerate(handles):
                try:
                    if list(h.result()) != refs[i]:
                        mismatched += 1
                except Exception:  # noqa: BLE001
                    lost += 1
            if roller is not None:
                roller.join(120)
                report = ctl_result.get("report") or {}
                if report.get("status") != "completed":
                    print("WARNING: mid-trace rollout did not "
                          "complete — the artifact will fail schema "
                          "validation", flush=True)
                transitions.extend(report.get("transitions", []))
                swaps = pool.route_stats["weight_swaps"]
            ttfts = sorted(h.ttft_s for h in handles
                           if h.ttft_s is not None)
        finally:
            pool.shutdown()
        return {
            "requests": n_requests,
            "lost": lost,
            "mismatched": mismatched,
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
            "ttft_p95_s": round(
                ttfts[min(len(ttfts) - 1,
                          int(0.95 * len(ttfts)))], 4),
            "tokens": n_requests * gen_tokens - lost * gen_tokens,
            **({"swaps": swaps} if rollout else {}),
        }, transitions

    print("rollout A/B: baseline arm (no rollout)", flush=True)
    baseline, _ = run_arm(rollout=False)
    print("rollout A/B: live-rollout arm", flush=True)
    rolled, transitions = run_arm(rollout=True)

    # fence proof: every transition advances, per replica
    last = {}
    monotonic = bool(transitions)
    for tr in transitions:
        if tr["to"] <= tr["from"] or tr["to"] <= last.get(tr["idx"],
                                                          -1):
            monotonic = False
        last[tr["idx"]] = tr["to"]
    ratio = _ratio(rolled["ttft_p95_s"],
                   max(baseline["ttft_p95_s"], 0.01))
    identical = (baseline["mismatched"] == 0
                 and rolled["mismatched"] == 0)
    for arm, sec in (("baseline", baseline), ("rollout", rolled)):
        if sec["lost"] or sec["mismatched"]:
            print(f"WARNING: {arm} arm lost/mismatched requests — "
                  "the artifact will fail schema validation",
                  flush=True)
    if ratio is None or ratio > ttft_impact_limit:
        print("WARNING: rollout TTFT impact exceeded the stamped "
              "bound — the artifact will fail schema validation",
              flush=True)

    # ---- injected-regression leg: the canary must roll it back ----
    print("rollout A/B: injected-regression canary rollback",
          flush=True)
    bad_params = jax.tree_util.tree_map(lambda x: x + 0.25, params)
    bad_path, bad_wid = publish_weights(
        bad_params, os.path.join(workdir, "bad"), step=3)
    pool = EnginePool(factory, 2, seed=args.seed)
    try:
        pool.replica(0).engine.submit(
            list(prompts[0]), max_new_tokens=2).result()
        ctl = WeightRolloutController(
            pool, canary_fraction=0.5,
            probes=[(prompts[0], refs[0][:6])],
            swap_mode="preempt", flight_dir=flight_dir)
        report = ctl.rollout(load_weights(bad_path)[0],
                             weights_id=bad_wid,
                             baseline_params=params,
                             baseline_weights_id="g0")
        rb = report.get("rollback") or {}
        bundle = rb.get("bundle") or ""
        rollback = {
            "injected_regression": True,
            "rolled_back": report.get("status") == "rolled_back",
            "reason": report.get("rollback_reason", ""),
            "converged": bool(rb.get("converged")),
            "probe_failures": len(report.get("probe_failures", [])),
            "baseline_weights_id": "g0",
            "flight_bundle": os.path.basename(bundle)
            if bundle else "",
        }
    finally:
        pool.shutdown()
    if not (rollback["rolled_back"] and rollback["converged"]
            and rollback["flight_bundle"]):
        print("WARNING: injected regression was not rolled back "
              "convergently — the artifact will fail schema "
              "validation", flush=True)

    return {
        "rollout_ab": {
            "replicas": n_replicas,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "arrival_gap_s": gap_s,
            "baseline": baseline,
            "rollout": rolled,
            "token_identical": identical,
            "ttft_p95_ratio": ratio,
            "ttft_impact_limit": ttft_impact_limit,
            "fence": {"monotonic": monotonic,
                      "transitions": transitions},
            "generations": {"from": "g0", "to": wid2},
            "rollback": rollback,
        },
        "mesh": {"tp": 1, "replicas": n_replicas},
        "model": "llama-tiny",
        "notes": "Live weight rollout A/B (serve_bench.py "
                 "--rollout-ab): one paced arrival trace vs the SAME "
                 "trace with a staged canary rollout walking the "
                 "3-replica pool mid-flight in preempt mode (the new "
                 "payload is the same tensors republished under a "
                 "new checkpoint identity, so every completion must "
                 "match the greedy reference — 0 lost / 0 mismatched "
                 "gated). TTFT p95 impact is bounded against the "
                 "stamped limit; the fence proof records per-replica "
                 "generation transitions; the injected-regression "
                 "leg proves the canary parity probe triggers a "
                 "convergent, flight-explained auto-rollback.",
    }


def _batch_bench_model(args):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def run_batch_ab(args):
    """Batch-tier profile A/B (serve_bench.py --batch-ab): the SAME
    offline corpus driven through ``BatchInferenceJob`` on an engine
    configured from each named scheduler profile —
    ``engine_kwargs_for_profile('latency')`` (shallow online-tuned
    queue, small decode chunks) vs ``'throughput'`` (deep no-TTFT-SLO
    queue, big prefill chunks, long decode run-ahead). Greedy
    sampling, so the arms must be TOKEN-IDENTICAL: a knob preset may
    only move walltime, never tokens (the artifact REFUSES to exist
    otherwise — tools/check_bench_schema.py ``batch_ab`` family).
    Each arm runs an unmeasured warmup job first so jit compiles of
    its chunk shapes land outside the measured window."""
    from ray_tpu.serve.batch_tier import (BatchInferenceJob,
                                          engine_kwargs_for_profile)
    from ray_tpu.serve.engine import LLMEngine

    cfg, model, params = _batch_bench_model(args)
    rng = np.random.RandomState(args.seed + 17)
    prompt_len, gen_tokens, rows = 8, 8, 16
    corpus = [rng.randint(1, cfg.vocab_size - 1,
                          size=prompt_len).tolist()
              for _ in range(rows)]
    warm = [rng.randint(1, cfg.vocab_size - 1,
                        size=prompt_len).tolist() for _ in range(2)]

    def run_arm(profile):
        kw = engine_kwargs_for_profile(profile)
        eng = LLMEngine(model, params, max_slots=4, page_size=8,
                        n_pages=64, temperature=0.0, eos_id=-1,
                        seed=args.seed, **kw).start()
        try:
            BatchInferenceJob(eng, warm, max_new_tokens=gen_tokens,
                              max_in_flight=4, job_id="warmup").run()
            t0 = time.perf_counter()
            job = BatchInferenceJob(eng, corpus,
                                    max_new_tokens=gen_tokens,
                                    max_in_flight=8,
                                    job_id=f"ab-{profile}")
            streams = job.run()
            wall = time.perf_counter() - t0
            batch_tokens = eng.stats.get("batch_tokens", 0)
        finally:
            eng.shutdown()
        toks = sum(len(s) for s in streams)
        return streams, {
            "profile": profile,
            "engine_kwargs": dict(kw),
            "rows": rows,
            "tokens": toks,
            "batch_lane_tokens": int(batch_tokens),
            "wall_s": round(wall, 4),
            "tokens_per_s": round(toks / wall, 2) if wall else None,
        }

    print("batch A/B: latency-profile arm", flush=True)
    lat_streams, lat = run_arm("latency")
    print("batch A/B: throughput-profile arm", flush=True)
    thr_streams, thr = run_arm("throughput")
    identical = lat_streams == thr_streams
    if not identical:
        print("WARNING: profile arms diverged token-wise — the "
              "artifact will fail schema validation", flush=True)
    return {
        "batch_ab": {
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "latency": lat,
            "throughput": thr,
            "token_identical": identical,
            "tokens_per_s_ratio": _ratio(thr["tokens_per_s"],
                                         lat["tokens_per_s"]),
        },
        "model": "llama-tiny",
        "notes": "Batch-tier profile A/B (serve_bench.py --batch-ab):"
                 " one offline corpus through BatchInferenceJob on an"
                 " engine built from engine_kwargs_for_profile("
                 "'latency') vs ('throughput'). Greedy arms are gated"
                 " token-identical — profiles may move walltime only."
                 " Per-arm warmup jobs keep chunk-shape compiles out"
                 " of the measured window; tokens_per_s_ratio is the"
                 " throughput arm over the latency arm.",
    }


def run_mixed_ab(args):
    """Mixed online+batch A/B with a chaos leg (serve_bench.py
    --mixed-ab): the SAME paced online trace replayed against (A) an
    engine serving nothing else — the no-batch baseline — and (B) the
    same engine while a ``BatchInferenceJob`` soaks every idle slot
    on ``priority=LANE_BATCH``. The lane contract says colocation is
    free for the online lane (batch admits behind it and is the first
    preemption victim), so the artifact REFUSES to exist
    (tools/check_bench_schema.py ``mixed_ab`` family) when the mixed
    arm's SLO attainment falls more than the noise floor below the
    baseline's, when the batch tier absorbed zero tokens, or when the
    chaos leg violated exactly-once.

    The chaos leg kills the batch driver mid-run (its submit path
    raises after N rows, with rows committed AND in flight), then
    resumes from the sha256 manifest: committed rows must never be
    resubmitted (0 duplicates), every row must land (0 missing —
    ``run()`` raises otherwise), and the resumed results must be
    token-identical to the clean baseline batch run."""
    from ray_tpu.serve.batch_tier import BatchInferenceJob
    from ray_tpu.serve.engine import LLMEngine

    cfg, model, params = _batch_bench_model(args)
    rng = np.random.RandomState(args.seed + 29)
    prompt_len, gen_tokens = 8, 8
    n_online, online_gap_s = 10, 0.05
    online = [rng.randint(1, cfg.vocab_size - 1,
                          size=prompt_len).tolist()
              for _ in range(n_online)]
    batch_rows = [rng.randint(1, cfg.vocab_size - 1,
                              size=prompt_len).tolist()
                  for _ in range(12)]
    warm = rng.randint(1, cfg.vocab_size - 1,
                       size=prompt_len).tolist()
    slo_s = args.ttft_slo_ms / 1000.0
    crash_after = 5

    def make_engine():
        return LLMEngine(model, params, max_slots=2, page_size=8,
                         n_pages=64, chunk=4, temperature=0.0,
                         eos_id=-1, seed=args.seed).start()

    def replay_online(eng):
        handles = []
        for p in online:
            handles.append(eng.submit(list(p),
                                      max_new_tokens=gen_tokens))
            time.sleep(online_gap_s)
        streams = [h.result() for h in handles]
        ttfts = [h.ttft_s for h in handles]
        return streams, ttfts

    def summarize(ttfts):
        ms = sorted(t * 1000.0 for t in ttfts)
        return {
            "ttft_p50_ms": round(ms[len(ms) // 2], 2),
            "ttft_p99_ms": round(ms[-1], 2),
            "slo_attainment": round(
                sum(1 for t in ttfts if t <= slo_s) / len(ttfts), 4),
        }

    class _CrashingSubmit:
        """Batch driver whose submit raises after N rows — the
        mid-run kill, with committed and in-flight rows behind it."""

        def __init__(self, eng, left):
            self._eng, self._left = eng, left

        def submit(self, *a, **kw):
            if self._left <= 0:
                raise RuntimeError("mixed-ab chaos kill")
            self._left -= 1
            return self._eng.submit(*a, **kw)

    class _CountingSubmit:
        def __init__(self, eng):
            self._eng = eng
            self.n = 0

        def submit(self, *a, **kw):
            self.n += 1
            return self._eng.submit(*a, **kw)

    import shutil
    import tempfile
    ckpt_dir = tempfile.mkdtemp(prefix="mixed_ab_ck_")
    try:
        # ---- arm A: online only, plus the clean batch reference
        print("mixed A/B: no-batch baseline arm", flush=True)
        eng = make_engine()
        try:
            eng.submit(list(warm), max_new_tokens=gen_tokens).result()
            base_streams, base_ttfts = replay_online(eng)
            batch_ref = BatchInferenceJob(
                eng, batch_rows, max_new_tokens=gen_tokens,
                max_in_flight=4, job_id="mixed-ref").run()
        finally:
            eng.shutdown()

        # ---- arm B: same trace over a batch-soaked engine, with the
        # batch driver killed mid-run and resumed from its manifest
        print("mixed A/B: batch-soaked arm (chaos kill+resume)",
              flush=True)
        eng = make_engine()
        chaos = {}
        try:
            eng.submit(list(warm), max_new_tokens=gen_tokens).result()

            def drive_batch():
                try:
                    BatchInferenceJob(
                        _CrashingSubmit(eng, crash_after), batch_rows,
                        max_new_tokens=gen_tokens, max_in_flight=4,
                        checkpoint_dir=ckpt_dir, checkpoint_every=2,
                        job_id="mixed-chaos").run()
                except RuntimeError as e:
                    chaos["kill"] = str(e)
                from ray_tpu.air.checkpoint import Checkpoint
                committed = Checkpoint.from_directory(
                    ckpt_dir).to_dict()["completed"]
                chaos["committed_at_crash"] = len(committed)
                target = _CountingSubmit(eng)
                job = BatchInferenceJob(
                    target, batch_rows, max_new_tokens=gen_tokens,
                    max_in_flight=4, checkpoint_dir=ckpt_dir,
                    checkpoint_every=2, job_id="mixed-chaos")
                chaos["results"] = job.run()   # raises on missing rows
                chaos["rows_resumed"] = job.stats["rows_resumed"]
                chaos["resubmitted"] = target.n

            t = threading.Thread(target=drive_batch, daemon=True)
            t0 = time.perf_counter()
            t.start()
            mixed_streams, mixed_ttfts = replay_online(eng)
            t.join(timeout=120)
            mixed_wall = time.perf_counter() - t0
            if t.is_alive():
                raise RuntimeError("batch driver wedged in mixed arm")
            batch_tokens = eng.stats.get("batch_tokens", 0)
            preempted = eng.stats.get("batch_preemptions", 0)
        finally:
            eng.shutdown()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    dup = chaos["committed_at_crash"] + chaos["resubmitted"] \
        - len(batch_rows)
    token_identical = (mixed_streams == base_streams
                       and chaos["results"] == batch_ref)
    base = summarize(base_ttfts)
    mixed = summarize(mixed_ttfts)
    mixed.update({
        "batch_tokens": int(batch_tokens),
        "batch_tokens_per_chip_s": round(
            batch_tokens / mixed_wall, 2) if mixed_wall else None,
        "batch_preemptions": int(preempted),
    })
    noise_floor = 0.15
    if not token_identical:
        print("WARNING: mixed arm diverged token-wise — the artifact "
              "will fail schema validation", flush=True)
    if mixed["slo_attainment"] < base["slo_attainment"] - noise_floor:
        print("WARNING: online attainment sank under batch load — "
              "the artifact will fail schema validation", flush=True)
    if dup != 0:
        print("WARNING: chaos resume duplicated rows — the artifact "
              "will fail schema validation", flush=True)
    return {
        "mixed_ab": {
            "online_requests": n_online,
            "gen_tokens": gen_tokens,
            "ttft_slo_ms": args.ttft_slo_ms,
            "attainment_noise_floor": noise_floor,
            "baseline": base,
            "mixed": mixed,
            "token_identical": token_identical,
            "chaos": {
                "kill": chaos.get("kill"),
                "batch_rows": len(batch_rows),
                "crash_after": crash_after,
                "committed_at_crash": chaos["committed_at_crash"],
                "rows_resumed": chaos["rows_resumed"],
                "resubmitted": chaos["resubmitted"],
                "dup_rows": int(dup),
                "missing_rows": 0,   # run() raised otherwise
            },
        },
        "model": "llama-tiny",
        "notes": "Mixed online+batch A/B (serve_bench.py --mixed-ab):"
                 " one paced online trace replayed against an idle"
                 " engine (baseline) and the same engine soaked by a"
                 " LANE_BATCH BatchInferenceJob whose driver is"
                 " killed mid-run and resumed from its sha256"
                 " manifest. Gated: online SLO attainment within the"
                 " noise floor of the baseline, batch tokens absorbed"
                 " > 0, chaos resume exactly-once (0 dup / 0 missing)"
                 " and token-identical to the clean batch reference.",
    }


def _ratio(a, b):
    return round(a / b, 2) if b else None


def _stamp(result, args, replicas=None):
    """Attribution every artifact carries: the RNG seed, the git sha,
    and the mesh shape the run was placed on (tp = tensor-parallel
    width per replica, replicas = data-parallel engine replicas) —
    cross-round comparisons are meaningless without knowing how many
    chips each number came from."""
    result["seed"] = args.seed
    result["git_sha"] = git_sha()
    # a run that already recorded its actual placement (e.g. --tp-ab
    # defaulting to a 4-way mesh) keeps its own stamp
    result.setdefault("mesh",
                      {"tp": args.tp,
                       "replicas": (args.replicas if replicas is None
                                    else replicas)})
    # KV representation stamp: which page dtype the run served from
    # and which paged-attention backend read it. Numbers from an int8
    # pool or the pallas kernel are not comparable to fp/gather runs
    # without this. setdefault so runs that record several arms
    # (e.g. --kvq-ab) keep their own richer stamp.
    from ray_tpu.models.llama import _use_paged_kernel
    from ray_tpu.util.envknobs import resolve_kv_dtype
    result.setdefault("kv", {
        "kv_dtype": resolve_kv_dtype(getattr(args, "kv_dtype", None)),
        "paged_kernel": ("pallas" if _use_paged_kernel()
                         else "gather"),
    })
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="7b",
                    choices=["7b", "1b", "tiny"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--legacy", action="store_true",
                    help="decode-to-completion @serve.batch path "
                         "(engine off) for A/B on the same load")
    ap.add_argument("--ab", action="store_true",
                    help="run engine AND legacy paths in THIS process "
                         "and write one artifact with both + ratios")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--threads", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=GEN_TOKENS)
    ap.add_argument("--prompt-len", type=int, default=PROMPT_LEN)
    ap.add_argument("--slots", type=int, default=SLOTS)
    ap.add_argument("--decode-chunk", type=int, default=DECODE_CHUNK)
    ap.add_argument("--prefill-chunk", type=int, default=PREFILL_CHUNK)
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV page size in tokens (smaller pages make "
                         "short shared prefixes cacheable: matching "
                         "is page-granular)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="every prompt opens with this many IDENTICAL "
                         "tokens (system-prompt load shape); implies "
                         "--prefix-cache unless overridden")
    ap.add_argument("--prefix-cache",
                    action=argparse.BooleanOptionalAction,
                    default=None,
                    help="radix-tree prefix KV cache in the engine "
                         "(default: on iff --shared-prefix-len > 0)")
    ap.add_argument("--spec-len", type=int, default=0,
                    help="draft tokens per slot per round for "
                         "prompt-lookup speculative decoding "
                         "(0 = off; greedy-only, exact parity)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="suffix n-gram order for the prompt-lookup "
                         "proposer")
    ap.add_argument("--prompt-period", type=int, default=0,
                    help="cycle each prompt's tail with this period "
                         "(repetitive-suffix load shape speculation "
                         "targets; 0 = fully random tails)")
    ap.add_argument("--prompt-pool", type=int, default=0,
                    help="multi-session load shape: draw every "
                         "request from this many FIXED distinct "
                         "prompts (sessions re-asking with their own "
                         "long context). Sized past one replica's "
                         "radix-cache capacity but under the pool "
                         "aggregate, it is the regime prefix-affinity "
                         "routing exists for (0 = fresh random tails)")
    ap.add_argument("--prompt-order", default="random",
                    choices=["random", "cyclic"],
                    help="session selection order under --prompt-pool:"
                         " random draws, or cyclic round-robin (each "
                         "session re-asks only after every other one "
                         "— LRU-adversarial for a single cache, "
                         "natural for affinity-sharded replicas)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="engine eos token id (eos-BOUNDED decode "
                         "scheduling, the realistic serving mode: "
                         "chunked decode rounds with per-round "
                         "drains instead of the no-eos deferred "
                         "run-ahead; -1 = eos configured but never "
                         "sampled)")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="override the model config's max_seq_len "
                         "(tiny defaults to 128; longer contexts "
                         "raise the per-miss re-prefill cost the "
                         "prefix cache / pool affinity removes)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="PER-REPLICA KV pool size in pages (default: "
                         "full residency for max_slots). Sizing this "
                         "below slots*seq_len makes the paged pool the "
                         "bottleneck: chunk-budget admission "
                         "overcommits, preemption recomputes — the "
                         "regime where a replica pool's AGGREGATE KV "
                         "(N replicas = N pools) is what scales")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind one deployment "
                         "(EnginePool). With --ab runs pool-vs-single "
                         "A/B on the same load and adds a replica-kill "
                         "recovery phase to the artifact")
    ap.add_argument("--fleet", type=int, default=0,
                    help="back the deployment with a loopback fleet "
                         "of N lease-renewing replica agents behind "
                         "a FleetRouter (serve/fleet/) instead of an "
                         "in-process EnginePool; the fleet topology "
                         "is stamped into the artifact. Exclusive "
                         "with --replicas > 1")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width per engine replica "
                         "(serve/sharding.py: Megatron-sharded "
                         "weights, head-sharded paged KV over a 1-D "
                         "tp mesh; composes with --replicas into the "
                         "2-D replica x tp layout). Must divide the "
                         "model's heads / kv heads / hidden dim")
    ap.add_argument("--tp-ab", action="store_true",
                    help="tensor-parallel A/B: the identical engine "
                         "+ greedy load at tp=1 and sharded tp-way "
                         "(--tp, default 4), with a token-parity "
                         "check across plain decode, prefix-cache "
                         "hits, and speculative decoding")
    ap.add_argument("--overlap-ab", action="store_true",
                    help="overlapped-vs-lockstep hot-loop A/B: the "
                         "identical engine + greedy eos-bounded load "
                         "under the lockstep loop (full pre-plan "
                         "readback drain) and the double-buffered "
                         "overlapped loop, with a token-parity gate "
                         "and per-round host-gap accounting; "
                         "self-gated by tools/check_bench_schema.py")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="add a third --overlap-ab arm running the "
                         "overlapped loop under the pallas paged "
                         "decode kernel (RAY_TPU_PAGED_KERNEL=1) — "
                         "the kernel-vs-gather re-ranking measurement "
                         "for real TPUs (models/llama.py "
                         "_use_paged_kernel); off-TPU it runs the "
                         "interpreter and carries no ranking signal")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["fp", "int8"],
                    help="paged KV pool element dtype for the engine "
                         "path (int8 = quantized pages + per-page "
                         "absmax scales, ~2x pages per byte; "
                         "models/kv_cache.py). RAY_TPU_KV_DTYPE "
                         "overrides; default fp")
    ap.add_argument("--kvq-ab", action="store_true",
                    help="int8-KV A/B: the identical engine + greedy "
                         "load with fp pages and with int8 pages at "
                         "ONE fixed page-pool byte budget — parity "
                         "sub-run gates token agreement/spec accept "
                         "rate, capacity sub-run proves ~2x pages/"
                         "slots and fewer sheds from the same bytes; "
                         "self-gated by tools/check_bench_schema.py")
    ap.add_argument("--prefix-share-ab", action="store_true",
                    help="fleet-shared prefix cache A/B: the SAME "
                         "2-replica pool + multi-session thrashing "
                         "trace with private per-replica prefix "
                         "caches vs share_prefixes=True (cold "
                         "replica PULLS the holder's pinned int8 "
                         "pages instead of recomputing) — gates "
                         "token identity, cross-replica hit rate, "
                         "and TTFT p50 ratio; self-gated by "
                         "tools/check_bench_schema.py")
    ap.add_argument("--disagg-ab", action="store_true",
                    help="prefill/decode disaggregation A/B: the SAME "
                         "2-replica pool + continuous-arrival trace "
                         "unified vs role-split (prefill replica "
                         "hands finished pages to the decode replica "
                         "over kv_migration.pull_prefix) — gates "
                         "token identity, handoffs > 0, steady-state "
                         "TTFT p50 ratio < 1.0 and tokens/s >= "
                         "unified; adds a per-role autoscale phase "
                         "and a decode-kill chaos arm; self-gated by "
                         "tools/check_bench_schema.py")
    ap.add_argument("--rollout-ab", action="store_true",
                    help="live weight rollout A/B: one paced arrival "
                         "trace with no swap vs the SAME trace while "
                         "a staged canary rollout (preempt-mode hot "
                         "swap, parity probes, auto-advance) walks "
                         "the 3-replica pool mid-flight — gates 0 "
                         "lost / 0 mismatched, bounded TTFT p95 "
                         "impact, a monotonic generation fence, and "
                         "an injected-regression canary rollback "
                         "proven flight-explained; self-gated by "
                         "tools/check_bench_schema.py")
    ap.add_argument("--batch-ab", action="store_true",
                    help="batch-tier profile A/B: one offline corpus "
                         "through BatchInferenceJob on an engine "
                         "built from the 'latency' vs 'throughput' "
                         "scheduler profile — greedy arms gated "
                         "token-identical; self-gated by "
                         "tools/check_bench_schema.py")
    ap.add_argument("--mixed-ab", action="store_true",
                    help="mixed online+batch A/B: one paced online "
                         "trace against an idle engine vs the same "
                         "engine soaked by a LANE_BATCH batch job "
                         "whose driver is chaos-killed mid-run and "
                         "resumed from its manifest — gates online "
                         "attainment within noise of the no-batch "
                         "arm, batch tokens absorbed, and exactly-"
                         "once resume (0 dup / 0 missing rows); "
                         "self-gated by tools/check_bench_schema.py")
    ap.add_argument("--lifecycle", action="store_true",
                    help="request-lifecycle smoke: unsaturated pass "
                         "then an overload burst against --max-queued "
                         "with injected cancels + deadline probes")
    ap.add_argument("--max-queued", type=int, default=2,
                    help="admission-queue bound for the --lifecycle "
                         "overload phase (excess submits shed with "
                         "EngineOverloaded / HTTP 429)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed for prompts / client jitter / "
                         "traces; stamped into every artifact so a "
                         "run can be reproduced from its JSON alone")
    ap.add_argument("--autoscale", action="store_true",
                    help="trace-driven autoscaling run: replay one "
                         "arrival trace against an SLO-driven "
                         "autoscaled pool AND a static pool at max, "
                         "emit SLO attainment + replica timeline + "
                         "chip-seconds for both")
    ap.add_argument("--trace", nargs="?", const="capture",
                    default="bursty",
                    help="bare --trace: run the request-scope trace "
                         "capture instead of a throughput bench — "
                         "drive a small engine with the typed event "
                         "log on, emit a SERVE_TRACE artifact "
                         "(Chrome/Perfetto trace_events + per-request "
                         "phase index + events-on/off overhead A/B), "
                         "self-gated by tools/check_bench_schema.py. "
                         "With a value (diurnal|bursty|multitenant): "
                         "the arrival-trace shape for --autoscale")
    ap.add_argument("--autoscale-min", type=int, default=1,
                    help="pool floor (autoscaled arm starts here)")
    ap.add_argument("--autoscale-max", type=int, default=4,
                    help="pool ceiling (= the static arm's size)")
    ap.add_argument("--provision-delay", type=float, default=0.4,
                    help="SimulatedTPUCloud slice-provisioning delay "
                         "in seconds (scale-up is NOT free)")
    ap.add_argument("--trace-duration", type=float, default=20.0,
                    help="trace length in seconds")
    ap.add_argument("--base-rps", type=float, default=3.0,
                    help="off-peak arrival rate")
    ap.add_argument("--peak-rps", type=float, default=50.0,
                    help="peak arrival rate (sized so the burst "
                         "genuinely needs the replica ceiling)")
    ap.add_argument("--ttft-slo-ms", type=float, default=1000.0,
                    help="TTFT SLO threshold; attainment = fraction "
                         "of ALL arrivals whose first token landed "
                         "within this (sheds count against)")
    ap.add_argument("--attainment-floor", type=float, default=0.9,
                    help="minimum acceptable autoscale-arm SLO "
                         "attainment, recorded in the artifact and "
                         "enforced by tools/check_bench_schema.py")
    ap.add_argument("--slots-per-replica", type=int, default=2,
                    help="--autoscale engine max_slots per replica "
                         "(small, so the trace actually pressures "
                         "capacity)")
    ap.add_argument("--max-queued-per-replica", type=int, default=8,
                    help="--autoscale per-replica admission bound "
                         "(deep enough to buffer a burst while "
                         "capacity provisions, bounded so a true "
                         "overload sheds instead of queueing forever)")
    args = ap.parse_args()
    prefix_cache = (args.shared_prefix_len > 0
                    if args.prefix_cache is None else args.prefix_cache)
    knobs = dict(requests=args.requests, threads=args.threads,
                 gen_tokens=args.gen_tokens,
                 prompt_len=args.prompt_len, slots=args.slots,
                 decode_chunk=args.decode_chunk,
                 prefill_chunk=args.prefill_chunk,
                 page_size=args.page_size,
                 shared_prefix_len=args.shared_prefix_len,
                 prefix_cache=prefix_cache,
                 spec_len=args.spec_len, spec_ngram=args.spec_ngram,
                 prompt_period=args.prompt_period,
                 prompt_pool=args.prompt_pool,
                 prompt_order=args.prompt_order,
                 replicas=args.replicas, kv_pages=args.kv_pages,
                 eos_id=args.eos_id, max_seq_len=args.max_seq_len,
                 seed=args.seed, tp=args.tp, fleet=args.fleet,
                 kv_dtype=args.kv_dtype)

    import os
    if (args.tp > 1 or args.tp_ab) \
            and os.environ.get("JAX_PLATFORMS") == "cpu" \
            and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # sharded arms need a multi-device mesh; on a CPU smoke that
        # means forcing host devices BEFORE jax initializes (same
        # trick as tests/conftest.py)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    import ray_tpu
    from ray_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    ray_tpu.init()

    if args.fleet and args.trace == "capture" and not args.autoscale:
        result = _stamp(run_fleet_trace(args), args)
        out = args.out or "SERVE_FLEET_TRACE_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a malformed or unstitched artifact fails its
        # OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps({k: result[k] for k in
                          ("stitch", "collector", "seed", "mesh")},
                         default=str))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.trace == "capture" and not args.autoscale:
        result = _stamp(run_trace(args), args)
        from tools.trace_report import report
        result["report"] = report(result)
        out = args.out or "SERVE_TRACE_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a malformed trace artifact fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        # the full artifact is bulky (every event twice); print the
        # headline blocks only
        print(json.dumps({k: result[k] for k in
                          ("requests_n", "overhead", "seed", "mesh")},
                         default=str))
        print(json.dumps({"ttft_check":
                          result["report"]["ttft_check"]}))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.tp_ab:
        result = _stamp(run_tp_ab(args), args)
        out = args.out or "SERVE_BENCH_tp_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        ray_tpu.shutdown()
        return

    if args.overlap_ab:
        result = _stamp(run_overlap_ab(args), args)
        out = args.out or "SERVE_BENCH_overlap_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a malformed or non-improving artifact fails its
        # OWN run (same discipline as the trace capture)
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.kvq_ab:
        result = _stamp(run_kvq_ab(args), args)
        out = args.out or "SERVE_BENCH_kvq_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: an artifact missing its byte-budget stamp, below
        # the 1.9x capacity ratio, or below the parity floor fails
        # its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.prefix_share_ab:
        result = _stamp(run_prefix_share_ab(args), args, replicas=2)
        out = args.out or "SERVE_BENCH_prefix_share_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a non-token-identical pulled arm, a shared arm
        # with no cross-replica hits, or a missing kv/mesh stamp
        # fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.disagg_ab:
        result = _stamp(run_disagg_ab(args), args, replicas=2)
        out = args.out or "SERVE_BENCH_disagg_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: token divergence across the handoff, zero
        # handoffs, a TTFT ratio that didn't improve, or a missing
        # role/kv/mesh stamp fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.rollout_ab:
        result = _stamp(run_rollout_ab(args), args, replicas=3)
        out = args.out or "SERVE_BENCH_rollout_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a lost or token-diverging request under the
        # swap, zero swaps, unbounded TTFT impact, a broken fence,
        # or a missing rollback proof fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.batch_ab:
        result = _stamp(run_batch_ab(args), args, replicas=1)
        out = args.out or "SERVE_BENCH_batch_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: a token-diverging or zero-token profile arm
        # fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.mixed_ab:
        result = _stamp(run_mixed_ab(args), args, replicas=1)
        out = args.out or "SERVE_BENCH_mixed_ab_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: sunk online attainment, an idle batch lane, or a
        # non-exactly-once chaos resume fails its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.fleet and args.autoscale:
        # combined: autoscaling where capacity is real agent
        # PROCESSES behind the durable fleet directory
        result = _stamp(run_fleet_autoscale(args), args,
                        replicas=args.autoscale_max)
        out = args.out or "SERVE_BENCH_fleet_autoscale_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        # self-gate: the artifact must pass the autoscale family
        # checks (chip-seconds ratio, attainment, Retry-After) on
        # its OWN run
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        print(json.dumps(result))
        ray_tpu.shutdown()
        if problems:
            raise SystemExit(1)
        return

    if args.autoscale:
        # the autoscaled arm peaks at --autoscale-max replicas
        result = _stamp(run_autoscale(args), args,
                        replicas=args.autoscale_max)
        out = args.out or "SERVE_BENCH_autoscale_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        ray_tpu.shutdown()
        return

    if args.lifecycle:
        result = _stamp(run_lifecycle(args, knobs), args)
        out = args.out or "SERVE_BENCH_lifecycle_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        ray_tpu.shutdown()
        return

    if args.fleet:
        # One engine-path run with the deployment backed by the
        # loopback fleet control plane (LlamaDeployment fleet=N):
        # same bench load as a pool run, the delta is every request
        # crossing the lease/fencing state machine and the transport
        # seam. run_path stamps the fleet topology into the result.
        result = _stamp(run_path(args, knobs, use_engine=True),
                        args, replicas=args.fleet)
        out = args.out or "SERVE_BENCH_fleet_cpu_smoke.json"
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        ray_tpu.shutdown()
        return

    if args.ab and args.replicas > 1:
        # Pool-vs-single A/B: SAME engine path and load shape, the
        # only delta is num_engine_replicas — so pool_throughput_ratio
        # isolates what data parallelism adds (and what routing
        # costs). Plus an in-process replica-kill recovery phase.
        pool = run_path(args, knobs, use_engine=True)
        single = run_path(args, dict(knobs, replicas=1),
                          use_engine=True)
        pstats = pool.get("pool") or {}
        result = {
            "engine_pool": pool,
            "engine_single": single,
            "replicas": args.replicas,
            "pool_throughput_ratio": _ratio(
                pool["throughput_tok_s"], single["throughput_tok_s"]),
            "affinity_hit_rate": pstats.get("affinity_hit_rate"),
            "spill_rate": pstats.get("spill_rate"),
            "single_prefix_hit_rate": (single.get("prefix_cache")
                                       or {}).get("hit_rate"),
            "notes": "Same-session pool-vs-single A/B (serve_bench.py "
                     "--ab --replicas N): one deployment backed by an "
                     "EnginePool of N engine replicas with "
                     "prefix-affinity + P2C routing vs the identical "
                     "single-engine deployment, same shared-prefix "
                     "load. replica_kill is an in-process "
                     "FaultInjector run: replica 0 dies mid-decode; "
                     "unstarted requests resubmit to the survivor "
                     "token-identically, partially-streamed ones fail "
                     "typed EngineShutdown, lost must be 0.",
        }
        print("replica-kill recovery phase", flush=True)
        result["replica_kill"] = run_pool_kill(args.seed)
        out = args.out or "SERVE_BENCH_pool_cpu_smoke.json"
        _stamp(result, args)
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
        ray_tpu.shutdown()
        return

    if args.ab:
        eng = run_path(args, knobs, use_engine=True)
        leg = run_path(args, knobs, use_engine=False)
        result = {
            "engine_continuous_batching": eng,
            "legacy_decode_to_completion": leg,
            "throughput_ratio": _ratio(eng["throughput_tok_s"],
                                       leg["throughput_tok_s"]),
            "p50_ratio": _ratio(eng["p50_ms"], leg["p50_ms"]),
            "ttft_ratio": _ratio(eng["ttft_ms"], leg["ttft_ms"]),
            "notes": "Same-session A/B: both paths served and "
                     "measured in ONE process against the same load "
                     "shape (serve_bench.py --ab). TTFT is "
                     "client-observed first stream item; the engine "
                     "path also reports engine-internal "
                     "first-emission TTFT.",
        }
        if knobs["prefix_cache"] and knobs["shared_prefix_len"] > 0:
            # third run: SAME engine path + load, prefix cache OFF —
            # the cache's own A/B, free of engine-vs-legacy effects
            off = run_path(args, dict(knobs, prefix_cache=False),
                           use_engine=True)
            result["engine_prefix_cache_off"] = off
            on_ms = eng.get("engine_ttft_mean_ms")
            off_ms = off.get("engine_ttft_mean_ms")
            if on_ms and off_ms:
                # < 1.0 means the cache lowered mean prefill latency
                result["prefix_ttft_ratio"] = round(on_ms / off_ms, 3)
        if knobs["spec_len"] > 0:
            # third (or fourth) run: SAME engine path + load,
            # speculation OFF — spec's own A/B, free of
            # engine-vs-legacy effects
            off = run_path(args, dict(knobs, spec_len=0),
                           use_engine=True)
            result["engine_spec_off"] = off
            # > 1.0 means speculation raised same-load throughput
            result["spec_throughput_ratio"] = _ratio(
                eng["throughput_tok_s"], off["throughput_tok_s"])
        out = args.out or "SERVE_BENCH_ab.json"
    else:
        result = run_path(args, knobs, use_engine=not args.legacy)
        out = args.out or ("SERVE_BENCH_r05_legacy.json" if args.legacy
                           else "SERVE_BENCH_r05.json")
    _stamp(result, args)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
