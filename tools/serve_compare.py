"""Continuous batching vs decode-to-completion: controlled comparison.

Same model, same 16-thread load, same machine — one run with the
round-3 serving shape (@serve.batch coalescing + whole-batch decode to
completion) and one with the round-4 engine (paged-KV continuous
batching). Writes SERVE_COMPARE JSON. Runs on CPU with a small Llama:
it compares request counts and scheduling shape, not device speed.

Run: python tools/serve_compare.py [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROMPT_LEN = 32
GEN_TOKENS = 48
N_REQ = 32
N_THREADS = 16
BATCH = 8          # legacy coalescing width (round-3 shape)


def small_llama():
    """Large enough that per-step COMPUTE dominates dispatch overhead
    (the on-chip regime the engine targets); a toy config would just
    measure the host loop."""
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=2048, max_seq_len=128, dim=512,
                       n_layers=8, n_heads=8, n_kv_heads=4,
                       hidden_dim=1408, dtype=jnp.float32)


def run_mode(use_engine: bool):
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment

    cfg = small_llama()

    if use_engine:
        @serve.deployment(max_ongoing_requests=64)
        class Server:
            def __init__(self):
                self.inner = LlamaDeployment(
                    config=cfg, max_new_tokens=GEN_TOKENS,
                    max_slots=16, page_size=16, decode_chunk=4)

            def __call__(self, prompt):
                return self.inner(prompt)[len(prompt):]
    else:
        @serve.deployment(max_ongoing_requests=64)
        class Server:
            def __init__(self):
                self.inner = LlamaDeployment(
                    config=cfg, max_new_tokens=GEN_TOKENS,
                    use_engine=False)

            @serve.batch(max_batch_size=BATCH,
                         batch_wait_timeout_s=0.02)
            async def __call__(self, prompts):
                n = len(prompts)
                padded = list(prompts) + [prompts[0]] * (BATCH - n)
                return self.inner.generate_batch(padded)[:n]

    handle = serve.run(Server.bind(), timeout_s=600)
    rng = np.random.RandomState(0)

    def prompt():
        return rng.randint(1, 500, size=PROMPT_LEN).tolist()

    ray_tpu.get(handle.remote(prompt()), timeout=600)   # warm/compile

    latencies = []
    lock = threading.Lock()

    def client(n):
        for _ in range(n):
            t = time.time()
            out = ray_tpu.get(handle.remote(prompt()), timeout=600)
            assert len(out) == GEN_TOKENS
            with lock:
                latencies.append(time.time() - t)

    t0 = time.time()
    ts = [threading.Thread(target=client, args=(N_REQ // N_THREADS,))
          for _ in range(N_THREADS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.time() - t0
    lat = sorted(x * 1000 for x in latencies)
    out = {
        "throughput_tok_s": round(N_REQ * GEN_TOKENS / wall, 1),
        "p50_ms": round(statistics.median(lat), 1),
        "p99_ms": round(lat[min(len(lat) - 1,
                                int(len(lat) * 0.99))], 1),
    }
    serve.shutdown()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import ray_tpu
    ray_tpu.init()
    legacy = run_mode(use_engine=False)
    print("legacy (decode-to-completion):", json.dumps(legacy),
          flush=True)
    engine = run_mode(use_engine=True)
    print("engine (continuous batching):", json.dumps(engine),
          flush=True)
    result = {
        "notes": (
            "CPU-only proxy, NOT the target regime: on CPU (fp32, "
            "~10GB/s, no paged-attention kernel) the engine's "
            "page-window gather dominates per-step cost, while the "
            "legacy whole-batch while_loop pays zero per-step host "
            "or gather overhead. On-chip decode of a >=1B bf16 model "
            "is WEIGHT-bound: the gather is <1% of step traffic and "
            "the engine's wider live batch (16 slots vs 8) + "
            "join-at-chunk admission are the dominant terms. The "
            "decisive artifact is SERVE_BENCH_r{N}.json on the TPU."),
        "model": "llama-small-cpu",
        "load": {"requests": N_REQ, "threads": N_THREADS,
                 "prompt_len": PROMPT_LEN, "gen_tokens": GEN_TOKENS},
        "legacy_decode_to_completion": legacy,
        "engine_continuous_batching": engine,
        "throughput_ratio": round(
            engine["throughput_tok_s"] /
            max(legacy["throughput_tok_s"], 1e-9), 2),
        "p50_ratio": round(
            engine["p50_ms"] / max(legacy["p50_ms"], 1e-9), 2),
    }
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
