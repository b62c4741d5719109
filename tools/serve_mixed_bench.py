"""Mixed-length serving: where continuous batching structurally wins.

The round-4 uniform-length comparison (SERVE_COMPARE) measures the
regime kindest to decode-to-completion: every batched request wants the
same number of tokens, so nothing ever blocks behind a longer
neighbor. Real LLM traffic is mixed; there, the legacy shape decodes
every batch to its LONGEST member (short requests pay the straggler's
full decode before their reply leaves), while the engine retires a
short request the moment it finishes and admits a waiting one into the
freed slot (reference being surpassed: python/ray/serve/batching.py —
coalesced batches complete as a unit).

Load: short "riders" (8 tokens) mixed with long "stragglers"
(128 tokens), 3:1, under 16 concurrent clients. Metrics: useful tokens/s
and per-class p50. Writes ENGINE_MIXED json (VERDICT r5 #3: one
artifact where engine > legacy).

Run: python tools/serve_mixed_bench.py [--out FILE]
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

PROMPT_LEN = 24
SHORT, LONG = 8, 128
N_REQ = 32                      # 24 riders + 8 stragglers
N_THREADS = 16
BATCH = 8


def model_cfg():
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=2048, max_seq_len=160, dim=512,
                       n_layers=8, n_heads=8, n_kv_heads=4,
                       hidden_dim=1408, dtype=jnp.float32)


def _requests(rng):
    """Deterministic interleaved mix: every 4th request is a
    straggler."""
    out = []
    for i in range(N_REQ):
        n = LONG if i % 4 == 3 else SHORT
        out.append((rng.randint(1, 500, size=PROMPT_LEN).tolist(), n))
    return out


def run_mode(use_engine: bool):
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment

    cfg = model_cfg()

    if use_engine:
        @serve.deployment(max_ongoing_requests=64)
        class Server:
            def __init__(self):
                self.inner = LlamaDeployment(
                    config=cfg, max_new_tokens=LONG,
                    max_slots=16, page_size=16, decode_chunk=4)

            def __call__(self, item):
                prompt, n = item
                return self.inner.engine().submit(
                    prompt, max_new_tokens=n).result()
    else:
        @serve.deployment(max_ongoing_requests=64)
        class Server:
            def __init__(self):
                self.inner = LlamaDeployment(
                    config=cfg, max_new_tokens=LONG, use_engine=False)

            @serve.batch(max_batch_size=BATCH,
                         batch_wait_timeout_s=0.02)
            async def __call__(self, items):
                # Decode-to-completion: the whole batch runs to the
                # LONGEST request in it, then each reply truncates —
                # the head-of-line cost this benchmark measures.
                import jax.numpy as jnp
                from ray_tpu.models.llama import generate
                prompts = [p for p, _ in items]
                ns = [n for _, n in items]
                steps = max(ns)
                padded = list(prompts) + \
                    [prompts[0]] * (BATCH - len(prompts))
                batch = jnp.asarray(padded, jnp.int32)
                out = generate(self.inner.model, self.inner.params,
                               batch, max_new_tokens=steps,
                               temperature=0.0)
                arr = np.asarray(out)[:len(prompts), PROMPT_LEN:]
                return [arr[i, :ns[i]].tolist()
                        for i in range(len(prompts))]

    handle = serve.run(Server.bind(), timeout_s=900)
    rng = np.random.RandomState(0)
    reqs = _requests(rng)
    # warm/compile both step shapes
    ray_tpu.get(handle.remote((reqs[0][0], SHORT)), timeout=900)
    ray_tpu.get(handle.remote((reqs[0][0], LONG)), timeout=900)

    lock = threading.Lock()
    lat = {SHORT: [], LONG: []}
    done_tokens = [0]
    qi = [0]

    def client():
        while True:
            with lock:
                if qi[0] >= len(reqs):
                    return
                prompt, n = reqs[qi[0]]
                qi[0] += 1
            t = time.time()
            out = ray_tpu.get(handle.remote((prompt, n)),
                              timeout=900)
            assert len(out) == n, (len(out), n)
            with lock:
                lat[n].append(time.time() - t)
                done_tokens[0] += n

    t0 = time.time()
    ts = [threading.Thread(target=client) for _ in range(N_THREADS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.time() - t0
    out = {
        "useful_tok_s": round(done_tokens[0] / wall, 1),
        "wall_s": round(wall, 1),
        "rider_p50_ms": round(
            statistics.median(lat[SHORT]) * 1000, 1),
        "straggler_p50_ms": round(
            statistics.median(lat[LONG]) * 1000, 1),
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
    print("legacy:", json.dumps(legacy), flush=True)
    engine = run_mode(use_engine=True)
    print("engine:", json.dumps(engine), flush=True)
    result = {
        "notes": (
            f"Mixed-length load (3:1 riders of {SHORT} tokens to "
            f"stragglers of {LONG}) on CPU: "
            "decode-to-completion batches run to their "
            "longest member, so riders queue behind stragglers; "
            "continuous batching retires riders immediately and "
            "refills the freed slots."),
        "load": {"requests": N_REQ, "threads": N_THREADS,
                 "prompt_len": PROMPT_LEN,
                 "short_tokens": SHORT, "long_tokens": LONG},
        "legacy_decode_to_completion": legacy,
        "engine_continuous_batching": engine,
        "useful_throughput_ratio": round(
            engine["useful_tok_s"] /
            max(legacy["useful_tok_s"], 1e-9), 2),
        "rider_p50_ratio": round(
            engine["rider_p50_ms"] /
            max(legacy["rider_p50_ms"], 1e-9), 2),
    }
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
