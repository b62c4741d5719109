"""Continuous-batching LLM serving: the engine behind serve.

Run (CPU demo):
    JAX_PLATFORMS=cpu python examples/10_llm_engine.py

What this shows
---------------
- `LlamaDeployment` serves every Llama-shaped family through the device-paced continuous-batching
  engine (ray_tpu/serve/engine.py): requests join/leave the decode
  batch at token granularity — a short completion never waits for a
  long one to finish the way whole-call batching makes it
  (the convoy effect `@serve.batch` has for LLMs).
- Streaming: tokens arrive as the engine emits them.
- The same deployment runs unchanged on a TPU chip, where the paged
  KV pool and the decode dispatch chain live in HBM; chip_smoke.py
  at the repo root drives exactly this shape at 1.1B on the chip.
"""
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.llama import llama_tiny
    from ray_tpu.serve.llm import LlamaDeployment

    ray_tpu.init()
    cfg = llama_tiny()

    @serve.deployment(max_ongoing_requests=32)
    class Llm:
        def __init__(self):
            self.inner = LlamaDeployment(
                config=cfg, max_new_tokens=24,
                max_slots=4, page_size=8, decode_chunk=4)

        def __call__(self, prompt_ids):
            return self.inner(prompt_ids)

        def stream(self, prompt_ids):
            yield from self.inner.stream(prompt_ids)

    handle = serve.run(Llm.bind(), timeout_s=300)
    rng = np.random.RandomState(0)

    def prompt():
        return rng.randint(1, cfg.vocab_size - 1, size=8).tolist()

    # --- concurrent requests share the decode batch ------------------
    t0 = time.time()
    outs = []

    def client():
        outs.append(ray_tpu.get(handle.remote(prompt()), timeout=300))

    threads = [threading.Thread(target=client) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    print(f"6 concurrent generations in {time.time() - t0:.1f}s; "
          f"lengths: {[len(o) for o in outs]}")

    # --- streaming ---------------------------------------------------
    toks = []
    for tok in handle.stream.options(stream=True).remote(prompt()):
        toks.append(tok)
    print(f"streamed {len(toks)} tokens: {toks[:6]}...")

    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
