"""Pallas flash attention vs XLA attention on the local TPU chip.

Long-context is first-class in this framework (ring/Ulysses SP ride
the same kernel); this artifact records the causal fwd+bwd step time
and achieved attention FLOP/s of the pallas kernel against the plain
XLA softmax(QK^T)V path across sequence lengths, plus the longest
sequence each path can run at all (the XLA path materializes the
[T, T] score matrix; flash never does). Writes FLASH_r05.json on TPU.

``--shape B,T,H,D`` times both paths at that one shape instead (the
train cell's attention is 24,1024,12,64) and writes nothing.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_one(impl: str, B: int, H: int, T: int, D: int,
              steps: int = 10):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import multi_head_attention

    rng = np.random.RandomState(0)

    def mk():
        return jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)

    q, k, v = mk(), mk(), mk()

    def loss(q, k, v):
        o = multi_head_attention(q, k, v, causal=True, impl=impl)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    try:
        g = step(q, k, v)
        float(jnp.sum(g[0].astype(jnp.float32)))   # barrier
    except Exception as e:  # noqa: BLE001
        return {"error": type(e).__name__, "detail": str(e)[:160]}
    t0 = time.perf_counter()
    for _ in range(steps):
        g = step(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))
    dt = (time.perf_counter() - t0) / steps
    # Causal attention FLOPs (fwd 2 matmuls + bwd ~2.5x fwd):
    # 3.5 * 2 * B*H*T^2*D * 2 (QK^T and PV) / 2 (causal half).
    flops = 3.5 * 2.0 * 2.0 * B * H * T * T * D / 2.0
    return {"ms": round(dt * 1000, 2),
            "tflops": round(flops / dt / 1e12, 2)}


def main():
    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if sys.argv[1:2] == ["--shape"]:
        B, T, H, D = (int(x) for x in sys.argv[2].split(","))
        for impl in ("xla", "flash"):
            print(json.dumps({"shape": [B, T, H, D], "impl": impl,
                              **bench_one(impl, B, H, T, D)}))
        return
    B, H, D = 4, 8, 64
    seqs = [1024, 2048, 4096, 8192] if on_tpu else [128]
    out = {"device": getattr(dev, "device_kind", "cpu"),
           "shape": {"batch": B, "heads": H, "head_dim": D},
           "mode": "causal fwd+bwd", "rows": []}
    for T in seqs:
        row = {"seq": T, "xla": bench_one("xla", B, H, T, D)}
        if on_tpu:
            # impl="flash" dispatches the pallas kernel with NO
            # silent fallback (attention.py), so a broken kernel
            # surfaces as an error row, never as fake flash numbers.
            row["flash"] = bench_one("flash", B, H, T, D)
            f, x = row["flash"], row["xla"]
            if "ms" in f and "ms" in x:
                row["speedup"] = round(x["ms"] / f["ms"], 2)
        else:
            row["note"] = "flash skipped (no TPU; smoke run)"
        out["rows"].append(row)
        print(json.dumps(row))
    if on_tpu:
        # Long-context headroom: largest power-of-two seq that runs.
        for T in (16384, 32768, 65536):
            r = bench_one("flash", 1, H, T, D, steps=3)
            print(json.dumps({"seq": T, "flash_b1": r}))
            if "error" in r:
                break
            out["max_seq_flash_b1"] = {"seq": T, **r}
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "FLASH_r05.json")
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
