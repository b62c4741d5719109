"""The one-token delta-rule step on the local TPU chip: the Pallas
kernel against the ``jax.numpy`` form XLA compiles
(``ops/linear_attention.py``), 8 chained steps in one program as a
decode dispatch runs them, at the two serving cells' state shapes
(``[128, 32, 128, 128]``: kimi-linear-d8.gen-sat; ``[32, 64, 128,
128]``: solar-open2-d4.doc-sat). One JSON line a reading: ms a
layer-step and the riders' state bytes (one read, one write) a second.

``--plans 8x1,16x2,32x2`` times the kernel under those plans (heads a
block x heads a loop step) instead of its own; ``--free N`` leaves N
rows riding nothing (default: 3 of 128 and 3 of 32, as the cells run).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEPS = 8
SHAPES = ((128, 32), (32, 64))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import linear_attention as la

    ap = argparse.ArgumentParser()
    ap.add_argument("--plans", default="")
    ap.add_argument("--free", type=int, default=3)
    ap.add_argument("--d", type=int, default=128)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("kda_step_bench times a TPU; none is attached")
    d = args.d
    plans = [la.StepPlan(*(int(x) for x in p.split("x")))
             for p in args.plans.split(",") if p] or [None]

    def chain(step):
        def run(xs, state, valid, fresh):
            def body(s, x):
                o, s = step(*x, s, valid, fresh)
                return s, o
            s, o = jax.lax.scan(body, state, xs)
            return o, s
        return jax.jit(run, donate_argnums=1)

    def timed(fn, xs, state, valid, fresh, n=10):
        o, state = fn(xs, state, valid, fresh)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(n):
            o, state = fn(xs, state, valid, fresh)
        jax.block_until_ready((o, state))
        return (time.perf_counter() - t0) / n / STEPS * 1e3

    for B, H in SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(B), 6)

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-6)
        xs = (unit(jax.random.normal(ks[0], (STEPS, B, H, d))) * d ** -0.5,
              unit(jax.random.normal(ks[1], (STEPS, B, H, d))),
              jax.random.normal(ks[2], (STEPS, B, H, d)),
              -jnp.exp(jax.random.uniform(ks[3], (STEPS, B, H, d),
                                          minval=-7.0, maxval=3.0)),
              2.0 * jax.nn.sigmoid(jax.random.normal(ks[4],
                                                     (STEPS, B, H))))
        state = jax.random.normal(ks[5], (B, H, d, d))
        free = np.linspace(1, B - 2, args.free).astype(int)
        valid = jnp.ones((B,), bool).at[free].set(False)
        fresh = (jnp.arange(B) % 17 == 5) & valid
        riders = int(valid.sum())
        moved = 2 * riders * H * d * d * 4

        def line(impl, ms, **more):
            print(json.dumps({
                "shape": [B, H, d, d], "riders": riders, "impl": impl,
                "ms": round(ms, 4),
                "riders_GBps": round(moved / ms / 1e6, 1), **more}),
                flush=True)

        xla = chain(la._kda_step_xla)
        want_o, want_s = xla(xs, state + 0, valid, fresh)
        line("xla", timed(xla, xs, state + 0, valid, fresh))
        for plan in plans:
            if plan is not None and plan.heads > H:
                continue
            fn = chain(functools.partial(la.kda_step_kernel, plan=plan))
            o, s = fn(xs, state + 0, valid, fresh)
            rows = np.asarray(valid)
            err_o = float(jnp.max(jnp.abs(o - want_o)[:, rows]))
            err_s = float(jnp.max(jnp.abs(s - want_s)))
            kept = bool((np.asarray(s)[~rows]
                         == np.asarray(state)[~rows]).all())
            line({"kernel": list(plan or la.step_plan(H, d, d))},
                 timed(fn, xs, state + 0, valid, fresh),
                 err_o=err_o, err_s=err_s, free_rows_kept=kept)


if __name__ == "__main__":
    main()
