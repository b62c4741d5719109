"""A decode step's attention over K/V pages on the local TPU chip: the
Pallas kernel (``ops/paged_decode_attention.py``) against the block loop
XLA compiles (``ops/paged_attention.py`` ``_paged_window_attention``),
one layer-step each at the serving cells' shapes and contexts:

  ouro     16 rows, 16 heads on 16 KV heads, contexts 256-352
  olmoe    32 rows, 16 on 16, contexts 256-352
  mistral  32 rows, 32 on 8, contexts 256-352, and 16-2,560 mixed with
           a quarter of the rows without a rider (the open loop's)
  mellum2  32 rows, 32 on 4, contexts 8,192-8,704, a quarter null
  solar    32 rows, 64 on 8, contexts 1,024-1,280

One JSON line a reading: ms a layer-step (the mean of 50 calls inside
ONE device loop, each fed the one before it, so no dispatch of the
host's is in it) and the rate at which the riders' OWN K and V bytes
went by. ``schedule`` is the kernel's visit schedule alone
(``visit_schedule``: a step program computes it once a step, its
layers share it). ``--pages 1,2,4,8`` times the kernel at so many pages
a visit beside its own plan's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PAGE, D, CALLS = 64, 128, 50
POOL_BYTES = 1 << 30              # of K, and of V
# name: (rows, heads, KV heads, table columns, (lo, hi) contexts, rows
# without a rider)
SHAPES = {
    "ouro": (16, 16, 16, 64, (256, 352), 0),
    "olmoe": (32, 16, 16, 64, (256, 352), 0),
    "mistral": (32, 32, 8, 64, (256, 352), 0),
    "mistral-open": (32, 32, 8, 64, (16, 2560), 8),
    "mellum2": (32, 32, 4, 256, (8192, 8704), 8),
    "solar": (32, 64, 8, 64, (1024, 1280), 0),
}


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import paged_decode_attention as pd

    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--pages", default="")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("paged_decode_bench times a TPU; none is "
                         "attached")
    sweep = [int(p) for p in args.pages.split(",") if p]
    rng = np.random.default_rng(0)

    def looped(attend):
        """``CALLS`` calls in one device loop, each call's queries
        nudged by the last one's output."""
        @jax.jit
        def run(q, pk, pv, table, pos):
            def body(_, q):
                y = attend(q, pk, pv, table, pos)
                return q + (y * 1e-3).astype(q.dtype)
            return jax.lax.fori_loop(0, CALLS, body, q)
        return run

    def timed(fn, *a, n=5):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n / CALLS * 1e3

    def loop(q, pk, pv, table, pos):
        with mock.patch.object(pd, "_on_one_tpu", lambda: False):
            return pa._paged_window_attention(q, pk, pv, None, None,
                                              table, pos)

    def kernel(pages, **kw):
        def run(q, pk, pv, table, pos):
            return pd._attend(q, pk, pv, table, pos,
                              softmax_scale=D ** -0.5, pages=pages, **kw)
        return run

    for name in args.shapes.split(","):
        B, H, KH, max_pages, (lo, hi), idle = SHAPES[name]
        # every row's pages scattered over the pool, as an allocator
        # leaves them; page 0 is the null page
        contexts = rng.integers(lo, hi + 1, B)
        contexts[rng.permutation(B)[:idle]] = 0
        held = -(-contexts // PAGE)
        # a pool of a deployment's size and no smaller: one that fits
        # the chip's 128 MiB of fast memory is moved there whole, and
        # both forms then read at a rate no HBM has
        n_pages = max(1 + int(held.sum()),
                      POOL_BYTES // (PAGE * KH * D * 2))
        ids = 1 + rng.permutation(n_pages - 1).astype(np.int32)
        table = np.zeros((B, max_pages), np.int32)
        at = 0
        for b in range(B):
            table[b, :held[b]] = ids[at:at + held[b]]
            at += held[b]
        # a row without a rider keeps a stale position
        pos = np.where(contexts > 0, contexts - 1, 3000).astype(np.int32)
        pk, pv = (0.5 * jax.random.normal(
            jax.random.PRNGKey(k), (n_pages, PAGE, KH, D), jnp.bfloat16)
            for k in range(2))
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
        a = (q, pk, pv, jnp.asarray(table), jnp.asarray(pos))
        own = int(contexts.sum()) * KH * D * 2 * 2
        riders = contexts > 0

        def line(impl, ms, **more):
            print(json.dumps({
                "shape": name, "rows": B, "riders": int(riders.sum()),
                "heads": H, "kv_heads": KH,
                "context_tokens": int(contexts.sum()), "impl": impl,
                "ms": round(ms, 4),
                "own_GBps": round(own / ms / 1e6, 1), **more}),
                flush=True)

        want = np.asarray(jax.jit(loop)(*a), np.float32)[riders]
        line("loop", timed(looped(loop), *a))
        plan = pd.pages_per_visit(H, PAGE, KH, max_pages)
        for pages in [plan] + [p for p in sweep if p != plan]:
            got = np.asarray(jax.jit(kernel(pages))(*a),
                             np.float32)[riders]
            line({"kernel_pages_a_visit": pages,
                  "plan": pages == plan},
                 timed(looped(kernel(pages)), *a),
                 err=float(np.abs(got - want).max()),
                 of=float(np.abs(want).max()))

        def schedule(q, pk, pv, table, pos):
            # never true, and nothing the compiler can know
            nudge = (q[0, 0, 0, 0] > 1e30).astype(jnp.int32)
            out = pd.visit_schedule(table, pos + nudge, PAGE, plan)
            return (sum(o.sum() for o in out) * 0).astype(q.dtype)
        line("schedule", timed(looped(schedule), *a))


if __name__ == "__main__":
    main()
