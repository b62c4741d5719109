"""The mixture's grouped matmul on the local TPU chip
(``ops/grouped_matmul.py``: its own Pallas kernel under ``tile_plan``,
the matrix fetched by group), one call at a time as a step program
makes it. A line
is one (experts held E, K, N, sorted pairs m, pairs that have a held
expert, seed): the pairs are dealt to the experts evenly at random,
the rest lie past the last group. One JSON line a reading: the plan,
ms a call by the host's clock over ``--calls`` calls queued back to
back (each on the next of ``--stacks`` weight stacks: nothing is read
twice in a row), the touched experts' bytes a second and their share
of the chip's 819 GB/s, and for a compute-bound call the share of its
bfloat16 peak that the held pairs' operations come to.

``--cells`` times the (K, N) the seven mixtures present (w1/w3 and w2
each), at their decode call's m and pairs and at their prefill call's,
under the rule's plan: the op as it stands (``body`` "group_keyed") and
the kernel JAX ships under the same plan (``megablox.gmm``, ``body``
"gmm": what the op ran until PR 57).
``--tiling tm,tk,tn[;tm,tk,tn...]`` times those plans instead;
``--sweep`` every plan whose tiles divide the matrix inside the
kernel's memory at tm 128, then tm 64 and 256 at the fastest.
``--shape E,K,N,m,pairs`` (repeatable) times one shape of your own.
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

HBM = 819e9            # benchmarks/peaks.json, TPU v5e
PEAK = 197e12          # bfloat16

# (config, experts held, D, F, decode (m, held pairs), prefill (m, held
# pairs)): the serving cells' calls (PERF.md section 4)
CELLS = (
    ("olmoe", 64, 2048, 1024, (256, 248), (8192, 8192)),
    ("solar-open2", 40, 4096, 1280, (256, 30), (8192, 1024)),
    ("axk1", 12, 7168, 2048, (256, 15), (8192, 512)),
    ("kimi-linear", 64, 2304, 1024, (1024, 250), (8192, 2048)),
    ("mellum2", 64, 2304, 896, (256, 196), (8192, 8192)),
    ("laguna-xs2", 256, 2048, 512, (1024, 1000), (8192, 8192)),
    ("dsv32", 8, 7168, 2048, (256, 6), (8192, 256)),
)


def cell_lines():
    for name, e, d, f, decode, prefill in CELLS:
        for kind, (m, pairs) in (("decode", decode), ("prefill", prefill)):
            yield f"{name}.{kind}.w13", e, d, f, m, pairs
            yield f"{name}.{kind}.w2", e, f, d, m, pairs


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import grouped_matmul as gm

    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", action="store_true")
    ap.add_argument("--only", default="",
                    help="with --cells: lines whose name holds this")
    ap.add_argument("--shape", action="append", default=[])
    ap.add_argument("--tiling", default="")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--calls", type=int, default=48)
    ap.add_argument("--stacks", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_matmul_bench times a TPU; none is "
                         "attached")
    lines = [ln for ln in cell_lines() if args.only in ln[0]] \
        if args.cells else []
    for s in args.shape:
        e, k, n, m, pairs = (int(x) for x in s.split(","))
        lines.append((f"shape.{s}", e, k, n, m, pairs))
    asked = [tuple(int(x) for x in p.split(","))
             for p in args.tiling.split(";") if p]
    out = open(args.out, "a") if args.out else None
    bf16 = jnp.bfloat16

    def timed(fn, rows, stacks, sizes):
        jax.block_until_ready([fn(rows, w, sizes) for w in stacks])
        t0 = time.perf_counter()
        got = [fn(rows, stacks[i % len(stacks)], sizes)
               for i in range(args.calls)]
        jax.block_until_ready(got)
        return (time.perf_counter() - t0) / args.calls * 1e3

    for name, e, k, n, m, pairs in lines:
        rng = np.random.default_rng(args.seed)
        sizes_np = rng.multinomial(pairs, np.full(e, 1.0 / e))
        sizes = jnp.asarray(sizes_np, jnp.int32)
        touched = int((sizes_np > 0).sum())
        keys = jax.random.split(jax.random.PRNGKey(args.seed),
                                args.stacks + 1)
        rows = jax.random.normal(keys[0], (m, k), bf16)
        stacks = [jax.random.normal(key, (e, k, n), bf16) * k ** -0.5
                  for key in keys[1:]]
        want = np.asarray(jax.lax.ragged_dot(
            rows, stacks[0], sizes,
            preferred_element_type=jnp.float32))[:pairs]
        byts = touched * k * n * 2
        flops = 2 * pairs * k * n

        def read(plan, fn, body="group_keyed"):
            rec = {"line": name, "E": e, "K": k, "N": n, "m": m,
                   "pairs": pairs, "touched": touched,
                   "visits": int(gm.visits(sizes, m)), "plan": list(plan),
                   "body": body}
            try:
                got = np.asarray(fn(rows, stacks[0], sizes)[:pairs],
                                 np.float32)
                ms = timed(fn, rows, stacks, sizes)
            except jax.errors.JaxRuntimeError as err:   # a plan refused
                rec["refused"] = str(err).splitlines()[0][:160]
                ms = None
            else:
                rec.update(
                    ms=round(ms, 4),
                    touched_GBps=round(byts / ms / 1e6, 1),
                    hbm_share=round(100 * byts / HBM / (ms / 1e3), 1),
                    peak_share=round(100 * flops / PEAK / (ms / 1e3), 1),
                    rel_err=float(np.linalg.norm(got - want)
                                  / (np.linalg.norm(want) + 1e-30)))
            text = json.dumps(rec)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
            return ms

        def with_plan(plan):
            def fn(rows, w, sizes):
                with mock.patch.object(gm, "tile_plan", lambda *_: plan):
                    return gm.grouped_matmul_kernel(rows, w, sizes)
            return jax.jit(fn)

        def shipped(plan):
            from jax.experimental.pallas.ops.tpu.megablox import gmm
            return jax.jit(lambda rows, w, sizes: gmm(
                rows, w, sizes, preferred_element_type=rows.dtype,
                tiling=plan))

        rule = gm.tile_plan(m, k, n, 2)
        if args.sweep:
            grid = [(128, tk, tn) for tk in gm.dividing_tiles(k)
                    for tn in gm.dividing_tiles(n)
                    if (2 << 20) <= tk * tn * 2
                    and gm.vmem_bytes(128, tk, tn, 2) <= 16 << 20]
            took = {p: read(p, with_plan(p))
                    for p in dict.fromkeys(grid + [rule])}
            best = min((p for p in took if took[p]), key=took.get)
            for tm in (64, 256):
                p = (tm,) + best[1:]
                read(p, with_plan(p))
        elif asked:
            for p in asked:
                read(p, with_plan(p))
        else:
            read(rule, jax.jit(gm.grouped_matmul))
            read(rule, shipped(rule), body="gmm")


if __name__ == "__main__":
    main()
