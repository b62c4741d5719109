"""A sliding layer's append and attention on the local TPU chip: the
Pallas kernel (``ops/ring_window_attention.py``) against the pair XLA
compiles (``ops/paged_attention.py`` ``ring_append`` +
``ring_attention`` over the rows' rings taken by slot), at
``mellum2-d8.longdoc-sat``'s shapes: rings ``[32, 4, 1344, 128]``, 32
query heads, a window of 1,024,

  step    a decode step: 32 rows of which ``--riders`` ride, contexts
          8,192-8,704
  chunk   a prefill call: ``[4, 256]`` at 4,096-7,936 of 8,192

over SIX layers' rings in turn, as a step program walks them: one ring
pair fits the chip's 128 MiB of fast memory and the compiler moves it
there whole, six do not (PERF.md section 6, PR 47: a stand-alone timer
whose operand fits reads a rate no HBM has).

One JSON line a reading: ms a layer-call (the mean of ``CALLS`` walks
of the six layers inside ONE device loop, each fed the one before it
and the rings carried, so no dispatch of the host's is in it), the rate
at which the riders' windows went by (a step), and how far the kernel's
``y`` and rings sit from the pair's on the same operands. ``--blocks
128,256,512`` times the kernel at so many keys a fold beside
``key_spans``'s.
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

SLOTS, H, KH, D, L, WINDOW = 32, 32, 4, 128, 1344, 1024
LAYERS, CALLS = 6, 20


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import ring_window_attention as rw

    ap = argparse.ArgumentParser()
    ap.add_argument("--riders", default="24,32")
    ap.add_argument("--blocks", default="")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("ring_window_bench times a TPU; none is attached")
    blocks = [int(b) for b in args.blocks.split(",") if b]
    rng = np.random.default_rng(0)

    pair = functools.partial(rw.ring_pair, window=WINDOW)

    def kernel(block):
        def run(q, k, v, rk, rv, slots, pos, valid):
            return rw.ring_window_kernel(q, k, v, rk, rv, slots, pos,
                                         valid, window=WINDOW,
                                         key_block=block)
        return run

    def looped(layer):
        """``CALLS`` walks of the six layers in one device loop: each
        layer's queries nudged by the last one's output, the rings and
        the positions carried."""
        @functools.partial(jax.jit, donate_argnums=(3,))
        def run(q, k, v, rings, slots, pos, valid):
            def body(_, carry):
                q, rings, pos = carry
                out = []
                for rk, rv in rings:
                    y, rk, rv = layer(q, k, v, rk, rv, slots, pos, valid)
                    q = q + (y * 1e-3).astype(q.dtype)
                    out.append((rk, rv))
                return q, out, pos + valid.sum(axis=1).astype(pos.dtype)
            return jax.lax.fori_loop(0, CALLS, body, (q, rings, pos))
        return run

    def timed(fn, q, k, v, slots, pos, valid, n=3):
        """ms a layer-call; the rings are donated and handed on."""
        held = jax.block_until_ready(
            fn(q, k, v, rings(), slots, pos, valid))[1]
        t0 = time.perf_counter()
        for _ in range(n):
            held = fn(q, k, v, held, slots, pos, valid)[1]
        jax.block_until_ready(held)
        return (time.perf_counter() - t0) / n / CALLS / LAYERS * 1e3

    def rings():
        return [tuple(0.5 * jax.random.normal(
            jax.random.PRNGKey(2 * i + j), (SLOTS, KH, L, D), jnp.bfloat16)
            for j in range(2)) for i in range(LAYERS)]

    cases = [("step", SLOTS, 1, int(r)) for r in args.riders.split(",")]
    cases.append(("chunk", 4, 256, 4))
    for name, B, T, riders in cases:
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.standard_normal((B, T, KH, D)),
                            jnp.bfloat16) for _ in range(2))
        live = np.zeros(B, bool)
        live[rng.permutation(B)[:riders]] = True
        if T == 1:
            slots = None
            pos = np.where(live, rng.integers(8192, 8705, B), 3000)
        else:
            slots = jnp.asarray(rng.permutation(SLOTS)[:B], jnp.int32)
            pos = 256 * rng.integers(16, 32, B)
        valid = jnp.asarray(live[:, None] & np.ones((B, T), bool))
        a = (q, k, v, slots, jnp.asarray(pos, jnp.int32), valid)
        windows = int(np.minimum(pos + 1, WINDOW)[live].sum())

        def line(impl, ms, **more):
            print(json.dumps({
                "case": name, "rows": B, "riders": riders, "impl": impl,
                "ms": round(ms, 4), **(
                    {"windows_GBps": round(
                        windows * 2 * KH * D * 2 / ms / 1e6, 1)}
                    if T == 1 else {}), **more}), flush=True)

        def once(layer):
            """One call's (y of the live rows, rings), inside a device
            loop of one turn as a step program has it (alone, with the
            rings pinned to HBM, the chip's compiler refuses a ring that
            is not donated, or one that is: PERF.md section 7)."""
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def run(rk, rv, turns):
                return jax.lax.fori_loop(
                    0, turns, lambda _, c: layer(q, k, v, *c[1:], *a[3:]),
                    (jnp.zeros_like(q), rk, rv))
            y, rk, rv = run(*rings()[0], jnp.int32(1))
            return (np.asarray(y, np.float32)[live],
                    np.asarray(rk, np.float32), np.asarray(rv, np.float32))
        want = once(pair)
        line("pair", timed(looped(pair), *a))
        for block in [None] + blocks:
            impl = {"kernel_keys_a_fold": block or [
                c1 - c0 for c0, c1 in rw.key_spans(H * T // (
                    1 if T == 1 else KH), L)]}
            try:
                got = once(kernel(block))
                ms = timed(looped(kernel(block)), *a)
            except Exception as e:      # a fold the chip has no room for
                print(json.dumps({"case": name, "impl": impl,
                                  "refused": str(e)[:200]}), flush=True)
                continue
            line(impl, ms, y_err=float(np.abs(got[0] - want[0]).max()),
                 y_of=float(np.abs(want[0]).max()),
                 rings_differ=int((got[1] != want[1]).sum()
                                  + (got[2] != want[2]).sum()))


if __name__ == "__main__":
    main()
