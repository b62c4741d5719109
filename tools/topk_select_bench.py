"""The selector's choice of ``index_topk`` entries on the local TPU chip:
the Pallas kernel (``ops/sparse_latent_attention.py`` ``topk_select``)
against the XLA form (``topk_mask`` over the narrowest quarter of the
table that holds the walk), one layer's choice as the step programs make
it (``_chosen_of_the_walk``: the mask in bfloat16 and the count a query)
at dsv32-d5.longdoc-sat's two shapes: a decode step's [32, 1, 16384]
scores and a prefill call's [4, 256, 16384], every row's sight ending at
4,096 / 8,192 / 8,704 / 12,288 / 16,384 positions (the cell's decode
steps end near 8,440 and its calls between 256 and 8,192). One JSON line
a reading: ms a layer's choice (the mean of ``--loop`` choices inside one
device loop, so no launch from the host is in it) and, for the kernel,
the key compares a second its passes come to (rows x columns passed x
passes x candidates a pass: the VPU's work, three vector operations a
compare).

``--bits 1,2,4`` times the kernel at those digit widths (bits of the
k-th key found a pass); ``--rows 16,32,128`` and ``--chunk 256,1024``
at other tiles of rows and other steps of columns than its own;
``--ties`` adds scores of small integers, where the k-th key has more
equals than places left and the kernel's second search runs.
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

K, PAGE, MAX_PAGES = 2048, 64, 256
SHAPES = {"decode_step": (32, 1), "chunk": (4, 256)}
ENDS = (4096, 8192, 8704, 12288, 16384)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import sparse_latent_attention as sp

    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", default="1,2,4")
    ap.add_argument("--rows", default="")
    ap.add_argument("--chunk", default="")
    ap.add_argument("--ends", default=",".join(str(e) for e in ENDS))
    ap.add_argument("--shapes", default="decode_step,chunk")
    ap.add_argument("--loop", type=int, default=20)
    ap.add_argument("--ties", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("topk_select_bench times a TPU; none is attached")

    def ints(text, default):
        return [int(t) for t in text.split(",") if t] or [default]
    S = PAGE * MAX_PAGES
    rng = np.random.default_rng(0)

    def looped(choose):
        """``--loop`` choices in one device loop, each fed the last
        one's count so that none is hoisted or dropped."""
        def run(scores, table, pos):
            def body(_, carry):
                scores, total = carry
                member, chosen = choose(scores, table, pos)
                total = total + jnp.sum(chosen) + member[0, 0, 0].astype(
                    jnp.int32)
                return scores.at[0, 0, S - 1].add(
                    (total == -1).astype(jnp.float32)), total
            return jax.lax.fori_loop(0, args.loop, body,
                                     (scores, jnp.int32(0)))[1]
        return jax.jit(run)

    def timed(fn, *a, n=3):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n / args.loop * 1e3

    def xla(scores, table, pos):
        with mock.patch.object(sp, "_on_one_tpu", lambda: False):
            return sp._chosen_of_the_walk(scores, table, pos, PAGE, K,
                                          jnp.bfloat16)[:2]

    def kernel(bits, rows, chunk):
        def choose(scores, table, pos):
            with mock.patch.object(sp, "_SELECT_DIGIT_BITS", bits), \
                    mock.patch.object(sp, "_SELECT_ROWS", rows), \
                    mock.patch.object(         # (room for a wider tile)
                        sp, "_SELECT_VMEM",
                        max(sp._SELECT_VMEM, rows[0] * S * 16)), \
                    mock.patch.object(sp, "_SELECT_CHUNK", chunk):
                member, chosen, by_kernel = sp._chosen_of_the_walk(
                    scores, table, pos, PAGE, K, jnp.bfloat16)
            assert by_kernel
            return member, chosen
        return choose

    for shape in args.shapes.split(","):
        B, T = SHAPES[shape]
        table = jnp.asarray(
            1 + rng.permutation(B * MAX_PAGES).reshape(B, MAX_PAGES),
            jnp.int32)
        for end in ints(args.ends, 0):
            pos = jnp.full((B,), end - T, jnp.int32)
            seen = np.arange(S)[None, None] <= (
                end - T + np.arange(T))[None, :, None]
            kinds = {"normal": rng.standard_normal((B, T, S))}
            if args.ties:
                kinds["small_integers"] = rng.integers(-3, 4, (B, T, S))
            for kind, values in kinds.items():
                scores = jnp.asarray(np.where(seen, values, -np.inf),
                                     jnp.float32)

                def line(impl, ms, **more):
                    print(json.dumps({
                        "shape": shape, "end": end, "scores": kind,
                        "impl": impl, "ms": round(ms, 5), **more}),
                        flush=True)
                want, count = jax.jit(xla)(scores, table, pos)
                want = np.asarray(want.astype(jnp.float32))
                line("xla", timed(looped(xla), scores, table, pos))
                for bits in ints(args.bits, sp._SELECT_DIGIT_BITS):
                    for rows in ints(args.rows, 0):
                        # (a tile of so many rows, or the kernel's own)
                        tiles = (rows, 16) if rows else sp._SELECT_ROWS
                        for chunk in ints(args.chunk, sp._SELECT_CHUNK):
                            fn = kernel(bits, tiles, chunk)
                            got, n = jax.jit(fn)(scores, table, pos)
                            same = bool((np.asarray(got.astype(
                                jnp.float32)) == want).all()
                                and (np.asarray(n) == np.asarray(
                                    count)).all())
                            ms = timed(looped(fn), scores, table, pos)
                            passed = B * T * (-(-end // chunk) * chunk)
                            compares = passed * (32 // bits) * (
                                2 ** bits - 1)
                            line({"kernel_bits": bits, "chunk": chunk,
                                  "rows": next(r for r in tiles
                                               if B * T % r == 0)},
                                 ms, same=same,
                                 gcompares_per_s=round(
                                     compares / ms / 1e6, 1))


if __name__ == "__main__":
    main()
