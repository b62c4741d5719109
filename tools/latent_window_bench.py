"""A prefill chunk's attention over latent pages on the local TPU chip:
the Pallas kernel (``ops/latent_window_attention.py``) against the
block loop XLA compiles (``ops/paged_attention.py``
``_paged_window_attention``), one layer's call at the serving cells'
shape: a [4, 256] chunk of 64 heads (axk1-d5.longdoc-sat) and of 32
(kimi-linear-d8.gen-sat) over entries 640 wide with values of 512,
pages of 64, every row's window ending at 512 / 1,024 / 4,352 / 8,192
tokens. One JSON line a reading: ms a layer-call and the share of the
chip's bfloat16 peak that the call's NEEDED operations come to (each
query against the keys it can see, both contractions; what a kernel
scores above the diagonal is not counted).

``--tokens 16,32`` times the kernel with query tiles of so many tokens
(x the heads: a tile's rows) instead of its own; ``--mixed`` adds one
call whose four rows end at 512 / 2,304 / 4,352 / 8,192, where the loop
walks every row to the longest's last block.
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

B, T, D, DV, PAGE, MAX_PAGES = 4, 256, 640, 512, 64, 256
WINDOWS = (512, 1024, 4352, 8192)
MIXED = (512, 2304, 4352, 8192)
PEAK = 197e12          # benchmarks/peaks.json, TPU v5e, bfloat16


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import latent_window_attention as lw
    from ray_tpu.ops import paged_attention as pa

    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="")
    ap.add_argument("--heads", default="64,32")
    ap.add_argument("--mixed", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_window_bench times a TPU; none is "
                         "attached")
    tiles = [int(t) for t in args.tokens.split(",") if t] or [None]
    block_pages = pa.paged_window_block_pages(PAGE, MAX_PAGES)
    scale = 192 ** -0.5
    rng = np.random.default_rng(0)
    # every row's pages scattered over the pool, as an allocator leaves
    # them; page 0 is the null page
    n_pages = 1 + B * MAX_PAGES
    ids = 1 + rng.permutation(B * MAX_PAGES).astype(np.int32)
    table = jnp.asarray(ids.reshape(B, MAX_PAGES))
    pages = jnp.asarray(
        rng.standard_normal((n_pages, PAGE, D)), jnp.bfloat16)

    def loop(q, pages, table, pos):
        with mock.patch.object(lw, "_on_one_tpu", lambda: False):
            return pa._paged_window_attention(
                q, pages, None, None, None, table, pos,
                softmax_scale=scale, value_dim=DV)

    def kernel(tokens):
        def run(q, pages, table, pos):
            return lw.latent_window_attention(
                q, pages, table, pos, softmax_scale=scale, value_dim=DV,
                block_pages=block_pages, tokens=tokens)
        return run

    def timed(fn, *a, n=10):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    for H in (int(h) for h in args.heads.split(",")):
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
        calls = [(w,) * B for w in WINDOWS]
        if args.mixed:
            calls.append(MIXED)
        for ends in calls:
            pos = jnp.asarray([e - T for e in ends], jnp.int32)
            seen = sum(e - T + t + 1 for e in ends for t in range(T))
            needed = seen * H * 2 * (D + DV)

            def line(impl, ms, **more):
                print(json.dumps({
                    "heads": H, "windows": sorted(set(ends)),
                    "impl": impl, "ms": round(ms, 4),
                    "peak_share": round(needed / ms / 1e-3 / PEAK, 4),
                    **more}), flush=True)

            xla = jax.jit(loop)
            want = np.asarray(xla(q, pages, table, pos), np.float32)
            line("loop", timed(xla, q, pages, table, pos))
            for tokens in tiles:
                tokens = tokens or lw.tile_tokens(T, H)
                fn = jax.jit(kernel(tokens))
                got = np.asarray(fn(q, pages, table, pos), np.float32)
                line({"kernel_tile_rows": tokens * H},
                     timed(fn, q, pages, table, pos),
                     err=float(np.abs(got - want).max()),
                     of=float(np.abs(want).max()))


if __name__ == "__main__":
    main()
