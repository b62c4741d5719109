"""A decode step's attention over K/V or latent pages on the local TPU
chip: the Pallas kernel (``ops/paged_decode_attention.py``) against the
block loop XLA compiles (``ops/paged_attention.py``
``_paged_window_attention``), one layer-step each at the serving cells'
shapes and contexts:

  ouro     16 rows, 16 heads on 16 KV heads, contexts 256-352
  olmoe    32 rows, 16 on 16, contexts 256-352
  mistral  32 rows, 32 on 8, contexts 256-352, and 16-2,560 mixed with
           a quarter of the rows without a rider (the open loop's)
  mellum2  32 rows, 32 on 4, contexts 8,192-8,704, a quarter null
  solar    32 rows, 64 on 8, contexts 1,024-1,280
  axk1     32 rows, 64 heads over LATENT pages [64, 640] whose value is
           their first 512 columns, contexts 8,192-8,704, a quarter null
  kimi     128 rows, 32 heads over latent pages, contexts 1,024-2,048,
           three rows null
  sdar     128 rows of a BLOCK of 4 queries under the block mask, 32 on
           4, a table of 512 columns, contexts 1,024-2,048 (the block
           among them), three rows null
  olmo     96 rows, 32 on 32, a table of 16 columns, contexts 256-512
  phi4     64 rows, 64 on 16 head rows a page, a table of 48 columns,
           contexts 2,048-3,072, four rows null
  laguna   128 rows, 48 on 8, contexts 1,024-2,048, three rows null
  granite  112 rows, 32 on 8, a table of 36 columns, contexts
           1,024-2,048, nine rows null
  widest   32 rows, 32 on 4, Mellum 2's contexts under the widest table
           the rule hands the kernel (3,584 columns: 229,376 tokens a
           row): what the schedule costs where the table is mostly
           empty
  verify   32 rows of 5 queries under the causal mask (a speculative
           verify of four drafts), 32 on 8, contexts 256-352: a made-up
           shape no cell runs, which ``applies`` leaves to the loop
           (0.145 ms the kernel at its plan, 0.131 the loop; at 2-8
           pages a visit 0.118: PERF.md section 7)

One JSON line a reading: ms a layer-step (the mean of 50 calls inside
ONE device loop, each fed the one before it, so no dispatch of the
host's is in it) and the rate at which the riders' OWN K and V bytes
(a latent pool's entries as stored, 1,280 B each) went by. ``schedule``
is the kernel's visit schedule alone (``visit_schedule``: a step
program computes it once a step, its layers share it). ``--pages
1,2,4,8`` times the kernel at so many pages a visit beside its own
plan's, ``--spans 1,2`` each of those at so many pages a contraction
beside ``pages_per_dot``'s.
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
# a latent entry's width as stored, its value's, and A.X-K1's scale
LATENT_D, LATENT_DV, LATENT_SCALE = 640, 512, 0.1309
POOL_BYTES = 1 << 30              # of K, and of V
# name: (rows, heads, KV heads (None: latent pages), table columns,
# (lo, hi) contexts, rows without a rider[, queries a row, the mask's
# block length (1: causal)]); a row's queries are its context's last
SHAPES = {
    "ouro": (16, 16, 16, 64, (256, 352), 0),
    "olmoe": (32, 16, 16, 64, (256, 352), 0),
    "mistral": (32, 32, 8, 64, (256, 352), 0),
    "mistral-open": (32, 32, 8, 64, (16, 2560), 8),
    "mellum2": (32, 32, 4, 256, (8192, 8704), 8),
    "solar": (32, 64, 8, 64, (1024, 1280), 0),
    "axk1": (32, 64, None, 256, (8192, 8704), 8),
    "kimi": (128, 32, None, 64, (1024, 2048), 3),
    "sdar": (128, 32, 4, 512, (1024, 2048), 3, 4, 4),
    "olmo": (96, 32, 32, 16, (256, 512), 0),
    "phi4": (64, 64, 16, 48, (2048, 3072), 4),
    "laguna": (128, 48, 8, 64, (1024, 2048), 3),
    "granite": (112, 32, 8, 36, (1024, 2048), 9),
    "widest": (32, 32, 4, 3584, (8192, 8704), 8),
    "verify": (32, 32, 8, 64, (256, 352), 0, 5, 1),
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
    ap.add_argument("--spans", default="")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("paged_decode_bench times a TPU; none is "
                         "attached")
    sweep = [int(p) for p in args.pages.split(",") if p]
    spans = [int(p) for p in args.spans.split(",") if p]
    rng = np.random.default_rng(0)

    def looped(attend):
        """``CALLS`` calls in one device loop, each call's queries
        nudged by the last one's output."""
        @jax.jit
        def run(q, pk, pv, table, pos):
            def body(_, q):
                y = attend(q, pk, pv, table, pos)
                return q.at[..., :y.shape[-1]].add(
                    (y * 1e-3).astype(q.dtype))
            return jax.lax.fori_loop(0, CALLS, body, q)
        return run

    def timed(fn, *a, n=5):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n / CALLS * 1e3

    def how(pv):
        """What a latent pool's call says beside a K/V pool's."""
        return ({} if pv is not None else
                dict(softmax_scale=LATENT_SCALE, value_dim=LATENT_DV))

    for name in args.shapes.split(","):
        B, H, KH, max_pages, (lo, hi), idle, T, block = (
            SHAPES[name] + (1, 1))[:8]

        def loop(q, pk, pv, table, pos):
            with mock.patch.object(pd, "_on_one_tpu", lambda: False):
                return pa._paged_window_attention(
                    q, pk, pv, None, None, table, pos, block_len=block,
                    **how(pv))

        def kernel(pages, span=None):
            def run(q, pk, pv, table, pos):
                return pd._attend(
                    q, pk, pv, table, pos, pages=pages, span=span,
                    block_len=block,
                    **{"softmax_scale": D ** -0.5, **how(pv)})
            return run

        # every row's pages scattered over the pool, as an allocator
        # leaves them; page 0 is the null page. A block ends on a
        # block's edge
        contexts = rng.integers(lo, hi + 1, B) // block * block
        contexts[rng.permutation(B)[:idle]] = 0
        held = -(-contexts // PAGE)
        # a pool of a deployment's size and no smaller: one that fits
        # the chip's 128 MiB of fast memory is moved there whole, and
        # both forms then read at a rate no HBM has
        entry = (LATENT_D if KH is None else KH * D) * 2   # bytes a token
        n_pages = max(1 + int(held.sum()), POOL_BYTES // (PAGE * entry))
        ids = 1 + rng.permutation(n_pages - 1).astype(np.int32)
        table = np.zeros((B, max_pages), np.int32)
        at = 0
        for b in range(B):
            table[b, :held[b]] = ids[at:at + held[b]]
            at += held[b]
        # a row without a rider keeps a stale position
        pos = np.where(contexts > 0, contexts - T, 3000).astype(np.int32)
        if KH is None:
            pk, pv = 0.5 * jax.random.normal(
                jax.random.PRNGKey(0), (n_pages, PAGE, LATENT_D),
                jnp.bfloat16), None
            q = 0.3 * jnp.asarray(rng.standard_normal(
                (B, 1, H, LATENT_D)), jnp.bfloat16)
        else:
            pk, pv = (0.5 * jax.random.normal(
                jax.random.PRNGKey(k), (n_pages, PAGE, KH, D),
                jnp.bfloat16) for k in range(2))
            q = jnp.asarray(rng.standard_normal((B, T, H, D)),
                            jnp.bfloat16)
        a = (q, pk, pv, jnp.asarray(table), jnp.asarray(pos))
        own = int(contexts.sum()) * entry * (1 if KH is None else 2)
        riders = contexts > 0

        def line(impl, ms, **more):
            print(json.dumps({
                "shape": name, "rows": B, "riders": int(riders.sum()),
                "queries_a_row": T, "mask_block": block,
                "heads": H, "kv_heads": KH or "latent",
                "context_tokens": int(contexts.sum()), "impl": impl,
                "ms": round(ms, 4),
                "own_GBps": round(own / ms / 1e6, 1), **more}),
                flush=True)

        want = np.asarray(jax.jit(loop)(*a), np.float32)[riders]
        line("loop", timed(looped(loop), *a))
        plan = pd.pages_per_visit(T * H, PAGE, KH or 1, max_pages)
        for pages in [plan] + [p for p in sweep if p != plan]:
            dot = pd.pages_per_dot(PAGE * (KH or 1), pages)
            for span in [dot] + [x for x in spans
                                 if x != dot and pages % x == 0]:
                got = np.asarray(jax.jit(kernel(pages, span))(*a),
                                 np.float32)[riders]
                line({"kernel_pages_a_visit": pages, "pages_a_dot": span,
                      "plan": pages == plan and span == dot},
                     timed(looped(kernel(pages, span)), *a),
                     err=float(np.abs(got - want).max()),
                     of=float(np.abs(want).max()))

        def schedule(q, pk, pv, table, pos):
            # never true, and nothing the compiler can know
            nudge = (q[0, 0, 0, 0] > 1e30).astype(jnp.int32)
            out = pd.visit_schedule(table, pos + nudge, PAGE, plan, T,
                                    block)
            # a bit of every output: a product with zero is folded away
            # and the schedule with it (PR 47's line read 0.0055 ms so)
            bit = sum(o.sum() for o in out) & 1
            return jnp.full(q.shape, bit, q.dtype) * 1e-30
        line("schedule", timed(looped(schedule), *a))


if __name__ == "__main__":
    main()
