"""The delta rule under ONE decay a head (Gated DeltaNet) on the local
TPU chip, at olmo-hybrid-d16.sample-sat's shapes: 30 heads of 96 x 192.

``--chunks 16,32,64,128``: one prefill call's rows ([4, 256]) through
``ops/linear_attention.py`` ``kda_chunked`` at those chunk lengths, and
through the per-channel form fed the gate broadcast over the 96
channels at its own chunk of 16 (what the layer would cost without a
form of its own). Before them the chunk's SYSTEM alone, a ``solve`` line
a model: the unit lower-triangular systems of one layer-call (Olmo-
Hybrid's 4 chunks of [4, 30, 64, 64] against 192 columns, Solar-Open2's
and Kimi-Linear's 16 chunks of [4, 64 | 32, 16, 16] against 128) solved
a chunk at a time in a scan, as the chunk form meets them, by XLA's
``triangular_solve`` (the chunk form's until PR 50) and by
``unit_lower_inverse`` and one matmul; both return the whole solution,
so nothing is folded away. ``--slots 96 --free 3``: 8 chained one-token
steps in one program, as a decode dispatch runs them, over a state pool
of that many slots stored as declared ([slots, 30, 96, 192], which the chip
pads to 256 lanes: the ``jax.numpy`` form) and stored PACKED two heads
side by side ([slots, 15, 96, 384], whole lane tiles: the ``jax.numpy``
form and the kernel ``kda_step_packed_kernel`` at ``--unrolls`` groups a
loop step), and a copy of the packed pool in place (the bound of any
form). One JSON line a reading: ms a layer-call or a layer-step, and for
a step the riders' state bytes (one read, one write of what the
arithmetic needs) a second.
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
H, DK, DV = 30, 96, 192


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import linear_attention as la

    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", default="16,32,64,128")
    ap.add_argument("--slots", type=int, default=96)
    ap.add_argument("--free", type=int, default=3)
    ap.add_argument("--unrolls", default="1,3,5")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("gated_delta_bench times a TPU; none is attached")

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def inputs(lead, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        return (unit(jax.random.normal(ks[0], lead + (H, DK))) * DK ** -0.5,
                unit(jax.random.normal(ks[1], lead + (H, DK))),
                jax.random.normal(ks[2], lead + (H, DV)),
                -jnp.exp(jax.random.uniform(ks[3], lead + (H,),
                                            minval=-7.0, maxval=1.0)),
                2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (H,))))

    def timed(fn, *a, n=10, donated=None):
        out = fn(*a)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            if donated is not None:
                a = a[:donated] + (out[1],) + a[donated + 1:]
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3, out

    # ------------------------------------- a layer-call's systems alone
    B, T = 4, 256

    def solved_by(solve):
        """A layer-call's chunks one after another in a scan, as the
        chunk form meets them."""
        def run(below, rhs):
            with jax.default_matmul_precision("highest"):
                return jax.lax.scan(lambda _, xs: (None, solve(*xs)), None,
                                    (below, rhs))[1]
        return jax.jit(run)

    def xla_solve(below, rhs):
        return jax.lax.linalg.triangular_solve(
            jnp.eye(below.shape[-1], dtype=jnp.float32) + below, rhs,
            left_side=True, lower=True, unit_diagonal=True)

    def by_inverse(below, rhs):
        return jnp.einsum("...ij,...jk->...ik", la.unit_lower_inverse(below),
                          rhs)

    for model, heads, C, cols in (("olmo-hybrid", H, 64, DV),
                                  ("solar-open2", 64, 16, 128),
                                  ("kimi-linear", 32, 16, 128)):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        keys = unit(jax.random.normal(ks[0], (T // C, B, heads, C, 96)))
        below = jnp.tril(
            2.0 * jax.nn.sigmoid(jax.random.normal(
                ks[1], keys.shape[:-1]))[..., None]
            * jnp.einsum("...tc,...ic->...ti", keys, keys), -1)
        rhs = jax.random.normal(ks[2], keys.shape[:-1] + (cols,))
        ms_xla, want = timed(solved_by(xla_solve), below, rhs)
        ms_inv, got = timed(solved_by(by_inverse), below, rhs)
        print(json.dumps({
            "solve": list(below.shape), "columns": cols, "model": model,
            "ms_triangular_solve": round(ms_xla, 4),
            "ms_inverse_and_matmul": round(ms_inv, 4),
            "err": float(jnp.max(jnp.abs(got - want)))}), flush=True)

    # ------------------------------------------------ a prefill call
    q, k, v, g, beta = inputs((B, T), 1)
    state = jax.random.normal(jax.random.PRNGKey(2), (B, H, DK, DV))
    want = None
    for c in [int(x) for x in args.chunks.split(",") if x]:
        fn = jax.jit(lambda *a, c=c: la.kda_chunked(*a, chunk=c))
        ms, (o, s) = timed(fn, q, k, v, g, beta, state)
        want = (o, s) if want is None else want
        print(json.dumps({
            "call": [B, T, H, DK, DV], "form": "one gate a head",
            "chunk": c, "ms": round(ms, 4),
            "err_o": float(jnp.max(jnp.abs(o - want[0]))),
            "err_s": float(jnp.max(jnp.abs(s - want[1])))}), flush=True)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    fn = jax.jit(la.kda_chunked)
    ms, (o, s) = timed(fn, q, k, v, wide, beta, state)
    print(json.dumps({
        "call": [B, T, H, DK, DV], "form": "a gate a channel, fed the "
        "broadcast", "chunk": la._CHUNK_PER_CHANNEL, "ms": round(ms, 4),
        "err_o": float(jnp.max(jnp.abs(o - want[0]))) if want else None}),
        flush=True)

    # ------------------------------------------------- a decode step
    S = args.slots
    xs = inputs((STEPS, S), 3)
    state = jax.random.normal(jax.random.PRNGKey(4), (S, H, DK, DV))
    free = np.linspace(1, S - 2, args.free).astype(int)
    valid = jnp.ones((S,), bool).at[free].set(False)
    fresh = (jnp.arange(S) % 17 == 5) & valid
    riders = int(valid.sum())
    moved = 2 * riders * H * DK * DV * 4

    def chain(step):
        def run(xs, state, valid, fresh):
            def body(s, x):
                o, s = step(*x, s, valid, fresh)
                return s, o
            s, o = jax.lax.scan(body, state, xs)
            return o, s
        return jax.jit(run, donate_argnums=1)

    def line(impl, ms, **more):
        print(json.dumps({
            "state": impl, "slots": S, "riders": riders,
            "ms": round(ms / STEPS, 4),
            "riders_GBps": round(moved / (ms / STEPS) / 1e6, 1), **more}),
            flush=True)

    fn = chain(la.kda_step)
    want_o, want_s = fn(xs, state + 0, valid, fresh)
    ms, _ = timed(fn, xs, state + 0, valid, fresh, donated=1)
    line([S, H, DK, DV], ms, form="jax.numpy")
    packed = la.pack_heads(state, 2)
    rows = np.asarray(valid)
    forms = [("jax.numpy", lambda *a: la._kda_step_packed(
        *a[:3], a[3][..., None], *a[4:]))]
    forms += [(f"kernel, {u} groups a loop step", functools.partial(
        la.kda_step_packed_kernel, unroll=u))
        for u in (int(x) for x in args.unrolls.split(",") if x)]
    for form, step in forms:
        fn = chain(step)
        o, s = fn(xs, packed + 0, valid, fresh)
        err_o = float(jnp.max(jnp.abs(o - want_o)[:, rows]))
        err_s = float(jnp.max(jnp.abs(la.unpack_heads(s, 2) - want_s)))
        ms, _ = timed(fn, xs, packed + 0, valid, fresh, donated=1)
        line(list(packed.shape), ms, form=form, err_o=err_o, err_s=err_s)
    # the bound of any form on this chip: the packed pool copied in place
    copy = jax.jit(lambda s: s + 1.0, donate_argnums=0)
    ms, _ = timed(lambda s: (None, copy(s)), packed + 0, donated=0)
    print(json.dumps({"state": list(packed.shape), "form": "a copy in place "
                      "of every slot", "ms": round(ms, 4), "GBps": round(
                          2 * packed.size * 4 / ms / 1e6, 1)}), flush=True)


if __name__ == "__main__":
    main()
