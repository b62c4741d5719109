"""A prefill chunk's selective scan on the local TPU chip: the Pallas
kernel (``ops/selective_scan.py`` ``selective_scan``) against the
``lax.scan`` it replaces (``ssm_chunked`` with the module's rule steered
off), one state-space layer's call as ``phi4-mini-flash.reason-sat``'s
prefill programs make it: four rows of 256, 128 and 64 positions over
5,120 channels of 16 states, ``u``, ``B`` and ``C`` in bfloat16, ``delta``
and the state float32, one row cut short and one a dummy. One JSON line
a reading: ms a layer-call (the mean of ``--loop`` calls inside one
device loop, each fed the last one's state, so no launch from the host
is in it), the GB/s its operands and results come to (what
``ssm_prefill_scan_roofline`` divides by the HBM's peak) and the
vector-register steps a microsecond (a step: one float32 register of
the state, 1,024 elements, advanced one position: six vector operations
and one ``exp``), and whether the kernel's answer is the loop's.

``--beside N`` times each form ALONE and BESIDE A STREAM: every
layer-call is followed, inside the same loop, by ``N`` products of four
positions with ``N`` different ``[640, 10240]`` bfloat16 matrices (what
the call's narrowed layers do since PR 61: the compiler fetches the next
matrices while the scan runs). ``stream_ms`` is those products alone,
``exposed_ms`` the layer-call's share of the two together (both less
the stream): the loop's grows where the kernel's does not if the loop is
bound by the latency of its trips through HBM (PERF.md section 7, after
PR 61 (c)).

Alone, the loop is flattered: its 45 MB of operands and its carry fit
the chip's fast memory, which a whole model's call never leaves them.
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

ROWS, CHANNELS, STATES = 4, 5120, 16


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import selective_scan as ss

    ap = argparse.ArgumentParser()
    ap.add_argument("--positions", default="256,128,64")
    ap.add_argument("--loop", type=int, default=10)
    ap.add_argument("--beside", type=int, default=0)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("selective_scan_bench times a TPU; none is attached")
    B, C, N = ROWS, CHANNELS, STATES
    rng = np.random.default_rng(0)
    f32, bf16 = jnp.float32, jnp.bfloat16

    def normal(*shape, dtype=f32):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def loop(*a):
        with mock.patch.object(ss, "_on_one_tpu", lambda: False):
            return ss.ssm_chunked(*a)

    def kernel(*a):
        assert ss.serves(a[0].shape[1], a[6])
        return ss.ssm_chunked(*a)

    def looped(scan, stream):
        """``--loop`` layer-calls in one device loop, each from the last
        one's state; ``stream``: the narrowed layers' products after
        each (None: the scan alone), their sum fed on so that none is
        dropped."""
        def run(u, delta, A, Bm, Cm, D, state, valid, x, ws):
            def body(_, carry):
                state, acc = carry
                if scan is not None:
                    y, state = scan(u, delta, A, Bm, Cm, D, state, valid)
                    acc = acc + y[0, 0, 0]
                if stream:
                    xi = x + acc.astype(x.dtype)
                    for w in ws:
                        acc = acc + jnp.dot(
                            xi, w, preferred_element_type=f32)[0, 0]
                return state, acc
            return jax.lax.fori_loop(0, args.loop, body,
                                     (state, jnp.float32(0)))
        return jax.jit(run)

    def timed(fn, *a, n=3):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n / args.loop * 1e3

    x = normal(4, 640, dtype=bf16)
    ws = [normal(640, 10240, dtype=bf16) for _ in range(args.beside)]
    for T in (int(t) for t in args.positions.split(",")):
        u, Bm, Cm = (normal(B, T, C, dtype=bf16),
                     normal(B, T, N, dtype=bf16), normal(B, T, N, dtype=bf16))
        delta = jax.nn.softplus(normal(B, T, C) - 4.0)
        A, D, state = -jnp.exp(normal(N, C)), normal(C), normal(B, N, C)
        valid = jnp.arange(T)[None] < jnp.asarray([T, T, T - 13, 0])[:, None]
        a = (u, delta, A, Bm, Cm, D, state, valid)
        want = jax.jit(loop)(*a)
        got = jax.jit(kernel)(*a)
        same = all(bool(jnp.allclose(g, w, rtol=1e-5, atol=1e-5))
                   for g, w in zip(got, want))
        moved = (u.nbytes + delta.nbytes + Bm.nbytes + Cm.nbytes
                 + A.nbytes + D.nbytes + 2 * state.nbytes + 4 * B * T * C)
        steps = B * T * N * C // 1024
        stream_ms = timed(looped(None, True), *a, x, ws) if ws else 0.0
        for impl, scan in (("lax_scan", loop), ("kernel", kernel)):
            line = {"positions": T, "impl": impl}
            if impl == "kernel":
                line["same"] = same
            ms = timed(looped(scan, False), *a, x, ws)
            line.update(ms=round(ms, 4), gb_per_s=round(moved / ms / 1e6, 1),
                        register_steps_per_us=round(steps / ms / 1e3, 1))
            if ws:
                both = timed(looped(scan, True), *a, x, ws)
                line.update(beside=args.beside,
                            stream_ms=round(stream_ms, 4),
                            exposed_ms=round(both - stream_ms, 4))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
