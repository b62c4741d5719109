"""EQuARX-style int8 quantized psum (two-sided scale exchange).

EQuARX (arXiv:2506.17615, PAPERS.md) shows that all-reduce traffic —
the tensor-parallel serving stack's only cross-device KV-adjacent
cost — tolerates aggressive in-flight quantization at negligible
quality loss. This module is the GROUNDWORK half of the ROADMAP item
"Quantized KV cache + quantized collectives": a standalone shard_map
collective that moves int8 payloads instead of fp, with the absmax
scales exchanged ALONGSIDE the payloads (two-sided: every rank both
sends its own (q, scale) pair and dequantizes every peer's with the
peer's scale — no rank ever applies its local scale to remote bytes).

NOT wired into the serving engine: the engine's two per-layer psums
(row-parallel wo/w2 reductions) stay exact until an engine-level A/B
proves the accept-rate/parity budget tolerates quantized reductions.
Wiring it in is a one-line swap at the `psum` call sites precisely
because this op is already a drop-in shard_map collective.

Numerics: symmetric absmax int8 (q = round(x * 127 / amax), value =
q * amax / 127), one fp32 scale per row of the LAST axis — the same
code the int8 KV pages use (ops/paged_attention.py), so both halves
of the ROADMAP item share one quantization contract. Error per
element is bounded by n_ranks * (amax_r / 254) summed over ranks'
scales; the unit tests assert that bound, not a loose rtol.

Byte math: a bf16 psum moves 2 bytes/element each way; this moves
1 byte/element plus 4 bytes per row of the last axis — ~2x less for
any realistic hidden dim (the scale amortizes over >= 128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_QMAX = 127.0


def quantize_rowwise(x):
    """Symmetric absmax int8 over the LAST axis: returns (q int8,
    scale fp32 with a keepdims 1 in the last axis). All-zero rows get
    scale 0 and quantize to 0 — the guarded divide is exact for them,
    not an approximation."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / _QMAX
    inv = jnp.where(scale > 0.0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xf * inv), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_rowwise(q, scale):
    return q.astype(jnp.float32) * scale


def quantized_psum(x, axis_name: str):
    """Drop-in `jax.lax.psum(x, axis_name)` with int8 wire format.
    Call INSIDE shard_map. Returns the (approximate) full sum in
    x.dtype on every rank.

    Each rank quantizes its local partial, all-gathers the int8
    payloads AND their scales (the two-sided exchange), then
    dequantizes each peer contribution with that peer's own scale
    before summing in fp32. Accumulation is fp32 regardless of
    x.dtype so the only loss is the per-rank rounding, never the
    reduction order.
    """
    q, scale = quantize_rowwise(x)
    qg = jax.lax.all_gather(q, axis_name)          # [n, ...] int8
    sg = jax.lax.all_gather(scale, axis_name)      # [n, ..., 1] fp32
    out = jnp.sum(dequantize_rowwise(qg, sg), axis=0)
    return out.astype(x.dtype)


def quantized_psum_error_bound(x_shards):
    """Worst-case |quantized_psum - psum| per element: each rank's
    rounding error is <= scale_r / 2 = amax_r / 254. Host-side helper
    for tests and for sizing the engine-integration tolerance budget;
    x_shards is the per-rank stacked array [n, ...]."""
    import numpy as np
    amax = np.max(np.abs(np.asarray(x_shards, np.float32)), axis=-1,
                  keepdims=True)
    return np.sum(amax / (2.0 * _QMAX), axis=0)


def quantized_psum_sharded(x, mesh: Mesh, axis: str = "tensor"):
    """Outside-jit convenience wrapper for tests/benchmarks: shard x
    over ``axis`` along its FIRST dimension and quantized-psum the
    shards back to a replicated sum."""
    n = mesh.shape[axis]
    if x.shape[0] % n:
        raise ValueError(
            f"leading dim {x.shape[0]} does not shard over "
            f"{axis}={n}")
    spec = P(axis, *([None] * (x.ndim - 1)))
    x = jax.device_put(x, NamedSharding(mesh, spec))

    # check_vma=False: the output IS replicated (every rank computes
    # the identical gathered sum) but the static checker cannot
    # infer that through all_gather-then-sum
    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=spec, out_specs=P(),
        check_vma=False)
    def run(xs):
        # sum over the local shard first so each rank contributes ONE
        # quantized partial (the EQuARX shape), then exchange
        local = jnp.sum(xs, axis=0)
        return quantized_psum(local, axis)

    return run(x)
