"""Attention ops with pluggable implementations.

impl:
- "xla":   einsum attention; XLA fuses mask+softmax well on TPU.
- "flash": pallas blockwise flash-attention kernel (compiled on TPU,
           interpreted elsewhere) — ray_tpu.ops.flash_attention.
- "ring":  sequence-parallel ring attention over the mesh `sequence` axis —
           ray_tpu.parallel.sequence (callers use it via shard_map).
- "auto":  flash on TPU when shapes allow, else xla.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _flash_on_mesh(q, k, v, causal: bool) -> jax.Array:
    """The flash kernel under whatever mesh is ambient (jax.set_mesh).

    GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh
    the kernel runs per shard inside a shard_map: batch over the data
    axes (the ones put_batch shards it on), heads over ``tensor`` when
    they divide. Attention is independent per (batch row, head), so no
    collective is needed. With no mesh, or one device, it is a plain
    call."""
    from ray_tpu.ops.flash_attention import flash_attention
    attn = functools.partial(flash_attention, causal=causal)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return attn(q, k, v)
    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("dcn", "data", "fsdp")
                  if sizes.get(a, 1) > 1)
    tensor = sizes.get("tensor", 1)
    heads = "tensor" if tensor > 1 and q.shape[2] % tensor == 0 \
        else None
    spec = P(batch or None, None, heads, None)
    return jax.shard_map(attn, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def xla_attention(q, k, v, causal: bool = True,
                  bias: Optional[jax.Array] = None,
                  precision: str = "default",
                  block_len: int = 1) -> jax.Array:
    """Reference attention, [B, T, H, D] layout. ``block_len`` > 1
    (with ``causal``): the BLOCK-causal mask of a model that decodes by
    blocks (ops/paged_attention.py ``_paged_window_attention``): the
    query at i sees the keys below ``(i // block_len + 1) * block_len``.

    precision="default": scores materialize in the input dtype (bf16 on
    TPU) and only the softmax runs in fp32 — halves the dominant HBM
    traffic of the [B,H,T,T] scores tensor (measured +3.8% MFU on GPT-2
    124M / v5e vs fp32 scores). "highest": fp32 scores throughout.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    if precision == "highest":
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores * scale
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        scores = scores * jnp.asarray(scale, scores.dtype)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        if block_len > 1:
            i = jnp.arange(Tk - Tq, Tk)[:, None] // block_len
            mask = jnp.arange(Tk)[None] // block_len <= i
        else:
            mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), k=Tk - Tq)
        scores = jnp.where(mask[None, None], scores,
                           jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def multi_head_attention(q, k, v, causal: bool = True,
                         impl: str = "auto",
                         bias: Optional[jax.Array] = None,
                         block_len: int = 1) -> jax.Array:
    """``block_len`` > 1: the block-causal mask (``xla_attention``),
    which the flash kernel does not have: ``"auto"`` is then XLA's."""
    if block_len > 1:
        if impl not in ("auto", "xla") or not causal:
            raise ValueError(
                f"a block-causal mask (block_len={block_len}) is served "
                f"by impl='xla' under causal=True only, got impl="
                f"{impl!r}, causal={causal}")
        return xla_attention(q, k, v, causal=True, bias=bias,
                             block_len=block_len)
    if impl == "auto":
        # Measured on v5e (PR 37, tools/flash_bench.py --shape, causal
        # fwd+bwd, H=12 D=64, ms flash | xla): at T=1024 B=24 3.30 |
        # 9.90 (XLA's [B,H,T,T] scores are pure HBM traffic in the
        # backward), B=8 1.06 | 3.36, B=4 0.60 | 0.88, B=2 and B=1 level
        # at the 0.55 ms a dispatch costs; T=2048 B=4 2.31 | 6.14, B=1
        # 0.68 | 0.86. At T>=8192 flash is the only option (scores
        # exhaust HBM).
        T, B = q.shape[1], q.shape[0]
        impl = "flash" if (_on_tpu() and bias is None and
                           T % 128 == 0 and
                           (T >= 2048 or (T >= 1024 and B >= 8))) \
            else "xla"
    if impl == "flash":
        # No fallback: a kernel that fails to lower must fail the
        # model, not quietly become its XLA reference.
        return _flash_on_mesh(q, k, v, causal)
    if impl == "ring":
        raise ValueError(
            "impl='ring' must be invoked through "
            "ray_tpu.parallel.sequence.ring_attention inside shard_map")
    return xla_attention(q, k, v, causal=causal, bias=bias)


def padding_bias(attention_mask):
    """[B, T] 1/0 mask -> additive [B, 1, 1, T] fp32 bias (0 keep,
    -1e30 drop) broadcast over heads and query positions. The shared
    mask convention for encoder models (bert, t5)."""
    return jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e30)
