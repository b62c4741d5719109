"""GPT-2 in flax, designed mesh-first.

The reference has no model zoo (its Train wraps user torch models); this model
family exists because the build's north-star benchmarks (BASELINE.md: GPT-2
124M ≥40% MFU on v4) need TPU-optimal reference models. Design choices for
the MXU/HBM (see SURVEY.md §7 and the pallas guide):

- bfloat16 activations/weights by default, fp32 layernorm + logits + loss.
- All matmuls keep a trailing dim that is a multiple of 128 (MXU tiles).
- Attention dispatches to ray_tpu.ops (pallas flash attention on TPU,
  XLA einsum fallback elsewhere, ring attention when the mesh has a
  nontrivial `sequence` axis).
- Sharding is declared as logical-axis rules (gpt2_sharding_rules):
  Megatron-style tensor parallel + optional FSDP on the hidden axis, so the
  same model runs DP, FSDP, TP, SP and combinations by changing the mesh.
- `remat` checkpoints each block to trade FLOPs for HBM.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.mesh.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # padded to a multiple of 128 (MXU)
    n_ctx: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # Off by default: at 124M/1024ctx activations fit HBM and remat costs
    # ~13% MFU (measured 30.1% -> 26.1% on v5e). Enable for big models.
    remat: bool = False
    attention_impl: str = "auto"     # auto | xla | flash | ring

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def gpt2_124m(**overrides) -> GPT2Config:
    return GPT2Config(**overrides)


def gpt2_tiny(**overrides) -> GPT2Config:
    """Test-size config for CPU-mesh tests."""
    d = dict(vocab_size=256, n_ctx=64, n_embd=64, n_layer=2, n_head=4)
    d.update(overrides)
    return GPT2Config(**d)


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        B, T, C = x.shape
        qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, cfg.n_head, cfg.head_dim)

        q, k, v = heads(q), heads(k), heads(v)
        from ray_tpu.ops.attention import multi_head_attention
        y = multi_head_attention(q, k, v, causal=True,
                                 impl=cfg.attention_impl)
        y = y.reshape(B, T, C)
        y = nn.Dense(cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_fc")(x)
        h = nn.gelu(h)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        # LayerNorm in fp32 for stability, cast back for the matmuls.
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        x = x + CausalSelfAttention(cfg, name="attn")(
            h.astype(cfg.dtype), deterministic)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        x = x + MLP(cfg, name="mlp")(h.astype(cfg.dtype), deterministic)
        return x


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 return_features: bool = False):
        cfg = self.config
        B, T = input_ids.shape
        wte = self.param(
            "wte", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param(
            "wpe", nn.initializers.normal(0.01),
            (cfg.n_ctx, cfg.n_embd), cfg.param_dtype)
        x = wte[input_ids].astype(cfg.dtype) + \
            wpe[None, :T].astype(cfg.dtype)
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=(2,))
        for i in range(cfg.n_layer):
            x = block(cfg, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_features:
            # For the fused chunked loss: final hidden states; the tied
            # embedding is fetched from params by the caller.
            return x.astype(cfg.dtype)
        # Tied embeddings. bf16 operands on the MXU with fp32
        # accumulation — fp32 operands would halve matmul throughput for
        # ~30% of the model's FLOPs (vocab is 50k wide).
        logits = jax.lax.dot_general(
            x.astype(cfg.dtype), wte.astype(cfg.dtype),
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits


def cross_entropy_loss(logits, targets, ignore_index: int = -100):
    """Mean token cross-entropy in fp32."""
    mask = (targets != ignore_index)
    targets = jnp.where(mask, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


def linear_cross_entropy(features, wte, targets,
                         ignore_index: int = -100):
    """Tied-embedding projection + cross-entropy via logsumexp-gather.

    Keeps the [B, T, V] logits fp32 (needed for a stable softmax over
    50k classes) but never materializes log-softmax as a saved
    residual — backward recomputes softmax from the logits, so HBM
    sees one logits tensor instead of two. Measured on v5e (GPT-2-124M
    b24, a pre-PR-1 sweep): 46.9% MFU vs 42.5% for the
    log_softmax/take_along_axis formulation, and it beats the
    scan-chunked variant (fused_linear_cross_entropy) by 7+ points —
    XLA overlaps the one big projection better than a serialized scan.
    """
    # "loss_head" names this work in a device trace, forward and
    # backward (the transpose keeps the scope): metadata only
    with jax.named_scope("loss_head"):
        mask = (targets != ignore_index)
        tgt = jnp.where(mask, targets, 0)
        logits = jax.lax.dot_general(
            features, wte.astype(features.dtype),
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None],
                                   axis=-1)[..., 0]
        return ((lse - gold) * mask).sum() / jnp.maximum(mask.sum(), 1)


def fused_linear_cross_entropy(features, wte, targets,
                               chunk: int = 128,
                               ignore_index: int = -100):
    """Projection + softmax-xent over sequence chunks: never
    materializes the [B, T, vocab] fp32 logits (6 GiB at B=32/T=1024 —
    the single biggest HBM allocation of the naive path). Each scan
    step is rematerialized, so the backward recomputes one chunk's
    logits at a time instead of saving them all.

    features: [B, T, C] (bf16), wte: [V, C], targets: [B, T] int.
    """
    B, T, C = features.shape
    n_chunks = max(1, T // chunk)
    assert T % n_chunks == 0, f"seq {T} not divisible by chunk {chunk}"
    step = T // n_chunks
    xs = features.reshape(B, n_chunks, step, C).swapaxes(0, 1)
    ts = targets.reshape(B, n_chunks, step).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(xx, tt):
        logits = jax.lax.dot_general(
            xx, wte.astype(xx.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = (tt != ignore_index)
        tt = jnp.where(mask, tt, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tt[..., None], axis=-1)[..., 0]
        return -(ll * mask).sum(), mask.sum()

    def body(carry, inp):
        loss_sum, count = carry
        ls, cnt = chunk_loss(*inp)
        return (loss_sum + ls, count + cnt), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (xs, ts))
    return loss_sum / jnp.maximum(count, 1)


def gpt2_sharding_rules(fsdp: bool = True) -> ShardingRules:
    """Megatron-style TP + optional FSDP rules for flax GPT-2 params.

    Param paths look like: params/h_0/attn/c_attn/kernel.
    Column-parallel (output sharded on `tensor`): c_attn, c_fc.
    Row-parallel (input sharded on `tensor`): attn c_proj, mlp c_proj.
    Embeddings shard vocab/ctx over `tensor`; FSDP shards the remaining
    large dim over `fsdp`.
    """
    f = "fsdp" if fsdp else None
    # Embeddings are vocab/ctx-parallel with the embedding dim UNSHARDED:
    # sharding wte/wpe's trailing dim over `fsdp` forces the partitioner
    # to reshard batch-sharded (data, fsdp) activation gradients onto an
    # embedding-dim fsdp layout with a transposed mesh order — an
    # "involuntary full rematerialization" (replicate-then-reshard) in
    # the embedding backward on dp x fsdp x tp meshes. Sharding only the
    # vocab/ctx dim (over tensor AND fsdp) keeps dwte/dwpe a pure
    # scatter into row shards; the dryrun log is remat-warning-free.
    wte_spec = P(("tensor", "fsdp") if fsdp else "tensor", None)
    return ShardingRules([
        (r"attn/c_attn/kernel", P(f, "tensor")),
        (r"attn/c_proj/kernel", P("tensor", f)),
        (r"mlp/c_fc/kernel",    P(f, "tensor")),
        (r"mlp/c_proj/kernel",  P("tensor", f)),
        (r"attn/c_attn/bias",   P("tensor")),
        (r"mlp/c_fc/bias",      P("tensor")),
        (r"wte$",               wte_spec),
        (r"wpe$",               P(f, None)),
        # ln_*/scale|bias and remaining biases: replicate (default).
    ])


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def flops_per_token(cfg: GPT2Config, seq_len: Optional[int] = None) -> float:
    """Approximate training FLOPs/token (fwd+bwd ≈ 6N + attention)."""
    T = seq_len or cfg.n_ctx
    n_params = (cfg.vocab_size * cfg.n_embd + cfg.n_ctx * cfg.n_embd +
                cfg.n_layer * (12 * cfg.n_embd ** 2) +
                2 * cfg.n_embd)
    # 6 flops/param/token for fwd+bwd matmuls + attention term.
    attn = 12 * cfg.n_layer * cfg.n_embd * T
    return 6.0 * n_params + attn
