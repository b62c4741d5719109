"""Mixtral-family sparse-MoE transformer: Llama blocks with the FFN
replaced by a top-k routed mixture of SwiGLU experts. OLMoE is the same
block with three declared differences, each a field of the config:
the router's rule (``norm_topk_prob``), an RMSNorm over the projected
query and key (``qk_norm``) and an untied output head
(``tie_word_embeddings``).

The mixture is DROPLESS and its work follows the routing: the (token,
expert) pairs are sorted by expert, each expert's rows go through its
three matrices in one grouped matmul (``jax.lax.ragged_dot``: static
shapes, the group sizes are values), and every pair comes back to its
token weighted by its gate. No capacity, no overflow, at any call size.
Expert weights carry the `expert` axis for EP sharding.
Attention/norm/RoPE and the KV-cache decode path are shared with
models/llama.py, so `generate` / `generate_stream` work unchanged."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.mesh.sharding import ShardingRules
from ray_tpu.models.kv_cache import PagedKVLayer
from ray_tpu.ops.grouped_matmul import grouped_matmul, visits
from ray_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                  attention_param_count, block_forward,
                                  embedding_param_count,
                                  transformer_forward)


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336        # per-expert SwiGLU inner dim
    num_experts: int = 8
    num_experts_per_tok: int = 2   # top-k routing (Mixtral: 2)
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"
    # True (Mixtral): the k chosen gates are renormalised to sum to 1
    # (a softmax over the chosen logits). False (OLMoE): the softmax
    # over ALL experts' logits, the k largest as they are.
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = True   # as LlamaConfig's
    qk_norm: bool = False              # as LlamaConfig's
    # What MoEFeedForward reads beyond the above (its docstring says
    # what each means); the defaults are Mixtral's and OLMoE's.
    router: str = "softmax"            # or "sigmoid_bias", "sigmoid"
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None   # (lo, n) or all

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def attention_config(self) -> LlamaConfig:
        """The attention stack is exactly Llama's; reuse its module
        with a mirrored config."""
        return LlamaConfig(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            hidden_dim=self.hidden_dim, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=self.remat,
            attention_impl=self.attention_impl,
            tie_word_embeddings=self.tie_word_embeddings,
            qk_norm=self.qk_norm)

    # what serving asks of a family's config (models/llama.py)
    @property
    def model_class(self):
        return Mixtral

    @property
    def serving_rules(self) -> ShardingRules:
        return mixtral_sharding_rules(fsdp=False)

    def tp_validate(self, tp: int, ep: int = 1) -> None:
        mixtral_tp_validate(self, tp, ep)


def mixtral_8x7b(**overrides) -> MixtralConfig:
    return MixtralConfig(**overrides)


def mixtral_tiny(**overrides) -> MixtralConfig:
    """Test-size config (GQA + 4 experts top-2) for CPU-mesh tests."""
    d = dict(vocab_size=256, max_seq_len=128, dim=64, n_layers=2,
             n_heads=4, n_kv_heads=2, hidden_dim=128, num_experts=4,
             num_experts_per_tok=2)
    d.update(overrides)
    return MixtralConfig(**d)


def olmoe_1b_7b(**overrides) -> MixtralConfig:
    """OLMoE-1B-7B-0125 as published (allenai, config.json): 16
    layers, 64 experts of 1,024, 8 a token, gates not renormalised,
    query/key norm, untied head."""
    d = dict(vocab_size=50304, max_seq_len=4096, dim=2048, n_layers=16,
             n_heads=16, n_kv_heads=16, hidden_dim=1024, num_experts=64,
             num_experts_per_tok=8, rope_theta=10000.0, norm_eps=1e-5,
             norm_topk_prob=False, tie_word_embeddings=False,
             qk_norm=True)
    d.update(overrides)
    return MixtralConfig(**d)


def olmoe_tiny(**overrides) -> MixtralConfig:
    """Test-size OLMoE (8 experts, 3 a token) for the CPU tests."""
    d = dict(vocab_size=256, max_seq_len=128, dim=64, n_layers=2,
             n_heads=4, n_kv_heads=4, hidden_dim=32, num_experts=8,
             num_experts_per_tok=3, rope_theta=10000.0,
             norm_topk_prob=False, tie_word_embeddings=False,
             qk_norm=True)
    d.update(overrides)
    return MixtralConfig(**d)


# The collection a mixture sows what its router chose into, for a
# caller that asks for it (``mutable=[MOE_STATS]``): each layer's
# ``topk`` [B, T, K] expert indices. The serving engine's programs
# reduce it to counters over their live rows (moe_stats_vector).
MOE_STATS = "moe_stats"


ROUTERS = ("softmax", "sigmoid_bias", "sigmoid")


def experts_held(cfg) -> Tuple[int, int]:
    """(lo, n): the experts whose weights this mixture holds, lo ..
    lo + n of the router's ``num_experts``; all of them where the
    config names no share."""
    return cfg.experts_held or (0, cfg.num_experts)


class MoEFeedForward(nn.Module):
    """Top-k routed SwiGLU experts, dropless (the module docstring says
    how). ``live`` [B] bool, where the caller knows it (a paged call:
    rows of free slots ride every decode call), marks the rows that
    carry a request: the others are given no expert, so they stream no
    expert's weights, and their output is zero.

    What the config declares, each as a field:

    - ``router``: ``"softmax"`` (Mixtral, OLMoE: softmax over all
      experts' logits, the k largest, renormalised or not by
      ``norm_topk_prob``), ``"sigmoid_bias"`` (the DeepSeek-V3 /
      GLM-4.5 rule: s = sigmoid(logits); the k experts with the
      largest s + b, b a stored bias a expert that takes part in the
      CHOICE only, inside ``group_limited``'s groups where ``n_group``;
      gates s_chosen, divided by their sum where ``norm_topk_prob``) or
      ``"sigmoid"`` (no stored bias), times ``routed_scaling_factor``;
    - ``n_shared_experts``: a SwiGLU of that many experts' width which
      every token passes, added to the routed result;
    - ``experts_held`` (lo, n): THE CHIP'S SHARE under expert
      parallelism. The router keeps its published width and k; the
      weights are [n, D, F], experts lo .. lo + n; a pair whose expert
      is not held gets the group "none", exactly as a free slot's row
      does (it sorts behind every group and streams nothing), and adds
      nothing. The gates are normalised over all k chosen, held or
      not: the shares' results add up to the whole layer's
      (tests/test_solar_open2.py). The exchange that would bring other
      chips' tokens here is not this module's."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.config
        B, T, D = x.shape
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        lo, held = experts_held(cfg)
        router = cfg.router
        if router not in ROUTERS:
            raise ValueError(f"router={router!r} is not one of {ROUTERS}")
        N = B * T
        tokens = x.reshape(N, D)

        with jax.named_scope("moe_router"):
            router_w = self.param("router", nn.initializers.normal(0.02),
                                  (D, E), jnp.float32)
            logits = tokens.astype(jnp.float32) @ router_w    # [N, E]
            if router == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)
                # the k largest probabilities are the k largest logits
                gates, topk_idx = jax.lax.top_k(probs, K)     # [N, K]
            elif router == "sigmoid_bias":
                bias = self.param("router_bias", nn.initializers.zeros,
                                  (E,), jnp.float32)
                probs = jax.nn.sigmoid(logits)
                _, topk_idx = jax.lax.top_k(group_limited(cfg, probs + bias), K)
                gates = jnp.take_along_axis(probs, topk_idx, axis=1)
            else:
                probs = jax.nn.sigmoid(logits)
                gates, topk_idx = jax.lax.top_k(probs, K)
            if cfg.norm_topk_prob:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
            if cfg.routed_scaling_factor != 1.0:
                gates = gates * cfg.routed_scaling_factor
        if not self.is_initializing():      # no weights: not in init's tree
            self.sow(MOE_STATS, "topk", topk_idx.reshape(B, T, K),
                     reduce_fn=lambda _prev, new: new,
                     init_fn=lambda: None)

        pd = cfg.param_dtype
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (held, D, cfg.hidden_dim), pd).astype(cfg.dtype)
        w3 = self.param("w3", nn.initializers.lecun_normal(),
                        (held, D, cfg.hidden_dim), pd).astype(cfg.dtype)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (held, cfg.hidden_dim, D), pd).astype(cfg.dtype)

        with jax.named_scope("moe_dispatch"):
            # Sort the N*K pairs by expert: each held expert's rows
            # become one contiguous group. A pair that carries no
            # request, or whose expert is not held here, gets the group
            # "held": it sorts behind every group and belongs to none.
            pair_expert = topk_idx.reshape(N * K)
            away = None
            if (lo, held) != (0, E):
                pair_expert = pair_expert - lo
                away = (pair_expert < 0) | (pair_expert >= held)
                pair_expert = jnp.where(away, held, pair_expert)
            if live is not None:
                token_live = jnp.repeat(live, T)              # [N]
                pair_expert = jnp.where(jnp.repeat(token_live, K),
                                        pair_expert, held)
            order = jnp.argsort(pair_expert, stable=True)     # [N*K]
            group_sizes = jnp.sum(
                pair_expert[:, None] == jnp.arange(held)[None, :],
                axis=0, dtype=jnp.int32)                      # [held]
            rows = tokens.astype(cfg.dtype)[order // K]       # [N*K, D]
        with jax.named_scope("moe_experts"):
            h = nn.silu(grouped_matmul(rows, w1, group_sizes)) * \
                grouped_matmul(rows, w3, group_sizes)
            out_rows = grouped_matmul(h, w2, group_sizes)     # [N*K, D]
        with jax.named_scope("moe_combine"):
            # back to pair order (the inverse permutation), then each
            # token's K rows weighted by their gates
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(N * K, dtype=order.dtype))
            pairs = out_rows[back].reshape(N, K, D)
            # rows past the last group are whatever the grouped
            # matmul left there
            if live is not None:
                pairs = jnp.where(token_live[:, None, None], pairs, 0)
            if away is not None:
                pairs = jnp.where(away.reshape(N, K, 1), 0, pairs)
            out = jnp.einsum("nkd,nk->nd", pairs.astype(jnp.float32),
                             gates).astype(cfg.dtype)

        n_shared = cfg.n_shared_experts
        if n_shared:
            with jax.named_scope("moe_shared"):
                Fs = n_shared * cfg.hidden_dim
                s1, s3 = (self.param(name, nn.initializers.lecun_normal(),
                                     (D, Fs), pd).astype(cfg.dtype)
                          for name in ("shared_w1", "shared_w3"))
                s2 = self.param("shared_w2", nn.initializers.lecun_normal(),
                                (Fs, D), pd).astype(cfg.dtype)
                t = tokens.astype(cfg.dtype)
                out = out + (nn.silu(t @ s1) * (t @ s3)) @ s2

        if router == "softmax":
            # Load-balance auxiliary (Switch eq. 4 over top-1 choice);
            # a biased router is balanced through its bias instead.
            top1 = jax.nn.one_hot(topk_idx[:, 0], E, dtype=jnp.float32)
            frac_tokens = jnp.mean(top1, axis=0)
            frac_probs = jnp.mean(probs, axis=0)
            self.sow("losses", "load_balance",
                     E * jnp.sum(frac_tokens * frac_probs))
        return out.reshape(B, T, D)


def moe_stats_vector(stats, live, num_experts: int, held=None):
    """What the router chose in one forward pass, over live tokens
    only, as one int32 vector [E + 4]: each expert's pairs summed over
    the layers, then the distinct experts touched summed over the
    layers, the fullest expert's pairs summed over the layers, the
    number of layers (what to divide the sums by), and the (row tile,
    expert) visits the grouped matmul's grid makes over those pairs
    summed over the layers (ops/grouped_matmul.py ``visits``: less the
    experts touched, the visits that multiply out of a matrix already
    fetched). ``stats`` is the ``MOE_STATS`` collection of an apply,
    ``live`` [B, T] bool.

    A mixture that holds a share ``held`` = (lo, n) of its router's
    experts (``experts_held``) counts THOSE, and its vector [n + 5]
    ends with one number more: the pairs the router made of the live
    tokens, held or not. A mixture that holds every expert routes
    exactly the pairs it counts, and its vector has no such entry."""
    lo, n = held or (0, num_experts)
    experts = jnp.arange(n) if held is None else lo + jnp.arange(n)
    counts = jnp.zeros((n,), jnp.int32)
    touched = fullest = layers = tiles = routed = jnp.int32(0)
    for topk in jax.tree_util.tree_leaves(stats):             # [B, T, K]
        hit = (topk[..., None] == experts) & \
            live[:, :, None, None]
        c = jnp.sum(hit, axis=(0, 1, 2), dtype=jnp.int32)     # [n]
        counts = counts + c
        touched = touched + jnp.sum(c > 0, dtype=jnp.int32)
        fullest = fullest + jnp.max(c)
        layers = layers + 1
        tiles = tiles + visits(c, topk.size)
        if held is not None:
            routed = routed + topk.shape[-1] * jnp.sum(
                live, dtype=jnp.int32)
    tail = [touched, fullest, layers, tiles] + (
        [] if held is None else [routed])
    return jnp.concatenate([counts, jnp.stack(tail)])


@dataclasses.dataclass(frozen=True)
class MoEStats:
    """``moe_stats_vector``'s result as a SECTION of the vector a step
    program returns (serve/step_programs.py concatenates its model's
    sections, serve/round_accounts.py splits them by ``len``), and the
    host's reading of it: under ``prefix``, and for the decode
    dispatches' part ``prefix + "decode_"``, the ``round`` event reports
    ``names``. ``pairs`` is the sum of the ``head``, the leading entries
    one a counted expert, whose running totals feed ``load_report``;
    the rest is the scalar tail in its order. A mixture that holds
    every expert has no last entry and reports ``pairs`` under both
    names."""
    num_experts: int
    held: Optional[Tuple[int, int]] = None
    collection = MOE_STATS
    prefix = "moe_"
    names = ("pairs", "experts_touched", "load_max", "layer_steps",
             "tile_visits", "pairs_routed")

    @property
    def head(self) -> int:
        return self.num_experts if self.held is None else self.held[1]

    def __len__(self) -> int:
        return self.head + len(self.names) - 2 + (self.held is not None)

    def reduce(self, sown, live):
        return moe_stats_vector(sown, live, self.num_experts, self.held)

    def read(self, vec) -> Dict[str, int]:
        """A host copy of the vector as ``{name: count}``."""
        sums = dict(zip(self.names, (int(vec[:self.head].sum()),
                                     *(int(x) for x in vec[self.head:]))))
        sums.setdefault(self.names[-1], sums["pairs"])
        return sums

    def load_report(self, pairs) -> Dict[str, Any]:
        """The routing so far, from the head's running totals: each
        expert's share of the (token, expert) pairs of live rows, and
        their number."""
        total = int(pairs.sum())
        return {"moe_pairs_total": total,
                "moe_expert_share": (pairs / max(1, total)).tolist()}


def stats_sections(cfg) -> tuple:
    """The sections of the counter vector a model's step programs
    return, in its order: the mixture's where the config has experts,
    then those the config names itself (``stats_sections``, declared
    beside the code that sows their collection: models/axk1.py). ()
    for a model that counts nothing on the device."""
    mixture = ((MoEStats(cfg.num_experts, cfg.experts_held),)
               if getattr(cfg, "num_experts", 0) else ())
    return mixture + tuple(getattr(cfg, "stats_sections", ()))


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        moe = MoEFeedForward(cfg, name="moe")
        live = None
        if isinstance(kv_cache, PagedKVLayer):
            # a row whose page-table row is the null row carries no
            # request (ops/paged_attention.py _paged_window_attention's rule)
            live = kv_cache.page_table[:, 0] != 0
        return block_forward(
            cfg, LlamaAttention(cfg.attention_config(), name="attention"),
            lambda h: moe(h, live),
            x, freqs, positions, kv_cache, cache_len)


class Mixtral(nn.Module):
    """Call signature mirrors models/llama.py Llama — enforced by
    construction: both families run the shared transformer_forward, so
    the decode paths (generate / generate_stream, KV caches) apply
    unchanged."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(self, self.config,
                                   lambda i: MixtralBlock,
                                   input_ids, kv_caches, cache_len,
                                   logits_at=logits_at)


def mixtral_sharding_rules(fsdp: bool = True) -> ShardingRules:
    """Llama TP/FSDP rules + expert-parallel rules for the MoE params:
    expert tensors shard their leading E dim over `expert` and their
    inner dim over `tensor`."""
    f = "fsdp" if fsdp else None
    return ShardingRules([
        (r"attention/w[qkv]/kernel", P(f, "tensor")),
        (r"attention/wo/kernel",     P("tensor", f)),
        (r"attention/[qk]_norm/scale", P("tensor")),
        (r"moe/w[13]$",              P("expert", f, "tensor")),
        (r"moe/w2$",                 P("expert", "tensor", f)),
        (r"moe/router$",             P(None, None)),
        (r"(tok_embeddings|lm_head)$",
         P(("tensor", "fsdp") if fsdp else "tensor", None)),
    ])


def mixtral_tp_validate(cfg: MixtralConfig, tp: int,
                        ep: int = 1) -> None:
    """Check ``cfg`` divides over a ``tp``-way tensor x ``ep``-way
    expert mesh under mixtral_sharding_rules: attention like Llama,
    expert hidden dim over tensor, expert count over expert. Raises
    ValueError naming the offending dimension."""
    from ray_tpu.models.llama import llama_tp_validate
    llama_tp_validate(cfg.attention_config(), tp)
    if ep <= 0:
        raise ValueError(f"ep must be >= 1, got {ep}")
    if cfg.num_experts % ep:
        raise ValueError(
            f"expert parallelism ep={ep} does not divide "
            f"num_experts={cfg.num_experts}")
    if cfg.hidden_dim % tp:
        raise ValueError(
            f"tensor parallelism tp={tp} does not divide expert "
            f"hidden_dim={cfg.hidden_dim}")


def moe_aux_loss(variables) -> jnp.ndarray:
    """Mean load-balance loss over layers (add `mutable=['losses']` to
    apply, then weight this into the training loss)."""
    losses = variables.get("losses", {})
    vals = jax.tree_util.tree_leaves(losses)
    if not vals:
        return jnp.float32(0.0)
    return sum(jnp.asarray(v).mean() for v in vals) / len(vals)


def _param_count(cfg: MixtralConfig, experts: int) -> int:
    moe = experts * 3 * cfg.dim * cfg.hidden_dim + \
        cfg.dim * cfg.num_experts
    per_layer = attention_param_count(cfg) + moe + 2 * cfg.dim
    return embedding_param_count(cfg) + cfg.n_layers * per_layer


def mixtral_param_count(cfg: MixtralConfig) -> int:
    return _param_count(cfg, cfg.num_experts)


def active_params_per_token(cfg: MixtralConfig) -> int:
    """Sparse models are priced by ACTIVE params: K experts of E."""
    return _param_count(cfg, cfg.num_experts_per_tok)


def group_limited(cfg, choice):
    """``choice`` [N, E] (a biased router's s + b) with every expert
    outside the token's best groups at ``-inf``: the config's
    ``n_group`` equal groups of consecutive experts are each scored by
    the sum of their two largest values, and the ``topk_group`` best
    groups stay (the DeepSeek-V3 ``noaux_tc`` rule; a tie to the lower
    group). A config without ``n_group``, or with one group, limits
    nothing: ``choice`` as it is. (Defined at the file's end and called
    inside the line that ranked ``choice`` before: the step programs'
    compile-cache key carries the line of every operation above.)"""
    n_group = getattr(cfg, "n_group", None) or 1
    if n_group <= 1:
        return choice
    N, E = choice.shape
    in_group = choice.reshape(N, n_group, E // n_group)
    best_two, _ = jax.lax.top_k(in_group, 2)
    _, stay = jax.lax.top_k(jnp.sum(best_two, axis=-1), cfg.topk_group)
    allowed = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], stay].set(True)
    return jnp.where(allowed[:, :, None], in_group, -jnp.inf).reshape(N, E)
