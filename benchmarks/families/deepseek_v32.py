"""The DeepSeek-V3.2 family (deepseek-ai/DeepSeek-V3.2, ``model_type:
deepseek_v32``) as the benchmark sees it: A.X-K1's decoder (latent
attention in every layer, leading dense layers, a mixture with a shared
expert) whose queries attend the ``index_topk`` entries a lightning
indexer CHOOSES, not their whole context, and whose router ranks inside
its best groups under a stored choice bias. The program serves it as
``ray_tpu.models.deepseek_v32``; ``program_config`` refuses at once
(SystemExit, before a weight is made) a program that has no such module
or whose config lacks a field the model needs.

A configuration of this family is ONE CHIP'S SHARE of an
expert-parallel group (model-configs, section 4), stated as A.X-K1's
is: ``n_routed_experts`` counts the experts HELD, ``router_width`` the
router's published width, ``experts_held_from`` the first held expert.
The plain reference (benchmarks/reference/deepseek_v32.py: the
expanded form, the selection an explicit sort and mask) is handed the
same share.

The weights are ``seeded`` (every leaf from ``--seed`` and its name)
and the routers' choice biases then ``balanced`` on the plain reference,
as Solar-Open2's are. ``reference_logits`` does not let the comparison that
decides ``correct`` score a position whose choice of held experts is a
near-tie (``NEAR_TIE``; the group limit's boundary among the ties).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import (common, costs, trace_dispatch, trace_parts,
                        trace_reduce, weights)
from benchmarks.reference import deepseek_v32 as ref

CONTROLS = ref.CONTROLS

_NEEDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "rope_factor",
          "first_k_dense", "dense_hidden_dim", "n_shared_experts",
          "router", "routed_scaling_factor", "experts_held", "n_group",
          "topk_group", "index_n_heads", "index_head_dim", "index_topk")


def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return max(0, cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def program_config(cfg: Dict[str, Any]):
    """DeepSeekV32Config from the published key names."""
    try:
        from ray_tpu.models.deepseek_v32 import DeepSeekV32Config
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"DeepSeek-V3.2: it has no "
                         f"ray_tpu.models.deepseek_v32 ({e})")
    have = {f.name for f in dataclasses.fields(DeepSeekV32Config)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's DeepSeekV32Config "
                         f"cannot express DeepSeek-V3.2: it has no {lacks}")
    rs = cfg["rope_scaling"]
    refused = {
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "rope_scaling.type other than yarn": rs["type"] != "yarn",
        "scoring_func other than sigmoid": cfg["scoring_func"] != "sigmoid",
        "topk_method other than noaux_tc": cfg["topk_method"] != "noaux_tc",
        "moe_layer_freq other than 1": cfg["moe_layer_freq"] != 1,
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "num_key_value_heads other than the heads":
            cfg["num_key_value_heads"] != cfg["num_attention_heads"],
        "num_nextn_predict_layers (the MTP module, ROADMAP M5)":
            cfg["num_nextn_predict_layers"] != 0,
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's DeepSeek-V3.2 has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return DeepSeekV32Config(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max_seq_len=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        first_k_dense=cfg["first_k_dense_replace"],
        dense_hidden_dim=cfg["intermediate_size"],
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router="sigmoid_bias", n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        experts_held=(cfg["experts_held_from"], cfg["n_routed_experts"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype)


@jax.tree_util.register_pytree_with_keys_class
class Variables(dict):
    """A model's variables, or their shapes, WITH the program config
    they belong to (families/solar_open2.py ``Variables``):
    ``init_params`` has to run the plain reference on the weights it
    makes (``balanced``), which takes the model's sizes."""

    def __init__(self, tree, pcfg):
        super().__init__(tree)
        self.pcfg = pcfg

    def tree_flatten_with_keys(self):
        keys = sorted(self)
        return ([(jax.tree_util.DictKey(k), self[k]) for k in keys],
                (tuple(keys), self.pcfg))

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, pcfg = aux
        return cls(zip(keys, children), pcfg)


class _Model:
    """The served model as the harness and the tests ask for it:
    ``init`` (for the parameters' shapes) and ``apply``."""

    def __init__(self, pcfg):
        from ray_tpu.models.deepseek_v32 import DeepSeekV32
        self.net = DeepSeekV32(pcfg)
        self.config = pcfg

    def init(self, *args, **kwargs):
        return Variables(self.net.init(*args, **kwargs), self.net.config)

    def apply(self, *args, **kwargs):
        return self.net.apply(*args, **kwargs)


def model(pcfg):
    return _Model(pcfg)


def _std_of(name: str, leaf):
    if name.endswith("['bias']") or "router_bias" in name:
        # the index key norm's bias: zeros. The routers' choice bias:
        # zeros BEFORE it is balanced (``balanced``)
        return 0.0
    if leaf.ndim == 1:
        return None                        # every norm's scale: ones
    if "tok_embeddings" in name:
        return 1.0
    if "lm_head" in name or "router" in name:
        return 0.02                        # the model's own
    # 1/sqrt(fan_in); an expert tensor is [n, in, out]
    return leaf.shape[-2] ** -0.5


def seeded(shapes, seed: int, shardings=None):
    """Every leaf of ``shapes['params']`` from ``--seed`` and its name
    alone (``_std_of``): the weights before the routers' choice biases
    are balanced."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


# balancing: sequences x tokens of seeded random ids, steps of the bias
# and its move a step in units of a sigmoid, falling geometrically
# (families/solar_open2.py's schedule)
BALANCE_ROWS, BALANCE_LEN = 32, 256
BALANCE_STEPS, BALANCE_RATES = 96, (0.02, 0.0001)


def balanced(params, pcfg, seed: int):
    """``params`` with every mixture layer's choice bias moved from zero
    until all the experts of the router's whole width are chosen equally
    often on seeded random tokens: DeepSeek-V3's auxiliary-loss-free
    rule (raise by the rate the bias of an expert chosen less often than
    its equal share, lower the others'), which is what ``noaux_tc``'s
    bias is for, through the group limit as the router applies it.
    Unbalanced (a seeded normal of 0.02 for a bias) the share of the
    routing that lands on this chip's eight experts, ALL of one group,
    read 2.55-3.53 % by the seed around the even 3.125 %, and with it
    the experts a step touches and the cell's tokens a second (-1.4 % a
    point of share: six runs spread 0.65 %, PERF.md section 6, PR 56).
    Balanced, a chip's share sees an even thirty-second, as its
    deployment's trained router is balanced to give it.

    The hidden states are the PLAIN REFERENCE's (its sub-layers at the
    default matmul precision: a balance is statistical; nothing of the
    program under test runs), one layer after the other: a layer's bias
    is fitted on the reference's own router scores of all the tokens,
    then its output computed WITH that bias feeds the next layer. A
    function of the weights and the seed: the two sides of a comparison
    get the same bits."""
    rw = reference_weights(params, pcfg)
    first, last = BALANCE_RATES
    rates = jnp.asarray(first * (last / first) ** (
        np.arange(BALANCE_STEPS) / (BALANCE_STEPS - 1)), jnp.float32)
    x = _balance_tokens(rw["embed"], common.jax_key(seed, 7))
    p = dict(params["params"])
    for i, w in enumerate(rw["layers"]):
        x, bias = _balance_layer(x, w, rates, **_sizes(pcfg))
        if bias is not None:
            lp = dict(p[f"layers_{i}"])
            lp["moe"] = dict(lp["moe"], router_bias=bias)
            p[f"layers_{i}"] = lp
    return jax.block_until_ready({"params": p})


@jax.jit
def _balance_tokens(embed, key):
    ids = jax.random.randint(key, (BALANCE_ROWS, BALANCE_LEN), 1,
                             embed.shape[0] - 1)
    return embed[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "eps", "yarn", "idx_heads", "topk", "top_k",
    "lo", "norm_topk", "scaling", "n_group", "topk_group"))
def _balance_layer(x, w, rates, *, n_heads, nope, rope, eps, yarn,
                   idx_heads, topk, top_k, lo, norm_topk, scaling, n_group,
                   topk_group):
    """x [sequences, T, D] float32 -> the layer's output with its bias
    fitted, and that bias (None: a dense layer)."""
    F32 = jnp.float32
    w = {k: a if k in ref.EXPERT_TENSORS else a.astype(F32)
         for k, a in w.items()}
    x = ref.dsa(x, w, n_heads=n_heads, nope=nope, rope=rope, eps=eps,
                yarn=yarn, idx_heads=idx_heads, topk=topk)
    rule = dict(eps=eps, top_k=top_k, lo=lo, norm_topk=norm_topk,
                scaling=scaling, n_group=n_group, topk_group=topk_group)
    if "router" not in w:
        return ref.feed_forward(x, w, **rule)[0], None
    h = ref.llama.rms_norm(x, w["ffn_norm"], eps)
    scores = jax.nn.sigmoid(h.reshape(-1, h.shape[-1]) @ w["router"])
    E = scores.shape[1]

    def move(bias, rate):
        chosen = ref._best(ref.group_limit(scores + bias, n_group,
                                           topk_group)[0], top_k)
        load = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(E), axis=0)
        return bias + rate * jnp.sign(jnp.mean(load) - load), None
    bias, _ = jax.lax.scan(move, jnp.zeros((E,), F32), rates)
    y = ref.feed_forward(x, dict(w, router_bias=bias), **rule)[0]
    return y, bias.astype(w["router_bias"].dtype)


def init_params(shapes, seed: int, shardings=None):
    """``shapes``: what ``model(pcfg).init`` gives (``Variables``). The
    seeded weights with the routers' choice biases balanced."""
    return balanced(seeded(shapes, seed, shardings), shapes.pcfg, seed)


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(
        n_heads=pcfg.n_heads, nope=pcfg.qk_nope_head_dim,
        rope=pcfg.qk_rope_head_dim, eps=pcfg.norm_eps,
        yarn=(pcfg.rope_theta, pcfg.rope_factor,
              pcfg.rope_original_max_seq_len, pcfg.rope_beta_fast,
              pcfg.rope_beta_slow, pcfg.rope_mscale,
              pcfg.rope_mscale_all_dim),
        idx_heads=pcfg.index_n_heads, topk=pcfg.index_topk,
        top_k=pcfg.num_experts_per_tok,
        lo=(pcfg.experts_held or (0, None))[0],
        norm_topk=pcfg.norm_topk_prob,
        scaling=pcfg.routed_scaling_factor,
        n_group=pcfg.n_group, topk_group=pcfg.topk_group)


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, ix = lp["attention"], lp["indexer"]
        w = {"attn_norm": lp["attention_norm"]["scale"],
             "ffn_norm": lp["ffn_norm"]["scale"],
             "wq_a": a["wq_a"]["kernel"], "q_norm": a["q_norm"]["scale"],
             "wq_b": a["wq_b"]["kernel"], "wkv_a": a["wkv_a"]["kernel"],
             "kv_norm": a["kv_norm"]["scale"], "wkv_b": a["wkv_b"],
             "wo": a["wo"]["kernel"],
             "index_wq": ix["wq_b"]["kernel"],
             "index_wk": ix["wk"]["kernel"],
             "index_k_scale": ix["k_norm"]["scale"],
             "index_k_bias": ix["k_norm"]["bias"],
             "index_w": ix["weights_proj"]["kernel"]}
        if "moe" in lp:
            m = lp["moe"]
            w.update(router=m["router"], router_bias=m["router_bias"],
                     w_gate=m["w1"], w_up=m["w3"], w_down=m["w2"],
                     shared_gate=m["shared_w1"], shared_up=m["shared_w3"],
                     shared_down=m["shared_w2"])
        else:
            f = lp["feed_forward"]
            w.update(w_gate=f["w1"]["kernel"], w_up=f["w3"]["kernel"],
                     w_down=f["w2"]["kernel"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


# A position is SCORED by the comparison that decides ``correct`` only
# where no relative error of the hidden state under this changes any
# layer's choice of held experts (reference/deepseek_v32.py
# ``choice_margin``: families/axk1.py ``NEAR_TIE``'s rule with the
# groups' boundary among the ties). PERF.md section 6, PR 56, has the
# two readings.
NEAR_TIE = 0.07


def reference_forward(rw, ids, pcfg, margins: bool = False, **control):
    """The plain reference's logits [B, T, V] of ids [B, T]; with
    ``margins`` also each position's least ``choice_margin`` over the
    layers, with ``chosen=True`` every layer's S_t too."""
    return ref.forward(rw, ids, margins=margins, **{**_sizes(pcfg),
                                                    **control})


# The generated positions a prompt is scored over (the configurations'
# ``parity.new_tokens``: 128 and not the other latent cell's 32, so
# that a mean over them is a mean), and the family's own limits on
# them, in the harness's tolerances (2**-5 of the scored rows' largest
# |logit|) of the scored positions' DEFICITS (the served token's
# reference logit under the best): their MEAN, the WORST of them, and
# HOW MANY of them may lie over one tolerance at all. PERF.md section
# 6, PR 56, has the two readings each limit lies between.
SCORED_TAIL = 128
MEAN_DEFICIT_LIMIT = 0.05
WORST_DEFICIT_LIMIT = 2.5
OVER_LIMIT = 2


def _scored_tail(logits, ids, tail: int):
    """(where, window, deficit): the rows of ``logits`` [B, T, V] that
    predict each row's last ``tail`` tokens of ``ids``, and how far the
    served token's logit lies under the best there [B, tail]."""
    ids = np.asarray(ids)
    T = ids.shape[1]
    G = min(tail, T - 1)
    where = slice(T - 1 - G, T - 1)
    window = np.asarray(logits[:, where], np.float32)
    served = np.take_along_axis(window, ids[:, T - G:, None], -1)[..., 0]
    return where, window, window.max(-1) - served


def deficits(logits, unsure, ids, tail: int = SCORED_TAIL):
    """(mean, worst, scored): over the last ``tail`` positions of each
    row of ``ids`` that ``unsure`` [B, T] does not excuse, how far the
    served token's logit lies under the best, in tolerances of 2**-5 of
    the scored rows' largest |logit|; and how many were scored."""
    where, window, deficit = _scored_tail(logits, ids, tail)
    keep = ~np.asarray(unsure)[:, where]
    if not keep.any():
        return 0.0, 0.0, 0
    tol = 2.0 ** -5 * float(np.abs(window[keep]).max())
    return (float(deficit[keep].mean()) / tol,
            float(deficit[keep].max()) / tol, int(keep.sum()))


def reference_logits(rw, ids, pcfg, **control):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule: the served token's reference logit within
    2**-5 of the logit scale of the best, at every generated position):
    the plain reference's, with a row of zeros (all tokens tie: the
    position is neither failed nor counted decisive) at every position
    whose CHOICE OF HELD EXPERTS the reference itself calls a near-tie
    (``NEAR_TIE``; families/axk1.py ``reference_logits`` has the
    argument, reference/deepseek_v32.py ``choice_margin`` the rule with
    the groups' boundary among the ties). Every other generated
    position stays under the harness's rule, one tolerance a position,
    but for the ``OVER_LIMIT`` positions ``judged`` may excuse inside
    the family's three limits; a run outside any of them gets logits
    that are not finite,
    which the rule reads as not correct, and the ``[correct]`` line
    says which limit.

    Why limits beside the near-tie rule. The served precision chose
    another held expert than the float32 reference at 3.1 % of
    positions, and 2-3 % of those at margins over ``NEAR_TIE`` (up to
    0.18) or where the reference sees no boundary in reach: flips the
    reference cannot show. A flipped choice also moves its position's
    two cache entries, which every later query may read. So a scored
    position now and then lies a little over one tolerance on an honest
    path (one of ~150 scored positions in one run of 37, at 1.24),
    which the harness's rule alone would fail, while the reference in
    float8 stays under one tolerance at all but 2-8 of its scored
    positions and is told by their mean. The tolerance is the harness's,
    unchanged, and is taken over the positions that stay scored, as
    the rule itself takes it."""
    logits, margin = reference_forward(rw, ids, pcfg, margins=True,
                                       **control)
    return judged(logits, margin, ids)


def judged(logits, margin, ids):
    """``reference_logits``'s rule on the plain reference's ``logits``
    [B, T, V] and ``choice_margin``s [B, T] of the served ``ids``: the
    near-ties' rows zeroed and, while the scored positions' deficits
    hold the family's three limits (their mean at most
    ``MEAN_DEFICIT_LIMIT`` tolerances, none over
    ``WORST_DEFICIT_LIMIT``, at most ``OVER_LIMIT`` of them over one
    tolerance), those one or two zeroed too."""
    unsure = margin < NEAR_TIE
    mean, worst, scored = deficits(logits, unsure, ids)
    where, window, deficit = _scored_tail(logits, ids, SCORED_TAIL)
    over = np.array(unsure)[:, where]
    while True:                      # the tolerance the rule will take
        scale = float(np.abs(window[~over]).max()) if (~over).any() else 0.0
        now = over | (deficit > 2.0 ** -5 * scale)
        if (now == over).all():
            break
        over = now
    n_over = int(over.sum() - unsure[:, where].sum())
    broken = [name for name, is_broken in (
        (f"the mean over {MEAN_DEFICIT_LIMIT}", mean > MEAN_DEFICIT_LIMIT),
        (f"the worst over {WORST_DEFICIT_LIMIT}",
         worst > WORST_DEFICIT_LIMIT),
        (f"more than {OVER_LIMIT} over one tolerance",
         n_over > OVER_LIMIT)) if is_broken]
    common.log(f"[correct] deepseek_v32: {int(unsure.sum())} of "
               f"{unsure.size} positions are near-ties of the choice of "
               f"held experts (margin under {NEAR_TIE}) and are not "
               f"scored; over the {scored} scored generated positions the "
               f"served token lies {mean:.4f} tolerances under the "
               f"reference's best in the mean (limit {MEAN_DEFICIT_LIMIT}"
               f"), {worst:.4f} at worst (limit {WORST_DEFICIT_LIMIT}), "
               f"{n_over} of them over one tolerance (limit "
               f"{OVER_LIMIT}): "
               + (f"NOT correct by {', '.join(broken)}" if broken
                  else "inside the family's limits"))
    if broken:
        return np.full(logits.shape, np.nan, np.float32)
    excused = np.array(unsure)
    excused[:, where] = over
    return np.where(excused[..., None], np.float32(0.0), logits)


# ---------------------------------------------------------- byte counts

_LANES = 128


def latent_entry_bytes(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """One token's latent entry in ONE layer AS STORED: ``[c | k_r]``
    in whole 128-lane tiles (576 -> 640)."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-width // _LANES) * _LANES * itemsize


def index_key_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One token's index key in ONE layer."""
    return cfg["index_head_dim"] * itemsize


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """What one token of context costs the pools: a latent entry and an
    index key a layer, as stored."""
    return cfg["num_hidden_layers"] * (latent_entry_bytes(cfg, itemsize)
                                       + index_key_bytes(cfg, itemsize))


def chosen_entries(cfg: Dict[str, Any], context_tokens: float,
                   riders: float) -> float:
    """Entries a step's riders attend a layer, from the sum of their
    contexts alone: every rider past ``index_topk`` attends that many
    (an upper bound where some riders are under it; the program's own
    counter, where the caller has it, is exact)."""
    return min(context_tokens, riders * cfg["index_topk"])


def index_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                     itemsize: int = costs.BF16) -> float:
    """Bytes ONE layer's index scoring of one decode step MUST move:
    each context token's index key read once."""
    return context_tokens * index_key_bytes(cfg, itemsize)


def index_step_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    """FLOPs of ONE layer's index scoring of one decode step: every
    index head's dot product with every key, and the weighted sum."""
    return (2.0 * cfg["index_n_heads"] * (cfg["index_head_dim"] + 1)
            * context_tokens)


def sparse_attn_step_bytes(cfg: Dict[str, Any], chosen: float,
                           itemsize: int = costs.BF16) -> float:
    """Bytes ONE layer's attention of one decode step MUST move: each
    CHOSEN entry read once, whatever implements the reading."""
    return chosen * latent_entry_bytes(cfg, itemsize)


def sparse_attn_step_flops(cfg: Dict[str, Any], chosen: float) -> float:
    R, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * ((R + dr) + R) * chosen


def attention_weight_bytes(cfg: Dict[str, Any],
                           itemsize: int = costs.BF16) -> int:
    """One layer's five attention matrices and the indexer's three."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (D * Rq + Rq * H * (dn + dr) + D * (R + dr)
            + R * H * (dn + dv) + H * dv * D
            + Rq * Hi * Di + D * Di + D * Hi) * itemsize


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * itemsize)


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the HELD experts' matmuls of ONE MIXTURE layer's step must
    move: the three matrices of each held expert touched, once, and
    each pair's row in and out. ``experts_touched`` and ``pairs`` are
    the program's counters a mixture layer-step."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    return 2.0 * 3 * pairs * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16,
                      experts_touched: float = None,
                      chosen: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    attention and indexer matrices, the dense layers' SwiGLU, the
    mixture layers' router (float32, and its bias), shared expert and
    the held experts a step really touched, the INDEX KEYS of the
    tokens in context, the latent entries CHOSEN (``chosen`` a layer,
    from the program's counter, at most ``chosen_entries``; a caller
    that has no counter is priced EVERY entry of the context, what a
    step that does not choose must move: the seam's contract,
    benchmarks/tests/test_families.py), the step's own two entries a
    rider, the head and an embedding row a rider. A step that reads
    the whole context's latent entries but counts what it chose moves
    more than this and reads low."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    n_moe = n_moe_layers(cfg)
    if experts_touched is None:
        experts_touched = min(cfg["n_routed_experts"],
                              slots * cfg["num_experts_per_tok"])
    if chosen is None:
        chosen = context_tokens
    dense = (L - n_moe) * 3 * D * cfg["intermediate_size"] * itemsize
    ffn = n_moe * ((experts_touched + cfg["n_shared_experts"])
                   * expert_bytes(cfg, itemsize)
                   + (D + 1) * cfg["router_width"] * 4)
    cache = L * (index_step_bytes(cfg, context_tokens, itemsize)
                 + sparse_attn_step_bytes(cfg, chosen, itemsize)
                 + slots * (latent_entry_bytes(cfg, itemsize)
                            + index_key_bytes(cfg, itemsize)))
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(L * attention_weight_bytes(cfg, itemsize) + dense + ffn
                 + cache + head)


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
MLA_SCOPES = ("mla_q", "mla_kv", "mla_absorb")
INDEX_SCOPES = ("dsa_index_scores", "dsa_topk")
DSA_SCOPES = ("dsa_index_q", "dsa_index_k") + INDEX_SCOPES + ("dsa_attn",)
# what prefill_attn_share adds up (as A.X-K1's): everything the latent
# attention and its selector name
LATENT_ATTN_SCOPES = DSA_SCOPES + MLA_SCOPES + (
    "kv_append", "kv_gather", "attn_scores", "attn_pv")

parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": LATENT_ATTN_SCOPES,
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe_shared", ("moe_shared",)),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wo",)),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention", "indexer"))),
}


def latent_parts(run, module: str):
    """``trace_parts.for_run`` of ``module`` where the program names
    the selection's scopes; None without a trace or on a program that
    names none (the parent, with this family's readers laid over it)."""
    got = trace_parts.for_run(run, module)
    if not got or not any(s.startswith("dsa_") for s in got["parts"]):
        return None
    return got


def decode_steps_traced(run):
    """Decode steps the traced ``jit_decode`` runs took, for the readers
    that take the whole trace and not the join (decode_latent_attn_ms):
    the executions of the most frequent operation under the ``head``
    scope inside the program's runs, as families/axk1.py counts them
    (the index scores' loop over blocks misleads
    ``trace_reduce.loop_steps`` here as the page window's did there).
    None without the trace's operations or without such an operation."""
    ir = getattr(run, "_trace_parts", {}).get("ir")
    if not ir:
        return None
    spans = sorted((s, s + d) for n, s, d in ir["modules"]
                   if trace_reduce.module_name(n) == "jit_decode")
    counts: Dict[str, int] = {}
    for name, start, _dur, tf_op in ir["ops"]:
        if (trace_parts.part_of(tf_op, parts) == "head"
                and any(s <= start < e for s, e in spans)):
            counts[name] = counts.get(name, 0) + 1
    return float(max(counts.values())) if counts else None


def _spans(run):
    spans = [run.window]
    if getattr(run, "trace_span", None) and None not in run.trace_span:
        spans.insert(0, run.trace_span)
    return spans


def selection_counters(run, prefix: str = "decode_"
                       ) -> Optional[Dict[str, float]]:
    """The selection's counters over the traced seconds or, where those
    hold none, the window: the ``round`` events' sums of
    ``<prefix>index_keys_scored``, ``<prefix>sparse_entries_chosen``
    and ``<prefix>sparse_entries_read`` (``decode_``: the decode
    dispatches alone; ``""``: every dispatch), and ``layer_steps``, the
    decode steps those counted times the layers (from the mixture's
    ``moe_decode_layer_steps``, which ride the same vector). None on a
    program that reports none."""
    L, n_moe = run.cfg["num_hidden_layers"], n_moe_layers(run.cfg)
    for t0, t1 in _spans(run):
        sums = {"index_keys_scored": 0, "sparse_entries_chosen": 0,
                "sparse_entries_read": 0}
        moe_steps = 0
        for e in run.events:
            if e[2] == "round" and t0 <= e[1] < t1:
                for k in sums:
                    sums[k] += e[5].get(prefix + k, 0)
                moe_steps += e[5].get("moe_decode_layer_steps", 0)
        if sums["sparse_entries_chosen"]:
            sums["layer_steps"] = moe_steps / max(1, n_moe) * L
            return sums
    return None


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched
    (families/laguna.py's join, over this family's table of parts):
    {"parts": {part: s}, "module_s", "steps", "riders" (a step's mean),
    "context_tokens" (a step's mean of the riders' own contexts)}. None
    without a joined trace, on a program that names no selection scope,
    or where the spans and the rows disagree in number."""
    if hasattr(run, "_dsv32_decode_parts"):
        return run._dsv32_decode_parts
    run._dsv32_decode_parts = None
    got = trace_dispatch.joined(run)
    if not got or not latent_parts(run, "jit_decode"):
        return None
    rows = [r for r in got["rows"]
            if r["program"] == "jit_decode" and r["steps"]]
    ir = run._trace_parts["ir"]
    mods = sorted(ir["modules"], key=lambda m: m[1])
    spans = [m for m in mods[:-1]
             if trace_reduce.module_name(m[0]) == "jit_decode"]
    steps = sum(r["steps"] for r in rows)
    if not steps or len(spans) != len(rows):
        return None
    split = trace_parts.split({"ops": ir["ops"], "modules": spans},
                              "jit_decode", parts)
    by_round = got["by_round"]
    riders = tokens = 0.0
    for r in rows:
        d, n = by_round[r["round"]], r["steps"]
        riders += d.get("decode_riders", 0) * n
        tokens += (d.get("decode_context_tokens", 0)
                   - d.get("decode_riders", 0) * (n - 1) / 2.0) * n
    out = run._dsv32_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps}
    common.log(f"[dsv32] jit_decode over the {len(rows)} matched "
               f"executions: {steps} steps of {riders / steps:.1f} riders "
               f"and {tokens / steps:.0f} context tokens; a step "
               f"{1e3 * split['module_s'] / steps:.3f} ms: "
               + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
                   split["parts"].items(), key=lambda kv: -kv[1])[:18]))
    return out


def decode_counters(run) -> Optional[Dict[str, float]]:
    """The mixture's counters of the DECODE steps, a mixture layer-step
    (families/laguna.py ``decode_counters``)."""
    for t0, t1 in _spans(run):
        touched = pairs = layer_steps = 0
        for e in run.events:
            if e[2] == "round" and t0 <= e[1] < t1:
                touched += e[5].get("moe_decode_experts_touched", 0)
                pairs += e[5].get("moe_decode_pairs", 0)
                layer_steps += e[5].get("moe_decode_layer_steps", 0)
        if layer_steps:
            return {"experts_touched": touched / layer_steps,
                    "pairs": pairs / layer_steps,
                    "layer_steps": layer_steps}
    return None


# ------------------------------------------------------------- controls

LETTERS = {"a": dict(no_selection=True), "b": dict(recent=True),
           "c": dict(index_topk=1024), "d": dict(no_group_limit=True),
           "e": dict(no_bias=True), "f": dict(lower_precision=True)}


def main(argv=None) -> int:
    """``python -m benchmarks.families.deepseek_v32 [--config NAME]
    [--seeds N,M] [--controls a,b,c,d,e,f] [--dump DIR]``: the controls
    of the comparison that decides ``correct``, on the chip at the
    configuration's parity sizes, in one process. It serves the parity
    prompts through ``LlamaDeployment`` (the engine's two step
    programs, as benchmarks/serve_runner.py does without the traffic),
    then hands ``parity.margin_rule`` the plain reference's
    ``reference_logits`` of the served ids once as it is and once under
    each control of benchmarks/reference/deepseek_v32.py ``CONTROLS``
    (the harness sets none of them), and prints what the rule decided
    beside the three numbers the family's limits are set on. ``--dump``
    keeps every position's margin and deficit (an .npz a seed), which
    the limits' readings are taken from."""
    import argparse
    import os

    from benchmarks import parity, trafficgen
    from ray_tpu.serve.llm import LlamaDeployment
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="deepseek-v3.2-d5-ep32",
                    help="a name under configs/, or a path under "
                         "benchmarks/ (rehearsal/toy-deepseek-v32.json)")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--controls", default="a,b,c,d,e,f")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    cfg = common.load_json(*(("configs", args.config + ".json")
                             if os.sep not in args.config
                             else (args.config,)))
    pcfg = program_config(cfg)
    served_model = model(pcfg)
    par = cfg["parity"]
    P, G = par["prompt_len"], par["new_tokens"]
    deployment = {k: v for k, v in cfg["deployment"].items()
                  if k != "tensor_parallel"}
    for seed in (int(s) for s in args.seeds.split(",")):
        params = init_params(weights.param_shapes(served_model), seed)
        dep = LlamaDeployment(config=pcfg, params=params, **deployment)
        prompts = [trafficgen.prompt_tokens(seed, 20_000_000 + i, P,
                                            cfg["vocab_size"])
                   for i in range(par["prompts"])]
        dep.max_new_tokens = G
        ids = np.asarray([list(p) + out for p, out in zip(
            prompts, dep.generate_batch(prompts))], np.int32)
        dep.engine().shutdown()
        del dep
        common.log(f"[controls] {jax.devices()[0].device_kind}: served "
                   f"{ids.shape} of {args.config}, seed {seed}")
        rw = reference_weights(params, pcfg)
        kept = {"ids": ids}
        for name in [""] + [k for k in args.controls.split(",") if k]:
            logits, margin = reference_forward(
                rw, jnp.asarray(ids), pcfg, margins=True,
                **LETTERS.get(name, {}))
            check = parity.margin_rule(judged(logits, margin, ids), ids, P)
            _, _, deficit = _scored_tail(logits, ids, G)
            kept["margin_" + name], kept["deficit_" + name] = (
                margin[:, P - 1:P - 1 + G], deficit)
            kept["scale_" + name] = np.abs(logits[:, P - 1:P - 1 + G]
                                           ).max(-1)
            what = f"({name}) {LETTERS[name]}" if name else "as it is"
            common.log(f"[controls] seed {seed} {what}: correct "
                       f"{check['ok']}")
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            np.savez(os.path.join(args.dump, f"controls_{seed}.npz"),
                     **kept)
        del params, rw
    stats = jax.devices()[0].memory_stats() or {}
    common.log(f"[controls] peak bytes in use "
               f"{stats.get('peak_bytes_in_use', 0)}")
    return 0


if __name__ == "__main__":
    import os
    os._exit(main())  # past the engines' threads; every line is flushed
