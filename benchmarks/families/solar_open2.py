"""The Solar-Open2 family (upstage/Solar-Open2-250B, ``model_type:
solar_open2``) as the benchmark sees it: a hybrid decoder with one layer
of gated softmax GQA (no positions) in four, three of Kimi Delta
Attention (a gated delta rule with a per-channel decay, whose state is
a fixed-size matrix a head and not K/V), and in every layer a sparse
mixture with a sigmoid router, a choice bias and a shared expert. The
program serves it as ``ray_tpu.models.solar_open2``; ``program_config``
refuses at once (SystemExit, before a weight is made) a program that has
no such module or whose config lacks a field the model needs.

A configuration of this family is ONE CHIP'S SHARE of an expert-parallel
group (model-configs, section 4): ``n_routed_experts`` counts the
experts HELD, ``router_width`` the router's published width,
``experts_held_from`` the first held expert. The plain reference
(benchmarks/reference/solar_open2.py) is handed the same share.

The weights are ``seeded`` (every leaf from ``--seed`` and its name)
and then ``balanced`` (the routers' choice biases fitted on the plain
reference's own hidden states: nothing of the program under test makes
a weight). ``reference_logits`` does not let the comparison that
decides ``correct`` score a position whose choice of held experts is a
near-tie (``NEAR_TIE``); everywhere else the held experts weigh in the
logits at their natural scale.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common, costs, trace_parts, weights
from benchmarks.reference import llama as ref_llama
from benchmarks.reference import solar_open2 as ref

_NEEDS = ("gqa_interval", "kda_heads", "kda_head_dim", "conv_size",
          "n_shared_experts", "router", "routed_scaling_factor",
          "experts_held", "rope")


def n_kda_layers(cfg: Dict[str, Any]) -> int:
    period = cfg["gqa_interval"] + 1
    return sum(1 for i in range(cfg["num_hidden_layers"]) if i % period)


def program_config(cfg: Dict[str, Any]):
    """SolarOpen2Config from the published key names."""
    try:
        from ray_tpu.models.solar_open2 import SolarOpen2Config
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Solar-Open2: it has no "
                         f"ray_tpu.models.solar_open2 ({e})")
    have = {f.name for f in dataclasses.fields(SolarOpen2Config)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's SolarOpen2Config "
                         f"cannot express Solar-Open2: it has no {lacks}")
    lin = cfg["linear_attn_config"]
    period = cfg["gqa_interval"] + 1
    L = cfg["num_hidden_layers"]
    if [i for i in cfg["gqa_layers"] if i < L] != list(range(0, L, period)):
        raise SystemExit("benchmarks: gqa_layers is not every "
                         "(gqa_interval + 1)-th layer from 0")
    refused = {
        "use_rope": cfg["use_rope"],
        "first_k_dense_replace": cfg["first_k_dense_replace"] != 0,
        "kda_use_full_proj": cfg["kda_use_full_proj"],
        "use_gqa_gate false": not cfg["use_gqa_gate"],
        "kda_allow_neg_eigval false": not cfg["kda_allow_neg_eigval"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "linear num_kv_heads": lin["num_kv_heads"] not in (
            None, lin["num_heads"]),
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Solar-Open2 has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=L,
        gqa_interval=cfg["gqa_interval"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["experts_held_from"], cfg["n_routed_experts"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), dtype=dtype,
        param_dtype=dtype)


@jax.tree_util.register_pytree_with_keys_class
class Variables(dict):
    """A model's variables, or their shapes, WITH the program config
    they belong to: what ``model(pcfg).init`` gives. ``init_params``
    is handed shapes and a seed and has to run the plain reference on
    the weights it makes (``balanced``), which takes the model's
    sizes: they travel with the tree, as static data of the pytree."""

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
        from ray_tpu.models.solar_open2 import SolarOpen2
        self.net = SolarOpen2(pcfg)

    def init(self, *args, **kwargs):
        return Variables(self.net.init(*args, **kwargs), self.net.config)

    def apply(self, *args, **kwargs):
        return self.net.apply(*args, **kwargs)


def model(pcfg):
    return _Model(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        if "scale" in name:
            return None                    # every norm's scale: ones
        if "A_log" in name or "dt_bias" in name:
            return 1.0                     # moved by _decays below
        # the router's choice bias BEFORE it is balanced: about one gap
        # between neighbouring candidates' sigmoids where the 8th of
        # 320 lies (0.005), so it changes choices without deciding them
        return 0.005 if "router_bias" in name else 0.1   # else W_g2's
    if "tok_embeddings" in name:
        # a token's own vector leads its hidden state: at the other
        # families' 0.02 the layers' outputs led it, which the tokens
        # of a sequence share in part, and routing went by sequence
        # and by model more than by token (PERF.md section 6, PR 32)
        return 1.0
    if "lm_head" in name or "router" in name:
        return 0.02                        # the model's own
    if "conv" in name:
        return leaf.shape[0] ** -0.5       # [K, channels]
    # 1/sqrt(fan_in); an expert tensor is [n, in, out]
    return leaf.shape[-2] ** -0.5


def _decays(params):
    """A_log and dt_bias from their standard normals: exp(A_log)
    around 1 (0.4-2.7), dt_bias around -4 (+-1.5), so that with the
    decay projection's own spread a step's decay exp(g) runs from 0.5
    to 0.999 for most channels and a few decay hard (the configuration
    file's ``assumed.weights``)."""
    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            return (0.5 * leaf).astype(leaf.dtype)
        if "dt_bias" in name:
            return (1.5 * leaf - 4.0).astype(leaf.dtype)
        return leaf
    return jax.jit(lambda t: jax.tree_util.tree_map_with_path(move, t),
                   donate_argnums=0)(params)


def seeded(shapes, seed: int, shardings=None):
    """Every leaf of ``shapes['params']`` from ``--seed`` and its name
    alone (``_std_of``, ``_decays``): the weights before the routers'
    choice biases are balanced."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return _decays(weights.seeded_normal(only(shapes), seed, _std_of,
                                         only(shardings)))


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
                eps=pcfg.norm_eps, top_k=pcfg.num_experts_per_tok,
                lo=(pcfg.experts_held or (0, None))[0],
                norm_topk=pcfg.norm_topk_prob,
                scaling=pcfg.routed_scaling_factor)


# balancing: sequences x tokens of seeded random ids, sequences a pass
# (what is live at once), steps of the bias and its move a step in
# units of a sigmoid (0.005 is about one gap between neighbouring
# candidates near the 8th of 320), falling geometrically so that the
# last steps settle within a fifth of a percent of a load
BALANCE_ROWS, BALANCE_LEN, BALANCE_PASS = 128, 256, 4
BALANCE_STEPS, BALANCE_RATES = 96, (0.02, 0.0001)


def balanced(params, pcfg, seed: int):
    """``params`` with every layer's choice bias moved until all the
    experts of the router's whole width are chosen equally often on
    seeded random tokens: DeepSeek-V3's auxiliary-loss-free rule
    (section 2.1.2: raise by the rate the bias of an expert chosen less
    often than its equal share, lower the others'), which is what such
    a router's bias is for. A router of random weights prefers some
    experts for EVERY token (the delta-rule layers' outputs share a
    component: SiLU's positive mean makes every key and value lean one
    way), so without it the share of the routing that lands on the
    held experts, and with it a decode step's bytes and the cell's
    tokens a second, moved +-4 % with the seed (PERF.md section 6, PR
    32). Balanced, a chip's share sees an even eighth, as its
    deployment's trained router is balanced to give it.

    The hidden states are the PLAIN REFERENCE's (benchmarks/reference/
    solar_open2.py's sub-layers on float32 activations, at the default
    matmul precision and stored between layers in the embedding's
    type: a balance is statistical; nothing of the program under test
    runs), one layer after the other: a layer's bias is fitted on the
    reference's own router scores of all the tokens, then its output
    computed WITH that bias feeds the next layer. A function of the
    weights and the seed: the two sides of a comparison get the same
    bits."""
    rw = reference_weights(params, pcfg)
    first, last = BALANCE_RATES
    rates = (first * (last / first) ** (
        np.arange(BALANCE_STEPS) / (BALANCE_STEPS - 1))).astype(np.float32)
    x = _balance_tokens(rw["embed"], common.jax_key(seed, 7))
    p = dict(params["params"])
    for i, w in enumerate(rw["layers"]):
        x, bias = _balance_layer(x, w, rates, **_sizes(pcfg))
        lp = dict(p[f"layers_{i}"])
        lp["moe"] = dict(lp["moe"], router_bias=bias)
        p[f"layers_{i}"] = lp
    return jax.block_until_ready({"params": p})


@jax.jit
def _balance_tokens(embed, key):
    """The balancing's tokens, embedded: [passes, sequences, T, D]."""
    ids = jax.random.randint(
        key, (BALANCE_ROWS // BALANCE_PASS, BALANCE_PASS, BALANCE_LEN), 1,
        embed.shape[0] - 1)
    return embed[ids]


@functools.partial(jax.jit, donate_argnums=0, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "top_k", "lo", "norm_topk", "scaling"))
def _balance_layer(x, w, rates, *, n_heads, n_kv_heads, eps, top_k, lo,
                   norm_topk, scaling):
    """x [passes, sequences, T, D] in the embedding's type -> the
    layer's output on every token with its bias fitted, and that bias.
    One pass of sequences is live at a time."""
    E = w["router"].shape[1]
    F32 = ref_llama.F32

    def mixed_and_scores(xb):
        xb = ref.mix(xb.astype(F32), w, n_heads=n_heads,
                     n_kv_heads=n_kv_heads, eps=eps)
        h = ref_llama.rms_norm(xb, w["ffn_norm"], eps)
        return xb.astype(x.dtype), jax.nn.sigmoid(h @ w["router"])
    mixed, scores = jax.lax.map(mixed_and_scores, x)
    scores = scores.reshape(-1, E)

    def move(bias, rate):
        _, chosen = jax.lax.top_k(scores + bias, top_k)
        load = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(E), axis=0)
        return bias + rate * jnp.sign(jnp.mean(load) - load), None
    bias, _ = jax.lax.scan(move, w["router_bias"].astype(F32), rates)
    fitted = dict(w, router_bias=bias)

    def output(xb):
        y, _ = ref.feed_forward(
            xb.astype(F32), fitted, eps=eps, top_k=top_k, lo=lo,
            norm_topk=norm_topk, scaling=scaling)
        return y.astype(x.dtype)
    return jax.lax.map(output, mixed), bias.astype(w["router_bias"].dtype)


def init_params(shapes, seed: int, shardings=None):
    """``shapes``: what ``model(pcfg).init`` gives (``Variables``)."""
    return balanced(seeded(shapes, seed, shardings), shapes.pcfg, seed)


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, m = lp["attention"], lp["moe"]
        w = {"attn_norm": lp["attention_norm"]["scale"],
             "ffn_norm": lp["ffn_norm"]["scale"],
             "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
             "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"],
             "router": m["router"], "router_bias": m["router_bias"],
             "w_gate": m["w1"], "w_up": m["w3"], "w_down": m["w2"],
             "shared_gate": m["shared_w1"], "shared_up": m["shared_w3"],
             "shared_down": m["shared_w2"]}
        if "conv" in a:
            w.update(conv=a["conv"], f_a=a["f_a"]["kernel"],
                     f_b=a["f_b"]["kernel"], dt_bias=a["dt_bias"],
                     A_log=a["A_log"], wb=a["wb"]["kernel"],
                     g_a=a["g_a"]["kernel"], g_b=a["g_b"]["kernel"],
                     g_bias=a["g_b"]["bias"],
                     o_norm=a["o_norm"]["scale"])
        else:
            w["w_gate_attn"] = a["w_gate"]["kernel"]
        layers.append(w)
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


# A position is SCORED by the comparison that decides ``correct`` only
# where no relative error of the hidden state under this changes any
# layer's choice of held experts (reference/solar_open2.py
# ``choice_margin``). See ``reference_logits``.
NEAR_TIE = 0.05


def reference_forward(rw, ids, pcfg, margins: bool = False):
    """The plain reference's logits [B, T, V] of ids [B, T]; with
    ``margins`` also each position's least ``choice_margin`` over the
    layers (reference/solar_open2.py)."""
    return ref.forward(rw, ids, margins=margins, **_sizes(pcfg))


def reference_logits(rw, ids, pcfg):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule: the served token's reference logit within
    2**-5 of the logit scale of the best, at every position): the
    plain reference's, but a row of zeros at every position whose
    CHOICE OF HELD EXPERTS the reference itself calls a near-tie
    (``choice_margin`` under ``NEAR_TIE`` in some layer). All tokens
    tie there, so the rule neither fails the position nor counts it
    decisive: it is not scored.

    Why: the served path rounds its hidden state to bfloat16, which
    moves a candidate's s + b by about 0.001; the 8th and 9th of 320
    candidates lie about 0.005 apart; so at about one position in
    seven a layer the two computations choose different experts, and
    the chosen expert carries a whole 1/8 gate (renormalised sigmoid
    gates are near equal): with the experts at their natural scale
    that alone moved logits by up to four times the rule's tolerance
    (PERF.md section 6, PR 32). Both choices are right answers of the
    architecture at that precision; which one the served path took
    cannot be told from its tokens. Only a flip of an expert HELD here
    changes the output, so only those count. Everywhere else the held
    experts weigh in the logits at their full scale, and an expert
    computed wrongly, a bfloat16 state or another router's rule reads
    not correct (the controls: PERF.md section 6, PR 32)."""
    logits, margin = (np.asarray(a) for a in reference_forward(
        rw, ids, pcfg, margins=True))
    unsure = margin < NEAR_TIE
    common.log(f"[correct] solar_open2: {int(unsure.sum())} of "
               f"{unsure.size} positions are near-ties of the choice "
               f"of held experts (margin under {NEAR_TIE}) and are not "
               f"scored")
    return np.where(unsure[..., None], np.float32(0.0), logits)


# ---------------------------------------------------------- byte counts

def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """K and V of one token over the layers that HAVE K/V: the GQA
    layers of the cut."""
    n_gqa = cfg["num_hidden_layers"] - n_kda_layers(cfg)
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * n_gqa
            * itemsize)


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * itemsize)


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the HELD experts' matmuls of ONE layer's step must move
    (as the OLMoE family's): the three matrices of each held expert
    touched, once, and each pair's row in and out. ``experts_touched``
    and ``pairs`` are the program's counters, which count held experts
    and the pairs that landed on them."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    return (2.0 * 3 * pairs * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's delta-rule state in ONE KDA layer: heads x d x d
    float32."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * 4


def conv_tail_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One slot's convolution tail in ONE KDA layer: the last
    ``short_conv_kernel_size - 1`` inputs of q, k and v."""
    lin = cfg["linear_attn_config"]
    return ((lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"]
            * lin["head_dim"] * itemsize)


def state_step_bytes(cfg: Dict[str, Any], riders: float) -> float:
    """Bytes ONE KDA layer's decode step MUST move for the recurrent
    state: each rider's state read once and written once, and its
    convolution tail read and written. Slots that ride without a
    request need move nothing."""
    return riders * 2.0 * (state_bytes(cfg) + conv_tail_bytes(cfg))


def layer_weight_bytes(cfg: Dict[str, Any], kda: bool,
                       itemsize: int = costs.BF16) -> int:
    """One layer's token mixing: a GQA layer's five projections (the
    gate's too), or a KDA layer's four, its low-rank decay and gate
    projections, the beta projection and the convolution."""
    D = cfg["hidden_size"]
    if not kda:
        hq = cfg["num_attention_heads"] * cfg["head_dim"]
        hk = cfg["num_key_value_heads"] * cfg["head_dim"]
        return (3 * D * hq + 2 * D * hk) * itemsize
    lin = cfg["linear_attn_config"]
    d, C = lin["head_dim"], lin["num_heads"] * lin["head_dim"]
    return (4 * D * C + 2 * (D * d + d * C) + D * lin["num_heads"]
            + lin["short_conv_kernel_size"] * 3 * C) * itemsize


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: int, itemsize: int = costs.BF16,
                      experts_touched: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    token-mixing weights, router (float32) and shared expert once, the
    held experts a step really touched (``experts_touched`` a layer,
    from the program's counters; the most ``slots`` rows can touch
    where the caller has none: an UPPER bound, as the OLMoE family's),
    every slot's recurrent state in and out, the K/V of the tokens in
    context in the layers that have K/V, the head and an embedding row
    a slot."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    n_kda = n_kda_layers(cfg)
    if experts_touched is None:
        experts_touched = min(cfg["n_routed_experts"],
                              slots * cfg["num_experts_per_tok"])
    mixing = (n_kda * layer_weight_bytes(cfg, True, itemsize)
              + (L - n_kda) * layer_weight_bytes(cfg, False, itemsize))
    ffn = L * ((experts_touched + cfg["n_shared_experts"])
               * expert_bytes(cfg, itemsize) + D * cfg["router_width"] * 4)
    state = n_kda * state_step_bytes(cfg, slots)
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(mixing + ffn + state + kv + head)


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
KDA_SCOPES = ("kda_conv", "kda_gates", "kda_recurrence", "kda_out")

# trace_parts.DEFAULT_PARTS with the delta-rule layer's four scopes, the
# GQA layer's output gate, the mixture's four scopes and its shared
# expert as parts of their own, each before the module names that would
# otherwise claim their operations
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": trace_parts.DEFAULT_PARTS["attention"],
    "dense": (*((s, (s,)) for s in KDA_SCOPES),
              ("attn_gate", ("attn_gate",)),
              *((s, (s,)) for s in MOE_SCOPES),
              ("moe_shared", ("moe_shared",)),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}
