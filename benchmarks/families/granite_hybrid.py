"""The Granite-4.0-H family (ibm-granite/granite-4.0-h-small,
``model_type: granitemoehybrid``) as the benchmark sees it: a hybrid
decoder whose layers are nine in ten MAMBA-2 (a state-space rule under
one scalar decay a head; a float32 ``[128, 64, 128]`` state and a
convolution tail, a SLOT of the engine) and one in ten grouped-query
attention with no position encoding at a published softmax scale (K/V
pages), a top-10 softmax mixture of 72 SwiGLU experts beside a shared
SwiGLU behind EVERY layer, and four scalar multipliers. The program
serves it as ``ray_tpu.models.granite_hybrid``; ``program_config``
refuses at once (SystemExit, before a weight is made) a program that has
no such module and a file whose keys the module cannot express.

A configuration of this family is ONE CHIP'S SHARE of an expert-parallel
group (model-configs, section 4): ``num_local_experts`` counts the
experts HELD, ``router_width`` the router's published width,
``experts_held_from`` the first held expert. The plain reference
(benchmarks/reference/granite_hybrid.py: the recurrence scanned token by
token) is handed the same share.

``reference_logits`` hands the comparison that decides ``correct`` the
plain reference's logits, and excuses the generated positions at which a
flipped choice of a held expert carried the served token past the
tolerance only while they stay a small share (``FLIPPED_SHARE``:
families/kimi_linear.py has the argument).

The byte and FLOP counts are BY KIND of layer: a Mamba-2 layer-step's
state (``state_step_bytes``), a Mamba-2 layer's chunked recurrence of a
prefill call (``scan_call_bytes``, ``scan_call_flops``), a mixture
layer-step's held experts (``experts_step_bytes``), and the readers
divide a scope's time by the layers OF THAT KIND (``n_ssm_layers``,
``n_moe_layers``) and by the decode steps the engine's own rounds
dispatched (``decode_parts_by_rounds``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import numpy as np

from benchmarks import common, costs, trace_parts, trace_rounds, weights
from benchmarks.reference import granite_hybrid as ref

CONTROLS = ref.CONTROLS
MAMBA, ATTENTION = "mamba", "attention"

# What the state-space family before this one counts the same way is
# that file's: the prefill calls of a span by the ``round`` events, the
# sums of a split's parts, the mixture's decode counters.
_phi4 = common.load_family("phi4flash", "serve")
prefill_calls, under = _phi4.prefill_calls, _phi4.under
decode_counters = _phi4._laguna.decode_counters


def layer_types(cfg: Dict[str, Any]):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_ssm_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count(MAMBA)


def n_attn_layers(cfg: Dict[str, Any]) -> int:
    return layer_types(cfg).count(ATTENTION)


def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"]


def d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_width(cfg: Dict[str, Any]) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def program_config(cfg: Dict[str, Any]):
    """GraniteHybridConfig from the published key names."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.granite_hybrid import GraniteHybridConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Granite-4.0-H: it has no "
                         f"ray_tpu.models.granite_hybrid ({e})")
    refused = {
        "model_type other than granitemoehybrid":
            cfg["model_type"] != "granitemoehybrid",
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "an untied head": not cfg["tie_word_embeddings"],
        "attention_bias": cfg["attention_bias"],
        "mamba_proj_bias": cfg["mamba_proj_bias"],
        "a convolution without its bias": not cfg["mamba_conv_bias"],
        "position_embedding_type other than nope":
            cfg["position_embedding_type"] != "nope",
        "normalization_function other than rmsnorm":
            cfg["normalization_function"] != "rmsnorm",
        "mamba_expand other than heads x head / hidden":
            cfg["mamba_expand"] * cfg["hidden_size"] != d_inner(cfg),
        "a shared width that is no whole number of experts":
            cfg["shared_intermediate_size"] % cfg["intermediate_size"] != 0,
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Granite-4.0-H has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    try:
        return GraniteHybridConfig(
            vocab_size=cfg["vocab_size"],
            max_seq_len=cfg["max_position_embeddings"],
            dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
            layer_types=tuple(cfg["layer_types"]),
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            mamba_heads=cfg["mamba_n_heads"],
            mamba_head_dim=cfg["mamba_d_head"],
            mamba_state=cfg["mamba_d_state"],
            mamba_groups=cfg["mamba_n_groups"],
            mamba_conv=cfg["mamba_d_conv"],
            mamba_chunk=cfg["mamba_chunk_size"],
            hidden_dim=cfg["intermediate_size"],
            num_experts=cfg["router_width"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_shared_experts=(cfg["shared_intermediate_size"]
                              // cfg["intermediate_size"]),
            norm_topk_prob=True, router="softmax",
            experts_held=(cfg["experts_held_from"],
                          cfg["num_local_experts"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
            param_dtype=dtype)
    except ValueError as e:
        raise SystemExit(f"benchmarks: the program's Granite-4.0-H "
                         f"refuses the file: {e}")


# The variables that carry their program config are Solar-Open2's
# (``init_params`` is handed shapes and a seed, and the seeded scale of
# the attention's query and key goes by the model's own multiplier), and
# so is the model the harness and the tests ask ``init`` and ``apply`` of.
_solar = common.load_family("solar_open2", "serve")
Variables = _solar.Variables


class _Model(_solar._Model):
    def __init__(self, pcfg):
        from ray_tpu.models.granite_hybrid import GraniteHybrid
        self.net = GraniteHybrid(pcfg)


def model(pcfg):
    return _Model(pcfg)


def _std_of(name: str, leaf, qk: float = 1.0):
    if "A_log" in name or "dt_bias" in name or "['D']" in name:
        return 1.0                          # moved by _moved below
    if leaf.ndim == 1:
        # every norm's scale: ones; the convolution's bias
        return None if "scale" in name else 0.1
    if "tok_embeddings" in name:
        # TIED, and its rows enter the stream times 12: the token's own
        # row then stands in the final hidden state and scores against
        # itself in the head. At 1 / (8 sqrt(D)) its share of the stream
        # is small enough that the own-token logit is about two of the
        # other logits' deviations (it does not decide every step) and
        # large enough that ``residual_multiplier`` is no common factor
        # of the whole stream (the norms would cancel one)
        return 0.125 * leaf.shape[1] ** -0.5
    if "router" in name:
        return 0.02                         # the mixture families' own
    if "conv" in name:
        return leaf.shape[0] ** -0.5        # [K, channels]
    # 1/sqrt(fan_in); an expert tensor is [n, in, out]; the attention's
    # query and key ``qk`` times that
    std = leaf.shape[-2] ** -0.5
    return qk * std if "['wq']" in name or "['wk']" in name else std


def _moved(params):
    """A_log, dt_bias and D from their standard normals: exp(A_log) =
    exp(1 + n) (most of 1-7.4, Mamba-2's own 1..16 in spread), b_dt = -4
    + 1.5 n (softplus around 0.02, so a step's decay a = exp(-exp(A_log)
    dt) spans 0.5-0.99 for most heads and a few decay hard: a in (0, 1)
    whatever the draw, and the state a decaying sum of bounded writes),
    D = 1 + 0.5 n."""
    import jax

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            return (1.0 + leaf).astype(leaf.dtype)
        if "dt_bias" in name:
            return (1.5 * leaf - 4.0).astype(leaf.dtype)
        if "['D']" in name:
            return (1.0 + 0.5 * leaf).astype(leaf.dtype)
        return leaf
    return jax.jit(lambda t: jax.tree_util.tree_map_with_path(move, t),
                   donate_argnums=0)(params)


# The deviation the seeded attention's scores are given (``qk_scale``).
SCORE_SPREAD = 2.0


def qk_scale(pcfg) -> float:
    """What the seeded query and key matrices are scaled by beside 1 /
    sqrt(fan_in), so that the scores ``m q . k`` under the published
    ``attention_multiplier`` m deviate by ``SCORE_SPREAD``: ``(spread /
    (m sqrt(d)))^(1/2)`` a matrix, 4.76 as published. m is 1 / d where
    the usual scale is 1 / sqrt(d) (the model is trained under it, and
    its q . k grows with d): unit-variance queries and keys would score
    with a deviation of 1 / sqrt(d) = 0.09, every softmax would be flat,
    and the attention layer, a running mean of a thousand random values,
    would add a twentieth of what a Mamba-2 layer adds. On the chip the
    comparison then read CORRECT with the scores over sqrt(d) and with
    rotary positions applied (PERF.md section 6, PR 65): nothing a wrong
    mask, scale or rotation could move reached a logit. At a deviation
    of 2 a softmax over a thousand keys rests on about twenty of them
    (n exp(-spread^2)), and the layer adds a fifth of a Mamba-2
    layer's."""
    return (SCORE_SPREAD / (pcfg.attention_multiplier
                            * pcfg.head_dim ** 0.5)) ** 0.5


def init_params(shapes, seed: int, shardings=None):
    """``shapes``: what ``model(pcfg).init`` gives (``Variables``).
    Normal, std 1/sqrt(fan_in) for matrices (an expert tensor by its in;
    the attention's query and key ``qk_scale`` times that), 0.02 for the
    routers, 1/(8 sqrt(D)) for the tied embedding, 0.1 for the
    convolution's bias, ones for every norm's scale; the state-space
    layer's A, b_dt and D by ``_moved``."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    std_of = functools.partial(_std_of, qk=qk_scale(shapes.pcfg))
    return _moved(weights.seeded_normal(only(shapes), seed, std_of,
                                        only(shardings)))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names: the
    program's own arrays (the reference upcasts a layer's at a time)."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, m = lp["attention"], lp["moe"]
        w = {"attn_norm": lp["attention_norm"]["scale"],
             "ffn_norm": lp["ffn_norm"]["scale"],
             "router": m["router"], "w_gate": m["w1"], "w_up": m["w3"],
             "w_down": m["w2"], "shared_gate": m["shared_w1"],
             "shared_up": m["shared_w3"], "shared_down": m["shared_w2"]}
        if "conv" in a:
            w.update(w_in=a["w_in"]["kernel"], conv=a["conv"],
                     conv_bias=a["conv_bias"], dt_bias=a["dt_bias"],
                     A_log=a["A_log"], D=a["D"],
                     o_norm=a["o_norm"]["scale"], w_out=a["wo"]["kernel"])
        else:
            w.update(wq=a["wq"]["kernel"], wk=a["wk"]["kernel"],
                     wv=a["wv"]["kernel"], wo=a["wo"]["kernel"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "norm": p["norm"]["scale"],
            "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
                eps=pcfg.norm_eps, top_k=pcfg.num_experts_per_tok,
                lo=(pcfg.experts_held or (0, None))[0],
                residual=pcfg.residual_multiplier,
                attn_scale=pcfg.attention_multiplier,
                embed_scale=pcfg.embedding_multiplier)


def reference_forward(rw, ids, pcfg, **control):
    """The plain reference's logits [B, T, V] of ids [B, T].
    ``control``: the reference's controls (``CONTROLS``), which the
    harness never sets."""
    return ref.forward(rw, ids, logits_scaling=pcfg.logits_scaling,
                       **_sizes(pcfg), **control)


# The generated positions the comparison that decides ``correct`` reads
# are each row's last ``SCORED_TAIL`` (the configurations' ``parity``
# ``new_tokens``: the harness hands ``reference_logits`` the ids without
# saying where the prompt ends; a test ties the two), and of them at
# most ``FLIPPED_SHARE`` may lie more than the tolerance under the
# reference's best. PERF.md section 6, PR 65, has the two readings the
# limit lies between.
SCORED_TAIL = 256
FLIPPED_SHARE = 0.01


def reference_logits(rw, ids, pcfg, **control):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule, unchanged: the served token's reference logit
    within 2**-5 of the logit scale of the best, at every generated
    position): the plain reference's, [B, T, V] with the rows that
    predict each prompt's last ``SCORED_TAIL`` tokens filled and the
    others zero (never read: the rule takes positions P - 1 .. P + G -
    2; the head runs over the scored rows alone, so that it fits beside
    a chip this cell fills), and with a row of zeros (all tokens tie:
    neither failed nor decisive) at the generated positions where the
    served token lies MORE than that tolerance under the best, as long
    as those are at most ``FLIPPED_SHARE`` of the generated positions:
    a held expert's logit within bfloat16's rounding of the top-10
    boundary is chosen by one precision and not by the other, both right
    answers of the architecture at that precision (families/
    kimi_linear.py ``reference_logits`` has the argument behind a
    share). Where they are more, nothing is excused and the rule fails
    on them. The share is a TENTH of Kimi-Linear's: a softmax's tenth
    gate is its smallest, so a flip at the boundary moves little, and on
    the chip the served path read 0-1 of 512 positions over the
    tolerance where a state handed on in bfloat16 (the precision below
    the configuration's float32 state) read 9-36, the reference under
    rotary positions 19-23 and float8 matrices 476-485 (PERF.md section
    6, PR 65): 5 of 512 lies between, five times the served path's worst
    and under the least any control read. The ``[correct]
    granite_hybrid:`` line says how many, and how far under the
    tolerance the others stood."""
    x = ref.blocks(rw, ids, **_sizes(pcfg), **control)
    ids = np.asarray(ids)
    T = ids.shape[1]
    G = min(SCORED_TAIL, T - 1)
    window = np.array(ref.head(
        x[:, T - 1 - G:T - 1], rw["norm"], ref.embedding(rw, **control),
        eps=pcfg.norm_eps, scaling=pcfg.logits_scaling))
    served = np.take_along_axis(window, ids[:, T - G:, None], -1)[..., 0]
    deficit = window.max(-1) - served
    flipped = np.zeros(deficit.shape, bool)
    while True:
        scale = float(np.abs(window[~flipped]).max()) if (
            ~flipped).any() else 0.0
        now = flipped | (deficit > 2.0 ** -5 * scale)
        if (now == flipped).all():
            break
        flipped = now
    tol = 2.0 ** -5 * scale
    share = float(flipped.mean())
    excused = share <= FLIPPED_SHARE
    kept = deficit[~flipped]
    common.log(
        f"[correct] granite_hybrid: at {int(flipped.sum())} of "
        f"{flipped.size} generated positions ({100 * share:.1f} %; limit "
        f"{100 * FLIPPED_SHARE:.1f} %) the served token lies more than "
        f"the tolerance {tol:.4f} under the reference's best (worst "
        f"{float(deficit.max()) / max(tol, 1e-30):.2f} tolerances): "
        + ("a choice of held experts flipped there or before; not scored"
           if excused else "too many for flipped choices: scored as they "
           "are")
        + (f"; the others lie {float(kept.mean()) / tol:.4f} tolerances "
           f"under it in the mean and {float(kept.max()) / tol:.3f} at "
           f"worst" if kept.size and tol else ""))
    if excused:
        window[flipped] = 0.0
    logits = np.zeros((ids.shape[0], T, window.shape[-1]), np.float32)
    logits[:, T - 1 - G:T - 1] = window
    return logits


# ---------------------------------------------------------- byte counts

def mixer_params(cfg: Dict[str, Any]) -> int:
    """One Mamba-2 layer's token mixing, matrices only (biases, norms, A
    and D, a few thousand numbers, are left out)."""
    D, C, W = cfg["hidden_size"], d_inner(cfg), conv_width(cfg)
    return (D * (C + W + cfg["mamba_n_heads"]) + C * D
            + cfg["mamba_d_conv"] * W)


def attention_params(cfg: Dict[str, Any]) -> int:
    D = cfg["hidden_size"]
    hd = D // cfg["num_attention_heads"]
    return 2 * D * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def mixing_params(cfg: Dict[str, Any]) -> int:
    """Every layer's token mixing."""
    return (n_ssm_layers(cfg) * mixer_params(cfg)
            + n_attn_layers(cfg) * attention_params(cfg))


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * itemsize


def shared_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_bytes(cfg: Dict[str, Any]) -> int:
    """One layer's router, float32, its whole width."""
    return cfg["hidden_size"] * cfg["router_width"] * 4


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """K and V of one token over the layers that HAVE K/V."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (n_attn_layers(cfg) * 2 * cfg["num_key_value_heads"] * hd
            * itemsize)


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's state in ONE Mamba-2 layer: heads x head x states
    float32."""
    return d_inner(cfg) * cfg["mamba_d_state"] * 4


def conv_tail_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One slot's convolution tail in ONE Mamba-2 layer."""
    return (cfg["mamba_d_conv"] - 1) * conv_width(cfg) * itemsize


def state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """What one slot keeps whatever its context, as ``load_report()``
    counts it: the Mamba-2 layers' states and tails."""
    return n_ssm_layers(cfg) * (state_bytes(cfg) + conv_tail_bytes(cfg))


def state_step_bytes(cfg: Dict[str, Any], riders: float) -> float:
    """Bytes ONE Mamba-2 layer's decode step MUST move: each rider's
    state read once and written once, and its convolution tail."""
    return riders * 2.0 * (state_bytes(cfg) + conv_tail_bytes(cfg))


def state_step_flops(cfg: Dict[str, Any], riders: float) -> float:
    """FLOPs of ONE Mamba-2 layer's state step: a multiply-add for the
    decay and the write and one for the read-out, an entry."""
    return riders * 4.0 * d_inner(cfg) * cfg["mamba_d_state"]


def scan_call_bytes(cfg: Dict[str, Any], rows: float, tokens: float,
                    itemsize: int = costs.BF16) -> float:
    """Bytes ONE Mamba-2 layer's chunked recurrence of a prefill call
    MUST move: the rows' states in and out, and a token's x', B, C and
    dt in and its y out, in the model's type."""
    C, N, H = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_n_heads"]
    return (rows * 2.0 * state_bytes(cfg)
            + tokens * (2 * C + 2 * N + H) * itemsize)


def scan_call_flops(cfg: Dict[str, Any], rows: float,
                    tokens: float) -> float:
    """FLOPs of the matrix products of ONE Mamba-2 layer's chunked
    recurrence of a prefill call of ``tokens`` positions in ``rows``
    rows: a position's ``C . B`` against the positions of its chunk at
    or before it (half a chunk in the mean; once a row, one group), its
    heads' read-out of those positions' writes, its read-out of the
    carried state and its write into the state the chunk hands on."""
    C, N = d_inner(cfg), cfg["mamba_d_state"]
    chunk = min(cfg["mamba_chunk_size"], tokens / max(rows, 1.0))
    seen = (chunk + 1) / 2.0
    return tokens * 2.0 * (seen * N + seen * C + 2 * C * N)


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the HELD experts' matmuls of ONE mixture layer's step must
    move: the three matrices of each held expert touched, once, and each
    pair's row in and out. ``experts_touched`` and ``pairs`` are the
    program's counters a mixture layer-step."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    return (2.0 * 3 * pairs * cfg["hidden_size"]
            * cfg["intermediate_size"])


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16,
                      experts_touched: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    token-mixing matrices once, every layer's float32 router, shared
    SwiGLU and the held experts a step really touched
    (``experts_touched`` a mixture layer, from the program's counters;
    the most ``slots`` rows can touch where the caller has none: an
    UPPER bound), every rider's recurrent state and tail in and out in
    the Mamba-2 layers, the K/V of the tokens in context with the step's
    own write in the attention layers, the vocabulary's slice once as
    the head and a row of it a rider."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    if experts_touched is None:
        experts_touched = min(cfg["num_local_experts"],
                              slots * cfg["num_experts_per_tok"])
    mixing = mixing_params(cfg) * itemsize
    ffn = L * (experts_touched * expert_bytes(cfg, itemsize)
               + shared_params(cfg) * itemsize + router_bytes(cfg))
    state = n_ssm_layers(cfg) * state_step_bytes(cfg, slots)
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(mixing + ffn + state + kv + head)


def decode_step_flops(cfg: Dict[str, Any], context_tokens: float,
                      riders: float, pairs: float = None) -> float:
    """FLOPs of ONE decode step: a multiply-add a rider for every matrix
    entry it passes (token mixing, router, shared SwiGLU, the head's
    slice), the held experts' by the routed ``pairs`` a mixture layer
    (the program's counter; half the riders' choices where the caller
    has none: even routing over a half share), the state steps, and the
    attention's scores and read-out over the contexts."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    if pairs is None:
        pairs = (riders * cfg["num_experts_per_tok"]
                 * cfg["num_local_experts"] / cfg["router_width"])
    per_rider = (mixing_params(cfg) + L * (shared_params(cfg)
                                           + D * cfg["router_width"])
                 + cfg["vocab_size"] * D)
    attention = (n_attn_layers(cfg) * 2.0 * 2 * D * context_tokens)
    return float(2.0 * riders * per_rider
                 + L * experts_step_flops(cfg, pairs)
                 + n_ssm_layers(cfg) * state_step_flops(cfg, riders)
                 + attention)


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
# the chunked form's two scopes lie INSIDE ssm_scan and are sorted first:
# a prefill call's recurrence is the three together (SCAN_PARTS), a
# decode step's is ssm_scan alone
SCAN_PARTS = ("ssd_intra", "ssd_carry", "ssm_scan")
SSM_SCOPES = ("ssm_conv", "ssm_gates") + SCAN_PARTS + ("ssm_out",)
ATTENTION_PARTS = ("kv_append", "kv_gather", "attn_scores", "attn_pv")

parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": ATTENTION_PARTS,
    "dense": (*((s, (s,)) for s in SSM_SCOPES),
              *((s, (s,)) for s in MOE_SCOPES),
              ("moe_shared", ("moe_shared",)),
              ("moe", ("moe", "moe_stats")),
              ("ssm_in", ("w_in",)),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}


def typed_parts(run, module: str):
    """``trace_parts.for_run`` of ``module`` where the program names
    this family's scopes; None without a trace or on a program that
    names none."""
    got = trace_parts.for_run(run, module)
    if not got or not under(got, SCAN_PARTS):
        return None
    return got


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """benchmarks/trace_rounds.py's join over this family's table of
    parts, on a program whose decode names ``ssm_scan``."""
    return trace_rounds.decode_parts_by_rounds(
        run, parts, "ssm_scan", tag="granite", groups=(
            ("state-space", SSM_SCOPES),
            ("mixture", MOE_SCOPES + ("moe_shared", "moe")),
            ("attention", ATTENTION_PARTS)))


def main(argv=None) -> int:
    """``python -m benchmarks.families.granite_hybrid [--seeds a,b]
    [--controls a,b]``: the reference's eight controls at the real
    configuration, on the chip (trace_rounds.controls_main)."""
    return trace_rounds.controls_main(
        common.load_family("granite_hybrid", "serve"),
        "granite-4.0-h-small-d10-ep2", argv)


if __name__ == "__main__":
    import os
    os._exit(main())
