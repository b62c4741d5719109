"""The SDAR family (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``:
a Qwen3-MoE-shaped decoder that GENERATES BY DIFFUSION OVER BLOCKS) as
the benchmark sees it. The program serves it as ``ray_tpu.models.sdar``
(explicit ``head_dim``, a per-head query/key norm, a block-causal mask,
``models/mixtral.py``'s mixture with every expert held) through the
engine's block program (serve/step_programs.py ``_jit_decode_blocks``):
a decode step is a forward of a whole block of positions a rider.

THE ``reference_logits`` CONVENTION, for a reader of benchmarks/README.md
(which this family cannot edit). The unedited ``serve_runner.py`` hands
``reference_logits`` the served greedy ``ids [B, P + G]`` and gives its
result to ``parity.margin_rule(logits, ids, P)``, whose index convention
is an autoregressive model's: row ``P - 1 + g`` must hold the logits
FROM WHICH generated token ``g`` WAS CHOSEN. For this model those are
not the logits of position ``P - 1 + g`` of any one forward: token ``g``
sits at position ``P + g``, was chosen from the logits AT that position
(no shift), at whichever forward of its block revealed it, with the
block's other positions revealed or still masked as they then stood. So
this family REPLAYS the generation on ``reference.forward``, block by
block, from the flags the served path began with, one reference forward
a denoising step, and puts each token's logits in the rule's row. The
order of the reveals is not in ``ids``, and two positions' confidences
can lie inside bfloat16's error of each other, so the replay is
order-tolerant (``ORDER_TOL``, ``replay_row``). A position the replay
cannot account for (its served token more than the rule's tolerance
under the reference's best when it was revealed, or revealed out of
every order the strategy allows) is left unscored only while such
positions are a small share (``UNACCOUNTED_SHARE``); past it nothing is
excused and each fails the rule. The tolerance is the harness's,
unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import common, costs, parity, trace_dispatch, trace_parts
from benchmarks import trace_reduce, weights
from benchmarks.reference import sdar as ref

# KV pages and their byte counts are the Llama family's
_llama = common.load_family("llama", "serve")

# the configurations' ``parity`` ``new_tokens``: the harness hands
# ``reference_logits`` the ids without saying where the prompt ends (a
# test ties the two)
NEW_TOKENS = 32

# How far under the strategy's own pick a position's reference
# log-confidence may lie and still have been the served path's pick, as
# a multiple of the margin rule's tolerance (in logit units: 2**-5 of
# the logit scale). A confidence is exp(l_max - logsumexp(l)): both
# terms carry the logits' error, which the rule's tolerance bounds, so
# the log-confidence carries at most twice that.
ORDER_TOL = 2.0

# Of the generated positions, the share the replay may fail to account
# for and still leave unscored (``reference_logits``; families/
# kimi_linear.py has the rule's argument, for a mixture whose choice of
# experts flips at bfloat16, and this is its use at the granularity of a
# reveal). Readings (my chip runs, PR 63; 8 prompts x 32 tokens = 256
# positions a seed): the served path 0, 1 or 2 positions (0-0.78 %)
# over eighteen seeds, none in eleven of them (a served token 0.15-0.37
# under its position's best against a tolerance of 0.14-0.16: a
# mixture's choice of experts flipped at bfloat16, there or before); the
# reference with every matrix in float8 e4m3 9, 12, 21 and 43 positions
# (3.5-16.8 %) over four. 1.6 % is 4 positions of 256: twice the served
# path's most, under half the control's least.
UNACCOUNTED_SHARE = 0.016


def generation(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The file's ``generation`` group: what ``config.json`` has no key
    for (the file's ``assumed`` says where each value is from)."""
    return cfg["generation"]


def program_config(cfg: Dict[str, Any]):
    """SdarConfig from the published key names and the file's
    ``generation`` group."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.sdar import SdarConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program has no SDAR model "
                         f"(ray_tpu.models.sdar): {e}")
    have = {f.name for f in dataclasses.fields(SdarConfig)}
    lacks = [n for n in ("head_dim", "block_length", "mask_token_id",
                         "denoising_steps", "remasking",
                         "confidence_threshold") if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's SdarConfig cannot "
                         f"express SDAR: it has no {lacks}")
    if cfg.get("attention_bias") or cfg.get("rope_scaling") is not None:
        raise SystemExit("benchmarks: the program has no attention bias "
                         "and scales no rope")
    if cfg.get("use_sliding_window") or cfg.get("sliding_window"):
        raise SystemExit("benchmarks: the program's SDAR has no sliding "
                         "window")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step") != 1:
        raise SystemExit("benchmarks: the program's SDAR has a mixture in "
                         "every layer")
    if cfg.get("hidden_act") != "silu" or not cfg.get("norm_topk_prob"):
        raise SystemExit("benchmarks: the program's mixture is SwiGLU "
                         "with renormalised gates")
    gen = generation(cfg)
    if gen.get("temperature", 0.0) != 0.0:
        raise SystemExit("benchmarks: correct compares greedy tokens")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return SdarConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=True, norm_eps=float(cfg["rms_norm_eps"]),
        dtype=dtype, param_dtype=dtype,
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        block_length=int(gen["block_length"]),
        mask_token_id=int(gen["mask_token_id"]),
        denoising_steps=int(gen["denoising_steps"]),
        remasking=gen["remasking"],
        confidence_threshold=float(gen["confidence_threshold"]))


def model(pcfg):
    from ray_tpu.models.sdar import Sdar
    return Sdar(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None                # every norm's scale ([D], [hd]): ones
    if "tok_embeddings" in name or "lm_head" in name or "router" in name:
        return 0.02                # the model's own (OLMoE's file's)
    # 1/sqrt(fan_in); an expert tensor is [E, in, out]
    return leaf.shape[-2] ** -0.5


def init_params(shapes, seed: int, shardings=None):
    """The ``params`` collection only (families/olmoe.py says why)."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, m = lp["attention"], lp["moe"]
        layers.append({**_llama.reference_attention_weights(lp),
                       "q_norm": a["q_norm"]["scale"],
                       "k_norm": a["k_norm"]["scale"],
                       "router": m["router"], "w_gate": m["w1"],
                       "w_up": m["w3"], "w_down": m["w2"]})
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    return dict(n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
                eps=pcfg.norm_eps, theta=pcfg.rope_theta,
                top_k=pcfg.num_experts_per_tok,
                mask_token_id=pcfg.mask_token_id)


def reference_forward(rw, ids, pcfg, masked=None, **control):
    """The plain reference's block-causal logits [B, T, V] of ids."""
    sizes = {**_sizes(pcfg), **control}
    L = sizes.pop("block_length", pcfg.block_length)
    return ref.forward(rw, ids, L, masked, **sizes)


def reference_generate(rw, prompt, n_new, pcfg, **control):
    """``reference.generate`` under the program config's constants."""
    bd = pcfg.block_decode
    return ref.generate(
        rw, prompt, n_new, block_length=bd.block_length,
        denoising_steps=bd.denoising_steps, remasking=bd.remasking,
        confidence_threshold=bd.confidence_threshold,
        **{**_sizes(pcfg), **control})


# ------------------------------------------------------------ the replay

def _candidates(masked, known, ok_token, log_conf, n, strategy, threshold,
                slack):
    """What the served path may have revealed at a step: [(set of masked
    positions as a sorted tuple, its members the reference CANNOT
    account for), ...], the sets it can account for whole first, the
    reference's own pick first of all. A member is accounted for when
    its served token is within the rule's tolerance of its position's
    best (``ok_token``; a position whose served token is not known
    always is) AND the strategy may have picked it from confidences
    that are each off by up to ``slack`` (``ORDER_TOL`` times the
    tolerance): ``sequential`` the first n masked, nothing else; the
    two confidence strategies any n masked positions, a member in order
    when it lies at most ``slack`` under the n-th most confident; the
    dynamic one, besides, every position over the threshold where those
    that may be over it are at least n (each position within ``slack``
    of the threshold either way: in order by construction)."""
    import itertools
    at = [int(i) for i in np.flatnonzero(masked)]
    n = min(n, len(at))
    own = tuple(int(i) for i in np.flatnonzero(ref.reveal(
        masked, log_conf, n, strategy, threshold)))

    def bad_token(i):
        return known[i] and not ok_token[i]
    out = {}
    if strategy == "sequential":
        out[tuple(at[:n])] = tuple(i for i in at[:n] if bad_token(i))
    else:
        top_n = True
        if strategy == "low_confidence_dynamic":
            line = np.log(threshold) if threshold > 0 else -np.inf
            surely = [i for i in at if log_conf[i] > line + slack]
            maybe = [i for i in at if line - slack < log_conf[i]
                     <= line + slack]
            for r in range(len(maybe) + 1):
                for extra in itertools.combinations(maybe, r):
                    high = tuple(sorted(surely + list(extra)))
                    if len(high) >= n and high:
                        out[high] = tuple(i for i in high if bad_token(i))
            top_n = len(surely) < n     # else the top-n branch never ran
        if top_n:
            kth = np.sort(log_conf[at])[-n]
            for c in itertools.combinations(at, n):
                out.setdefault(c, tuple(
                    i for i in c
                    if bad_token(i) or log_conf[i] < kth - slack))
    order = sorted(out, key=lambda c: (len(out[c]), c != own))
    return [(c, out[c]) for c in order]


def replay_row(forward, ids, P: int, G: int, bd, tol_fraction: float,
               shift: int = 0):
    """Replay ONE row's generation on the plain reference, teacher-forced
    on the served tokens ``ids [P + G]``: block by block, from the flags
    the served path began with (the prompt's remainder revealed, the
    rest masked), one reference forward a denoising step
    (``forward(x [T], flags [T], lo, hi)`` -> logits [L, V] of the block
    at lo..hi). At a step the served path revealed some set of masked
    positions; what it may have been is ``_candidates``', explored
    depth-first, the reference's own pick first (L = 4: a handful of
    paths). A path that the reference accounts for WHOLE (every token
    within the tolerance of its position's best when it was revealed,
    every reveal in an order the strategy allows) is looked for first;
    where there is none, the path with the fewest members it cannot
    account for. Returns (rows [G, V]: row g the logits generated token
    g was chosen from on that path, ``accounted`` [G] bool, back-tracks,
    the most of ``ORDER_TOL``'s room an accounted reveal used, in
    tolerances). ``shift``: the CONTROL that reads a position's logits
    ``shift`` rows early (an autoregressive model's convention)."""
    L, T = bd.block_length, bd.denoising_steps
    counts = ref.transfer_counts(L, T)
    n_blocks = -(-(P + G) // L)
    total = n_blocks * L
    x = np.zeros((total,), np.int32)
    x[:P + G] = ids
    known = np.arange(total) < P + G
    rows = None
    accounted = np.ones((G,), bool)
    scale = [0.0]                   # the logit scale seen so far
    backtracks = [0]
    slack_used = [0.0]
    seen = {}                       # a block's forwards, by its state

    def look(tokens, flags, lo, hi):
        key = (lo, tokens[lo:hi].tobytes(), flags[lo:hi].tobytes())
        if key not in seen:
            seen[key] = np.asarray(
                forward(tokens, flags, lo - shift, hi - shift), np.float32)
            scale[0] = max(scale[0], float(np.abs(seen[key]).max()))
        return seen[key]

    def descend(tokens, flags, lo, hi, s, budget):
        """The first path from step ``s`` that leaves no mask in the
        block with at most ``budget`` members unaccounted for:
        [(positions, those unaccounted for, logits [L, V], room used),
        ...] from this step on, or None."""
        block = flags[lo:hi]
        if not block.any():
            return []
        if s >= T:
            return None
        logits = look(tokens, flags, lo, hi)
        tol = tol_fraction * scale[0]
        served = np.take_along_axis(
            logits, tokens[lo:hi, None].astype(np.int64), -1)[:, 0]
        best = logits.max(-1)
        ok_token = best - served <= tol
        lse = best + np.log(np.exp(logits - best[:, None]).sum(-1))
        # its reference log-confidence: the served token's where the
        # reference accounts for that token, else the position's best
        log_conf = np.where(known[lo:hi] & ok_token, served, best) - lse
        first = True
        for pick, lost in _candidates(
                block, known[lo:hi], ok_token, log_conf, counts[s],
                bd.remasking, bd.confidence_threshold, ORDER_TOL * tol):
            if len(lost) > budget:
                break               # sorted by what they cost
            if not first:
                backtracks[0] += 1
            first = False
            nxt = flags.copy()
            nxt[lo + np.asarray(pick)] = False
            toks = tokens
            unknown = [i for i in pick if not known[lo + i]]
            if unknown:             # past the request's budget: its own
                toks = tokens.copy()
                toks[lo + np.asarray(unknown)] = logits[unknown].argmax(-1)
            rest = descend(toks, nxt, lo, hi, s + 1, budget - len(lost))
            if rest is not None:
                room = 0.0
                if bd.remasking != "sequential" and tol > 0:
                    at = np.flatnonzero(block)
                    kth = np.sort(log_conf[at])[-min(counts[s], len(at))]
                    mine = [i for i in pick if i not in lost]
                    if mine:
                        room = float(kth - log_conf[mine].min()) / tol
                return [(pick, lost, logits, room)] + rest
        return None

    flags = np.arange(total) >= P
    for b in range(P // L, n_blocks):
        lo, hi = b * L, (b + 1) * L
        for budget in range(L + 1):
            path = descend(x, flags, lo, hi, 0, budget)
            if path is not None:
                break
        for pick, lost, logits, room in path:
            if rows is None:
                rows = np.zeros((G, logits.shape[-1]), np.float32)
            slack_used[0] = max(slack_used[0], room)
            for i in pick:
                g = lo + i - P
                if 0 <= g < G:
                    rows[g], accounted[g] = logits[i], i not in lost
        flags[lo:hi] = False        # committed: the served tokens stand
    return rows, accounted, backtracks[0], slack_used[0]


def reference_logits(rw, ids, pcfg, **control):
    """[B, P + G, V] for ``parity.margin_rule(logits, ids, P)``: row
    ``P - 1 + g`` holds the plain reference's logits FROM WHICH
    generated token ``g`` was chosen (the rule's index convention is an
    autoregressive model's; the module docstring says why this family
    must replay), the other rows zero (never read). G is ``NEW_TOKENS``.

    The positions the replay cannot account for (the served token more
    than the rule's tolerance under the reference's best when it was
    revealed, or revealed out of every order the strategy allows within
    ``ORDER_TOL``) get a row of zeros (all tokens tie: neither failed
    nor counted decisive) while they are at most ``UNACCOUNTED_SHARE``
    of the generated positions; where they are more, nothing is excused
    and each of them fails the rule (a reveal out of order by having
    its served token's entry lowered to the row's least). The
    tolerance is the harness's, unchanged.
    ``control``: the reference's controls (``block_length`` 1: the
    causal mask; ``whole_width_norm``; ``lower_precision``) and the
    replay's (``shift``)."""
    ids = np.asarray(ids)
    B, T = ids.shape
    G = min(NEW_TOKENS, T - 1)
    P = T - G
    shift = control.pop("shift", 0)
    sizes = {**_sizes(pcfg), **control}
    L = sizes.pop("block_length", pcfg.block_length)
    bd = pcfg.block_decode

    def forward(tokens, flags, lo, hi):
        return ref.forward(rw, tokens[None], L, flags[None],
                           rows=(lo, hi), **sizes)[0]
    window, accounted = None, np.ones((B, G), bool)
    backtracks, slack = 0, 0.0
    for b in range(B):
        rows, accounted[b], back, used = replay_row(
            forward, ids[b], P, G, bd, parity.LOGIT_TOL_FRACTION, shift)
        backtracks, slack = backtracks + back, max(slack, used)
        if window is None:
            window = np.zeros((B, G, rows.shape[-1]), np.float32)
        window[b] = rows
    lost = ~accounted
    share = float(lost.mean())
    excused = share <= UNACCOUNTED_SHARE
    served = np.take_along_axis(window, ids[:, P:, None].astype(np.int64),
                                -1)[..., 0]
    scale = float(np.abs(window[accounted]).max()) if accounted.any() \
        else 0.0
    over = lost & (window.max(-1) - served
                   > parity.LOGIT_TOL_FRACTION * scale)
    common.log(
        f"[correct] sdar: replayed {B} x {G} generated tokens in blocks "
        f"of {bd.block_length} ({bd.remasking}, {bd.denoising_steps} "
        f"steps) on the plain reference: {int(lost.sum())} of {lost.size} "
        f"positions it cannot account for ({100 * share:.2f} %; limit "
        f"{100 * UNACCOUNTED_SHARE:.2f} %): {int(over.sum())} with the "
        f"served token over the tolerance (worst "
        f"{float((window.max(-1) - served)[lost].max()) if lost.any() else 0.0:.4f}"
        f"), {int((lost & ~over).sum())} revealed out of order; "
        + ("not scored" if excused else "too many: scored as they are")
        + f"; by row {lost.sum(-1).tolist()}; {backtracks} back-tracks; the "
        f"reveals accounted for lay at most {slack:.3f} tolerances under "
        f"the strategy's own pick (limit {ORDER_TOL})")
    if excused:
        window[lost] = 0.0
    else:
        b_, g_ = np.nonzero(lost)
        window[b_, g_, ids[b_, P + g_]] = window[b_, g_].min(-1)
    logits = np.zeros((B, T, window.shape[-1]), np.float32)
    logits[:, P - 1:P - 1 + G] = window
    return logits


kv_bytes_per_token = _llama.kv_bytes_per_token


# ---------------------------------------------------------- byte counts

def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"]


def block_length(cfg: Dict[str, Any]) -> int:
    return int(generation(cfg)["block_length"])


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the experts' matmuls of ONE layer's forward must move: the
    three matrices of each expert touched, once, and each routed pair's
    row in and out (families/olmoe.py's count at this family's expert
    width). ``experts_touched`` and ``pairs`` are what the program's
    counters say, a layer-step."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """FLOPs of one layer's expert matmuls over ``pairs`` routed rows."""
    return (2.0 * 3 * pairs * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def attention_weight_bytes(cfg: Dict[str, Any],
                           itemsize: int = costs.BF16) -> int:
    """One layer's four projections and its two [head_dim] norm scales
    (float32)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * D * H * hd + 2 * D * KH * hd) * itemsize + 2 * hd * 4


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes ONE forward of ``slots`` riders x L positions (a step of
    the block program) must move: every layer's attention matrices
    once, its float32 router, the experts the forward REALLY touched a
    layer (``experts_touched``, from the program's counters; where the
    caller has none, the most the rows can touch, min(E, rows x k): an
    UPPER bound, so a share over it may pass 100 % and must not be
    reported) with each routed pair's row in and out, the head once, an
    embedding row a position, the K/V of the tokens really in context
    (``context_tokens``, summed over the riders: what lies before each
    rider's block) read once, and the block's own K/V written and read.
    The float32 logits of the block (rows x vocabulary, written and
    read back for the choice) are left out: a fused head need not move
    them."""
    D, L = cfg["hidden_size"], block_length(cfg)
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    rows = slots * L
    if experts_touched is None:
        experts_touched = min(E, rows * k)
    layer = (attention_weight_bytes(cfg, itemsize) + D * E * 4
             + experts_step_bytes(cfg, experts_touched, rows * k,
                                  itemsize))
    head = cfg["vocab_size"] * D * itemsize + rows * D * itemsize
    kv = (context_tokens + 2 * rows) * kv_bytes_per_token(cfg, itemsize)
    return float(cfg["num_hidden_layers"] * layer + head + kv)


def decode_step_flops(cfg: Dict[str, Any], context_tokens: float,
                      slots: float) -> float:
    """FLOPs of ONE forward of ``slots`` riders x L positions: the
    projections, the router, k experts a position, the head, and L
    queries a rider over its context and its own block (scores and
    values)."""
    D, hd, L = cfg["hidden_size"], cfg["head_dim"], block_length(cfg)
    H, KH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rows = slots * L
    per_row = (2 * D * H * hd + 2 * D * KH * hd + D * cfg["num_experts"]
               + 3 * D * cfg["num_experts_per_tok"]
               * cfg["moe_intermediate_size"])
    keys = L * context_tokens + rows * L      # (query, key) pairs
    attention = 2 * 2.0 * H * hd * keys
    return float(cfg["num_hidden_layers"] * (2.0 * rows * per_row
                                             + attention)
                 + 2.0 * rows * D * cfg["vocab_size"])


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")

# families/olmoe.py's table: the mixture's four scopes as parts of their
# own, the query/key norms with the norms
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": trace_parts.DEFAULT_PARTS["attention"],
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("norms", ("attention_norm", "ffn_norm", "norm", "q_norm",
                         "k_norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}


def denoise_counters(run, span=None) -> Optional[Dict[str, int]]:
    """The block program's counters over ``span`` (the window where
    None): the ``round`` events' ``denoise_*`` keys summed
    (serve/step_programs.py ``BLOCK_COUNTERS``). None on a program whose
    events lack them (the parent's: it has no such program)."""
    t0, t1 = span or run.window
    keys = ("rider_forwards", "commits", "revealed", "emitted",
            "idle_forwards")
    got = dict.fromkeys(keys, 0)
    seen = False
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1 and \
                "denoise_rider_forwards" in e[5]:
            seen = True
            for key in keys:
                got[key] += e[5].get("denoise_" + key, 0)
    return got if seen and got["rider_forwards"] else None


def decode_counters(run) -> Optional[Dict[str, float]]:
    """The mixture's counters of the decode program's forwards, a
    layer-step (families/laguna.py's reading), over the traced seconds
    or, where those hold none, the window; None without them."""
    spans = [run.window]
    if getattr(run, "trace_span", None) and None not in run.trace_span:
        spans.insert(0, run.trace_span)
    for t0, t1 in spans:
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


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched
    (families/mellum2.py's join, over this family's table of parts):
    {"parts": {part: s}, "module_s", "steps" (forwards), "riders" (a
    forward's mean of the slots in the dispatch), "rounds"}. None
    without a joined trace, or where the spans and the rows disagree in
    number."""
    if hasattr(run, "_sdar_decode_parts"):
        return run._sdar_decode_parts
    run._sdar_decode_parts = None
    got = trace_dispatch.joined(run)
    if not got or not trace_parts.for_run(run, "jit_decode"):
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
    riders = sum(by_round[r["round"]].get("decode_riders", 0) * r["steps"]
                 for r in rows)
    run._sdar_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "rounds": [r["round"] for r in rows]}
    attn = sum(split["parts"].get(p, 0.0) for p in parts["attention"])
    common.log(f"[sdar] jit_decode over the {len(rows)} matched "
               f"executions: {steps} forwards of {riders / steps:.1f} "
               f"riders x {block_length(run.cfg)} positions; a forward "
               f"{1e3 * split['module_s'] / steps:.3f} ms: attention "
               f"{1e3 * attn / steps:.3f}; "
               + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
                   split["parts"].items(), key=lambda kv: -kv[1])[:16]))
    return run._sdar_decode_parts
