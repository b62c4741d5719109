"""The Phi-4-mini-flash family (microsoft/Phi-4-mini-flash-reasoning,
``model_type: phi4flash``: the SambaY decoder-hybrid-decoder with
differential attention) as the benchmark sees it: of 32 layers, nine
SELECTIVE STATE-SPACE layers (a float32 ``[16, 5120]`` state and a
convolution tail, a SLOT of the engine), eight differential-attention
layers under a sliding window of 512 (a ring a slot), ONE full
differential-attention layer whose K/V pages SEVEN cross layers read
and none of them keeps, and seven gated memory units that read the
ninth state-space layer's output in the same call; LayerNorm with a
bias, a SwiGLU in every layer, no position encoding, a tied head. The
program serves it as ``ray_tpu.models.phi4flash``; ``program_config``
refuses at once (SystemExit, before a weight is made) a program that has
no such module and a file whose keys the module cannot express.

Nothing is cut: every layer, every head, the whole vocabulary. What the
catalog row does not carry is the file's ``assumed`` (one line each
below, with the equation of benchmarks/reference/phi4flash.py it
enters):

- ``mamba_sizes`` (d_state 16, d_conv 4, expand 2, dt_rank 160): the
  state-space layer's shapes, equations 1's ``W_in``, ``conv``, ``W_x``,
  ``W_dt`` and the state ``h`` [c, n];
- ``layout``: which layer is which (``mixers``), the layer loop;
- ``memory``: the memory is ``y`` before the gate, ``D u'`` included,
  equation 4's ``m``;
- ``differential_pairs``: heads (2i, 2i+1) pair, the value is a K/V
  pair's two heads side by side, equation 2's ``P1 v_g - lambda P2
  v_g``;
- ``lambdas``: ``lambda_l`` from four learned vectors and ``lambda0_l =
  0.8 - 0.6 exp(-0.3 l)``, the sub-norm and ``1 - lambda0``, equation 2;
- ``window``: the last 512 positions, the query's own included,
  equation 2's mask ``s > t - W``;
- ``layer_norm``: LayerNorm with scale and bias, the block's ``LN``;
- ``biases``: on q, k, v and o of the attention and nowhere else,
  equations 2 and 3's ``+ b``;
- ``nope``: no position encoding, every equation;
- ``swiglu_halves``: which half of the fused up-projection is gated, the
  block's ``SiLU(g) * u``;
- ``weights``: the seeded weights' scales (``_std_of``, ``_moved``).

The byte counts are BY KIND of layer and count what the arithmetic
MUST move (a K/V entry a token is 5,120 B; the chip's page keeps 16
head rows for the 10 pairs, 8,192 B: PERF.md section 4), and the
readers divide a scope's time by the layers of that kind and by the
decode steps the engine's own rounds dispatched
(``decode_parts_by_rounds``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmarks import (common, costs, trace_dispatch, trace_parts,
                        trace_reduce, weights)
from benchmarks.reference import phi4flash as ref

SSM, SLIDING, FULL, CROSS, GMU = (ref.SSM, ref.SLIDING, ref.FULL,
                                  ref.CROSS, ref.GMU)
CONTROLS = ref.CONTROLS

# What Laguna's family (and Mellum 2's behind it) counts the same way is
# that file's: a ring's length under a deployment, the sums of a split's
# parts, the sliding layers' parts and the compiler's whole-ring copies
# by opcode. The byte counts are this file's: the configurations name
# their sizes by other keys.
_laguna = common.load_family("laguna", "serve")
ring_len, under, sliding_s = (_laguna.ring_len, _laguna.under,
                              _laguna.sliding_s)
SLIDING_PARTS, RING_COPIES = _laguna.SLIDING_PARTS, _laguna.RING_COPIES


def mixers(cfg: Dict[str, Any]):
    return ref.mixers(cfg["num_hidden_layers"])


def n_ssm_layers(cfg: Dict[str, Any]) -> int:
    return mixers(cfg).count(SSM)


def n_sliding_layers(cfg: Dict[str, Any]) -> int:
    return mixers(cfg).count(SLIDING)


def n_gmu_layers(cfg: Dict[str, Any]) -> int:
    return mixers(cfg).count(GMU)


def n_page_readers(cfg: Dict[str, Any]) -> int:
    """The layers that READ the one layer's K/V pages: that layer and
    the cross layers."""
    return mixers(cfg).count(FULL) + mixers(cfg).count(CROSS)


def d_inner(cfg: Dict[str, Any]) -> int:
    return cfg["mamba"]["expand"] * cfg["hidden_size"]


def program_config(cfg: Dict[str, Any]):
    """Phi4FlashConfig from the published key names (and the assumed
    state-space sizes, the file's ``mamba`` group)."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.phi4flash import Phi4FlashConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Phi-4-mini-flash: it has no "
                         f"ray_tpu.models.phi4flash ({e})")
    refused = {
        "model_type other than phi4flash": cfg["model_type"] != "phi4flash",
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "an untied head": not cfg["tie_word_embeddings"],
        "mlp_bias": cfg["mlp_bias"],
        "lm_head_bias": cfg["lm_head_bias"],
        "dropout": cfg["embd_pdrop"] or cfg["resid_pdrop"],
        "mb_per_layer other than 2": cfg["mb_per_layer"] != 2,
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Phi-4-mini-flash has "
                         f"no {[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    m = cfg["mamba"]
    try:
        return Phi4FlashConfig(
            vocab_size=cfg["vocab_size"],
            max_seq_len=cfg["max_position_embeddings"],
            dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
            attn_heads=cfg["num_attention_heads"],
            attn_kv_heads=cfg["num_key_value_heads"],
            hidden_dim=cfg["intermediate_size"],
            mb_per_layer=cfg["mb_per_layer"],
            sliding_window=cfg["sliding_window"],
            ssm_state=m["d_state"], ssm_conv=m["d_conv"],
            ssm_expand=m["expand"], ssm_dt_rank=m["dt_rank"],
            norm_eps=float(cfg["layer_norm_eps"]), dtype=dtype,
            param_dtype=dtype)
    except ValueError as e:
        raise SystemExit(f"benchmarks: the program's Phi-4-mini-flash "
                         f"refuses the file: {e}")


def model(pcfg):
    from ray_tpu.models.phi4flash import Phi4Flash
    return Phi4Flash(pcfg)


def _std_of(name: str, leaf):
    if "A_log" in name or "dt_bias" in name or "['D']" in name:
        return 1.0                          # moved by _moved below
    if leaf.ndim == 1:
        if "scale" in name or "subln" in name:
            return None                     # every norm's scale: ones
        # LayerNorm's and the attention's biases, the convolution's and
        # the four lambda vectors (N(0, 0.1), ISSUE 60)
        return 0.1
    if "tok_embeddings" in name:
        return 0.02                         # tied: the head's own scale
    # 1/sqrt(fan_in); the convolution [K, channels] by its width
    return leaf.shape[0] ** -0.5


def _moved(params):
    """A_log, dt_bias and D from their standard normals: A = exp(1 + n)
    (most of 1-7.4, Mamba's own 1..16 in spread), b_dt = -4 + 1.5 n
    (softplus around 0.02, so a step's decay exp(delta A) spans 0.5-0.99
    for most channels and a few decay hard), D = 1 + 0.5 n."""
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


def init_params(shapes, seed: int, shardings=None):
    """Normal, std 1/sqrt(fan_in) for matrices, 0.02 for the tied
    embedding, 0.1 for biases and the lambda vectors, ones for every
    norm's scale; the state-space layer's A, b_dt and D by ``_moved``."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return _moved(weights.seeded_normal(only(shapes), seed, _std_of,
                                        only(shardings)))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names: the
    program's own arrays (the reference upcasts a layer's at a time).
    The program keeps a K/V pair's two heads side by side, which is the
    published heads' own order: nothing is permuted."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, f = lp["attention"], lp["feed_forward"]
        w = {"ln1": lp["attention_norm"]["scale"],
             "ln1_bias": lp["attention_norm"]["bias"],
             "ln2": lp["ffn_norm"]["scale"],
             "ln2_bias": lp["ffn_norm"]["bias"],
             "w_gate": f["w1"]["kernel"], "w_up": f["w3"]["kernel"],
             "w_down": f["w2"]["kernel"]}
        if "conv" in a:
            w.update(w_in=a["w_in"]["kernel"], conv=a["conv"],
                     conv_bias=a["conv_bias"], w_x=a["w_x"]["kernel"],
                     w_dt=a["w_dt"]["kernel"], dt_bias=a["dt_bias"],
                     A_log=a["A_log"], D=a["D"], w_out=a["wo"]["kernel"])
        elif "w1" in a:
            w.update(w1=a["w1"]["kernel"], w2=a["w2"]["kernel"])
        else:
            w.update(wq=a["wq"]["kernel"], bq=a["wq"]["bias"],
                     wo=a["wo"]["kernel"], bo=a["wo"]["bias"],
                     subln=a["subln"],
                     **{n: a[n] for n in ("lambda_q1", "lambda_k1",
                                          "lambda_q2", "lambda_k2")})
            if "wk" in a:
                w.update(wk=a["wk"]["kernel"], bk=a["wk"]["bias"],
                         wv=a["wv"]["kernel"], bv=a["wv"]["bias"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "norm": p["norm"]["scale"],
            "norm_bias": p["norm"]["bias"], "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model: the
    PUBLISHED heads (the program's paired layout is its own)."""
    return dict(n_heads=pcfg.attn_heads, n_kv_heads=pcfg.attn_kv_heads,
                eps=pcfg.norm_eps, window=pcfg.sliding_window)


def reference_forward(rw, ids, pcfg, **control):
    """The plain reference's logits [B, T, V] of ids [B, T].
    ``control``: the reference's controls (``CONTROLS``), which the
    harness never sets."""
    return ref.forward(rw, ids, **_sizes(pcfg), **control)


# The generated positions the comparison that decides ``correct`` reads
# are each row's last ``SCORED_TAIL`` (the configurations' ``parity``
# ``new_tokens``: the harness hands ``reference_logits`` the ids without
# saying where the prompt ends; a test ties the two).
SCORED_TAIL = 64


def reference_logits(rw, ids, pcfg, **control):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule, unchanged: the served token's reference logit
    within 2**-5 of the logit scale of the best, at EVERY generated
    position): the plain reference's, [B, T, V] with the rows that
    predict each prompt's last ``SCORED_TAIL`` tokens filled and the
    others zero (never read: the rule takes positions P - 1 .. P + G -
    2). The blocks run over every position (one row and one key pair of
    an attention at a time); the head, a vocabulary of 200,064 in
    float32, over the scored rows alone, so that it fits beside a chip
    this cell fills. Nothing is excused: this family has no choice of
    experts to flip. The ``[correct] phi4flash:`` line says how far
    under the rule's one tolerance a run stood: the served tokens'
    deficits (the reference's best logit less the served token's), their
    mean and their largest, in tolerances (on the chip 0.004-0.014 and
    0.20-0.55 over nineteen seeds with the residual stream in float32,
    0.53-0.94 at worst with it in bfloat16; the reference's controls
    0.22-5.6 and 1.8-15.3: PERF.md section 6, PR 60)."""
    x = ref.blocks(rw, ids, **_sizes(pcfg), **control)
    ids = np.asarray(ids)
    T = ids.shape[1]
    G = min(SCORED_TAIL, T - 1)
    window = np.asarray(ref.head(
        x[:, T - 1 - G:T - 1], rw["norm"], rw["norm_bias"],
        ref.embedding(rw, **control), eps=pcfg.norm_eps))
    served = np.take_along_axis(window, ids[:, T - G:, None], -1)[..., 0]
    deficit = window.max(-1) - served
    tol = 2.0 ** -5 * float(np.abs(window).max())
    common.log(
        f"[correct] phi4flash: over {deficit.size} generated positions "
        f"the served token lies {float(deficit.mean()) / tol:.4f} "
        f"tolerances under the reference's best in the mean and "
        f"{float(deficit.max()) / tol:.3f} at worst ({int((deficit > tol).sum())} "
        f"over one: the rule fails on those); tolerance {tol:.4f}")
    logits = np.zeros((ids.shape[0], T, window.shape[-1]), np.float32)
    logits[:, T - 1 - G:T - 1] = window
    return logits


# ---------------------------------------------------------- byte counts

def mixing_params(cfg: Dict[str, Any], mixer: str) -> int:
    """One layer's token mixing, matrices only (biases, norms, lambdas,
    A and D, a few thousand numbers, are left out but A's 16 x 5,120)."""
    D, C = cfg["hidden_size"], d_inner(cfg)
    m = cfg["mamba"]
    if mixer == SSM:
        return (D * 2 * C + C * D + m["d_conv"] * C
                + C * (m["dt_rank"] + 2 * m["d_state"])
                + m["dt_rank"] * C + m["d_state"] * C)
    if mixer == GMU:
        return 2 * D * C
    hd = D // cfg["num_attention_heads"]
    q_o = 2 * D * cfg["num_attention_heads"] * hd
    return q_o if mixer == CROSS else (
        q_o + 2 * D * cfg["num_key_value_heads"] * hd)


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def model_params(cfg: Dict[str, Any]) -> int:
    """The matrices: every layer's, and the embedding that is the head."""
    return (sum(mixing_params(cfg, m) + mlp_params(cfg)
                for m in mixers(cfg))
            + cfg["vocab_size"] * cfg["hidden_size"])


def key_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One position's key AND value in ONE layer, every K/V head, as
    the arithmetic needs them (a page keeps 16 head rows for the 10
    pairs: ``page_token_bytes``)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * hd * itemsize


def page_token_bytes(cfg: Dict[str, Any],
                     itemsize: int = costs.BF16) -> int:
    """What one token costs the POOL as the chip keeps it: K and V of
    the pairs rounded up to whole 16-row tiles (models/phi4flash.py
    ``kv_page_heads``), in the one layer that has pages."""
    hd = 2 * cfg["hidden_size"] // cfg["num_attention_heads"]
    pairs = cfg["num_key_value_heads"] // 2
    return 2 * (-(-pairs // 16) * 16) * hd * itemsize


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """K and V of one token over the layers that HAVE K/V: one."""
    return key_bytes(cfg, itemsize)


def ring_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's ring in ONE sliding layer, whatever its context."""
    return ring_len(cfg) * key_bytes(cfg)


def sliding_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """One slot's rings over the sliding layers."""
    return n_sliding_layers(cfg) * ring_bytes(cfg)


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's state in ONE state-space layer: d_state x d_inner
    float32."""
    return cfg["mamba"]["d_state"] * d_inner(cfg) * 4


def conv_tail_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One slot's convolution tail in ONE state-space layer."""
    return (cfg["mamba"]["d_conv"] - 1) * d_inner(cfg) * itemsize


def state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """What one slot keeps whatever its context, as ``load_report()``
    counts it: the state-space layers' states and tails, and the sliding
    layers' rings."""
    return (n_ssm_layers(cfg) * (state_bytes(cfg) + conv_tail_bytes(cfg))
            + sliding_bytes_per_slot(cfg))


def unaged_bytes(cfg: Dict[str, Any], kv_bytes_in_use: float) -> float:
    """What the sliding layers would hold for the contexts whose full
    layer holds ``kv_bytes_in_use`` of pages (as the pool keeps them),
    did nothing age: those tokens' keys and values a layer."""
    tokens = kv_bytes_in_use / page_token_bytes(cfg)
    return tokens * key_bytes(cfg) * n_sliding_layers(cfg)


def state_step_bytes(cfg: Dict[str, Any], riders: float) -> float:
    """Bytes ONE state-space layer's decode step MUST move: each rider's
    state read once and written once, and its convolution tail."""
    return riders * 2.0 * (state_bytes(cfg) + conv_tail_bytes(cfg))


def scan_call_bytes(cfg: Dict[str, Any], rows: float,
                    tokens: float, itemsize: int = costs.BF16) -> float:
    """Bytes ONE state-space layer's scan of a prefill call MUST move:
    the rows' states in and out, and a token's u', delta, B and C in and
    its y out, in the model's type."""
    C, N = d_inner(cfg), cfg["mamba"]["d_state"]
    return (rows * 2.0 * state_bytes(cfg)
            + tokens * (3 * C + 2 * N) * itemsize)


def sliding_step_bytes(cfg: Dict[str, Any], keys: float,
                       itemsize: int = costs.BF16) -> float:
    """Bytes ONE sliding layer's decode step MUST move for its cache:
    ``keys`` = the riders' min(context, window) summed, each key and
    value read once."""
    return keys * key_bytes(cfg, itemsize)


def sliding_step_flops(cfg: Dict[str, Any], keys: float) -> float:
    """FLOPs of ONE sliding layer's attention of one decode step over
    ``keys``: every published head's score (64 wide) and its read-out
    (128 wide: the pair's value under each map)."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * cfg["num_attention_heads"] * (hd + 2 * hd) * keys


def shared_step_bytes(cfg: Dict[str, Any], reads: float,
                      itemsize: int = costs.BF16) -> float:
    """Bytes the layers that read the ONE layer's pages MUST move in a
    decode step: ``reads`` = the riders' context entries x the reading
    layers (the rounds' ``decode_shared_kv_reads``)."""
    return reads * key_bytes(cfg, itemsize)


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16) -> float:
    """Bytes ONE decode step of the whole batch must move: every
    layer's matrices once, the tied embedding once as the head and a row
    of it a rider, the ONE layer's K/V of the tokens in context once a
    READING layer (with the step's own write), every rider's window in
    the sliding layers (the contexts cut at the window: taken as
    ``min(context_tokens, slots x window)``, exact where every context
    lies on one side of the window), and every rider's state and tail
    in and out in the state-space layers."""
    D = cfg["hidden_size"]
    weights_ = model_params(cfg) * itemsize
    embed_rows = slots * D * itemsize
    pages = (context_tokens * n_page_readers(cfg) + slots) * key_bytes(
        cfg, itemsize)
    keys = min(context_tokens, slots * cfg["sliding_window"])
    rings = n_sliding_layers(cfg) * (keys + slots) * key_bytes(cfg, itemsize)
    state = n_ssm_layers(cfg) * state_step_bytes(cfg, slots)
    return float(weights_ + embed_rows + pages + rings + state)


# ---------------------------------------------------------- trace parts

SSM_SCOPES = ("ssm_conv", "ssm_gates", "ssm_scan", "ssm_out")
# the layers that read the ONE layer's pages: the page window's parts
# inside ``attn_shared``, then that scope for what is left under it
SHARED_PARTS = ("kv_append", "kv_gather", "attn_scores", "attn_pv",
                "attn_shared")

parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": SLIDING_PARTS[:4] + SHARED_PARTS,
    "dense": (*((s, (s,)) for s in SSM_SCOPES),
              ("gmu", ("gmu",)),
              ("diff_merge", ("diff_merge",)),
              ("ssm_in", ("w_in",)),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}

def _ring_shapes(cfg: Dict[str, Any]):
    """A whole ring as the trace names it, and a quarter of one."""
    whole = (cfg["deployment"]["max_slots"] * ring_len(cfg)
             * key_bytes(cfg, 1) // 2)
    return {whole, whole // 4}


def _with_ring_copies(run, got, spans):
    """``got`` (a split over ``spans``) with the whole-ring copies that
    the table left unsorted made a part of their own
    (families/laguna.py ``_with_ring_copies``, over this family's ring
    and table)."""
    spans = sorted((s, s + d) for _n, s, d in spans)
    sizes = _ring_shapes(run.cfg)
    moved = dict.fromkeys(("unnamed", "other"), 0.0)
    i = 0
    for name, start, dur, tf_op in sorted(run._trace_parts["ir"]["ops"],
                                          key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if (i < len(spans) and spans[i][0] <= start
                and _laguna.is_ring_copy(run.cfg, name, sizes)):
            part = trace_parts.part_of(tf_op, parts)
            if part in moved:
                moved[part] += dur / 1e9
    parts_ = dict(got["parts"])
    parts_[RING_COPIES] = 0.0
    for part, took in moved.items():
        took = min(took, parts_.get(part, 0.0))
        parts_[part] = parts_.get(part, 0.0) - took
        parts_[RING_COPIES] += took
    return dict(got, parts=parts_)


def typed_parts(run, module: str):
    """``trace_parts.for_run`` of ``module`` where the program names
    this family's scopes, with the unnamed whole-ring copies as the part
    ``ring_copies``; None without a trace or on a program that names
    none."""
    got = trace_parts.for_run(run, module)
    if not got or not any(got["parts"].get(s)
                          for s in ("ssm_scan", "attn_sliding",
                                    "attn_shared")):
        return None
    spans = [m for m in run._trace_parts["ir"]["modules"]
             if trace_reduce.module_name(m[0]) == module]
    return _with_ring_copies(run, got, spans)


def prefill_calls(run) -> Optional[Dict[str, float]]:
    """The prefill calls of the traced seconds (or, where those hold
    none, of the window) by the ``round`` events: {"calls", "rows",
    "tokens"}; None without them."""
    spans = [run.window]
    if getattr(run, "trace_span", None) and None not in run.trace_span:
        spans.insert(0, run.trace_span)
    for t0, t1 in spans:
        calls = rows = tokens = 0
        for e in run.events:
            if e[2] == "round" and t0 <= e[1] < t1 \
                    and e[5].get("prefill_width"):
                calls += 1
                rows += e[5].get("prefill_rows", 0)
                tokens += e[5].get("prefill_tokens", 0)
        if calls:
            return {"calls": calls, "rows": rows, "tokens": tokens}
    return None


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched
    (families/laguna.py's join, over this family's table of parts):
    {"parts": {part: s}, "module_s", "steps", "riders" (a step's mean),
    "context_tokens" (a step's mean of the riders' own contexts),
    "sliding_keys" (a step's mean of the riders' contexts cut at the
    window), "shared_reads" (a step's mean of the riders' context
    entries x the layers that read pages), "rounds"}. None without a
    joined trace, on a program that names none of the family's scopes,
    or where the spans and the rows disagree in number."""
    if hasattr(run, "_phi4flash_decode_parts"):
        return run._phi4flash_decode_parts
    run._phi4flash_decode_parts = None
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
    split = _with_ring_copies(run, trace_parts.split(
        {"ops": ir["ops"], "modules": spans}, "jit_decode", parts), spans)
    if not split["parts"].get("ssm_scan"):
        return None
    by_round = got["by_round"]
    readers = n_page_readers(run.cfg)
    riders = tokens = keys = reads = 0.0
    for r in rows:
        d, n = by_round[r["round"]], r["steps"]
        back = d.get("decode_riders", 0) * (n - 1) / 2.0
        riders += d.get("decode_riders", 0) * n
        tokens += (d.get("decode_context_tokens", 0) - back) * n
        keys += d.get("decode_sliding_keys", 0) * n
        reads += (d.get("decode_shared_kv_reads", 0) - back * readers) * n \
            if d.get("decode_shared_kv_reads") else 0.0
    run._phi4flash_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps, "sliding_keys": keys / steps,
        "shared_reads": reads / steps,
        "rounds": [r["round"] for r in rows]}
    kernels = {k: sum(by_round[r["round"]].get(k, 0) for r in rows)
               for k in ("decode_kernel_pages", "sliding_kernel_keys")}
    common.log(
        f"[phi4flash] jit_decode over the {len(rows)} matched executions: "
        f"{steps} steps of {riders / steps:.1f} riders, "
        f"{tokens / steps:.0f} context tokens, {keys / steps:.0f} sliding "
        f"keys and {reads / steps:.0f} shared reads; their rounds' "
        f"decode_kernel_pages {kernels['decode_kernel_pages']} and "
        f"sliding_kernel_keys {kernels['sliding_kernel_keys']}; a step "
        f"{1e3 * split['module_s'] / steps:.3f} ms: state-space "
        f"{1e3 * under(split, SSM_SCOPES) / steps:.3f}, sliding "
        f"{1e3 * sliding_s(split) / steps:.3f}, shared "
        f"{1e3 * under(split, SHARED_PARTS) / steps:.3f}; "
        + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
            split["parts"].items(), key=lambda kv: -kv[1])[:18]))
    return run._phi4flash_decode_parts


# ------------------------------------------------ the controls, on the chip

def main(argv=None) -> int:
    """``python -m benchmarks.families.phi4flash [--seeds a,b]
    [--controls a,b]``: the comparison that decides ``correct`` at the
    real configuration under each of the reference's controls, in one
    process. The served path (``LlamaDeployment`` over the seeded
    weights, the configuration's own deployment arguments) generates
    the parity tokens once a seed; the reference is then computed as it
    is and under each control, and each prints the margin rule's verdict
    beside the ``[correct] phi4flash:`` line's mean and worst."""
    import argparse
    import json

    import jax.numpy as jnp

    from benchmarks import parity, trafficgen
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.util.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3000000307")
    ap.add_argument("--controls", default=",".join(
        c for c in CONTROLS))
    ap.add_argument("--config", default="phi-4-mini-flash-reasoning")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = common.load_json("configs", args.config + ".json")
    pcfg = program_config(cfg)
    dep_args = dict(cfg["deployment"])
    dep_args.pop("tensor_parallel", None)
    par = cfg["parity"]
    P, G = par["prompt_len"], par["new_tokens"]
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        params = init_params(weights.param_shapes(model(pcfg)), seed)
        dep = LlamaDeployment(config=pcfg, params=params, **dep_args)
        prompts = [trafficgen.prompt_tokens(seed, 20_000_000 + i, P,
                                            cfg["vocab_size"])
                   for i in range(par["prompts"])]
        ids = np.asarray([dep({"prompt_ids": p, "max_new_tokens": G})
                          for p in prompts], np.int32)
        rw = reference_weights(params, pcfg)
        for control in [None] + controls:
            kw = {control: True} if control else {}
            logits = reference_logits(rw, jnp.asarray(ids), pcfg, **kw)
            check = parity.margin_rule(logits, ids, P)
            print(f"CONTROL seed {seed} {control}: "
                  f"{json.dumps(check)}", flush=True)
        dep.engine().shutdown()
        del dep, params, rw
    return 0


if __name__ == "__main__":
    import os
    import sys
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
