"""The Olmo-Hybrid family (allenai/Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``) as the benchmark sees it: a DENSE hybrid decoder. Three
layers in four are Gated DeltaNet (a delta rule gated by ONE decay a
head, heads of 96 x 192, whose state is a fixed-size float32 matrix a
head and a SLOT of the engine), the fourth plain multi-head softmax
attention over K/V pages with no position encoding and a norm over the
whole projected query and key; every layer's feed-forward is a SwiGLU,
and each branch is normed on its way OUT. The program serves it as
``ray_tpu.models.olmo_hybrid``; ``program_config`` refuses at once
(SystemExit, before a weight is made) a program that has no such module
and a file whose keys the module cannot express.

Nothing is a chip's share here: every head, the whole vocabulary, no
expert to hold. The weights are seeded as Solar-Open2's family scales
them (the two delta-rule families' leaves go by the same rule of
names); nothing is balanced, so ``init_params`` is that one call.

The byte counts are BY KIND of layer: a linear layer-step's state
(``state_step_bytes``: what the step MUST move, not the padded bytes a
chip may keep), a full layer's K/V a token (``kv_bytes_per_token``), and
a whole step's (``decode_step_bytes``: weights once, the head once, K/V
in context AND the riders' state each way). The readers divide a
scope's time by the layers OF THAT KIND (``n_kda_layers``) and by the
decode steps the engine's own rounds dispatched
(``decode_parts_by_rounds``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmarks import (common, costs, trace_dispatch, trace_parts,
                        trace_reduce)
from benchmarks.reference import olmo_hybrid as ref

LINEAR, FULL = "linear_attention", "full_attention"


def _types(cfg: Dict[str, Any]):
    """The cut's layers' types (``layer_types`` is kept whole: the
    entries past the depth name layers the cut does not hold)."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def n_kda_layers(cfg: Dict[str, Any]) -> int:
    """The linear (delta-rule) layers, under the name the linear-state
    readers ask for."""
    return _types(cfg).count(LINEAR)


def n_full_layers(cfg: Dict[str, Any]) -> int:
    return _types(cfg).count(FULL)


def program_config(cfg: Dict[str, Any]):
    """OlmoHybridConfig from the published key names."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.olmo_hybrid import OlmoHybridConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Olmo-Hybrid: it has no "
                         f"ray_tpu.models.olmo_hybrid ({e})")
    refused = {
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "attention_bias": cfg["attention_bias"],
        "a rotary embedding (rope_theta not null)":
            cfg["rope_parameters"]["rope_theta"] is not None,
        "value heads other than the key heads (linear)":
            cfg["linear_num_value_heads"] != cfg["linear_num_key_heads"],
        "num_key_value_heads other than the heads":
            cfg["num_key_value_heads"] != cfg["num_attention_heads"],
        "a layer type other than linear_attention and full_attention":
            not set(cfg["layer_types"]) <= {LINEAR, FULL},
        "fewer layer_types than layers":
            len(cfg["layer_types"]) < cfg["num_hidden_layers"],
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Olmo-Hybrid has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return OlmoHybridConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        hidden_dim=cfg["intermediate_size"],
        linear_heads=cfg["linear_num_key_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        conv_size=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype)


def model(pcfg):
    from ray_tpu.models.olmo_hybrid import OlmoHybrid
    return OlmoHybrid(pcfg)


def init_params(shapes, seed: int, shardings=None):
    """Solar-Open2's rule of scales (families/solar_open2.py ``seeded``:
    normal, std 1/sqrt(fan_in) for matrices, the convolution by its
    width, 1.0 for the embedding, 0.02 for the head, ones for every
    norm's scale; A = 0.5 n and b_dt = -4 + 1.5 n, so that a step's
    decay spans 0.5-0.999 for most heads and a few decay hard)."""
    return common.load_family("solar_open2", "serve").seeded(
        shapes, seed, shardings)


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names: the
    program's own arrays (the reference upcasts a layer's at a time)."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, f = lp["attention"], lp["feed_forward"]
        w = {"attn_post_norm": lp["attention_post_norm"]["scale"],
             "ffn_post_norm": lp["ffn_post_norm"]["scale"],
             "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
             "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"],
             "w_gate": f["w1"]["kernel"], "w_up": f["w3"]["kernel"],
             "w_down": f["w2"]["kernel"]}
        if "conv" in a:
            w.update(conv=a["conv"], wa=a["wa"]["kernel"],
                     A_log=a["A_log"], dt_bias=a["dt_bias"],
                     wb=a["wb"]["kernel"], wz=a["wz"]["kernel"],
                     o_norm=a["o_norm"]["scale"])
        else:
            w.update(q_norm=a["q_norm"]["scale"],
                     k_norm=a["k_norm"]["scale"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(n_heads=pcfg.n_heads, eps=pcfg.norm_eps)


def reference_forward(rw, ids, pcfg, **control):
    """The plain reference's logits [B, T, V] of ids [B, T].
    ``control``: the reference's controls (reference/olmo_hybrid.py
    ``CONTROLS``), which the harness never sets."""
    return ref.forward(rw, ids, **_sizes(pcfg), **control)


# The generated positions the comparison that decides ``correct`` reads
# are each row's last ``SCORED_TAIL`` (the configurations' ``parity``
# ``new_tokens``: the harness hands ``reference_logits`` the ids without
# saying where the prompt ends; a test ties the two).
SCORED_TAIL = 32


def reference_logits(rw, ids, pcfg, **control):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule, unchanged: the served token's reference logit
    within 2**-5 of the logit scale of the best, at EVERY generated
    position): the plain reference's, [B, T, V] with the rows that
    predict each prompt's last ``SCORED_TAIL`` tokens filled and the
    others zero (never read: the rule takes positions P - 1 .. P + G -
    2). The blocks run over every position; the head, a vocabulary of
    100,352 in float32, over the scored rows alone, so that it fits
    beside a chip this cell fills. Nothing is excused: this family has
    no choice of experts to flip, and 16 layers of bfloat16 lie as far
    from float32 as Mistral-d16's do."""
    x = ref.blocks(rw, ids, **_sizes(pcfg), **control)
    ids = np.asarray(ids)
    T = ids.shape[1]
    G = min(SCORED_TAIL, T - 1)
    window = np.asarray(ref.head(
        x[:, T - 1 - G:T - 1], rw["norm"], ref.head_weights(rw, **control),
        eps=pcfg.norm_eps))
    logits = np.zeros((ids.shape[0], T, window.shape[-1]), np.float32)
    logits[:, T - 1 - G:T - 1] = window
    return logits


# ---------------------------------------------------------- byte counts

def linear_widths(cfg: Dict[str, Any]):
    """The widths of a linear layer's projected q, k and v."""
    H = cfg["linear_num_key_heads"]
    return (H * cfg["linear_key_head_dim"], H * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def mixing_params(cfg: Dict[str, Any], linear: bool) -> int:
    """One layer's token mixing, matrices only (the norms' scales, A
    and b_dt, a few thousand numbers, are left out): a full layer's
    four projections, or a linear layer's q, k, v, output gate and
    output projection, its decay and beta projections and the
    convolution."""
    D = cfg["hidden_size"]
    if not linear:
        hd = D // cfg["num_attention_heads"]
        return (2 * D * cfg["num_attention_heads"] * hd
                + 2 * D * cfg["num_key_value_heads"] * hd)
    wq, wk, wv = linear_widths(cfg)
    return (D * (wq + wk + 3 * wv) + 2 * D * cfg["linear_num_key_heads"]
            + cfg["linear_conv_kernel_dim"] * (wq + wk + wv))


def mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: Dict[str, Any], linear: bool) -> int:
    return mixing_params(cfg, linear) + mlp_params(cfg)


def model_params(cfg: Dict[str, Any]) -> int:
    """The cut's matrices: its layers, the embedding and the head."""
    return (n_kda_layers(cfg) * layer_params(cfg, True)
            + n_full_layers(cfg) * layer_params(cfg, False)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """K and V of one token over the layers that HAVE K/V: the full
    layers of the cut."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_key_value_heads"] * hd * n_full_layers(cfg)
            * itemsize)


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One slot's delta-rule state in ONE linear layer: heads x dk x dv
    float32, as the arithmetic needs it (a chip that pads dv to whole
    lane tiles keeps more: PERF.md section 4)."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 4)


def conv_tail_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One slot's convolution tail in ONE linear layer: the last
    ``linear_conv_kernel_dim - 1`` inputs of q, k and v."""
    return ((cfg["linear_conv_kernel_dim"] - 1) * sum(linear_widths(cfg))
            * itemsize)


def state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """What one slot's recurrent layers keep, all of them."""
    return n_kda_layers(cfg) * (state_bytes(cfg) + conv_tail_bytes(cfg))


def state_step_bytes(cfg: Dict[str, Any], riders: float) -> float:
    """Bytes ONE linear layer's decode step MUST move for the recurrent
    state: each rider's state read once and written once, and its
    convolution tail read and written. Slots that ride without a
    request need move nothing."""
    return riders * 2.0 * (state_bytes(cfg) + conv_tail_bytes(cfg))


# The packed one-token kernel (ops/linear_attention.py
# ``kda_step_packed_kernel``): the name its calls carry in a trace, and
# what ONE call (a linear layer's decode step) must move and compute.
STEP_KERNEL = "kda_step_packed"


def step_kernel_bytes(cfg: Dict[str, Any], riders: float) -> float:
    """Each rider's state in and out; the kernel moves no tail."""
    return riders * 2.0 * state_bytes(cfg)


def step_kernel_flops(cfg: Dict[str, Any], riders: float) -> float:
    """Seven operations an element of a rider's state: the decay, two
    products and two sums for the read-outs, the rank-one write's
    product and sum (vector work, far under the bytes' time)."""
    return riders * 7.0 * state_bytes(cfg) / 4


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16) -> float:
    """Bytes ONE decode step of the whole batch must move: every
    layer's matrices once, the head once and an embedding row a slot,
    the K/V of the tokens in context in the layers that have K/V (with
    the step's own writes), and every rider's recurrent state in and
    out in the layers that keep one."""
    D = cfg["hidden_size"]
    weights = (model_params(cfg) - cfg["vocab_size"] * D) * itemsize
    embed_rows = slots * D * itemsize
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    state = n_kda_layers(cfg) * state_step_bytes(cfg, slots)
    return float(weights + embed_rows + kv + state)


# ---------------------------------------------------------- trace parts

KDA_SCOPES = ("kda_conv", "kda_gates", "kda_recurrence", "kda_out")
# a full layer's attention: the parts ``LlamaAttention`` and the page
# window name inside the block's ``attn_full`` scope, then that scope
# for what is left under it (the projections and the q/k norm inside it
# are parts of their own, before it in the table)
FULL_PARTS = ("kv_append", "kv_gather", "attn_scores", "attn_pv",
              "attn_kernel", "attn_full")

# trace_parts.DEFAULT_PARTS with the delta-rule layer's four scopes and
# the full layers' q/k norm as parts of their own, this block's two
# norms among the norms, and ``attn_full`` after the module names that
# lie inside it
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": FULL_PARTS[:5],
    "dense": (*((s, (s,)) for s in KDA_SCOPES),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("qk_norm", ("q_norm", "k_norm")),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_post_norm", "ffn_post_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("attn_full", ("attn_full",)),
              ("rope", ("attention",))),
}


def under(got, scopes) -> float:
    """Seconds of ``got``'s parts under ``scopes``."""
    return sum(got["parts"].get(s, 0.0) for s in scopes)


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched: {"parts": {part:
    s}, "module_s", "steps", "riders" (a step's mean), "context_tokens"
    (a step's mean of the riders' own contexts, from the ``round``
    events' ``decode_context_tokens``: the sum after a dispatch's last
    step, less half a step's growth a step before it), "rounds",
    "kernel_s" (the self time of the operations named ``STEP_KERNEL``
    among them, 0.0 where the program holds no such call)}. The
    join matches every execution of the program in order but the chip's
    last of any program (which the stop may have cut), so those are the
    spans the split is made over. None without a joined trace, on a
    program that names none of the delta rule's scopes, or where the
    spans and the rows disagree in number. (families/kimi_linear.py has
    the same join for its own table of parts.)"""
    if hasattr(run, "_olmo_decode_parts"):
        return run._olmo_decode_parts
    run._olmo_decode_parts = None
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
    if not split or not split["parts"].get("kda_recurrence"):
        return None
    kernel = trace_parts.split(
        {"ops": ir["ops"], "modules": spans}, "jit_decode",
        {"attention": (STEP_KERNEL,)})
    by_round = got["by_round"]
    riders = tokens = 0.0
    for r in rows:
        d, n = by_round[r["round"]], r["steps"]
        riders += d.get("decode_riders", 0) * n
        tokens += (d.get("decode_context_tokens", 0)
                   - d.get("decode_riders", 0) * (n - 1) / 2.0) * n
    run._olmo_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps,
        "rounds": [r["round"] for r in rows],
        "kernel_s": kernel["parts"].get(STEP_KERNEL, 0.0)}
    common.log(
        f"[olmo] jit_decode over the {len(rows)} matched executions: "
        f"{steps} steps of {riders / steps:.1f} riders and "
        f"{tokens / steps:.0f} context tokens; a step "
        f"{1e3 * split['module_s'] / steps:.3f} ms: linear "
        f"{1e3 * under(split, KDA_SCOPES) / steps:.3f}, full "
        f"{1e3 * under(split, FULL_PARTS) / steps:.3f}; "
        + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
            split["parts"].items(), key=lambda kv: -kv[1])[:14]))
    return run._olmo_decode_parts
