"""What the families whose readers divide by the engine's own count of
decode steps share, so that a family brings its table of parts and its
scopes and nothing else: ``decode_parts_by_rounds`` (``jit_decode``'s
self time by part over exactly the executions that
benchmarks/trace_dispatch.py matched to their rounds, with what those
rounds dispatched) and ``controls_main`` (the comparison that decides
``correct`` at the real configuration under each of the reference's
controls, on the chip, in one process).

Written with PR 65's family (families/granite_hybrid.py). Seven
families before it carry a copy of the join (kimi_linear, laguna,
mellum2, olmo_hybrid, phi4flash, sdar, deepseek_v32) and two of the
driver (phi4flash, deepseek_v32): files a PR that adds a configuration
may not edit; PERF.md section 7 has what moving them over takes.
"""
from __future__ import annotations

import sys
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from benchmarks import common, trace_dispatch, trace_parts, trace_reduce


def under(split, names: Sequence[str]) -> float:
    """Seconds of ``split`` under the parts ``names``."""
    return sum(split["parts"].get(n, 0.0) for n in names)


def decode_parts_by_rounds(
        run, parts, need_scope: str, *, tag: str,
        groups: Sequence[Tuple[str, Sequence[str]]] = (),
) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part (``parts``: the family's
    table, trace_parts.split's) over EXACTLY the executions that
    benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched: {"parts": {part:
    s}, "module_s", "steps", "riders" (a step's mean), "context_tokens"
    (a step's mean of the riders' own contexts: a round's count is its
    LAST step's, so the earlier steps of a dispatch lie half a dispatch
    back in the mean), "rounds"}. None without a joined trace, on a
    program whose decode names no ``need_scope``, or where the spans and
    the rows disagree in number. Computed once a run and a ``tag`` (the
    family's name, which also heads the log line); ``groups``: (label,
    parts) sums the line gives before the largest parts."""
    kept = run.__dict__.setdefault("_trace_rounds", {})
    if tag in kept:
        return kept[tag]
    kept[tag] = None
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
    if not split["parts"].get(need_scope):
        return None
    by_round = got["by_round"]
    riders = tokens = 0.0
    for r in rows:
        d, n = by_round[r["round"]], r["steps"]
        riders += d.get("decode_riders", 0) * n
        tokens += (d.get("decode_context_tokens", 0)
                   - d.get("decode_riders", 0) * (n - 1) / 2.0) * n
    kept[tag] = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps,
        "rounds": [r["round"] for r in rows]}
    pages = sum(by_round[r["round"]].get("decode_kernel_pages", 0)
                for r in rows)
    common.log(
        f"[{tag}] jit_decode over the {len(rows)} matched executions: "
        f"{steps} steps of {riders / steps:.1f} riders and "
        f"{tokens / steps:.0f} context tokens; their rounds' "
        f"decode_kernel_pages {pages}; a step "
        f"{1e3 * split['module_s'] / steps:.3f} ms: "
        + ", ".join(f"{label} {1e3 * under(split, names) / steps:.3f}"
                    for label, names in groups) + "; "
        + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
            split["parts"].items(), key=lambda kv: -kv[1])[:18]))
    return kept[tag]


def controls_main(family, config: str, argv=None) -> int:
    """``python -m benchmarks.families.<family> [--seeds a,b] [--controls
    a,b] [--config name]``: the comparison that decides ``correct`` at
    the real configuration under each of ``family.CONTROLS``, in one
    process. The served path (``LlamaDeployment`` over the seeded
    weights, the configuration's own deployment arguments) generates
    the parity tokens once a seed; the reference is then computed as it
    is and under each control, and each prints the margin rule's verdict
    (``CONTROL seed <n> <control>: {...}``) beside the family's own
    ``[correct]`` line. Returns 0 where the reference as it is read
    ``ok`` and every control did not, at every seed; 1 otherwise."""
    import argparse
    import json

    import jax.numpy as jnp

    from benchmarks import parity, trafficgen, weights
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.util.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3000000307")
    ap.add_argument("--controls", default=",".join(family.CONTROLS))
    ap.add_argument("--config", default=config)
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = common.load_json("configs", args.config + ".json")
    pcfg = family.program_config(cfg)
    dep_args = dict(cfg["deployment"])
    dep_args.pop("tensor_parallel", None)
    par = cfg["parity"]
    P, G = par["prompt_len"], par["new_tokens"]
    controls = [c for c in args.controls.split(",") if c]
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        params = family.init_params(
            weights.param_shapes(family.model(pcfg)), seed)
        dep = LlamaDeployment(config=pcfg, params=params, **dep_args)
        prompts = [trafficgen.prompt_tokens(seed, 20_000_000 + i, P,
                                            cfg["vocab_size"])
                   for i in range(par["prompts"])]
        ids = np.asarray([dep({"prompt_ids": p, "max_new_tokens": G})
                          for p in prompts], np.int32)
        rw = family.reference_weights(params, pcfg)
        for control in [None] + controls:
            kw = {control: True} if control else {}
            logits = family.reference_logits(rw, jnp.asarray(ids), pcfg,
                                             **kw)
            check = parity.margin_rule(logits, ids, P)
            as_expected &= check["ok"] == (control is None)
            print(f"CONTROL seed {seed} {control}: "
                  f"{json.dumps(check)}", flush=True)
        dep.engine().shutdown()
        del dep, params, rw
    print(f"CONTROLS as expected: {as_expected}", flush=True)
    sys.stdout.flush()
    return 0 if as_expected else 1
