"""A program's device time split by the parts the program names itself.

``jax.named_scope`` (and flax's module names) reach the compiled
program as each operation's ``op_name`` metadata; the TPU's trace keeps
it in the ``tf_op`` stat of an operation's METADATA record
(``jit(decode)/while/body/Llama/layers_3/attention/kv_gather/gather:``),
which ``jax.profiler.ProfileData`` does not expose (its events carry
only offset and duration; read on a v5e trace, PR 25). So ``load``
reads the ``.xplane.pb`` itself, with a reader of the protobuf wire
format for the few fields it needs, into a plain structure that a test
can keep as small JSON; ``split`` sums ``XLA Ops`` SELF time (control
flow gives up its bodies' time: trace_reduce.self_times) by part.

Used by metrics/decode_attn_ms.py, decode_dense_ms.py and
train_loss_head_share.py through ``for_run``, which finds the trace
under ``run.trace_dir`` (``--trace 2``) and returns nothing without one.
``python -m benchmarks.trace_parts <dir-or-file> [module]`` prints the
split.
"""
from __future__ import annotations

import gzip
import json
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmarks import trace_reduce
from benchmarks.common import log

# The table a program's operations are sorted by. A family file gives
# its own as ``parts`` (benchmarks/families/); this one serves a family
# that gives none, and is the Llama block's and GPT-2's loss head's:
# ``wrapped`` scopes are looked for inside transformations
# (``transpose(jvp(loss_head))``) and are each their own part;
# ``attention`` scopes (a paged-attention module's names around the KV
# window) are each their own part and add up to decode_attn_ms;
# ``dense`` is (part, path components that mean it), by the flax module
# or scope that names the rest of a decode step, and adds up to
# decode_dense_ms. The first match in that order decides.
DEFAULT_PARTS = {
    "wrapped": ("loss_head",),
    "attention": ("kv_append", "kv_gather", "attn_scores", "attn_pv",
                  "attn_kernel"),
    "dense": (("projections", ("wq", "wk", "wv", "wo")),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              # rope and the reshapes around it: directly under the
              # module
              ("rope", ("attention",))),
}


# ------------------------------------------------- protobuf wire format

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: ints for varints, (start,
    end) for length-delimited fields, None for fixed-width ones."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            yield key >> 3, None
        else:
            raise ValueError(f"xplane: wire type {wire}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of a protobuf map entry."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def load(path: str, chip: int = 0) -> Dict[str, Any]:
    """{"ops": [[name, start_ns, duration_ns, tf_op], ...], "modules":
    [[name, start_ns, duration_ns], ...]} of plane ``/device:TPU:<chip>``
    from an ``.xplane.pb`` (or a profiler log directory holding one).
    XSpace.planes=1; XPlane: name=2, lines=3, event_metadata=4,
    stat_metadata=5; XLine: name=2, timestamp_ns=3, events=4; XEvent:
    metadata_id=1, offset_ps=2, duration_ps=3; XEventMetadata: id=1,
    name=2, stats=5; XStat: metadata_id=1, str_value=5, ref_value=7;
    XStatMetadata: id=1, name=2."""
    with open(trace_reduce.find_xplane(path), "rb") as f:
        buf = memoryview(f.read())
    want = f"/device:TPU:{chip}"
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        lines, emeta, smeta, name = [], [], [], None
        for f2, v in _fields(buf, *span):
            if f2 == 2:
                name = _text(buf, v)
                if name != want:
                    break
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                emeta.append(v)
            elif f2 == 5:
                smeta.append(v)
        if name != want:
            continue
        stat_names = {}
        for entry in smeta:
            sid = sname = None
            for f3, v in _fields(buf, *_map_value(buf, entry)):
                if f3 == 1:
                    sid = v
                elif f3 == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
        tf_op_ids = {i for i, n in stat_names.items() if n == "tf_op"}
        meta = {}                      # id -> (name, tf_op)
        for entry in emeta:
            mid, mname, tf_op = None, "", ""
            for f3, v in _fields(buf, *_map_value(buf, entry)):
                if f3 == 1:
                    mid = v
                elif f3 == 2:
                    mname = _text(buf, v)
                elif f3 == 5:
                    sid = val = None
                    for f4, v4 in _fields(buf, *v):
                        if f4 == 1:
                            sid = v4
                        elif f4 == 5:
                            val = _text(buf, v4)
                        elif f4 == 7:      # a reference to a stat name
                            val = stat_names.get(v4, "")
                    if sid in tf_op_ids and val:
                        tf_op = val
            meta[mid] = (mname, tf_op)
        out = {"ops": [], "modules": []}
        for span_l in lines:
            lname, t0, events = None, 0, []
            for f3, v in _fields(buf, *span_l):
                if f3 == 2:
                    lname = _text(buf, v)
                elif f3 == 3:
                    t0 = v
                elif f3 == 4:
                    events.append(v)
            if lname == trace_reduce.OPS_LINE:
                dest, with_scope = out["ops"], True
            elif lname == trace_reduce.MODULES_LINE:
                dest, with_scope = out["modules"], False
            else:
                continue
            for ev in events:
                mid = off = dur = 0
                for f4, v in _fields(buf, *ev):
                    if f4 == 1:
                        mid = v
                    elif f4 == 2:
                        off = v
                    elif f4 == 3:
                        dur = v
                mname, tf_op = meta.get(mid, ("", ""))
                row = [mname, t0 + off // 1000, dur // 1000]
                dest.append(row + [tf_op] if with_scope else row)
        return out
    return {"ops": [], "modules": []}


def save_json(ir: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ir, f, separators=(",", ":"))


def load_json(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------- split

def part_of(tf_op: str, parts: Optional[Dict[str, Any]] = None) -> str:
    """The part a scope path belongs to by the table ``parts``
    (DEFAULT_PARTS where none is given): a ``wrapped`` scope, an
    ``attention`` scope, one of ``dense``'s names, ``other`` (a path
    that names none of them) or ``unnamed`` (no metadata).
    Transformations wrap a scope (``transpose(jvp(loss_head))``), so a
    ``wrapped`` scope matches by the name inside."""
    if not tf_op:
        return "unnamed"
    parts = parts or DEFAULT_PARTS
    comps = [c.rstrip(":") for c in tf_op.split("/")]
    inner = [c.rsplit("(", 1)[-1].rstrip(")") for c in comps]
    for name in parts.get("wrapped", ()):
        if name in inner:
            return name
    for name in parts.get("attention", ()):
        if name in comps:
            return name
    for part, names in parts.get("dense", ()):
        if any(n in comps for n in names):
            return part
    return "other"


def split(ir: Dict[str, Any], module: str,
          parts: Optional[Dict[str, Any]] = None
          ) -> Optional[Dict[str, Any]]:
    """Self time of ``module``'s operations by part (``part_of`` by the
    table ``parts``), in seconds: {"runs", "module_s" (its runs' device
    time), "parts": {part: s}, "gaps_s" (module time in which no
    operation ran)}. None where the trace has no run of the module."""
    spans = sorted((s, s + d) for n, s, d in ir["modules"]
                   if trace_reduce.module_name(n) == module)
    if not spans:
        return None
    ops = sorted(ir["ops"], key=lambda e: (e[1], -e[2]))
    selfs = trace_reduce.self_times([e[:3] for e in ops])
    by_part: Dict[str, float] = {}
    i = 0
    for (_n, start, _d, self_ns), op in zip(selfs, ops):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start:
            p = part_of(op[3], parts)
            by_part[p] = by_part.get(p, 0.0) + self_ns / 1e9
    module_s = sum(e - s for s, e in spans) / 1e9
    return {"runs": len(spans), "module_s": module_s, "parts": by_part,
            "gaps_s": module_s - sum(by_part.values())}


def parts_of_run(run) -> Dict[str, Any]:
    """The table of the run's family (``run.family.parts``), or
    DEFAULT_PARTS where the run or its family has none."""
    return getattr(getattr(run, "family", None), "parts",
                   None) or DEFAULT_PARTS


def for_run(run, module: str) -> Optional[Dict[str, Any]]:
    """``split`` of the trace under ``run.trace_dir`` by the table of
    the run's family, read once per run and module; None without a
    trace (``--trace 1`` keeps none for readers). Logs the ``[parts]``
    line."""
    trace_dir = getattr(run, "trace_dir", None)
    if not trace_dir:
        return None
    memo = run.__dict__.setdefault("_trace_parts", {})
    if module not in memo:
        if "ir" not in memo:
            try:
                memo["ir"] = load(trace_dir)
            except FileNotFoundError:
                memo["ir"] = {"ops": [], "modules": []}
        got = memo[module] = split(memo["ir"], module,
                                   parts_of_run(run))
        if got:
            log(f"[parts] {module}: {got['runs']} runs, "
                f"{got['module_s']:.6f} s; "
                + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
                    got["parts"].items(), key=lambda kv: -kv[1]))
                + f"; between operations {got['gaps_s']:.6f} s")
    return memo[module]


def decode_step_parts(run) -> Optional[Dict[str, float]]:
    """Milliseconds of ONE decode step (the same steps decode_step_ms
    divides by) under attention's scopes, under the dense parts, and
    the rest: operations that name neither, and time between
    operations. The three add up to decode_step_ms."""
    got = for_run(run, "jit_decode")
    mod = (run.trace or {}).get("modules", {}).get("jit_decode")
    if not got or not mod:
        return None
    memo = run.__dict__["_trace_parts"]
    if "decode_step" in memo:
        return memo["decode_step"]
    steps = trace_reduce.loop_steps(
        run.trace["module_ops"].get("jit_decode", {}), mod["runs"])
    if not steps:
        return None
    table = parts_of_run(run)
    attn = sum(got["parts"].get(p, 0.0)
               for p in table.get("attention", ()))
    dense = sum(got["parts"].get(p, 0.0)
                for p, _ in table.get("dense", ()))
    out = memo["decode_step"] = {
        "attn_ms": 1e3 * attn / steps, "dense_ms": 1e3 * dense / steps,
        "rest_ms": 1e3 * (got["module_s"] - attn - dense) / steps,
        "step_ms": 1e3 * got["module_s"] / steps}
    log(f"[parts] a decode step of {out['step_ms']:.3f} ms over "
        f"{steps:.0f} steps: attention {out['attn_ms']:.3f}, dense "
        f"{out['dense_ms']:.3f}, rest {out['rest_ms']:.3f} "
        f"(unnamed {1e3 * got['parts'].get('unnamed', 0) / steps:.3f}"
        f", other {1e3 * got['parts'].get('other', 0) / steps:.3f}, "
        f"between operations {1e3 * got['gaps_s'] / steps:.3f})")
    return out


if __name__ == "__main__":
    target = sys.argv[1]
    ir_ = (load_json(target) if target.endswith(".json.gz")
           else load(target))
    for mod_ in (sys.argv[2:] or sorted(
            {trace_reduce.module_name(m[0]) for m in ir_["modules"]})):
        print(mod_, json.dumps(split(ir_, mod_)))
