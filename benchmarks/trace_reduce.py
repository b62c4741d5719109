"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Kept with the benchmark so that every PR reduces a trace
in the same way; checked on the recorded trace in benchmarks/tests/data.

Two steps. ``load`` turns the file into a plain structure (``planes`` ->
``lines`` -> events ``[name, start_ns, duration_ns]``) using nothing but
``jax.profiler.ProfileData``. ``reduce`` works on that structure alone,
so the recorded trace can be kept as small JSON.

What the TPU's trace looks like (read by hand, PR 24): one plane per
chip, ``/device:TPU:<n>``; its line ``XLA Modules`` has one event per
run of a jitted program, named ``jit_<function>(<fingerprint>)``; its
line ``XLA Ops`` has one event per HLO operation executed, named as in
the compiled module, with control-flow operations (``while``,
``conditional``, ``call``) enclosing the events of their bodies. An
operation's SELF time is its duration less its enclosed events'. An
op event's name is its HLO text (``%copy.7 = bf16[...] copy(...)``); a
Pallas kernel is a ``custom-call`` named after the kernel's function.
The line ``Async XLA Ops`` (copies and collectives in flight beside the
operations) is not read: it overlaps ``XLA Ops``.
``python -m benchmarks.trace_reduce <dir-or-file>`` prints a summary.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host lines worth keeping for naming idle gaps: events this long or more
HOST_MIN_NS = 20_000


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def load(path: str) -> Dict[str, Any]:
    """The trace as {"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, duration_ns], ...]}]}]}. Host lines keep only
    events of HOST_MIN_NS or more."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if is_dev or e.duration_ns >= HOST_MIN_NS]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save_json(ir: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ir, f, separators=(",", ":"))


def load_json(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------ reduce

def _line(plane, name) -> List[list]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return sorted(ln["events"], key=lambda e: (e[1], -e[2]))
    return []


def self_times(events: List[list]) -> List[Tuple[str, int, int, int]]:
    """(name, start, duration, self) per event of one line, where
    enclosing events (control flow) give up their bodies' time.
    ``events`` sorted by (start, -duration)."""
    out = []
    stack = []          # indices into out of the open enclosing events
    for name, start, dur in events:
        end = start + dur
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            covered = min(end, p[1] + p[2]) - start
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - max(0, covered))
        out.append((name, start, dur, dur))
        stack.append(len(out) - 1)
    return out


def union_intervals(events: List[list]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def module_name(event_name: str) -> str:
    """``jit_decode(1234567)`` -> ``jit_decode``."""
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    """An op event's own name: ``%fusion.12 = bf16[...] fusion(...)``
    and ``fusion.12`` both give ``fusion.12``."""
    return event_name.split(" = ")[0].lstrip("%").strip()


_SHAPE = re.compile(r"\(?([a-z0-9]+\[[0-9,]*\])")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")


def op_kind(event_name: str) -> Tuple[str, str]:
    """(opcode, first result shape) of an op event whose name is its HLO
    text: ``%copy.7 = bf16[8,32]{1,0} copy(...)`` -> ("copy",
    "bf16[8,32]"). A bare name gives its stem and no shape."""
    if " = " not in event_name:
        return re.sub(r"[.\d]+$", "", op_name(event_name)), ""
    text = event_name.split(" = ", 1)[1]
    shape, op = _SHAPE.match(text), _OPCODE.search(text)
    return (op.group(1) if op else "?"), (shape.group(1) if shape else "")


def device_planes(ir) -> List[Dict[str, Any]]:
    planes = [p for p in ir["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(
        DEVICE_PLANE.match(p["name"]).group(1)))


def _host_events(ir) -> List[list]:
    evs = []
    for p in ir["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                evs.extend(ln["events"])
    return evs


def reduce(ir: Dict[str, Any], chips: int,
           window_ns: Optional[Tuple[int, int]] = None) -> Dict[str, Any]:
    """The trace's numbers. ``window_ns`` clips to a span of the
    trace's own clock; default is from the first to the last device
    operation on the chips used."""
    planes = device_planes(ir)[:chips]
    if not planes:
        return {}
    per_chip = []
    for p in planes:
        per_chip.append({"ops": _line(p, OPS_LINE),
                         "modules": _line(p, MODULES_LINE)})
    if window_ns is None:
        starts = [c["ops"][0][1] for c in per_chip if c["ops"]]
        ends = [max(e[1] + e[2] for e in c["ops"])
                for c in per_chip if c["ops"]]
        if not starts:
            return {}
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns

    def clip(events):
        out = []
        for name, start, dur in events:
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                out.append([name, s, e - s])
        return out

    busy = []
    for c in per_chip:
        c["ops"], c["modules"] = clip(c["ops"]), clip(c["modules"])
        busy.append(sum(e - s for s, e in union_intervals(c["ops"])))
    chip0 = per_chip[0]

    # modules: runs and device seconds, by name, on chip 0
    modules: Dict[str, Dict[str, float]] = {}
    for name, _s, dur in chip0["modules"]:
        m = modules.setdefault(module_name(name),
                               {"runs": 0, "seconds": 0.0})
        m["runs"] += 1
        m["seconds"] += dur / 1e9

    # operations by self time, chip 0; and which module each ran under
    selfs = self_times(chip0["ops"])
    by_op: Dict[str, float] = {}
    by_kind: Dict[str, List[float]] = {}
    for name, _s, _d, self_ns in selfs:
        by_op[op_name(name)] = by_op.get(op_name(name), 0.0) + self_ns / 1e9
        rec = by_kind.setdefault(" ".join(op_kind(name)).strip(), [0, 0.0])
        rec[0] += 1
        rec[1] += self_ns / 1e9
    spans = [(s, s + d, module_name(n)) for n, s, d in chip0["modules"]]
    by_module_op: Dict[str, Dict[str, List[float]]] = {}
    i = 0
    for name, start, _d, self_ns in selfs:
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start:
            ops = by_module_op.setdefault(spans[i][2], {})
            rec = ops.setdefault(op_name(name),
                                 [0, 0.0, op_kind(name)[0]])
            rec[0] += 1
            rec[1] += self_ns / 1e9

    # idle gaps on chip 0, named by the programs on either side and by
    # the host event that covers most of the gap
    merged = union_intervals(chip0["ops"])
    host = sorted(_host_events(ir), key=lambda e: e[1])
    gaps: Dict[str, float] = {}
    edges = [(w0, w0)] + merged + [(w1, w1)]
    for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
        gap = next_start - prev_end
        if gap < 20_000:
            continue
        before = _module_at(spans, prev_end - 1) or "start"
        after = _module_at(spans, next_start) or "end"
        label = f"{before}->{after}"
        doing = _host_cover(host, prev_end, next_start)
        if doing:
            label += f" | host: {doing}"
        gaps[label] = gaps.get(label, 0.0) + gap / 1e9

    def top(d, n=10):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:n]]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy],
        "modules": modules,
        "ops": by_op,
        "module_ops": by_module_op,
        # operations grouped by opcode and result shape ("copy
        # bf16[8,32,64,64,128] x1568"): a step's 32 equal copies are
        # one entry, not ten of the list's ten
        "breakdown": {
            "device_ops": top({f"{k} x{n}": s
                               for k, (n, s) in by_kind.items()}),
            "idle_gaps": top(gaps)},
    }


def _module_at(spans, t) -> Optional[str]:
    for s, e, name in spans:
        if s <= t < e:
            return name
    # nearest module that ended before t
    best = None
    for s, e, name in spans:
        if e <= t:
            best = name
    return best


def _host_cover(host: List[list], g0: int, g1: int) -> Optional[str]:
    best, best_cover = None, 0
    for name, start, dur in host:
        if start >= g1:
            break
        cover = min(start + dur, g1) - max(start, g0)
        if cover > best_cover:
            best, best_cover = name, cover
    return best[:60] if best and best_cover * 2 >= (g1 - g0) else None


def loop_steps(module_ops: Dict[str, List[float]], runs: int) -> float:
    """Steps a looping program (the engine's decode) took over its
    ``runs``: operations of the loop body run once per step, those
    outside once per run, so the most frequent count among the ten
    heaviest operations is the number of steps."""
    heavy = sorted(module_ops.values(), key=lambda r: -r[1])[:10]
    if not heavy:
        return 0.0
    counts = sorted(r[0] for r in heavy)
    return float(max(counts[len(counts) // 2], runs))


def loop_step_seconds(red: Dict[str, Any], module: str) -> Optional[float]:
    """Device seconds of ONE step of a looping program: its runs' device
    time over the steps they took."""
    mod = (red or {}).get("modules", {}).get(module)
    if not mod or not mod["runs"]:
        return None
    steps = loop_steps(red["module_ops"].get(module, {}), mod["runs"])
    return mod["seconds"] / steps if steps else None


def idle_share(red: Dict[str, Any]) -> Optional[float]:
    """Percent of the traced window in which no operation ran, averaged
    over the chips used."""
    if not red or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def summary(ir: Dict[str, Any], chips: int = 1) -> str:
    out = []
    for p in ir["planes"]:
        out.append(f"PLANE {p['name']}")
        for ln in p["lines"]:
            evs = ln["events"]
            total = sum(e[2] for e in evs) / 1e9
            out.append(f"  line {ln['name']!r}: {len(evs)} events, "
                       f"{total:.4f} s summed")
            seen: Dict[str, List[float]] = {}
            for n, _s, d in evs:
                r = seen.setdefault(n[:100], [0, 0.0])
                r[0] += 1
                r[1] += d / 1e9
            for n, (c, s) in sorted(seen.items(),
                                    key=lambda kv: -kv[1][1])[:12]:
                out.append(f"      {s:9.5f} s  x{c:<6d} {n}")
    red = reduce(ir, chips)
    if red:
        out.append(f"window {red['window_s']:.4f} s, busy "
                   f"{red['busy_s']:.4f} s; modules {red['modules']}")
        out.append(f"breakdown {json.dumps(red['breakdown'])}")
    return "\n".join(out)


if __name__ == "__main__":
    target = sys.argv[1]
    ir_ = (load_json(target) if target.endswith(".json.gz")
           else load(target))
    print(summary(ir_, int(sys.argv[2]) if len(sys.argv) > 2 else 1))
