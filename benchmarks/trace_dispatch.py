"""Every device execution of a step program, put down to the round that
dispatched it.

The engine decides what every dispatch carries (``decode_steps``,
``decode_riders``, ``prefill_rows``, ``prefill_width`` and the window
tokens of its ``round`` event); the trace knows what each execution
cost. ``LLMEngine.start_trace`` starts the profiler between two rounds
with nothing in flight, so the trace's n-th execution of ``jit_prefill``
(``jit_decode``, ``jit_verify``) IS the n-th dispatch of that program in
a round after ``trace_start``'s ``round``. ``join`` pairs them in that
order and infers nothing from how often an operation ran. It is checked
twice: by counts (``trace_start`` and ``trace_stop`` carry the
cumulative dispatches of each program, which must equal what the
``round`` events between them show, and the trace may hold no more
executions than the log has dispatches, nor lack more than the stop can
have cut off; the chip's LAST execution in the trace is left out, because
the profiler closes a running execution's event when it stops, with a
duration that is not the execution's) and by the trace's clock, which the device planes share
with the ``engine.dispatch_*`` annotations on the host plane: an
execution starts after its own round's annotation opened and before the
next dispatch of its program is made. Where a check fails the join is
REFUSED, one ``[dispatch] join refused: ...`` line says why, and every
reader returns None: never a guess.

``table(run)`` gives one row an execution; the readers under
``metrics/dispatch_*.py`` reduce it, and ``[dispatch]`` lines print each
program's price, a decode step's by class of dispatch and by riders, a
prefill call's by width and by window, and the longest device gaps with
what the host was doing (the round's phases, its ``cpu_s``, a collector
pass). A program older than ``trace_start``'s counts gives None and no
line but one.

``python -m benchmarks.trace_dispatch <trace dir> <events.json>`` prints
the same from an operator's files (docs/serving.md, "Tracing a
replica's chip").
"""
from __future__ import annotations

import json
import statistics
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmarks import trace_reduce
from benchmarks.common import log

# the step programs, by the cumulative counter of ``trace_start`` /
# ``trace_stop`` that counts their dispatches and the host annotation
# that wraps their dispatch
PROGRAMS = {"jit_prefill": ("prefills", "engine.dispatch_prefill"),
            "jit_decode": ("chunks", "engine.dispatch_decode"),
            "jit_verify": ("verifies", "engine.dispatch_spec")}
# what the stop may take off a program's tail: the execution it cut (the
# trace's last, which the join leaves out) and the newest dispatch the
# loop keeps queued behind the one it reads back
MAX_TAIL = 2
PHASES = ("admit_s", "plan_s", "dispatch_s", "readback_s")

Execution = Tuple[str, int, int]          # program, start_ns, duration_ns


# ------------------------------------------------------------ the log

def rounds_of(events: Iterable[tuple]) -> List[Dict[str, Any]]:
    """The ``round`` events' data in order, each with ``programs``: the
    step programs the round dispatched, in the order it dispatched them
    (a prefill call where ``prefill_rows`` is not 0; a decode dispatch
    where the round logged a ``decode`` event; a verify where it has
    riders without one)."""
    out, decoded = [], False
    for e in events:
        if e[2] == "decode":
            decoded = True
        elif e[2] == "round":
            d = dict(e[5])
            progs = []
            if d.get("prefill_rows"):
                progs.append("jit_prefill")
            if decoded:
                progs.append("jit_decode")
            elif d.get("decode_riders"):
                progs.append("jit_verify")
            d["programs"] = tuple(progs)
            out.append(d)
            decoded = False
    return out


def marks_of(events: Iterable[tuple]) -> Tuple[Optional[dict],
                                               Optional[dict]]:
    """The log's last ``trace_start`` and the ``trace_stop`` after it
    (their data), or None for either."""
    start = stop = None
    for e in events:
        if e[2] == "trace_start":
            start, stop = dict(e[5]), None
        elif e[2] == "trace_stop" and start is not None:
            stop = dict(e[5])
    return start, stop


# ---------------------------------------------------------- the trace

def load(trace_dir: str, chip: int = 0) -> Dict[str, Any]:
    """What the join needs of an ``.xplane.pb``: ``executions`` (every
    event of chip ``chip``'s ``XLA Modules`` line: (program, start_ns,
    duration_ns)) and ``dispatch_spans`` ({(annotation, round):
    start_ns} of the host plane's ``engine.dispatch_*``
    annotations)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    want = f"/device:TPU:{chip}"
    executions: List[Execution] = []
    spans: Dict[Tuple[str, int], int] = {}
    for plane in data.planes:
        if plane.name == want:
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    executions = [(trace_reduce.module_name(e.name),
                                   int(e.start_ns), int(e.duration_ns))
                                  for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine.dispatch_"):
                        rnd = dict(e.stats).get("round")
                        if rnd is not None:
                            spans[(e.name, int(rnd))] = int(e.start_ns)
    executions.sort(key=lambda x: x[1])
    return {"executions": executions, "dispatch_spans": spans}


# ----------------------------------------------------------- the join

def join(executions: List[Execution], round_events: List[Dict[str, Any]],
         trace_start: Optional[Dict[str, Any]],
         trace_stop: Optional[Dict[str, Any]],
         dispatch_spans: Optional[Dict[Tuple[str, int], int]] = None
         ) -> Optional[Dict[str, Any]]:
    """Pair every execution of a step program with the round that
    dispatched it, in order from ``trace_start``. ``executions``:
    (program, start_ns, duration_ns) of one chip, any programs;
    ``round_events``: ``rounds_of``'s dicts; ``dispatch_spans``: the
    host plane's {(annotation, round): start_ns}, or None where there is
    no host plane to check against. Returns {"rows", "violations",
    "unchecked", "tail", "rounds", "by_round"} or, refused, None (one
    line says why). A row: program, round, start_ns, device_ms, steps, riders,
    rows, width, prompt_tokens, window_tokens, backlog, gap_before_ms,
    and the round's four host phases and ``cpu_ms`` in ms.
    ``violations`` counts matched executions that start before their
    round's ``engine.dispatch_*`` opened, or after the next dispatch of
    their program did; ``tail`` the dispatches up to ``trace_stop``'s
    round that have no row: those the trace no longer holds, and the
    chip's last execution, which the stop may have cut (the profiler
    closes a running execution's event at the stop: its duration and the
    operations under it are then a part of the execution's)."""
    if not trace_start or "prefills" not in trace_start:
        log("[dispatch] nothing to join: the log's trace_start carries "
            "no dispatch counts (a program older than the join)")
        return None
    if not trace_stop or "prefills" not in trace_stop:
        return _refuse("the log has no trace_stop with dispatch counts "
                       "after its trace_start")
    r0, r1 = trace_start["round"], trace_stop["round"]
    after = [d for d in round_events if d.get("round", 0) > r0]
    last = max(executions, key=lambda x: x[1], default=None)
    runs = sorted((x for x in executions
                   if x[0] in PROGRAMS and x is not last),
                  key=lambda x: x[1])
    ends = sorted((s + d, s) for _n, s, d in executions)
    rows, tail = [], {}
    violations = unchecked = 0
    for prog, (counter, annotation) in PROGRAMS.items():
        made = [d for d in after if prog in d["programs"]]
        between = sum(1 for d in made if d["round"] <= r1)
        counted = trace_stop[counter] - trace_start[counter]
        if between != counted:
            return _refuse(
                f"the round events show {between} dispatches of {prog} "
                f"in rounds {r0 + 1}..{r1}, the engine's counter "
                f"{counted}")
        mine = [x for x in runs if x[0] == prog]
        if len(mine) > len(made):
            return _refuse(
                f"the trace holds {len(mine)} executions of {prog}, "
                f"the log {len(made)} dispatches after round {r0}")
        tail[prog] = max(0, between - len(mine))
        if tail[prog] > MAX_TAIL:
            return _refuse(
                f"{tail[prog]} dispatches of {prog} up to round {r1} "
                f"are missing from the trace's tail (the stop cuts off "
                f"at most {MAX_TAIL})")
        for i, ((_n, start, dur), d) in enumerate(zip(mine, made)):
            if dispatch_spans is not None:
                opened = dispatch_spans.get((annotation, d["round"]))
                nxt = (dispatch_spans.get((annotation,
                                           made[i + 1]["round"]))
                       if i + 1 < len(made) else None)
                if opened is None:
                    unchecked += 1
                elif start < opened or (nxt is not None and start > nxt):
                    violations += 1
            rows.append(_row(prog, start, dur, d, ends))
    rows.sort(key=lambda r: r["start_ns"])
    return {"rows": rows, "violations": violations,
            "unchecked": unchecked, "tail": tail, "rounds": (r0, r1),
            "by_round": {d["round"]: d for d in after}}


def _refuse(why: str) -> None:
    log(f"[dispatch] join refused: {why}")
    return None


def _row(prog: str, start: int, dur: int, d: Dict[str, Any],
         ends: List[Tuple[int, int]]) -> Dict[str, Any]:
    prefill = prog == "jit_prefill"
    # the device's gap before it: back to the latest end of any program
    # that started earlier
    before = max((e for e, s in ends if s < start), default=start)
    row = {
        "program": prog, "round": d["round"], "start_ns": start,
        "device_ms": dur / 1e6,
        "steps": 0 if prefill else d.get("decode_steps", 0),
        "riders": 0 if prefill else d.get("decode_riders", 0),
        "rows": d.get("prefill_rows", 0) if prefill else 0,
        "width": d.get("prefill_width", 0) if prefill else 0,
        "prompt_tokens": d.get("prefill_tokens", 0) if prefill else 0,
        "window_tokens": d.get("prefill_window_tokens" if prefill
                               else "decode_window_tokens", 0),
        "backlog": d.get("backlog", 0),
        "gap_before_ms": max(0, start - before) / 1e6,
        "cpu_ms": 1e3 * d["cpu_s"] if "cpu_s" in d else None}
    for k in PHASES:
        row[k[:-2] + "_ms"] = 1e3 * d.get(k, 0.0)
    return row


# ------------------------------------------------------- the run's table

def joined(run) -> Optional[Dict[str, Any]]:
    """``join`` over a run's trace and event log, once a run: None
    without a kept trace (``--trace 2`` keeps it until the readers have
    run), where the program's marks carry no counts, where the join is
    refused, and where the clock contradicts it."""
    if hasattr(run, "_dispatch"):
        return run._dispatch
    run._dispatch = None
    trace_dir = getattr(run, "trace_dir", None)
    if getattr(run, "kind", None) != "serve" or not trace_dir:
        return None
    try:
        got = load(trace_dir)
    except (OSError, ValueError) as e:
        log(f"[dispatch] no trace to join: {e!r}")
        return None
    if not got["executions"]:
        log("[dispatch] no trace to join: no device plane's "
            f"'{trace_reduce.MODULES_LINE}' line (a CPU run)")
        return None
    out = join_log(got, run.events)
    if out is None:
        return None
    for line in (lines(out, int(run.deployment.get("decode_chunk", 8)))
                 + _older_counts(run, out["rows"])):
        log(line)
    if out["violations"]:
        log(f"[dispatch] join refused: {out['violations']} matched "
            "executions contradict the trace's clock")
        return None
    run._dispatch = out
    return out


def join_log(trace: Dict[str, Any], events: List[tuple]
             ) -> Optional[Dict[str, Any]]:
    """``join`` of ``load``'s trace with an event log, and with it what
    ``lines`` prints beside the rows: the log's ``trace_start`` and its
    ``gc`` events."""
    start, stop = marks_of(events)
    out = join(trace["executions"], rounds_of(events), start, stop,
               trace["dispatch_spans"])
    if out is not None:
        out["trace_start"] = start
        out["gcs"] = [e[5] for e in events if e[2] == "gc"]
    return out


def table(run) -> Optional[List[Dict[str, Any]]]:
    """One row per matched execution (``join`` says what a row holds),
    in device order; None where ``joined`` gives nothing."""
    got = joined(run)
    return got["rows"] if got else None


def _older_counts(run, rows) -> List[str]:
    """The decode steps as the benchmark's older readers count them
    from how often operations ran, beside the log's own count: the two
    cross-checks until those readers divide by the join."""
    runs = _of(rows, "jit_decode")
    steps = sum(r["steps"] for r in runs)
    out = []
    loop = trace_reduce.loop_step_seconds(getattr(run, "trace", None),
                                          "jit_decode")
    if steps and loop:
        ms = sum(r["device_ms"] for r in runs)
        out.append(f"[dispatch] decode steps by the rounds {steps}; "
                   f"trace_reduce.loop_steps reads {ms / (1e3 * loop):.1f}"
                   f" ({1e3 * loop:.3f} ms a step: decode_step_ms)")
    counted = getattr(getattr(run, "family", None),
                      "decode_steps_traced", None)
    if steps and counted is not None:
        out.append(f"[dispatch] decode steps by the rounds {steps}; the "
                   f"family's decode_steps_traced reads {counted(run)}")
    return out


# ------------------------------------------------------------ reductions

def _of(rows, program: str) -> List[Dict[str, Any]]:
    return [r for r in rows if r["program"] == program]


def prefill_call_ms(rows, widest: bool = False) -> Optional[float]:
    """Median device time of one ``jit_prefill`` execution; with
    ``widest``, over the calls of the widest ``prefill_width`` seen."""
    calls = _of(rows, "jit_prefill")
    if widest and calls:
        top = max(r["width"] for r in calls)
        calls = [r for r in calls if r["width"] == top]
    return (statistics.median(r["device_ms"] for r in calls)
            if calls else None)


def decode_step_ms(rows) -> Optional[float]:
    """Device time of the matched ``jit_decode`` executions over the
    ``decode_steps`` their rounds dispatched."""
    runs = _of(rows, "jit_decode")
    steps = sum(r["steps"] for r in runs)
    return sum(r["device_ms"] for r in runs) / steps if steps else None


def prefill_share(rows) -> Optional[float]:
    """Percent of the matched device time of ``jit_prefill`` and
    ``jit_decode`` that ``jit_prefill`` took."""
    pre = sum(r["device_ms"] for r in _of(rows, "jit_prefill"))
    dec = sum(r["device_ms"] for r in _of(rows, "jit_decode"))
    return 100.0 * pre / (pre + dec) if pre + dec else None


def fit(points: List[Tuple[float, float]]) -> Optional[Tuple[float, float]]:
    """Least squares ``y = a + b x`` over (x, y); None where x does not
    spread."""
    n = len(points)
    mx = sum(x for x, _ in points) / n
    my = sum(y for _, y in points) / n
    sxx = sum((x - mx) ** 2 for x, _ in points)
    if n < 2 or not sxx:
        return None
    b = sum((x - mx) * (y - my) for x, y in points) / sxx
    return my - b * mx, b


# ----------------------------------------------------------- the lines

def _stats(ms: List[float]) -> str:
    return (f"{len(ms)} executions, median {statistics.median(ms):.3f} "
            f"min {min(ms):.3f} max {max(ms):.3f} ms")


def _decode_class(row, chunk: int) -> str:
    if row["backlog"]:
        return "backlog"
    if row["steps"] > chunk:
        return "run-ahead"
    return "decode_chunk" if row["steps"] == chunk else "short"


def lines(got: Dict[str, Any], chunk: int = 8) -> List[str]:
    """The ``[dispatch]`` lines of one join."""
    rows = got["rows"]
    r0, r1 = got["rounds"]
    ts = got.get("trace_start") or {}
    out = [f"[dispatch] join by order from trace_start (round {r0}, "
           f"nothing in flight; the start waited "
           f"{1e3 * ts.get('wait_s', 0):.1f} ms for the dispatches in "
           f"flight and {1e3 * ts.get('start_s', 0):.1f} ms for the "
           f"profiler): {len(rows)} executions matched to rounds "
           f"{r0 + 1}..{max((r['round'] for r in rows), default=r0)} "
           f"(trace_stop at round {r1}), cut off by the stop "
           f"{got['tail']}, clock violations {got['violations']} "
           f"(must be 0), without a host span {got['unchecked']}"]
    for prog in PROGRAMS:
        ms = [r["device_ms"] for r in _of(rows, prog)]
        if ms:
            out.append(f"[dispatch] {prog}: {_stats(ms)}")
    dec = [r for r in _of(rows, "jit_decode") if r["steps"]]
    if dec:
        out.append(f"[dispatch] a decode step: "
                   f"{decode_step_ms(rows):.3f} ms over "
                   f"{sum(r['steps'] for r in dec)} steps of "
                   f"{len(dec)} dispatches ("
                   f"{sum(r['steps'] for r in dec) / len(dec):.2f} steps"
                   f" and {sum(r['riders'] for r in dec) / len(dec):.2f}"
                   f" riders a dispatch)")
        by: Dict[str, List[dict]] = {}
        for r in dec:
            by.setdefault(_decode_class(r, chunk), []).append(r)
        for cls, rs in sorted(by.items()):
            steps = sum(r["steps"] for r in rs)
            out.append(
                f"[dispatch]   {cls}: {len(rs)} dispatches of "
                f"{steps / len(rs):.1f} steps and "
                f"{sum(r['riders'] for r in rs) / len(rs):.1f} riders: "
                f"{sum(r['device_ms'] for r in rs) / steps:.3f} ms a "
                f"step")
        riders = [r["riders"] for r in dec]
        if max(riders) - min(riders) >= 4:
            ab = fit([(r["riders"], r["device_ms"] / r["steps"])
                      for r in dec])
            out.append(f"[dispatch]   a step = {ab[0]:.3f} + "
                       f"{ab[1]:.4f} x riders ms (least squares over "
                       f"riders {min(riders)}..{max(riders)})")
    pre = _of(rows, "jit_prefill")
    if pre:
        share = prefill_share(rows)
        out.append(f"[dispatch] jit_prefill is {share:.1f} % of the "
                   f"matched device time of both programs")
        for w in sorted({r["width"] for r in pre}):
            rs = [r for r in pre if r["width"] == w]
            out.append(
                f"[dispatch]   prefill_width {w}: "
                f"{_stats([r['device_ms'] for r in rs])}, "
                f"{sum(r['rows'] for r in rs) / len(rs):.2f} rows and "
                f"{sum(r['prompt_tokens'] for r in rs) / len(rs):.0f} "
                f"prompt tokens a call")
            wins = [r["window_tokens"] for r in rs]
            ab = fit([(r["window_tokens"], r["device_ms"]) for r in rs])
            if ab and max(wins) > min(wins):
                out.append(
                    f"[dispatch]     a call = {ab[0]:.3f} + "
                    f"{1e3 * ab[1]:.4f} x prefill_window_tokens / 1000 "
                    f"ms (windows {min(wins)}..{max(wins)})")
    gcs = got.get("gcs") or []
    by_round = got.get("by_round") or {}
    for r in sorted(rows, key=lambda r: -r["gap_before_ms"])[:3]:
        # the host was late: in the round before's trailing readback, or
        # on this round's way to the dispatch
        prev = by_round.get(r["round"] - 1)
        near = [g for g in gcs if g.get("round") in (r["round"] - 1,
                                                     r["round"])]
        out.append(
            f"[dispatch] gap {r['gap_before_ms']:.3f} ms before "
            f"{r['program']} of round {r['round']}: "
            + (f"round {prev['round']} readback "
               f"{1e3 * prev.get('readback_s', 0):.2f} ms"
               + (f" (cpu {1e3 * prev['readback_cpu_s']:.2f})"
                  if "readback_cpu_s" in prev else "")
               + f" of a wall of {1e3 * prev.get('wall_s', 0):.2f}, then "
               if prev else "")
            + f"admit {r['admit_ms']:.2f} plan {r['plan_ms']:.2f} "
            f"dispatch {r['dispatch_ms']:.2f} ms"
            + (f" (cpu {r['cpu_ms']:.2f})"
               if r["cpu_ms"] is not None else "")
            + f", its own readback {r['readback_ms']:.2f} ms"
            + "".join(f"; gc generation {g['generation']} "
                      f"{1e3 * g['duration_s']:.1f} ms in round "
                      f"{g['round']}" for g in near))
    return out


if __name__ == "__main__":
    # an operator's files: the trace directory start_trace wrote into,
    # and the event log as JSON (obs.as_dicts of events.snapshot(), or
    # the raw tuples)
    with open(sys.argv[2]) as f:
        evs = [(e["seq"], e["t"], e["type"], e.get("rid"), e.get("sid"),
                e.get("data")) if isinstance(e, dict) else tuple(e)
               for e in json.load(f)]
    res = join_log(load(sys.argv[1]), evs)
    if res is not None:
        print("\n".join(lines(res)))
