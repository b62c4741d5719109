#!/usr/bin/env python3
"""Turn a SERVE_TRACE artifact (the committed SERVE_TRACE_*.json) into a
per-request phase breakdown and a p50/p99 critical-path table.

The artifact carries three views of the same run (serve/obs.py):
``events`` (the raw typed event log), ``requests`` (per-request phase
index derived from it), and ``trace_events`` (Chrome/Perfetto
timeline). This report reads the first two and CROSS-CHECKS them:
each request's TTFT is recomputed from its raw submit/first_token
event timestamps and compared against the engine-stamped ``ttft_s``
riding in the first_token event — they must agree to within 1ms or
the phase spans don't mean what they claim (ISSUE 10 acceptance).
When the event log carries pool ``handoff`` events (disaggregated
role-split pools, serve/engine_pool.py), the report also derives the
handoff latency — prefill-done to first decode token on the target
replica, paired by trace id.

Given a DIRECTORY instead of a file, it reads a CLUSTER flight
bundle (serve/fleet/telemetry.py dump_cluster_bundle): the trigger,
member coverage and clock-offset table from the manifest, plus the
tail of the merged offset-corrected event stream leading up to the
fault — the "one artifact explains the fault" view.

Usage: python tools/trace_report.py SERVE_TRACE_cpu_smoke.json
       python tools/trace_report.py flight/cluster-<reason>-000000/
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Optional

PHASES = ("queue_wait_s", "ttft_s", "decode_s", "total_s")


def _pct(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def _events_by_rid(events: List[Dict[str, Any]]
                   ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """rid -> {etype: first event of that type} for scalar-rid
    events (prefill events carry a rid LIST and index no single
    request)."""
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for ev in events:
        rid = ev.get("rid")
        if rid is None or isinstance(rid, list):
            continue
        slot = out.setdefault(str(rid), {})
        slot.setdefault(ev["type"], ev)
    return out


def report(artifact: Dict[str, Any]) -> Dict[str, Any]:
    """Phase breakdown + percentiles + the TTFT cross-check.
    Pure function over the artifact dict (``main`` feeds it a loaded
    file)."""
    requests: Dict[str, Any] = artifact.get("requests", {})
    events: List[Dict[str, Any]] = artifact.get("events", [])
    by_rid = _events_by_rid(events)

    rows: List[Dict[str, Any]] = []
    errs: List[float] = []
    for rid, ph in sorted(requests.items(),
                          key=lambda kv: str(kv[0])):
        row = {"rid": rid, "trace_id": ph.get("trace_id"),
               "outcome": ph.get("outcome"),
               "n_tokens": ph.get("n_tokens")}
        for k in PHASES:
            v = ph.get(k)
            row[k] = round(v, 6) if isinstance(v, (int, float)) \
                else None
        evs = by_rid.get(rid, {})
        sub, ft = evs.get("submit"), evs.get("first_token")
        if sub is not None and ft is not None:
            recomputed = ft["t"] - sub["t"]
            recorded = (ft.get("data") or {}).get("ttft_s")
            row["ttft_recomputed_s"] = round(recomputed, 6)
            if isinstance(recorded, (int, float)):
                err = abs(recomputed - recorded)
                row["ttft_err_s"] = round(err, 6)
                errs.append(err)
        rows.append(row)

    percentiles: Dict[str, Any] = {}
    for k in PHASES:
        xs = [r[k] for r in rows
              if isinstance(r.get(k), (int, float))]
        if xs:
            percentiles[k] = {
                "p50": round(_pct(xs, 0.50), 6),
                "p99": round(_pct(xs, 0.99), 6),
                "max": round(max(xs), 6), "n": len(xs)}
    return {
        "requests": rows,
        "phase_percentiles": percentiles,
        "rounds": _round_stats(events),
        "handoffs": _handoff_stats(events),
        "ttft_check": {
            "n": len(errs),
            "max_abs_err_s": round(max(errs), 6) if errs else None,
            "within_1ms": bool(errs) and max(errs) < 1e-3,
        },
    }


def _round_stats(events: List[Dict[str, Any]]
                 ) -> Optional[Dict[str, Any]]:
    """Per-round pipeline health, from the engine's typed "round"
    events (one per scheduler round, serve/engine.py): how much of
    each round the HOST gated dispatch (pre-plan readback drain +
    planner = ``host_gap_s``) versus the round's wall clock.
    ``overlap_efficiency`` = 1 - sum(gap)/sum(wall) — the fraction of
    round time the device pipeline stayed fed; the same quantity the
    ``serve_phase_host_gap_s`` histogram (serve/obs.py) accumulates,
    recomputed here from raw events so the two sources cross-check.
    None when the artifact predates round events."""
    gaps: List[float] = []
    walls: List[float] = []
    overlap = None
    for ev in events:
        if ev.get("type") != "round":
            continue
        d = ev.get("data") or {}
        g, w = d.get("host_gap_s"), d.get("wall_s")
        if isinstance(g, (int, float)) and isinstance(w, (int, float)):
            gaps.append(g)
            walls.append(w)
            overlap = d.get("overlap", overlap)
    if not gaps:
        return None
    total_gap, total_wall = sum(gaps), sum(walls)
    frac = total_gap / total_wall if total_wall else None
    return {
        "n": len(gaps),
        "overlap": overlap,
        "host_gap_total_s": round(total_gap, 6),
        "round_wall_total_s": round(total_wall, 6),
        "host_gap_fraction": (round(frac, 6)
                              if frac is not None else None),
        "overlap_efficiency": (round(1.0 - frac, 6)
                               if frac is not None else None),
        "host_gap_p50_s": round(_pct(gaps, 0.50), 6),
        "host_gap_p99_s": round(_pct(gaps, 0.99), 6),
        "round_wall_p50_s": round(_pct(walls, 0.50), 6),
    }


def _handoff_stats(events: List[Dict[str, Any]]
                   ) -> Optional[Dict[str, Any]]:
    """Disaggregation handoff latency, derived from the pool's typed
    events (serve/engine_pool.py): each ``handoff`` event (prefill
    leg done, decode leg admitted with the finished-prefill pull
    hint) is paired BY TRACE ID with the ``handoff_first_token``
    event of the same request (first decode token on the target
    replica). The interval is what the role split costs one stream —
    the KV-migration pull plus residual admission on the decode side
    — and is the number to watch when tuning the pull deadline /
    backoff knobs (LlamaDeployment kv_pull_deadline_s /
    kv_pull_backoff_s). ``handoff_fallback`` events are counted
    alongside: a fallback is one typed abort that decoded in place
    instead. None when the artifact carries no handoff events
    (unified pools, single engines)."""
    starts: Dict[str, float] = {}
    lats: List[float] = []
    fallbacks = 0
    for ev in events:
        et = ev.get("type")
        if et == "handoff_fallback":
            fallbacks += 1
            continue
        if et not in ("handoff", "handoff_first_token"):
            continue
        d = ev.get("data") or {}
        tid = d.get("trace_id")
        t = ev.get("t")
        if tid is None or not isinstance(t, (int, float)):
            continue
        if et == "handoff":
            starts.setdefault(str(tid), t)
        else:
            t0 = starts.get(str(tid))
            if t0 is not None:
                lats.append(t - t0)
    if not starts and not fallbacks:
        return None
    return {
        "handoffs": len(starts),
        "paired": len(lats),
        "fallbacks": fallbacks,
        "latency_p50_s": (round(_pct(lats, 0.50), 6)
                          if lats else None),
        "latency_p95_s": (round(_pct(lats, 0.95), 6)
                          if lats else None),
        "latency_max_s": round(max(lats), 6) if lats else None,
    }


def cluster_report(bundle: Dict[str, Any],
                   tail: int = 20) -> Dict[str, Any]:
    """Summarize one cluster flight bundle (the dict
    ``fleet.telemetry.load_cluster_bundle`` returns): trigger,
    coverage, the offset table, and the last ``tail`` merged events
    before the bundle was cut. Pure function, like ``report``."""
    events = bundle.get("events") or []
    members = bundle.get("members") or {}
    traces = set()
    for ev in events:
        d = ev.get("data")
        if isinstance(d, dict) and d.get("trace_id"):
            traces.add(str(d["trace_id"]))
    return {
        "reason": bundle.get("reason"),
        "trigger": bundle.get("trigger"),
        "coverage": bundle.get("coverage"),
        "members": {
            n: {k: m.get(k) for k in
                ("role", "up", "pid", "generation", "offset_s",
                 "uncertainty_s", "drift_s_per_s", "events_total",
                 "dropped")}
            for n, m in members.items()},
        "events_total": len(events),
        "events_torn_truncated": bundle.get(
            "events_torn_truncated", 0),
        "trace_ids": sorted(traces),
        "tail": events[-tail:],
    }


def _cluster_main(bdir: str) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from ray_tpu.serve.fleet.telemetry import load_cluster_bundle
    rep = cluster_report(load_cluster_bundle(bdir))
    print(f"cluster bundle: {rep['reason']}")
    print(f"  trigger: {json.dumps(rep['trigger'], default=str)}")
    cov = rep.get("coverage") or {}
    print(f"  coverage: scraped={cov.get('scraped')} "
          f"unreachable={cov.get('unreachable')}")
    print("  member clock offsets:")
    for n, m in sorted(rep["members"].items()):
        off = m.get("offset_s")
        unc = m.get("uncertainty_s")
        print(f"    {n:>12}  role={m.get('role'):>9}  "
              f"up={str(m.get('up')):>5}  pid={m.get('pid')}  "
              f"offset={off if off is not None else '-'}  "
              f"+-{unc if unc is not None else '-'}s")
    torn = rep["events_torn_truncated"]
    print(f"  merged events: {rep['events_total']}"
          + (f" ({torn} torn line(s) truncated)" if torn else ""))
    print(f"  trace ids seen: {rep['trace_ids']}")
    print(f"  last {len(rep['tail'])} events on the aligned "
          f"timebase:")
    for ev in rep["tail"]:
        print(f"    {ev.get('local_t')}  "
              f"{ev.get('member')}:{ev.get('type')}  "
              f"rid={ev.get('rid')}  "
              f"{json.dumps(ev.get('data'), default=str)[:80]}")
    return 0


def _fleet_main(artifact: Dict[str, Any]) -> int:
    """Render a --fleet --trace artifact: requests are cross-process
    span sets on the collector-aligned timebase, not single-engine
    phase rows."""
    stitch = artifact["stitch"]
    print(f"fleet trace: {stitch['traces']} request(s), "
          f"{stitch['stitched_traces']} stitched across "
          f"up to {stitch['max_processes']} OS processes "
          f"(proof={stitch['proof_trace_id']})")
    for tid, req in sorted((artifact.get("requests") or {}).items()):
        spans = req.get("spans") or []
        pids = sorted({s.get("pid") for s in spans})
        print(f"\n  {tid}  outcome={req.get('outcome')}  "
              f"n_tokens={req.get('n_tokens')}  "
              f"processes={len(pids)}")
        t0 = min((s["start_s"] for s in spans), default=0.0)
        for s in sorted(spans, key=lambda s: s["start_s"]):
            print(f"    {s.get('role', ''):>8}  "
                  f"{s.get('replica_id', ''):>10}  "
                  f"pid={s.get('pid')}  "
                  f"+{(s['start_s'] - t0) * 1e3:8.3f}ms -> "
                  f"+{(s['end_s'] - t0) * 1e3:8.3f}ms  "
                  f"(+-{s.get('offset_uncertainty_s', 0) * 1e3:.3f}ms)"
                  f"  {','.join(s.get('etypes') or [])}")
    col = artifact.get("collector") or {}
    if col:
        print(f"\ncollector: members_up={col.get('members_up')}"
              f"/{col.get('members')}  "
              f"max_offset_uncertainty_s="
              f"{col.get('max_offset_uncertainty_s')}  "
              f"within_bound={col.get('offset_within_bound')}")
    return 0 if stitch.get("stitched_traces", 0) >= 1 else 1


def _fmt(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v * 1e3:8.2f}"     # seconds -> ms columns
    return str(v)


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        for line in __doc__.strip().splitlines()[-2:]:
            print(line.strip(), file=sys.stderr)
        return 2
    if os.path.isdir(argv[1]):
        return _cluster_main(argv[1])
    with open(argv[1]) as f:
        artifact = json.load(f)
    if "stitch" in artifact:
        return _fleet_main(artifact)
    rep = report(artifact)

    cols = ("rid", "outcome", "n_tokens", "queue_wait_s", "ttft_s",
            "decode_s", "total_s", "ttft_err_s")
    print("per-request phases (ms):")
    print("  " + "  ".join(f"{c:>12}" for c in cols))
    for row in rep["requests"]:
        print("  " + "  ".join(
            f"{_fmt(row.get(c)):>12}" for c in cols))
    print("\ncritical-path percentiles (ms):")
    for k, p in rep["phase_percentiles"].items():
        print(f"  {k:>14}  p50={p['p50'] * 1e3:8.2f}  "
              f"p99={p['p99'] * 1e3:8.2f}  "
              f"max={p['max'] * 1e3:8.2f}  (n={p['n']})")
    rd = rep.get("rounds")
    if rd:
        print(f"\nscheduler rounds (n={rd['n']}, "
              f"overlap={rd['overlap']}):")
        print(f"  host_gap p50={rd['host_gap_p50_s'] * 1e3:8.2f}ms  "
              f"p99={rd['host_gap_p99_s'] * 1e3:8.2f}ms  "
              f"round_wall p50={rd['round_wall_p50_s'] * 1e3:8.2f}ms")
        print(f"  host_gap_fraction={rd['host_gap_fraction']}  "
              f"overlap_efficiency={rd['overlap_efficiency']}")
    ho = rep.get("handoffs")
    if ho:
        print(f"\ndisagg handoffs (n={ho['handoffs']}, "
              f"paired={ho['paired']}, "
              f"fallbacks={ho['fallbacks']}):")
        if ho["paired"]:
            print(f"  prefill-done -> first-decode-token latency  "
                  f"p50={ho['latency_p50_s'] * 1e3:8.2f}ms  "
                  f"p95={ho['latency_p95_s'] * 1e3:8.2f}ms  "
                  f"max={ho['latency_max_s'] * 1e3:8.2f}ms")
    chk = rep["ttft_check"]
    print(f"\nttft cross-check: n={chk['n']} "
          f"max_abs_err={chk['max_abs_err_s']}s "
          f"within_1ms={chk['within_1ms']}")
    overhead = artifact.get("overhead")
    if overhead:
        print(f"recorder overhead: on={overhead['tokens_s_events_on']}"
              f" tok/s off={overhead['tokens_s_events_off']} tok/s "
              f"ratio={overhead['ratio']}")
    return 0 if chk["within_1ms"] or chk["n"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
