"""Fleet observability plane: clock-offset estimation, cursored
telemetry scrape, cross-process trace stitching, cluster bundles.

The estimator tests are pure arithmetic over synthetic round trips
(no sleeping, no real clocks): t0/t3 are collector-side send/receive
stamps, t1 the member's clock read mid-call. The collector tests run
over the loopback fleet (tests/test_fleet.py harness) so every seam —
telemetry RPC, cursor resume, incarnation keying, bundle dump/load —
is the real code path.
"""
import json
import os
import threading
import time

import pytest

from ray_tpu.serve import obs
from ray_tpu.serve.fleet.agent import (ReplicaAgent, ScriptedEngine,
                                       scripted_completion)
from ray_tpu.serve.fleet.directory import (DirectoryClient,
                                           FleetDirectory)
from ray_tpu.serve.fleet.router import FleetRouter
from ray_tpu.serve.fleet.telemetry import (ClockOffsetEstimator,
                                           TelemetryCollector,
                                           load_cluster_bundle,
                                           merge_prometheus_texts)
from ray_tpu.serve.fleet.transport import LoopbackTransport
from ray_tpu.util import metrics


# ------------------------------------------------ offset estimator


def test_estimator_skew_ahead_and_behind():
    # member clock 5s AHEAD of the collector, symmetric 10ms RTT:
    # t1 = true_mid + 5; the midpoint formula recovers +5 exactly
    ahead = ClockOffsetEstimator()
    ahead.add_sample(t0=100.0, t1=105.005, t3=100.010)
    assert ahead.offset_s == pytest.approx(5.0)
    assert ahead.uncertainty_s == pytest.approx(0.005)
    assert ahead.rtt_s == pytest.approx(0.010)
    # a member stamp maps BACK by the offset onto the local timebase
    assert ahead.to_local(105.005) == pytest.approx(100.005)

    behind = ClockOffsetEstimator()
    behind.add_sample(t0=100.0, t1=95.005, t3=100.010)
    assert behind.offset_s == pytest.approx(-5.0)
    assert behind.to_local(95.005) == pytest.approx(100.005)


def test_estimator_asymmetric_rtt_error_stays_inside_bound():
    # true offset +2.0s, request leg 1ms but response leg 9ms: the
    # midpoint is pulled off the truth by (a-b)/2 = -4ms — an error
    # the RTT/2 = 5ms uncertainty must bound, by construction
    a, b, true = 0.001, 0.009, 2.0
    est = ClockOffsetEstimator()
    t0 = 50.0
    est.add_sample(t0=t0, t1=t0 + a + true, t3=t0 + a + b)
    assert est.offset_s != pytest.approx(true)      # biased ...
    assert abs(est.offset_s - true) <= est.uncertainty_s  # ... bounded
    assert est.uncertainty_s == pytest.approx((a + b) / 2)


def test_estimator_min_rtt_sample_wins():
    est = ClockOffsetEstimator()
    est.add_sample(t0=0.0, t1=1.1, t3=0.2)      # rtt 200ms
    est.add_sample(t0=10.0, t1=11.0, t3=10.01)  # rtt 10ms <- best
    est.add_sample(t0=20.0, t1=21.3, t3=20.5)   # rtt 500ms, ignored
    assert est.offset_s == pytest.approx(11.0 - 10.005)
    assert est.uncertainty_s == pytest.approx(0.005)
    assert est.n_samples == 3


def test_estimator_drift_across_scrape_gap():
    est = ClockOffsetEstimator()
    # offset grows 1ms per 10s of local time: 1e-4 s/s drift
    est.add_sample(t0=0.0, t1=5.0005, t3=0.001)
    assert est.drift_s_per_s is None            # one sample: no slope
    est.add_sample(t0=10.0, t1=15.0015, t3=10.001)
    drift = est.drift_s_per_s
    assert drift == pytest.approx(1e-4, rel=0.05)


def test_estimator_drift_gated_below_min_window():
    # two samples 10ms apart: any slope is RTT-asymmetry noise, and
    # the estimator must refuse to report one
    est = ClockOffsetEstimator(min_drift_window_s=1.0)
    est.add_sample(t0=0.0, t1=5.0, t3=0.001)
    est.add_sample(t0=0.010, t1=5.5, t3=0.011)
    assert est.drift_s_per_s is None


def test_estimator_rejects_backwards_round_trip():
    est = ClockOffsetEstimator()
    with pytest.raises(ValueError):
        est.add_sample(t0=5.0, t1=7.0, t3=4.0)


def test_estimator_bounded_sample_memory():
    est = ClockOffsetEstimator(max_samples=4)
    for i in range(10):
        est.add_sample(t0=float(i), t1=float(i) + 3.0,
                       t3=float(i) + 0.001)
    assert len(est._samples) == 4
    # drift window now spans only the retained samples (6..9)
    assert est.drift_s_per_s == pytest.approx(0.0, abs=1e-9)


# --------------------------------------- cursored scrape + restarts


class _FakeMemberFeed:
    """A scriptable telemetry endpoint: one 'incarnation' at a time,
    each with its own pid/generation, seq space, and clock base."""

    def __init__(self):
        self.pid = 1000
        self.generation = 0
        self.clock_base = 1000.0
        self.events = []

    def restart(self, clock_base):
        self.pid += 1
        self.generation += 1
        self.clock_base = clock_base
        self.events = []

    def append(self, etype, **data):
        self.events.append(
            {"seq": len(self.events),
             "t": self.clock_base + 0.001 * len(self.events),
             "type": etype, "rid": data.pop("rid", None),
             "data": data})

    def telemetry(self, cursor=0, limit=256):
        window = [e for e in self.events if e["seq"] >= cursor]
        window = window[:limit]
        nxt = (window[-1]["seq"] + 1) if window \
            else max(cursor, len(self.events))
        return {"role": "agent", "replica_id": "m",
                "generation": self.generation, "pid": self.pid,
                "clock": {"mono": self.clock_base, "wall": 0.0},
                "metrics_text": "", "events": window,
                "cursor": nxt, "events_total": len(self.events),
                "dropped": max(0, min((e["seq"] for e in
                                       self.events), default=0)
                               - cursor)}


def _bare_collector(**kw):
    class _NoRouter:
        pass
    return TelemetryCollector(_NoRouter(), **kw)


def test_scrape_cursor_resume_never_rereads():
    col = _bare_collector()
    st = col._state("m", "agent")
    feed = _FakeMemberFeed()
    for i in range(5):
        feed.append("submit", rid=f"r{i}")
    assert len(col._scrape_remote(st, feed.telemetry)) == 5
    # nothing new: the resumed cursor hands back an empty window
    assert col._scrape_remote(st, feed.telemetry) == []
    feed.append("retire", rid="r0")
    new = col._scrape_remote(st, feed.telemetry)
    assert [e["type"] for e in new] == ["retire"]
    assert col.counters["events_ingested"] == 6


def test_member_restart_resets_monotonic_base_and_cursor():
    col = _bare_collector()
    st = col._state("m", "agent")
    feed = _FakeMemberFeed()
    for _ in range(8):
        feed.append("submit")
    col._scrape_remote(st, feed.telemetry)
    old_offset = st.estimator.offset_s
    assert st.cursor == 8

    # the process restarts: seqs AND the monotonic clock base reset.
    # Without per-incarnation keying the stale cursor (8) would skip
    # the new log entirely and the old offset would misplace its
    # events by ~990s on the aligned timebase.
    feed.restart(clock_base=10.0)
    feed.append("self_fence")
    feed.append("submit")
    new = col._scrape_remote(st, feed.telemetry)
    assert [e["type"] for e in new] == ["self_fence", "submit"]
    assert st.incarnations == 2
    assert st.cursor == 2
    # fresh estimator for the fresh clock: offset tracks the NEW base
    assert st.estimator.n_samples == 1
    assert st.estimator.offset_s != pytest.approx(old_offset)
    # events land on the collector timebase near "now", not at the
    # dead incarnation's offset
    t_scrape = time.monotonic()
    for ev in new:
        assert abs(ev["local_t"] - t_scrape) < 5.0


def test_scrape_counts_ring_overwrite_as_dropped():
    col = _bare_collector()
    st = col._state("m", "agent")
    feed = _FakeMemberFeed()
    for i in range(4):
        feed.append("submit")
    col._scrape_remote(st, feed.telemetry)
    # the member's ring overwrote seqs 4..9 before the next scrape
    feed.events = [{"seq": s, "t": feed.clock_base + s,
                    "type": "submit", "rid": None, "data": {}}
                   for s in range(10, 13)]
    new = col._scrape_remote(st, feed.telemetry)
    assert [e["seq"] for e in new] == [10, 11, 12]
    assert st.dropped == 6


# ------------------------------------------- collector over loopback


def _loopback_fleet(n=2, token_delay_s=0.0005, seed=7,
                    wrap_transport=None, **router_kw):
    d = FleetDirectory(lease_ttl_s=1.0)
    dc = DirectoryClient(LoopbackTransport(d.handle))
    agents = {}

    def tf(addr):
        t = LoopbackTransport(agents[addr[1]].handle)
        return wrap_transport(addr[1], t) if wrap_transport else t

    for i in range(n):
        rid = f"a{i}"
        agents[rid] = ReplicaAgent(
            rid,
            lambda g, _d=token_delay_s: ScriptedEngine(
                token_delay_s=_d),
            dc, renew_period_s=0.05).start()
    kw = dict(seed=seed, snapshot_ttl_s=0.01, poll_interval_s=0.002)
    kw.update(router_kw)
    return d, dc, agents, FleetRouter(dc, tf, **kw)


def test_collector_loopback_scrape_trace_and_metrics(tmp_path):
    metrics.clear_registry()
    d, dc, agents, r = _loopback_fleet()
    col = TelemetryCollector(r, cluster_dir=str(tmp_path),
                             offset_bound_s=0.5).attach()
    try:
        assert r.telemetry_collector is col
        first = col.scrape_once()
        assert set(first) == {"router", "directory", "a0", "a1"}
        assert all(v is not None for v in first.values())

        tid = obs.mint_trace_id()
        h = r.submit([3, 1, 4], max_new_tokens=6, trace_id=tid)
        assert h.result() == scripted_completion([3, 1, 4], 6)
        col.scrape_once()
        # idempotent: a third scrape with nothing new returns zeros
        assert all(v == 0 for v in col.scrape_once().values())

        members = col.members()
        # the router member is the collector's own process: the
        # "round trip" is a function call, so the sample is exact
        assert members["router"]["offset_s"] == 0.0
        assert members["router"]["uncertainty_s"] == 0.0
        for m in members.values():
            assert m["up"] is True
            assert m["uncertainty_s"] <= 0.5

        phases = col.request_phases()
        assert tid in phases
        ph = phases[tid]
        served = h.replica_idx
        assert served in ph["members"]
        assert "router" in ph["members"]
        # loopback fleet = one OS process: spans exist per member but
        # the pid set collapses (the >=3-process stitch is on record
        # in the committed SERVE_FLEET_TRACE artifact)
        assert ph["n_processes"] == 1 and ph["stitched"] is False
        for span in ph["spans"]:
            assert span["end_s"] >= span["start_s"]
            assert span["offset_uncertainty_s"] <= 0.5

        trace = col.chrome_trace()
        assert isinstance(trace, list)
        names = {ev.get("name") for ev in trace
                 if ev.get("ph") == "M"}
        assert "process_name" in names
        assert any(ev.get("ph") == "X"
                   and ev["args"].get("trace_id") == tid
                   for ev in trace)

        text = col.metrics_text()
        assert 'member="' in text
        assert "serve_fleet_members" in text

        health = col.health()
        assert health["members_up"] == 4
        assert health["offset_within_bound"] is True
        assert health["counters"]["scrapes"] >= 3
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_collector_fault_bundle_roundtrip(tmp_path):
    metrics.clear_registry()
    d, dc, agents, r = _loopback_fleet()
    col = TelemetryCollector(r, cluster_dir=str(tmp_path)).attach()
    try:
        h = r.submit([2, 7], max_new_tokens=4, trace_id="t-bundle")
        h.result()
        col.scrape_once()
        bdir = col.on_fault("unit-fault",
                            trigger={"kind": "test", "x": 1})
        assert bdir is not None and os.path.isdir(bdir)
        assert col.bundles[-1]["reason"] == "unit-fault"

        cb = load_cluster_bundle(bdir)
        assert cb["reason"] == "unit-fault"
        assert cb["trigger"] == {"kind": "test", "x": 1}
        assert set(cb["members"]) == {"router", "directory",
                                      "a0", "a1"}
        assert cb["coverage"]["unreachable"] == []
        assert cb["events_torn_truncated"] == 0
        assert cb["member_payloads"]
        # merged stream is sorted on the aligned timebase and the
        # traced request's submit made it in
        ts = [e["local_t"] for e in cb["events"]
              if e["local_t"] is not None]
        assert ts == sorted(ts)
        assert any((e.get("data") or {}).get("trace_id")
                   == "t-bundle" for e in cb["events"])
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_collector_auto_bundles_on_scraped_fault_event(tmp_path):
    metrics.clear_registry()
    d, dc, agents, r = _loopback_fleet()
    col = TelemetryCollector(r, cluster_dir=str(tmp_path)).attach()
    try:
        col.scrape_once()
        agents["a0"].events.append("self_fence",
                                   data={"lease_overdue_s": 0.4})
        col.scrape_once()
        reasons = [b["reason"] for b in col.bundles]
        assert "self_fence-a0" in reasons
        trig = [b for b in col.bundles
                if b["reason"] == "self_fence-a0"][0]["trigger"]
        assert trig["kind"] == "self_fence"
        assert trig["data"]["lease_overdue_s"] == 0.4
        # the SAME event never fires twice (seen-fault dedup)
        col.scrape_once()
        assert [b["reason"] for b in col.bundles] == reasons
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_cluster_bundle_torn_tail_tolerated_midfile_raises(tmp_path):
    metrics.clear_registry()
    d, dc, agents, r = _loopback_fleet(n=1)
    col = TelemetryCollector(r, cluster_dir=str(tmp_path)).attach()
    try:
        r.submit([5], max_new_tokens=3).result()
        col.scrape_once()
        bdir = col.dump_cluster_bundle("torn-check")
        epath = os.path.join(bdir, "events.jsonl")
        n_events = sum(1 for _ in open(epath))
        # the dumper died mid-append: a trailing fragment with no
        # newline must be truncated, never raised over
        with open(epath, "a") as f:
            f.write('{"member": "a0", "ty')
        cb = load_cluster_bundle(bdir)
        assert cb["events_torn_truncated"] == 1
        assert len(cb["events"]) == n_events
        # a torn line ANYWHERE else is real corruption
        lines = open(epath).read().splitlines(keepends=True)
        lines[0] = '{"broken": \n'
        with open(epath, "w") as f:
            f.writelines(lines)
        with pytest.raises(json.JSONDecodeError):
            load_cluster_bundle(bdir)
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_collector_marks_unreachable_member_down(tmp_path):
    from ray_tpu.serve.fleet.transport import TransportError

    metrics.clear_registry()
    down = set()

    class _Gate:
        def __init__(self, rid, inner):
            self.rid, self.inner = rid, inner

        def call(self, *a, **kw):
            if self.rid in down:
                raise TransportError(f"{self.rid} unreachable")
            return self.inner.call(*a, **kw)

    d, dc, agents, r = _loopback_fleet(n=2, wrap_transport=_Gate)
    col = TelemetryCollector(r, cluster_dir=str(tmp_path)).attach()
    try:
        col.scrape_once()
        down.add("a0")     # partition a0's telemetry path
        res = col.scrape_once()
        assert res["a0"] is None
        m = col.members()["a0"]
        assert m["up"] is False and m["last_error"]
        bdir = col.dump_cluster_bundle("with-down-member")
        cb = load_cluster_bundle(bdir)
        assert "a0" in cb["coverage"]["unreachable"]
        assert "a1" in cb["coverage"]["scraped"]
        # heal: the next scrape flips it back up
        down.clear()
        assert col.scrape_once()["a0"] is not None
        assert col.members()["a0"]["up"] is True
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


# --------------------------------------------- prometheus merging


def test_merge_prometheus_texts_labels_and_sorts():
    a = ("# HELP serve_qps queries\n"
         "# TYPE serve_qps gauge\n"
         "serve_qps 3.0\n"
         'serve_qps{route="/v1"} 2.0\n')
    b = ("# HELP serve_qps queries\n"
         "# TYPE serve_qps gauge\n"
         "serve_qps 5.0\n")
    out = merge_prometheus_texts({"b": b, "a": a})
    lines = out.splitlines()
    # one HELP/TYPE per family, then member-labeled samples with the
    # member label injected FIRST so same-named samples can't collide
    assert lines[0] == "# HELP serve_qps queries"
    assert lines[1] == "# TYPE serve_qps gauge"
    assert 'serve_qps{member="a"} 3.0' in lines
    assert 'serve_qps{member="a",route="/v1"} 2.0' in lines
    assert 'serve_qps{member="b"} 5.0' in lines
    # deterministic: members sort, so a's samples precede b's
    assert lines.index('serve_qps{member="a"} 3.0') \
        < lines.index('serve_qps{member="b"} 5.0')
    # label values escape like the native exposition
    esc = merge_prometheus_texts({'we"ird\\': a})
    assert 'member="we\\"ird\\\\"' in esc


def test_merge_prometheus_texts_empty():
    assert merge_prometheus_texts({}) == ""
    assert merge_prometheus_texts({"m": ""}) == ""
