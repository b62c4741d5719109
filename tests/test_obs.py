"""serve/obs.py: typed event log, sched_trace compat view, phase
reconstruction, Chrome trace export, tracing bridge, flight recorder,
and the serve_phase_* metric singletons.

Pure unit tests over fakes — the engine/pool integration surface
(typed events on the real scheduler hot path, trace-id plumbing) is
covered by test_llm_engine.py / test_engine_pool.py and the
committed SERVE_TRACE artifact's schema gate.
"""
import json
import os
import threading
import time

import pytest

from ray_tpu.serve import obs
from ray_tpu.serve.obs import (DATA, ETYPE, RID, SEQ, SID, T,
                               EventLog, SchedTraceView)


# ---------------------------------------------------------- event log


def test_append_snapshot_order_and_fields():
    log = EventLog(16, name="t")
    log.append("submit", rid=1, data={"trace_id": "abc"})
    log.append("admit", rid=1, sid=0)
    log.append("decode", data=3)
    evs = log.snapshot()
    assert [e[ETYPE] for e in evs] == ["submit", "admit", "decode"]
    assert [e[SEQ] for e in evs] == [0, 1, 2]
    assert evs[0][RID] == 1 and evs[0][DATA] == {"trace_id": "abc"}
    assert evs[1][SID] == 0
    # timestamps are monotonic stamps in order
    assert evs[0][T] <= evs[1][T] <= evs[2][T]


def test_ring_wrap_keeps_newest():
    log = EventLog(4)
    for i in range(10):
        log.append("e", rid=i)
    assert log.total == 10
    assert len(log) == 4
    evs = log.snapshot()
    assert [e[RID] for e in evs] == [6, 7, 8, 9]
    assert [e[SEQ] for e in evs] == [6, 7, 8, 9]
    assert log.tail(2) == evs[-2:]


def test_explicit_timestamp_and_clear():
    log = EventLog(8)
    log.append("first_token", rid=7, t=123.5, data={"ttft_s": 0.25})
    assert log.snapshot()[0][T] == 123.5
    log.clear()
    assert log.total == 0 and not log.snapshot()


def test_disabled_log_is_a_noop():
    log = EventLog(8, enabled=False)
    log.append("submit", rid=1)
    assert log.total == 0 and log.snapshot() == []


def test_capacity_validated():
    with pytest.raises(ValueError):
        EventLog(0)


def test_concurrent_appends_never_tear():
    log = EventLog(256)
    stop = threading.Event()

    def writer(k):
        i = 0
        while not stop.is_set():
            log.append("w", rid=(k, i))
            i += 1

    threads = [threading.Thread(target=writer, args=(k,), daemon=True)
               for k in range(4)]
    for th in threads:
        th.start()
    deadline = time.time() + 0.2
    while time.time() < deadline:
        evs = log.snapshot()
        # every record is whole and the order is the total order
        assert all(len(e) == 6 for e in evs)
        assert [e[SEQ] for e in evs] == sorted(e[SEQ] for e in evs)
    stop.set()
    for th in threads:
        th.join(timeout=5)


def test_append_cost_is_bounded():
    """The recorder rides the decode hot path: both arms must stay in
    the microsecond class (generous bound — a loaded CI box)."""
    n = 20000
    for enabled in (True, False):
        log = EventLog(4096, enabled=enabled)
        t0 = time.perf_counter()
        for i in range(n):
            log.append("decode", sid=0, data=4)
        per = (time.perf_counter() - t0) / n
        assert per < 100e-6, f"append cost {per * 1e6:.1f}us/event"


def test_as_dicts_jsonable():
    log = EventLog(8)
    log.append("prefill", rid=(1, 2), data=((0, 4), (1, 4)))
    log.append("fault", rid=3, data={"error": ValueError("boom")})
    d = obs.as_dicts(log.snapshot())
    json.dumps(d)                       # artifact form must serialize
    assert d[0]["rid"] == [1, 2]
    assert d[0]["data"] == [[0, 4], [1, 4]]
    assert "ValueError" in d[1]["data"]["error"]
    assert set(d[0]) == {"seq", "t", "type", "rid", "sid", "data"}


# ------------------------------------------------- sched_trace compat


def test_view_renders_exact_legacy_shapes():
    log = EventLog(32)
    log.append("prefill", rid=(1,), data=((0, 8),))
    log.append("decode", data=4)
    log.append("spec", sid=2, data=(6, 5))
    log.append("cache_hit", sid=1, data=24)
    view = SchedTraceView(log)
    assert list(view) == [
        ("prefill", ((0, 8),)),
        ("decode", 4),
        ("spec", 2, 6, 5),
        ("cache_hit", (1, 24)),
    ]


def test_view_hides_new_event_kinds():
    log = EventLog(32)
    log.append("submit", rid=1)
    log.append("decode", data=2)
    log.append("first_token", rid=1)
    log.append("retire", rid=1)
    view = SchedTraceView(log)
    assert list(view) == [("decode", 2)]
    assert len(view) == 1 and bool(view)
    assert ("decode", 2) in view and ("submit", 1) not in view
    assert not SchedTraceView(EventLog(4))


def test_view_append_round_trips():
    log = EventLog(32)
    view = SchedTraceView(log)
    view.append(("prefill", ((0, 4), (1, 4))))
    view.append(("decode", 3))
    view.append(("spec", 1, 6, 4))
    view.append(("cache_hit", (0, 16)))
    assert list(view) == [
        ("prefill", ((0, 4), (1, 4))),
        ("decode", 3),
        ("spec", 1, 6, 4),
        ("cache_hit", (0, 16)),
    ]
    with pytest.raises(ValueError):
        view.append(("nonsense", 1))


# ----------------------------------------------------- request phases


def _lifecycle_log():
    log = EventLog(64)
    log.append("submit", rid=1, t=10.0, data={"trace_id": "t1"})
    log.append("admit", rid=1, sid=0, t=10.1)
    log.append("prefill", rid=(1,), t=10.15, data=((0, 8),))
    log.append("first_token", rid=1, t=10.3,
               data={"ttft_s": 0.3})
    log.append("emit", rid=1, t=10.3, data={"n": 1})
    log.append("emit", rid=1, t=10.5, data={"n": 3})
    log.append("retire", rid=1, t=10.6)
    log.append("submit", rid=2, t=10.2)
    log.append("shed", rid=2, t=10.25, data={"why": "queue_full"})
    return log


def test_request_phases_derivations():
    ph = obs.request_phases(_lifecycle_log().snapshot())
    r1 = ph[1]
    assert r1["trace_id"] == "t1" and r1["outcome"] == "retire"
    assert r1["queue_wait_s"] == pytest.approx(0.1)
    assert r1["ttft_s"] == pytest.approx(0.3)
    assert r1["prefill_s"] == pytest.approx(0.2)
    assert r1["decode_s"] == pytest.approx(0.3)
    assert r1["total_s"] == pytest.approx(0.6)
    assert r1["n_tokens"] == 4 and r1["n_emits"] == 2
    assert r1["sid"] == 0
    r2 = ph[2]
    assert r2["outcome"] == "shed" and r2["ttft_s"] is None


def test_request_phases_keeps_first_admit_on_resubmit():
    log = EventLog(16)
    log.append("submit", rid=1, t=1.0)
    log.append("admit", rid=1, sid=0, t=1.1)
    log.append("preempt", rid=1, t=1.2)
    log.append("admit", rid=1, sid=1, t=1.5)   # re-admitted elsewhere
    log.append("retire", rid=1, t=2.0)
    r = obs.request_phases(log.snapshot())[1]
    assert r["queue_wait_s"] == pytest.approx(0.1)
    assert r["sid"] == 1                        # latest placement


def test_request_phases_skips_batched_rids():
    log = EventLog(8)
    log.append("prefill", rid=(1, 2), t=1.0, data=((0, 4), (1, 4)))
    assert obs.request_phases(log.snapshot()) == {}


# ------------------------------------------------------- chrome trace


def test_chrome_trace_structure():
    trace = obs.chrome_trace({"engine": _lifecycle_log().snapshot()})
    json.dumps(trace)
    meta = [e for e in trace if e["ph"] == "M"
            and e["name"] == "process_name"]
    assert {m["args"]["name"] for m in meta} == {"engine", "requests"}
    inst = [e for e in trace if e["ph"] == "i"]
    assert len(inst) == 9 and all(e["s"] == "t" for e in inst)
    # instants rebase onto the earliest event at ts=0, in microseconds
    assert [e["ts"] for e in inst] == pytest.approx(
        [(ev[T] - 10.0) * 1e6 for ev in _lifecycle_log().snapshot()],
        abs=0.01)
    spans = {e["name"] for e in trace if e["ph"] == "X"}
    assert spans == {"request", "queue_wait", "prefill", "decode"}
    req = next(e for e in trace if e["ph"] == "X"
               and e["name"] == "request")
    assert req["dur"] == pytest.approx(0.6e6)
    assert req["args"]["trace_id"] == "t1"


# ----------------------------------------------------- tracing bridge


def test_emit_request_spans_shape_and_emission(tmp_path):
    from ray_tpu.util import tracing
    spans = obs.emit_request_spans(_lifecycle_log().snapshot())
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # request 2 shed before first token: root span only
    assert len(by_name["serve.request"]) == 2
    root = next(s for s in by_name["serve.request"]
                if s["attributes"]["rid"] == 1)
    assert root["trace_id"] == "t1" and root["parent_id"] is None
    for child in ("serve.queue_wait", "serve.prefill", "serve.decode"):
        (c,) = by_name[child]
        assert c["parent_id"] == root["span_id"]
        assert c["trace_id"] == "t1"
        assert c["end_time"] >= c["start_time"]
    shed_root = next(s for s in by_name["serve.request"]
                     if s["attributes"]["rid"] == 2)
    assert shed_root["status"] == "error"
    # with tracing enabled the same spans land in get_spans()
    tracing.setup_tracing(trace_dir=str(tmp_path / "tr"))
    try:
        obs.emit_request_spans(_lifecycle_log().snapshot())
        got = [s for s in tracing.get_spans()
               if s["name"] == "serve.request"]
        assert len(got) == 2
    finally:
        tracing.teardown_tracing()


def test_mint_trace_id_shape():
    a, b = obs.mint_trace_id(), obs.mint_trace_id()
    assert a != b
    assert len(a) == 16 and int(a, 16) >= 0


# ---------------------------------------------------- flight recorder


class _FakeAlloc:
    n_pages, n_free = 64, 60

    def occupancy(self):
        return 4 / 64


class _FakeFlightEngine:
    """The probe surface of a wedged engine. lifecycle_stats/spec_stats
    model the LOCKED accessors: the probe must derive its sections
    from the stats snapshot instead of calling them (calling would
    deadlock on the real engine — the wedged scheduler holds the
    lock)."""

    def __init__(self):
        self.events = EventLog(32)
        self.events.append("decode", sid=0, data=4)
        self.events.append("fault", rid=9,
                           data={"error": "EngineFault('x')"})
        self.stats = {"submitted": 5, "completed": 3, "shed": 1,
                      "spec_proposed": 10, "spec_accepted": 8}
        self.alloc = _FakeAlloc()
        self.prefix_cache = None

    def load_report(self):
        return {"heartbeat_age_s": 2.5, "queue_depth": 1}

    def lifecycle_stats(self):
        raise AssertionError("probe called a LOCKED accessor")

    def spec_stats(self):
        raise AssertionError("probe called a LOCKED accessor")


def test_dump_and_load_flight_bundle(tmp_path):
    eng = _FakeFlightEngine()
    bdir = obs.dump_flight_bundle(
        str(tmp_path), "wedged-r1", engine=eng,
        extra={"heartbeat_age_s": 2.5, "err": ValueError("x")})
    assert bdir is not None and os.path.isdir(bdir)
    assert os.path.basename(bdir).startswith("wedged-r1-")
    b = obs.load_flight_bundle(bdir)
    assert b["reason"] == "wedged-r1"
    e = b["engine"]
    assert e["events_total"] == 2
    assert [ev["type"] for ev in e["events"]] == ["decode", "fault"]
    # headline: the max of load-report heartbeat age and event gap
    assert e["heartbeat_gap_s"] >= 2.5
    assert e["lifecycle"]["submitted"] == 5
    assert e["spec"] == {"spec_proposed": 10, "spec_accepted": 8}
    # bytes view is None when the allocator wasn't priced (no
    # page_bytes) — present but honest, never a fake 0
    assert e["allocator"] == {"n_pages": 64, "n_free": 60,
                              "occupancy": 4 / 64, "page_bytes": None,
                              "bytes_in_use": None, "bytes_total": None}
    assert "ValueError" in b["extra"]["err"]
    # events.jsonl carries the same tail, one stream-tagged line each
    lines = [json.loads(ln) for ln in
             open(os.path.join(bdir, "events.jsonl"))]
    assert [ln["stream"] for ln in lines] == ["engine", "engine"]


def test_flight_bundle_tolerates_bare_fakes(tmp_path):
    class Bare:
        pass

    bdir = obs.dump_flight_bundle(str(tmp_path), "x", engine=Bare(),
                                  pool=Bare(), watchdog=Bare())
    b = obs.load_flight_bundle(bdir)
    assert b["engine"] == {} and b["pool"] == {}


def test_flight_bundle_never_raises_on_io_failure(tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    assert obs.dump_flight_bundle(
        str(blocker), "x", engine=_FakeFlightEngine()) is None


def test_default_flight_dir_env_override(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FLIGHT_DIR", "/tmp/elsewhere")
    assert obs.default_flight_dir() == "/tmp/elsewhere"
    monkeypatch.delenv("RAY_TPU_FLIGHT_DIR")
    assert f"p{os.getpid()}" in obs.default_flight_dir()


# ------------------------------------------------------ phase metrics


def test_phase_metrics_singleton_and_rebuild():
    from ray_tpu.util import metrics
    m1 = obs.phase_metrics()
    assert obs.phase_metrics() is m1
    assert set(m1) == {"queue_wait", "plan", "dispatch", "readback",
                       "round_wall", "host_gap", "ttft", "inter_token"}
    m1["ttft"].observe(0.12)
    text = metrics.prometheus_text()
    assert "serve_phase_ttft_s_bucket" in text
    # a registry clear (test isolation) triggers a rebuild
    metrics.clear_registry()
    m2 = obs.phase_metrics()
    assert m2 is not m1
    assert metrics.registry()["serve_phase_ttft_s"] is m2["ttft"]


def test_event_window_cursor_resume_limit_and_dropped():
    """The scrape seam: cursored reads over a ring snapshot resume
    exactly, cap at limit, and COUNT overwritten events as dropped
    instead of silently skipping them."""
    log = obs.EventLog(capacity=8, name="win")
    for i in range(5):
        log.append("e", rid=i)
    win, cur, dropped = obs.event_window(log.snapshot(), log.total,
                                         0, limit=3)
    assert [e[RID] for e in win] == [0, 1, 2]
    assert cur == 3 and dropped == 0
    win, cur, dropped = obs.event_window(log.snapshot(), log.total,
                                         cur, limit=10)
    assert [e[RID] for e in win] == [3, 4]
    assert cur == 5 and dropped == 0
    # caught up: empty window, cursor parks at total
    win, cur, dropped = obs.event_window(log.snapshot(), log.total,
                                         cur, limit=10)
    assert win == [] and cur == 5 and dropped == 0
    # ring wraps: seqs 0..4 are overwritten before the next read
    for i in range(5, 13):
        log.append("e", rid=i)
    win, cur, dropped = obs.event_window(log.snapshot(), log.total,
                                         0, limit=100)
    assert dropped == 5                      # seqs 0..4 lost
    assert [e[RID] for e in win] == list(range(5, 13))
    assert cur == 13


def test_load_flight_bundle_torn_final_line(tmp_path):
    """The dumper can die mid-append: a torn FINAL events.jsonl line
    is truncated (with a warning) and the rest returned; a torn line
    anywhere else is real corruption and raises."""
    eng = _FakeFlightEngine()
    bdir = obs.dump_flight_bundle(str(tmp_path), "crash", engine=eng)
    epath = os.path.join(bdir, "events.jsonl")
    good = open(epath).read()
    n_good = len(good.splitlines())
    with open(epath, "a") as f:
        f.write('{"stream": "engine", "ty')       # no newline
    with pytest.warns(RuntimeWarning, match="torn"):
        b = obs.load_flight_bundle(bdir)
    assert b["events_torn_truncated"] == 1
    assert len(b["events_jsonl"]) == n_good
    # the torn tail was truncated IN PLACE: a second load is clean
    assert open(epath).read() == good
    b2 = obs.load_flight_bundle(bdir)
    assert b2.get("events_torn_truncated", 0) == 0
    # a complete-but-garbled line followed by valid records raises
    with open(epath, "w") as f:
        f.write('{"broken": \n' + good)
    with pytest.raises(json.JSONDecodeError):
        obs.load_flight_bundle(bdir)
