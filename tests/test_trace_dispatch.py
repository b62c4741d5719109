"""benchmarks/trace_dispatch.py: device executions of the step programs
joined to the rounds that dispatched them, on synthetic traces and logs
(the chip's side is PERF.md's; the engine's side of the contract,
``trace_start`` on a round's edge with its counts, is
tests/test_engine_trace.py's)."""
import types

import pytest

from benchmarks import common, trace_dispatch as td

MS = 1_000_000          # ns


def _scene(plan, r0=10, first_ns=5 * MS, prefills0=100, chunks0=200):
    """A log and a trace of ``plan``: [(prefill or None, decode or None,
    verify riders or 0)] a round after round ``r0``; prefill = (rows,
    width, window, ms), decode = (steps, riders, ms[, backlog]). The
    device runs every dispatch back to back, the host dispatches each
    round 1 ms before its first execution starts. Returns (events,
    executions, spans, trace_start, trace_stop) with the marks around
    the whole plan."""
    events, execs, spans = [], [], {}
    seq = 0
    t = first_ns
    prefills, chunks, verifies = prefills0, chunks0, 0

    def ev(kind, data):
        nonlocal seq
        events.append((seq, seq * 0.001, kind, None, None, data))
        seq += 1
    start = {"round": r0, "prefills": prefills, "chunks": chunks,
             "verifies": 0, "wait_s": 0.09, "start_s": 0.02}
    ev("trace_start", start)
    for i, (pre, dec, ver) in enumerate(plan):
        rnd = r0 + 1 + i
        data = {"round": rnd, "admit_s": 0.001, "plan_s": 0.002,
                "dispatch_s": 0.003, "readback_s": 0.05, "cpu_s": 0.005,
                "readback_cpu_s": 0.004, "wall_s": 0.06,
                "decode_riders": 0, "decode_steps": 0, "backlog": 0,
                "decode_window_tokens": 0, "prefill_rows": 0,
                "prefill_tokens": 0, "prefill_width": 0,
                "prefill_window_tokens": 0}
        host = t - MS
        if pre:
            rows, width, window, ms = pre
            data.update(prefill_rows=rows, prefill_width=width,
                        prefill_tokens=rows * width,
                        prefill_window_tokens=window)
            spans[("engine.dispatch_prefill", rnd)] = host
            execs.append(("jit_prefill", t, int(ms * MS)))
            t += int(ms * MS)
            execs.append(("jit_seed", t, 2000))
            t += 2000
            prefills += 1
            ev("prefill", ((0, width),))
        if dec:
            steps, riders, ms, *rest = dec
            data.update(decode_steps=steps, decode_riders=riders,
                        decode_window_tokens=512,
                        backlog=rest[0] if rest else 0)
            spans[("engine.dispatch_decode", rnd)] = host + 1000
            execs.append(("jit_decode", t, int(ms * MS)))
            t += int(ms * MS)
            chunks += 1
            ev("decode", steps)
        if ver:
            data.update(decode_steps=1, decode_riders=ver)
            spans[("engine.dispatch_spec", rnd)] = host + 1000
            execs.append(("jit_verify", t, 3 * MS))
            t += 3 * MS
            verifies += 1
        ev("round", data)
    stop = {"round": r0 + len(plan), "prefills": prefills,
            "chunks": chunks, "verifies": verifies, "span_s": 4.0}
    ev("trace_stop", stop)
    # (the stop fell into another program's execution: every execution
    # of a step program is whole)
    execs.append(("jit_convert_element_type", t, 600))
    return events, execs, spans, start, stop


def _join(scene, **kw):
    events, execs, spans, start, stop = scene
    args = dict(executions=execs, round_events=td.rounds_of(events),
                trace_start=start, trace_stop=stop, dispatch_spans=spans)
    args.update(kw)
    return td.join(**args)


STEADY = [((4, 256, 512, 46.0), (8, 32, 93.0), 0)] * 5


@pytest.mark.parametrize("case", [
    "equal_counts", "run_ahead_between_chunks", "tail_cut_off",
    "trace_runs_past_the_stop_mark", "verify_rounds",
    "marks_are_the_last_pair"])
def test_join_pairs_in_order(case, capsys):
    if case == "equal_counts":
        got = _join(_scene(STEADY))
        rows = got["rows"]
        assert len(rows) == 10 and got["violations"] == 0
        assert got["unchecked"] == 0
        assert got["tail"] == {"jit_prefill": 0, "jit_decode": 0,
                               "jit_verify": 0}
        assert [r["round"] for r in rows] == [
            11, 11, 12, 12, 13, 13, 14, 14, 15, 15]
        pre, dec = rows[0], rows[1]
        assert (pre["program"], pre["rows"], pre["width"],
                pre["prompt_tokens"], pre["window_tokens"],
                pre["steps"]) == ("jit_prefill", 4, 256, 1024, 512, 0)
        assert (dec["program"], dec["steps"], dec["riders"],
                dec["rows"]) == ("jit_decode", 8, 32, 0)
        assert pre["device_ms"] == 46.0 and dec["device_ms"] == 93.0
        assert (pre["admit_ms"], pre["plan_ms"], pre["dispatch_ms"],
                pre["readback_ms"], pre["cpu_ms"]) == pytest.approx(
                    (1.0, 2.0, 3.0, 50.0, 5.0))
        # jit_seed's 2 us run between the call and the decode: no gap
        assert dec["gap_before_ms"] == 0
        assert td.prefill_call_ms(rows) == 46.0
        assert td.decode_step_ms(rows) == pytest.approx(93.0 / 8)
        assert td.prefill_share(rows) == pytest.approx(
            100 * 46.0 / 139.0)
    elif case == "run_ahead_between_chunks":
        # one decode-only dispatch of 23 steps between rounds of 8: the
        # step count is the round's, whatever an operation's frequency
        plan = (STEADY[:2] + [(None, (23, 32, 267.0), 0)] + STEADY[:2])
        got = _join(_scene(plan))
        dec = [r for r in got["rows"] if r["program"] == "jit_decode"]
        assert [r["steps"] for r in dec] == [8, 8, 23, 8, 8]
        assert dec[2]["device_ms"] == 267.0 and dec[2]["round"] == 13
        assert td.decode_step_ms(got["rows"]) == pytest.approx(
            (4 * 93.0 + 267.0) / 55)
        text = "\n".join(td.lines(got, chunk=8))
        assert "run-ahead: 1 dispatches of 23.0 steps" in text
        assert "decode_chunk: 4 dispatches of 8.0 steps" in text
    elif case == "tail_cut_off":
        # the stop fell into round 15's decode: its event is the chip's
        # last and is 11.8 ms long for eight steps of 11.6
        events, execs, spans, start, stop = _scene(STEADY)
        n, s0, _d = execs[-2]
        assert n == "jit_decode"
        got = _join((events, execs[:-2] + [(n, s0, int(11.8 * MS))],
                     spans, start, stop))
        assert td.decode_step_ms(got["rows"]) == pytest.approx(93.0 / 8)
        assert got["tail"]["jit_decode"] == 1
        assert got["tail"]["jit_prefill"] == 0
        assert len(got["rows"]) == 9 and got["violations"] == 0
        assert got["rows"][-1]["program"] == "jit_prefill"
        assert got["rows"][-1]["round"] == 15
    elif case == "trace_runs_past_the_stop_mark":
        # the counts are read just before the profiler stops: the trace
        # may hold a round or two more, which the log has too
        events, execs, spans, start, stop = _scene(STEADY)
        early = dict(stop, round=13, prefills=stop["prefills"] - 2,
                     chunks=stop["chunks"] - 2)
        got = _join((events, execs, spans, start, early))
        assert len(got["rows"]) == 10
        assert got["rows"][-1]["round"] == 15 and got["rounds"] == (10, 13)
    elif case == "verify_rounds":
        plan = [((1, 16, 512, 5.0), None, 0), (None, None, 3),
                (None, None, 3), (None, (4, 3, 8.0), 0)]
        got = _join(_scene(plan))
        assert [(r["program"], r["round"]) for r in got["rows"]] == [
            ("jit_prefill", 11), ("jit_verify", 12), ("jit_verify", 13),
            ("jit_decode", 14)]
        assert got["rows"][1]["riders"] == 3
    else:
        a = _scene(STEADY[:1], r0=3)[0]
        b = _scene(STEADY[:2], r0=20)[0]
        start, stop = td.marks_of(a + b)
        assert (start["round"], stop["round"]) == (20, 22)
        assert td.marks_of(a[:-1])[1] is None


@pytest.mark.parametrize("case", [
    "a_round_without_its_event", "more_executions_than_dispatches",
    "too_much_missing", "no_stop_mark", "a_program_without_counts"])
def test_join_refuses_what_it_cannot_reconcile(case, capsys):
    events, execs, spans, start, stop = _scene(STEADY)
    if case == "a_round_without_its_event":
        # a faulted round dispatched and wrote no ``round`` event: the
        # counters know, the round events do not
        cut = [e for e in events
               if not (e[2] in ("round", "decode", "prefill")
                       and e[0] in (7, 8, 9))]
        assert len(cut) == len(events) - 3
        got = _join((cut, execs, spans, start, stop))
    elif case == "more_executions_than_dispatches":
        # (one is left out as the chip's last: two more than dispatches)
        extra = execs + [("jit_decode", execs[-1][1] + 200 * MS, MS),
                         ("jit_decode", execs[-1][1] + 300 * MS, MS)]
        got = _join((events, extra, spans, start, stop))
    elif case == "too_much_missing":
        got = _join((events, [x for x in execs
                              if x[0] != "jit_decode"][:5] + execs[1:4],
                     spans, start, stop))
    elif case == "no_stop_mark":
        got = _join((events, execs, spans, start, None))
    else:
        old = {"round": 10, "log_dir": "/tmp/x"}
        got = _join((events, execs, spans, old, {"round": 15}))
        assert got is None
        out = capsys.readouterr().out
        assert "nothing to join" in out and "refused" not in out
        return
    assert got is None
    out = capsys.readouterr().out
    assert out.count("[dispatch] join refused: ") == 1
    assert {"a_round_without_its_event": "the engine's counter 5",
            "more_executions_than_dispatches": "holds 6 executions",
            "too_much_missing": "missing from the trace's tail",
            "no_stop_mark": "no trace_stop"}[case] in out


@pytest.mark.parametrize("case", ["early", "shifted_by_one", "none",
                                  "no_host_plane", "span_missing"])
def test_join_counts_clock_violations(case):
    events, execs, spans, start, stop = _scene(STEADY)
    if case == "early":
        # an execution that starts before its round's dispatch was made
        # cannot be that round's
        spans = dict(spans)
        spans[("engine.dispatch_decode", 12)] += 500 * MS
        got = _join((events, execs, spans, start, stop))
        assert got["violations"] == 1 and len(got["rows"]) == 10
    elif case == "shifted_by_one":
        # the profiler missed the first prefill call: every later one
        # pairs with the round before its own and starts after the next
        # dispatch was made
        lost = [x for i, x in enumerate(execs) if i != 0]
        got = _join((events, lost, spans, start, stop))
        assert got["violations"] == 4 and got["tail"]["jit_prefill"] == 1
    elif case == "none":
        assert _join((events, execs, spans, start, stop))[
            "violations"] == 0
    elif case == "no_host_plane":
        got = _join((events, execs, None, start, stop))
        assert (got["violations"], got["unchecked"]) == (0, 0)
    else:
        part = {k: v for k, v in spans.items() if k[1] != 13}
        got = _join((events, execs, part, start, stop))
        assert (got["violations"], got["unchecked"]) == (0, 2)


def _run(got=None, **kw):
    run = types.SimpleNamespace(kind="serve", events=[],
                                deployment={}, **kw)
    if got is not None:
        run._dispatch = got
    return run


@pytest.mark.parametrize("name,want", [
    ("dispatch_prefill_call_ms", 46.0),
    ("dispatch_prefill_call_ms.open", 46.0),
    ("dispatch_decode_step_ms", (4 * 93.0 + 12.0) / 34),
    ("dispatch_prefill_share", 100 * 198.0 / (198.0 + 384.0))])
def test_readers_reduce_the_table(name, want):
    """The four per-layer readers of BENCHMARK.json: each reduces the
    joined table, and gives None (no error) without one."""
    read = common.load_metric_reader(name)
    plan = STEADY[:4] + [((1, 64, 512, 14.0), (2, 28, 12.0, 3), 0)]
    got = _join(_scene(plan))
    assert read(_run(got)) == pytest.approx(want)
    assert read(_run()) is None                 # --trace 1: no trace kept
    assert read(_run(trace_dir=None)) is None
    train = _run(got)
    del train._dispatch
    train.kind = "train"
    assert read(train) is None


def test_lines_say_the_price_by_class_shape_and_riders():
    # a step costs 7.3 + 0.2 x riders ms, a call 62 + 1.953 ms a
    # thousand tokens of window
    plan = ([((4, 256, 4096 + 512 * i, 70.0 + i),
              (8, 8 + i, 8 * (7.3 + 0.2 * (8 + i))), 0) for i in range(6)]
            + [((2, 64, 1024, 20.0), (2, 24, 2 * 12.1, 5), 0)])
    events, execs, spans, start, stop = _scene(plan)
    # the device idled 80 ms before round 14's prefill call
    execs = [(n, s + (80 * MS if s >= execs[9][1] else 0), d)
             for n, s, d in execs]
    spans = {k: v + (80 * MS if k[1] >= 14 else 0)
             for k, v in spans.items()}
    got = _join((events, execs, spans, start, stop))
    got["trace_start"] = start
    got["gcs"] = [{"generation": 2, "duration_s": 0.075, "round": 14}]
    text = "\n".join(td.lines(got, chunk=8))
    assert "clock violations 0 (must be 0)" in text
    assert "the start waited 90.0 ms" in text and "20.0 ms for the" in text
    assert "[dispatch] jit_prefill: 7 executions, median 72.000" in text
    assert "[dispatch] jit_decode: 7 executions" in text
    assert "backlog: 1 dispatches of 2.0 steps and 24.0 riders: 12.100" \
        in text
    assert "a step = 7.300 + 0.2000 x riders ms" in text
    assert "a call = 62.000 + 1.9531 x prefill_window_tokens" in text
    assert "prefill_width 256: 6 executions" in text
    assert "prefill_width 64: 1 executions" in text
    assert "x prefill_window_tokens / 1000 ms (windows 4096..6656)" in text
    gap = [ln for ln in text.splitlines() if ln.startswith(
        "[dispatch] gap 80.0")]
    assert len(gap) == 1 and "jit_prefill of round 14" in gap[0]
    assert ("round 13 readback 50.00 ms (cpu 4.00) of a wall of 60.00, "
            "then admit 1.00 plan 2.00 dispatch 3.00 ms (cpu 5.00)"
            in gap[0])
    assert "gc generation 2 75.0 ms in round 14" in gap[0]


def test_fit_needs_a_spread():
    assert td.fit([(8, 1.0), (8, 2.0)]) is None
    a, b = td.fit([(8, 9.0), (16, 10.6), (24, 12.2)])
    assert (a, b) == pytest.approx((7.4, 0.2))


@pytest.mark.parametrize("case", ["exact", "the_stop_cut_the_last",
                                  "agrees_with_decode_step_ms",
                                  "widths", "one_lost_at_the_start"])
def test_join_on_a_recorded_chip_trace(case):
    """What ``load`` and ``rounds_of`` gave the join in one
    ``mistral7b-d16.chat-r80 --trace 2`` run on a TPU v5 lite (my chip
    run, PR 36; tests/data/dispatch_chat_r80.json.gz)."""
    import gzip
    import json
    import os
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "dispatch_chat_r80.json.gz"), "rt") as f:
        rec = json.load(f)
    execs = [tuple(x) for x in rec["executions"]]
    spans = {(a, r): t for a, r, t in rec["dispatch_spans"]}

    def go(ex):
        return td.join(ex, rec["round_events"], rec["trace_start"],
                       rec["trace_stop"], spans)
    got = go(execs)
    rows = got["rows"]
    if case == "exact":
        steps = [x for x in execs if x[0] in td.PROGRAMS]
        assert len(steps) == 54 and len(rows) == 53
        assert got["violations"] == 0 and got["unchecked"] == 0
        assert [r["round"] for r in rows] == sorted(r["round"] for r in rows)
        assert rows[0]["round"] == rec["trace_start"]["round"] + 1
    elif case == "the_stop_cut_the_last":
        # 4.7 ms for a dispatch of eight 12 ms steps: the profiler closed
        # the running execution's event when it stopped
        assert execs[-1][0] == "jit_decode" and execs[-1][2] < 5 * MS
        assert got["tail"] == {"jit_prefill": 0, "jit_decode": 1,
                               "jit_verify": 0}
        assert min(r["device_ms"] for r in rows
                   if r["program"] == "jit_decode") > 90
    elif case == "agrees_with_decode_step_ms":
        # the run's decode_step_ms read 12.258 ms (loop_steps: 256 steps)
        assert sum(r["steps"] for r in rows) == 256
        assert td.decode_step_ms(rows) == pytest.approx(12.258, rel=0.01)
    elif case == "widths":
        assert {r["width"] for r in rows if r["rows"]} == {64, 128, 256}
        assert td.prefill_call_ms(rows, widest=True) == pytest.approx(
            46.10, abs=0.01)
        assert td.prefill_call_ms(rows) == pytest.approx(46.09, abs=0.01)
    else:
        # had the profiler missed the trace's first decode execution,
        # the order would pair each with the round before its own: the
        # clock says so
        first = next(x for x in execs if x[0] == "jit_decode")
        shifted = go([x for x in execs if x is not first])
        assert shifted["violations"] >= 10
