"""Set-up accounted for from inside the program: the build log of
``ray_tpu/util/compile_cache.py`` (one record a build, from JAX's own
``jax.monitoring`` events), the engine's ``compile`` and ``engine_init``
events and ``load_report()``'s two counts, and the benchmark's four
readers of them on recorded logs (benchmarks/tests/data/
setup_builds.{cold,warm}.json: one CPU rehearsal of toy-llama.chat-sat
on an empty compile cache, and the next on the cache it left).

Every jitted function here has a name no other test builds, and every
check reads the process's log from a cursor taken first: the log is one
a process, and a worker runs other files before this one.
"""
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.util import compile_cache
from ray_tpu.util.compile_cache import BuildLog, build_log

PARTS = ("trace_s", "nested_trace_s", "lower_s", "backend_s",
         "cache_read_s")


def _work(x):
    # enough operations that tracing and lowering alone pass SMALL_S
    for _ in range(12):
        x = jnp.tanh(x) @ x + jnp.sin(x)
    return x


def _of(records, program):
    return [r for r in records if r["program"] == program]


# ------------------------------------------------------ one build

@pytest.fixture(scope="module")
def one_build():
    """A fresh jitted function that calls an inner jitted one, built
    once and called again: (its records, the outer's own trace event as
    a listener of the test's saw it, the clock before and after the
    build, the records the second call added)."""
    log = build_log()

    @jax.jit
    def build_probe_inner(x):
        return _work(x)

    def build_probe_outer(x):
        return build_probe_inner(x).sum() + 1.0

    own = []

    def listen(event, seconds, **kw):
        if (event.endswith("jaxpr_trace_duration")
                and kw.get("fun_name") == "build_probe_outer"):
            own.append(seconds)
    jax.monitoring.register_event_duration_secs_listener(listen)
    f = jax.jit(build_probe_outer)
    x = jnp.ones((8, 8))
    cursor = log.total
    t0 = time.monotonic()
    f(x).block_until_ready()
    t1 = time.monotonic()
    first = log.since(cursor)
    cursor = log.total
    f(x).block_until_ready()
    return types.SimpleNamespace(first=first, own=own, t0=t0, t1=t1,
                                 second=log.since(cursor))


@pytest.mark.parametrize("case", ["one_record", "the_outers_own_trace",
                                  "lowered_and_compiled", "clock",
                                  "none_when_warm", "totals"])
def test_a_build_is_one_record_under_the_outers_name(one_build, case):
    mine = _of(one_build.first, "jit_build_probe_outer")
    if case == "one_record":
        assert len(mine) == 1
        # the inner function was traced inside the outer's build and
        # never built on its own
        assert not _of(one_build.first, "jit_build_probe_inner")
        assert set(mine[0]) == {"seq", "program", "t", "thread",
                                "cache_hit", *PARTS}
        assert mine[0]["thread"] == threading.get_ident()
    elif case == "the_outers_own_trace":
        # trace_s is the ONE event that carries the outer's name; the
        # inner functions' events ended inside it and are kept apart
        assert len(one_build.own) == 1
        assert mine[0]["trace_s"] == one_build.own[0] > 0
        assert 0 < mine[0]["nested_trace_s"]
    elif case == "lowered_and_compiled":
        assert mine[0]["lower_s"] > 0 and mine[0]["backend_s"] > 0
        whole = one_build.t1 - one_build.t0
        assert sum(mine[0][k] for k in ("trace_s", "lower_s",
                                        "backend_s")) <= whole
    elif case == "clock":
        # time.monotonic(), the engine's EventLog's clock
        assert one_build.t0 <= mine[0]["t"] <= one_build.t1
    elif case == "none_when_warm":
        assert not _of(one_build.second, "jit_build_probe_outer")
    else:
        tot = build_log().totals()
        mine_tot = tot["programs"]["jit_build_probe_outer"]
        assert mine_tot["builds"] == 1
        for k in PARTS:
            assert mine_tot[k] == mine[0][k]
        assert tot["builds"] == sum(p["builds"]
                                    for p in tot["programs"].values())
        assert tot["records"] == build_log().total
        assert tot["events"] > 0 and tot["listener_s"] > 0


def test_two_threads_building_at_once_keep_their_records_apart():
    log = build_log()
    barrier = threading.Barrier(2)
    idents, cursor = {}, log.total

    def build(name):
        def fn(x):
            return _work(x).sum()
        fn.__name__ = name
        barrier.wait()
        idents[name] = threading.get_ident()
        jax.jit(fn)(jnp.ones((8, 8))).block_until_ready()

    threads = [threading.Thread(target=build, args=(n,))
               for n in ("build_probe_thread_a", "build_probe_thread_b")]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    new = log.since(cursor)
    for name, ident in idents.items():
        mine = _of(new, "jit_" + name)
        assert len(mine) == 1, (name, new)
        assert mine[0]["thread"] == ident != threading.get_ident()
        assert mine[0]["trace_s"] > 0 and mine[0]["backend_s"] > 0
        assert mine[0]["nested_trace_s"] <= mine[0]["trace_s"]


@pytest.fixture
def cache_in(tmp_path):
    """The persistent cache pointed at an empty directory of the
    test's own, and back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def _twice(name):
    """Two function objects with one name and one body: the second is
    traced, lowered and built anew in this process (as after
    ``jax.clear_caches()``, without clearing every other test's
    programs), and its module is the first's."""
    def make():
        def fn(x):
            return _work(x).sum() * 3.0
        fn.__name__ = name
        return jax.jit(fn)
    return make(), make()


@pytest.mark.parametrize("case", ["miss_then_hit", "no_cache_is_none"])
def test_a_rebuild_says_whether_the_persistent_cache_had_it(
        cache_in, case):
    log = build_log()
    x = jnp.ones((8, 8))
    if case == "no_cache_is_none":
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
        cursor = log.total
        _twice("build_probe_uncached")[0](x).block_until_ready()
        (rec,) = _of(log.since(cursor), "jit_build_probe_uncached")
        assert rec["cache_hit"] is None and rec["cache_read_s"] == 0
        return
    first, second = _twice("build_probe_cached")
    cursor = log.total
    first(x).block_until_ready()
    (miss,) = _of(log.since(cursor), "jit_build_probe_cached")
    assert miss["cache_hit"] is False and miss["cache_read_s"] == 0
    assert any(f.endswith("-cache") for f in os.listdir(cache_in))
    cursor = log.total
    second(x).block_until_ready()
    (hit,) = _of(log.since(cursor), "jit_build_probe_cached")
    assert hit["cache_hit"] is True
    # the backend span of a hit is the load, and cache_read_s the part
    # of it that read the file; tracing and lowering are paid again
    assert 0 < hit["cache_read_s"] <= hit["backend_s"]
    assert hit["trace_s"] > 0 and hit["lower_s"] > 0
    summed = compile_cache.summarize_builds([miss, hit])
    assert summed["cache_hit"] is False
    assert summed["backend_s"] == pytest.approx(
        miss["backend_s"] + hit["backend_s"], abs=2e-6)
    assert compile_cache.summarize_builds([hit])["cache_hit"] is True
    assert compile_cache.summarize_builds([])["cache_hit"] is None


# ------------------------------- the log alone, fed JAX's events by hand

_T, _L, _B = (compile_cache._TRACE, compile_cache._LOWER,
              compile_cache._BACKEND)


def _feed(log, name, trace=0.0, lower=0.0, backend=None, inner=()):
    """JAX's events of one build, the inner functions' traces first.
    ``trace`` is made to reach back over them (a listener is called
    when its span ENDS, so the outer's covers the inner ones' calls)."""
    t0 = time.monotonic()
    for inner_name, s in inner:
        log._on_seconds(_T, s, fun_name=inner_name)
    if inner:
        trace += time.monotonic() - t0
    log._on_seconds(_T, trace, fun_name=name)
    log._on_seconds(_L, lower, fun_name=f"jit({name})")
    if backend is not None:
        log._on_seconds(_B, backend, fun_name=f"jit({name})")


@pytest.mark.parametrize("case", ["small_goes_under_other",
                                  "watched_takes_a_record",
                                  "lowered_and_never_compiled",
                                  "a_trace_no_build_claimed",
                                  "nested_at_any_count_and_depth",
                                  "a_build_inside_a_trace",
                                  "the_ring_is_bounded"])
def test_what_takes_a_record_and_what_the_totals_keep(case):
    log = BuildLog(capacity=4)
    if case == "small_goes_under_other":
        _feed(log, "broadcast_in_dim", 0.001, 0.002, 0.003)
        assert log.snapshot() == [] and log.total == 0
        other = log.totals()["programs"]["other"]
        assert other["builds"] == 1
        assert other["backend_s"] == 0.003 and other["lower_s"] == 0.002
    elif case == "watched_takes_a_record":
        log.watch("jit_seed")
        _feed(log, "seed", 0.001, 0.002, 0.003)
        (rec,) = log.snapshot()
        assert rec["program"] == "jit_seed" and rec["seq"] == 0
        assert "other" not in log.totals()["programs"]
    elif case == "lowered_and_never_compiled":
        # .lower() without .compile(): its seconds are kept, it is no
        # build, and the next build is not charged with it
        _feed(log, "only_lowered", 0.5, 0.25)
        _feed(log, "built", 1.0, 0.5, 2.0)
        (rec,) = log.snapshot()
        assert (rec["program"], rec["trace_s"], rec["lower_s"]) == \
            ("jit_built", 1.0, 0.5)
        tot = log.totals()
        assert tot["builds"] == 1
        assert tot["programs"]["other"]["builds"] == 0
        assert tot["programs"]["other"]["lower_s"] == 0.25
        assert tot["lower_s"] == 0.75 and tot["trace_s"] == 1.5
    elif case == "a_trace_no_build_claimed":
        # jax.eval_shape: a trace event and no lowering; then a build
        # whose own trace is shorter than the time since
        log._on_seconds(_T, 0.25, fun_name="init")
        time.sleep(0.02)
        _feed(log, "built", 0.001, 0.5, 2.0, inner=[("tanh", 0.0005)])
        (rec,) = log.snapshot()
        assert rec["nested_trace_s"] == 0.0005
        tot = log.totals()
        assert tot["unbuilt_trace_s"] == pytest.approx(0.25)
        assert (tot["trace_s"] + tot["nested_trace_s"]
                + tot["unbuilt_trace_s"]) == pytest.approx(0.2515, abs=1e-3)
    elif case == "nested_at_any_count_and_depth":
        # a step program of 48 unrolled layers sends thousands of inner
        # traces before its own (8,700 in gpt2-124m.train-b24 on the
        # chip): all are the build's nested seconds, none is lost to a
        # bound, and what stays pending is the outermost trace alone
        n = 40 * log.PENDING
        _feed(log, "deep", 1.0, 0.5, 2.0,
              inner=[("tanh", 0.0)] * n + [("matmul", 0.25)])
        (rec,) = log.snapshot()
        assert rec["nested_trace_s"] == 0.25
        tot = log.totals()
        assert tot["unbuilt_trace_s"] == 0 and tot["events"] == n + 4
        # a function inside a function inside the build is counted at
        # both depths, as JAX sent it
        t0 = time.monotonic()
        log._on_seconds(_T, 0.125, fun_name="leaf")
        log._on_seconds(_T, 0.5 + time.monotonic() - t0, fun_name="mid")
        assert len(log._state().traces) == 1
        _feed(log, "outer", 1.0 + time.monotonic() - t0, 0.5, 2.0)
        rec = log.snapshot()[-1]
        assert rec["nested_trace_s"] == pytest.approx(0.625, abs=0.01)
        assert rec["trace_s"] == pytest.approx(1.0, abs=0.01)
    elif case == "a_build_inside_a_trace":
        # an eager operation on concrete values while a program is
        # tracing builds on its own: it claims its own trace and leaves
        # the program's pending inner ones for the program
        t0 = time.monotonic()
        log._on_seconds(_T, 0.125, fun_name="before")
        time.sleep(0.002)
        _feed(log, "eager_op", 0.001, 0.5, 2.0)
        log._on_seconds(_T, 0.25, fun_name="after")
        _feed(log, "program", 1.0 + time.monotonic() - t0, 0.5, 2.0)
        eager, program = log.snapshot()
        assert (eager["trace_s"], eager["nested_trace_s"]) == (0.001, 0)
        assert program["nested_trace_s"] == 0.375
        assert log.totals()["unbuilt_trace_s"] == pytest.approx(0)
        assert log._state().traces == []
        # more pending than a thread keeps: the older half is folded
        # into one entry, and the program still takes in all of it
        n = log.PENDING + 10
        _feed(log, "wide", 1.0, 0.5, 2.0, inner=[("f", 0.5)] * n)
        assert log.snapshot()[-1]["nested_trace_s"] == 0.5 * n
        assert log.totals()["unbuilt_trace_s"] == pytest.approx(0)
    else:
        for i in range(6):
            _feed(log, f"p{i}", 1.0, 1.0, 1.0)
        assert [r["seq"] for r in log.snapshot()] == [2, 3, 4, 5]
        assert [r["program"] for r in log.since(4)] == ["jit_p4", "jit_p5"]
        assert log.since(6) == [] and log.total == 6
        # the totals run on past the ring
        assert log.totals()["builds"] == 6
        assert len(log.totals()["programs"]) == 6


# ------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def started():
    """One engine of a model no other file builds, after one request:
    (the engine, the clock around its construction)."""
    cfg = llama_tiny(dtype=jnp.float32, vocab_size=227)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    t0 = time.monotonic()
    eng = LLMEngine(model, params, max_slots=4, page_size=8, n_pages=65,
                    chunk=4, prefill_chunk=16)
    t1 = time.monotonic()
    eng.start()
    try:
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4).result()
        assert eng.wait_idle(10)
        yield eng, t0, t1
    finally:
        eng.shutdown()


def _events(eng, kind):
    return [e for e in eng.events.snapshot() if e[2] == kind]


@pytest.mark.parametrize("case", ["compile_event", "engine_init",
                                  "load_report"])
def test_an_engines_start_is_in_its_own_log(started, case):
    eng, t0, t1 = started
    if case == "compile_event":
        by_name = {e[5]["program"]: e for e in _events(eng, "compile")}
        assert {"jit_prefill", "jit_decode"} <= set(by_name)
        for name in ("jit_prefill", "jit_decode"):
            t, data = by_name[name][1], by_name[name][5]
            # the keys it had, and what the build was
            assert {"program", "round", "built", "wall_s", "trace_s",
                    "lower_s", "backend_s", "cache_read_s",
                    "cache_hit"} == set(data)
            assert data["trace_s"] > 0 and data["lower_s"] > 0
            assert data["backend_s"] > 0
            # the build happened inside the round that reports it
            built = (data["trace_s"] + data["lower_s"]
                     + data["backend_s"])
            assert built <= data["wall_s"] + 1e-5
            (rec,) = [r for r in build_log().snapshot()
                      if r["program"] == name
                      and t - data["wall_s"] - 1e-3 <= r["t"] <= t]
            assert data["backend_s"] == round(rec["backend_s"], 6)
            assert data["cache_hit"] is rec["cache_hit"]
    elif case == "engine_init":
        (init,) = _events(eng, "engine_init")
        data = init[5]
        assert {"wall_s", "pool_s", "state_s", "programs_s",
                "other_s"} <= set(data)
        assert all(k.endswith("_s") and v >= 0 for k, v in data.items())
        parts = sum(v for k, v in data.items() if k != "wall_s")
        assert parts == pytest.approx(data["wall_s"], rel=0.05)
        # the constructor's own wall time, on the log's clock
        assert 0.9 * data["wall_s"] <= t1 - t0
        assert t0 <= init[1] <= t1
        assert data["wall_s"] == pytest.approx(t1 - t0, abs=0.25)
        # it is the log's first event
        assert eng.events.snapshot()[0][2] == "engine_init" \
            or eng.events.total > eng.events.capacity
    else:
        rep = eng.load_report()
        assert rep["programs_built"] == eng.stats["programs_built"] >= 2
        cold = sum(e[5]["cache_hit"] is not True
                   for e in _events(eng, "compile"))
        assert rep["cold_builds"] == eng.stats["cold_builds"] == cold
        assert rep["cold_builds"] <= rep["programs_built"]


def test_the_parts_of_a_start_over_50_ms_get_a_name(monkeypatch):
    from ray_tpu.serve import obs
    now = [100.0]
    clocked = types.ModuleType("time")
    clocked.__dict__.update(vars(time), monotonic=lambda: now[0])
    monkeypatch.setattr(obs, "time", clocked)
    clock = obs.PhaseClock()
    now[0] += 0.25
    clock.mark("pool")
    now[0] += 0.0625
    clock.mark("weights")
    now[0] += 0.015625
    clock.mark("quick")
    now[0] += 0.125
    clock.mark("pool")
    now[0] += 0.03125
    assert clock.parts() == {
        "wall_s": 0.484375, "pool_s": 0.375, "state_s": 0.0,
        "programs_s": 0.0, "weights_s": 0.0625, "other_s": 0.046875}


# ------------------------------------------- the benchmark's readers

def _recorded(which, kind="serve"):
    from benchmarks import common
    with open(os.path.join(common.HERE, "tests", "data",
                           f"setup_builds.{which}.json")) as f:
        rec = json.load(f)
    return rec, types.SimpleNamespace(
        kind=kind, window=tuple(rec["window"]), builds=rec["builds"],
        events=[tuple(e) for e in rec["events"]],
        e2e={"setup_s": rec["setup_s"]},
        traffic={"ramp_s": rec["ramp_s"]})


READERS = ("setup_build_s", "setup_program_trace_s", "setup_cold_builds",
           "engine_init_s")


@pytest.mark.parametrize("which", ["cold", "warm"])
@pytest.mark.parametrize("name", READERS)
def test_the_setup_readers_on_a_recorded_log(name, which, capsys):
    from benchmarks import common
    rec, run = _recorded(which)
    got = common.load_metric_reader(name)(run)
    records = [r for r in rec["builds"]["records"]
               if r["t"] < rec["window"][0]]
    assert records and len(records) == len(rec["builds"]["records"])
    other = rec["builds"]["totals"]["programs"].get("other")
    if name == "setup_build_s":
        want = sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                   for r in records)
        if which == "warm":
            # the loads too small for a record are in it
            assert other["builds"] == 6
            want += (other["trace_s"] + other["lower_s"]
                     + other["backend_s"])
        else:
            assert other is None
        assert got == pytest.approx(want)
        assert got == pytest.approx({"cold": 8.926401, "warm": 3.218521}[
            which], abs=1e-5)
        assert got < rec["setup_s"] - rec["ramp_s"]
        # the same JAX events the harness's meter summed
        tot = rec["builds"]["totals"]
        assert got == pytest.approx(tot["trace_s"] + tot["lower_s"]
                                    + tot["backend_s"])
    elif name == "setup_program_trace_s":
        mine = [r for r in records
                if r["program"] in ("jit_prefill", "jit_decode",
                                    "jit_seed")]
        assert len(mine) == 8          # six widths, decode, seed
        assert got == pytest.approx(sum(r["trace_s"] + r["lower_s"]
                                        for r in mine))
        assert got < common.load_metric_reader("setup_build_s")(run)
    elif name == "setup_cold_builds":
        # the harness's weights program alone compiles for a second at
        # the toy size; on the cache the cold run left, nothing does
        assert got == {"cold": 1, "warm": 0}[which]
        hits = {r["cache_hit"] for r in records}
        assert hits == {{"cold": False, "warm": True}[which]}
    else:
        (init,) = [e[5] for e in run.events if e[2] == "engine_init"]
        assert got == init["wall_s"] > 0
        train = _recorded(which, kind="train")[1]
        assert common.load_metric_reader(name)(train) is None
    assert "[setup] " in capsys.readouterr().out


def test_what_set_up_fell_by_is_what_building_fell_by():
    """Cold then warm on one machine: ``setup_s`` fell by what
    ``setup_build_s`` fell by, and the step programs' tracing and
    lowering was paid both times."""
    from benchmarks import common
    (cold, c), (warm, w) = _recorded("cold"), _recorded("warm")
    build = common.load_metric_reader("setup_build_s")
    trace = common.load_metric_reader("setup_program_trace_s")
    assert build(c) - build(w) == pytest.approx(
        cold["setup_s"] - warm["setup_s"], rel=0.10)
    assert 0.5 < trace(w) / trace(c) <= 1.1


@pytest.mark.parametrize("name", READERS)
def test_the_setup_readers_report_nothing_on_a_program_without_the_log(
        name, monkeypatch):
    """The parent of PR 51 under this PR's benchmark files: no
    ``build_log`` to import, no ``engine_init`` event; every reader
    says None and raises nothing."""
    from benchmarks import common
    monkeypatch.delattr(compile_cache, "build_log")
    run = types.SimpleNamespace(
        kind="serve", window=(10.0, 50.0), e2e={"setup_s": 30.0},
        traffic={}, events=[(0, 1.0, "round", None, None, {"round": 1}),
                            (1, 2.0, "compile", None, None,
                             {"program": "jit_decode", "round": 1,
                              "built": 1, "wall_s": 0.5})])
    assert common.load_metric_reader(name)(run) is None
    train = types.SimpleNamespace(kind="train", window=(10.0, 50.0),
                                  e2e={"setup_s": 30.0}, traffic={})
    assert common.load_metric_reader(name)(train) is None


@pytest.mark.parametrize("name", READERS)
def test_the_setup_readers_are_appended_to_the_benchmark(name):
    from benchmark_as_of import as_of, row
    from benchmarks import common
    bench = common.load_benchmark()
    # where the table of what each PR appended says (later PRs' readers
    # stand behind them)
    assert row(51).readers == READERS
    assert tuple(m["name"] for m in as_of(51)["per_layer"][-4:]) == READERS
    m = common.find_named(bench["per_layer"], name, "metric")
    cells = [w["name"] for w in bench["workloads"]]
    want = {"name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": "model step / train step",
            "moves": "setup_s", "workloads": cells}
    if name == "setup_cold_builds":
        want.update(unit="programs", source="program_counter")
    if name == "engine_init_s":
        want.update(layer="engine", workloads=[
            c for c in cells if c != "gpt2-124m.train-b24"])
    assert m == want
    # the first per-layer metrics that move setup_s, which every cell
    # reports
    moved = common.find_named(bench["end_to_end"], "setup_s", "metric")
    assert "workloads" not in moved
    assert [x["name"] for x in bench["per_layer"]
            if x["moves"] == "setup_s"] == list(READERS)
    assert callable(common.load_metric_reader(name))
