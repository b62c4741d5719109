"""What the engine says about its own work: the ``round`` event's keys,
the compile counter, the device-trace control and its ``engine.*``
annotations, and the program names the benchmark's readers key on.

Each engine here gets a model configuration no other test file uses:
the jitted step programs are shared per process by (model, knobs), and
a program another file already compiled would not count as built.
"""
import glob
import time
import types

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import profiling
from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine


def _model(vocab):
    cfg = llama_tiny(dtype=jnp.float32, vocab_size=vocab)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _engine(vocab, **kw):
    model, params = _model(vocab)
    opts = dict(max_slots=4, page_size=8, n_pages=65, chunk=4,
                prefill_chunk=16)
    opts.update(kw)
    return LLMEngine(model, params, **opts).start()


def _events(eng, *kinds):
    return [e for e in eng.events.snapshot() if e[2] in kinds]


# ----------------------------------------------------- the round event

@pytest.fixture(scope="module")
def rounds():
    """One engine's whole event log over a few mixed requests, grouped
    by round: [(round event's data, the prefill and decode events
    since the round before)]."""
    eng = _engine(251)
    try:
        hs = [eng.submit(list(range(1, 4 + 5 * i)), max_new_tokens=6 + i)
              for i in range(5)]
        for h in hs:
            h.result()
    finally:
        eng.shutdown()
    out, since = [], []
    for e in eng.events.snapshot():
        if e[2] in ("prefill", "decode"):
            since.append(e)
        elif e[2] == "round":
            out.append((e[5], since))
            since = []
    assert len(out) >= 4
    return out


NEW_KEYS = ("round", "admit_s", "plan_s", "dispatch_s", "readback_s",
            "decode_riders", "decode_steps", "prefill_tokens",
            "prefill_budget", "prefill_rows")


@pytest.mark.parametrize("case", ["keys", "phases_within_wall",
                                  "numbered", "prefill_agrees",
                                  "rows_agree", "decode_agrees",
                                  "budget"])
def test_round_event_says_what_the_round_was(rounds, case):
    if case == "keys":
        for data, _ in rounds:
            assert {"host_gap_s", "wall_s", "overlap", *NEW_KEYS} \
                <= set(data)
    elif case == "phases_within_wall":
        for data, _ in rounds:
            parts = (data["admit_s"] + data["plan_s"]
                     + data["dispatch_s"] + data["readback_s"])
            assert min(data[k] for k in NEW_KEYS[1:5]) >= 0
            assert parts <= data["wall_s"] + 4e-6     # 6-digit rounding
    elif case == "numbered":
        numbers = [data["round"] for data, _ in rounds]
        assert numbers == sorted(set(numbers)) and numbers[0] >= 1
    elif case == "prefill_agrees":
        for data, since in rounds:
            granted = sum(take for e in since if e[2] == "prefill"
                          for _sid, take in e[5])
            assert data["prefill_tokens"] == granted
        assert any(data["prefill_tokens"] for data, _ in rounds)
    elif case == "rows_agree":
        for data, since in rounds:
            calls = [e[5] for e in since if e[2] == "prefill"]
            assert data["prefill_rows"] == sum(len(c) for c in calls)
            assert data["prefill_rows"] <= 4
        # five requests submitted at once on four slots: some call
        # carried more than one row
        assert any(data["prefill_rows"] > 1 for data, _ in rounds)
    elif case == "decode_agrees":
        for data, since in rounds:
            steps = [e[5] for e in since if e[2] == "decode"]
            assert data["decode_steps"] == sum(steps)
            assert (data["decode_riders"] > 0) == bool(steps)
            assert data["decode_riders"] <= 4
        assert any(data["decode_riders"] > 1 for data, _ in rounds)
    else:
        # the call's four rows x prefill_chunk: what it could carry
        for data, _ in rounds:
            assert data["prefill_budget"] == 4 * 16
            assert data["prefill_tokens"] <= data["prefill_budget"]


@pytest.mark.parametrize("case", ["hand_made", "engine_log",
                                  "parent_has_no_key"])
def test_prefill_rows_mean_reads_the_round_event(rounds, case):
    """benchmarks/metrics/prefill_rows_mean.py: rows a prefill call
    carried, over the window's rounds that dispatched one; None (and
    no error) on a program whose events lack the key."""
    from benchmarks import common
    read = common.load_metric_reader("prefill_rows_mean")

    def run(datas, window=(0.5, 8.0)):
        events = [(0, float(t), "round", None, None, d)
                  for t, d in enumerate(datas, 1)]
        return types.SimpleNamespace(kind="serve", window=window,
                                     events=events)
    if case == "hand_made":
        got = read(run([{"prefill_rows": 4}, {"prefill_rows": 1},
                        {"prefill_rows": 0},           # no prefill
                        {"prefill_rows": 4}, {}, {}, {}, {},
                        {"prefill_rows": 2}]))         # outside
        assert got == pytest.approx(3.0)
        assert read(run([{"prefill_rows": 0}])) is None
    elif case == "engine_log":
        datas = [data for data, _ in rounds]
        calls = [d["prefill_rows"] for d in datas if d["prefill_rows"]]
        got = read(run(datas, window=(0.0, len(datas) + 1.0)))
        assert got == pytest.approx(sum(calls) / len(calls))
        assert 1.0 < got <= 4.0
    else:
        old = [{k: v for k, v in data.items() if k != "prefill_rows"}
               for data, _ in rounds]
        assert read(run(old, window=(0.0, len(old) + 1.0))) is None
        train = run([{"prefill_rows": 4}])
        train.kind = "train"
        assert read(train) is None


# ------------------------------------ a mixture's grouped-matmul visits

@pytest.mark.parametrize("case", ["prefill", "decode", "stats"])
def test_round_event_counts_the_grouped_matmuls_visits(moe_rounds, case):
    """``moe_tile_visits``: the (row tile of 128 sorted pairs, expert)
    visits the experts' grouped matmul makes over a dispatch's live
    pairs, summed over the layers; ``moe_decode_tile_visits`` the
    decode dispatches' part. Less ``moe_experts_touched`` it is the
    visits that found their expert's matrix fetched."""
    rounds, stats, by_hand, touched = moe_rounds
    if case == "prefill":
        # one call holds the whole prompt: 64 tokens x 3 = 192 sorted
        # pairs lie over two row tiles, so in every layer at least one
        # expert is visited twice
        got = sum(r["moe_tile_visits"] - r["moe_decode_tile_visits"]
                  for r in rounds)
        assert got == by_hand > touched
    elif case == "decode":
        # one rider: a step's k pairs are k experts of one row each
        steps = [r for r in rounds if r["moe_decode_layer_steps"]]
        assert steps and all(
            r["moe_decode_tile_visits"] == r["moe_decode_experts_touched"]
            == r["moe_decode_pairs"] for r in steps)
    else:
        for key in ("moe_tile_visits", "moe_decode_tile_visits"):
            assert stats[key] == sum(r[key] for r in rounds) > 0


@pytest.fixture(scope="module")
def moe_rounds():
    """One 64-token prompt through a toy mixture's engine in ONE
    prefill call, then a few decode steps: (the round events, the
    stats, the prompt's visits and touched experts counted by hand
    from a cache-less pass's routing)."""
    import numpy as np
    from ray_tpu.models.mixtral import MOE_STATS, Mixtral, olmoe_tiny
    cfg = olmoe_tiny(dtype=jnp.float32, vocab_size=241)
    model = Mixtral(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    prompt = np.random.default_rng(7).integers(1, 240, size=64).tolist()
    eng = LLMEngine(model, params, max_slots=4, page_size=8, n_pages=65,
                    chunk=4, prefill_chunk=64, temperature=0.0,
                    seed=0).start()
    try:
        eng.submit(prompt, max_new_tokens=9).result()
        eng.submit([5, 6, 7], max_new_tokens=2).result()  # reads the rest
    finally:
        eng.shutdown()
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    _, sown = jax.jit(lambda p, i: model.apply(p, i, mutable=[MOE_STATS])
                      )(params, jnp.asarray([prompt], jnp.int32))
    by_hand = touched = 0
    for topk in jax.tree_util.tree_leaves(sown[MOE_STATS]):   # [1, T, K]
        group_of_row = np.sort(np.asarray(topk).ravel())
        by_hand += len({(r // 128, g) for r, g in enumerate(group_of_row)})
        touched += len(set(group_of_row.tolist()))
    # the second request's own prefill call: 3 tokens x 3 pairs
    second = [np.asarray(t).ravel() for t in jax.tree_util.tree_leaves(
        jax.jit(lambda p, i: model.apply(p, i, mutable=[MOE_STATS]))(
            params, jnp.asarray([[5, 6, 7]], jnp.int32))[1][MOE_STATS])]
    by_hand += sum(len(set(t.tolist())) for t in second)
    touched += sum(len(set(t.tolist())) for t in second)
    return rounds, dict(eng.stats), by_hand, touched


# -------------------------------------------------- the compile counter

@pytest.mark.parametrize("case", ["warm_up_counts", "one_more",
                                  "one_event", "none_when_warm"])
def test_a_new_prefill_width_is_one_compile_event(case):
    eng = _engine(241 + ["warm_up_counts", "one_more", "one_event",
                         "none_when_warm"].index(case),
                  prefill_chunk=32)
    try:
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4).result()  # T=8
        assert eng.wait_idle(10)
        warm = eng.stats["programs_built"]
        seen = len(_events(eng, "compile"))
        if case == "warm_up_counts":
            names = {e[5]["program"] for e in _events(eng, "compile")}
            # (jit_seed is one program per process, built by whichever
            # engine came first)
            assert {"jit_prefill", "jit_decode"} <= names
            assert warm >= 2
            return
        if case == "none_when_warm":
            eng.submit([5, 4, 3, 2, 1, 7], max_new_tokens=4).result()
            assert eng.wait_idle(10)
            assert eng.stats["programs_built"] == warm
            assert len(_events(eng, "compile")) == seen
            return
        eng.submit(list(range(1, 21)), max_new_tokens=4).result()  # T=32
        assert eng.wait_idle(10)
        new = _events(eng, "compile")[seen:]
        if case == "one_more":
            assert eng.stats["programs_built"] == warm + 1
        else:
            assert len(new) == 1
            data = new[0][5]
            assert data["program"] == "jit_prefill"
            assert data["built"] == 1 and data["wall_s"] > 0
            at = [e[5] for e in _events(eng, "round")
                  if e[5]["round"] == data["round"]]
            assert len(at) == 1 and at[0]["prefill_tokens"] == 20
    finally:
        eng.shutdown()


# ---------------------------------------------------- the trace control

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced burst on one engine, and what the trace and the event
    log hold of it."""
    from jax.profiler import ProfileData
    log_dir = str(tmp_path_factory.mktemp("trace"))
    eng = _engine(239)
    facts = {}
    try:
        eng.submit([1, 2, 3], max_new_tokens=4).result()      # warm
        try:
            eng.stop_trace()
        except RuntimeError as e:
            facts["stop_without_start"] = str(e)
        t0 = eng.start_trace(log_dir)
        try:
            eng.start_trace(log_dir)
        except RuntimeError as e:
            facts["second_start"] = str(e)
        for h in [eng.submit(list(range(1, 5 + i)), max_new_tokens=6)
                  for i in range(4)]:
            h.result()
        facts["span"] = eng.stop_trace()
        facts["t0"] = t0
        try:
            eng.stop_trace()
        except RuntimeError as e:
            facts["second_stop"] = str(e)
    finally:
        eng.shutdown()
    facts["marks"] = _events(eng, "trace_start", "trace_stop")
    facts["rounds"] = [e[5]["round"] for e in _events(eng, "round")]
    files = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    facts["files"] = files
    host = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "PjitFunction(")):
                    host.setdefault(ev.name, []).append(dict(ev.stats))
    facts["host"] = host
    return facts


@pytest.mark.parametrize("case", [
    "writes_xplane", "engine.plan", "engine.readback", "engine.admit",
    "engine.drain_ready", "engine.dispatch_prefill",
    "engine.dispatch_decode", "second_start_refused",
    "stop_without_start_is_an_error", "marks_carry_the_round",
    "program_names_in_the_trace"])
def test_start_and_stop_trace(traced, case):
    if case == "writes_xplane":
        assert len(traced["files"]) == 1
        t0, t1 = traced["span"]
        assert t0 == traced["t0"] and t1 > t0
        assert "no device trace" in traced["second_stop"]
    elif case.startswith("engine."):
        stats = traced["host"].get(case)
        assert stats, sorted(traced["host"])
        # every annotation names its round, and the rounds are ones
        # the event log has too: the two join without mapping clocks
        assert all("round" in s for s in stats)
        assert {int(s["round"]) for s in stats} & set(traced["rounds"])
    elif case == "second_start_refused":
        assert "already running" in traced["second_start"]
    elif case == "stop_without_start_is_an_error":
        assert "no device trace" in traced["stop_without_start"]
    elif case == "marks_carry_the_round":
        start, stop = traced["marks"]
        assert start[2] == "trace_start" and stop[2] == "trace_stop"
        assert start[1] == traced["span"][0]
        assert stop[1] == traced["span"][1]
        assert 1 <= start[5]["round"] < stop[5]["round"]
        assert stop[5]["span_s"] == pytest.approx(
            traced["span"][1] - traced["span"][0], abs=1e-5)
    else:
        assert "PjitFunction(decode)" in traced["host"]
        assert "PjitFunction(prefill)" in traced["host"]


def test_module_control_refuses_and_recovers(tmp_path):
    with pytest.raises(RuntimeError):
        profiling.stop_device_trace()
    profiling.start_device_trace(str(tmp_path))
    with pytest.raises(RuntimeError):
        profiling.start_device_trace(str(tmp_path))
    t0, t1 = profiling.stop_device_trace()
    assert t1 >= t0
    with pytest.raises(RuntimeError):
        profiling.stop_device_trace()


# ------------------------- a trace starts on a round's edge, by counts

@pytest.fixture(scope="module")
def edge(tmp_path_factory):
    """A trace started and stopped in the middle of a busy engine's
    work: what was in flight when the profiler started, the marks, the
    rounds and dispatch events between them, and the host plane."""
    import gc
    from jax.profiler import ProfileData
    log_dir = str(tmp_path_factory.mktemp("edge"))
    eng = _engine(233)
    facts = {}
    real = profiling.start_device_trace

    def start(path):
        # called by start_trace, which must hold the engine's lock
        facts["at_start"] = {
            "locked": eng._lock.locked(),
            "fetchq": len(eng._fetchq),
            "pending_prefill": len(eng._pending_prefill),
            "ready": all(b.is_ready() for b in
                         (eng._dev_cur, eng._dev_pos))}
        return real(path)
    try:
        eng.submit([1, 2, 3], max_new_tokens=4).result()      # warm
        hs = [eng.submit(list(range(1, 6 + 3 * i)), max_new_tokens=60)
              for i in range(12)]
        while eng.stats["chunks"] < 3:                 # well under way
            time.sleep(0.001)
        profiling.start_device_trace = start
        try:
            eng.start_trace(log_dir)
        finally:
            profiling.start_device_trace = real
        hs += [eng.submit(list(range(1, 5 + 4 * i)), max_new_tokens=20)
               for i in range(4)]
        gc.collect()                       # a full pass, inside the trace
        for h in hs:
            h.result()
        eng.stop_trace()
    finally:
        eng.shutdown()
    evs = eng.events.snapshot()
    facts["events"] = evs
    facts["stats"] = dict(eng.stats)
    facts["marks"] = [e[5] for e in evs
                      if e[2] in ("trace_start", "trace_stop")]
    facts["gc"] = [e for e in evs if e[2] == "gc"]
    names = set()
    files = glob.glob(log_dir + "/plugins/profile/*/*.xplane.pb")
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names |= {ev.name for ev in line.events}
    facts["host_names"] = names
    return facts


@pytest.mark.parametrize("case", [
    "nothing_in_flight", "counts_add_up", "start_says_its_cost",
    "prefill_width_is_the_calls_T", "prefill_head_rows_is_the_calls_B",
    "no_layer_of_a_model_of_pages_runs_on_the_sampled_positions_alone",
    "no_scan_kernel_walks_a_model_of_pages", "cpu_within_wall",
    "gc_pass_is_an_event", "gc_pass_is_a_span",
    "gc_hook_installed_once", "the_log_joins"])
def test_trace_starts_on_a_rounds_edge(edge, case):
    from ray_tpu.serve import obs
    start, stop = edge["marks"]
    rounds = [(e[5], e[1]) for e in edge["events"] if e[2] == "round"]
    if case == "nothing_in_flight":
        assert edge["at_start"] == {"locked": True, "fetchq": 0,
                                    "pending_prefill": 0, "ready": True}
    elif case == "counts_add_up":
        # the marks' cumulative counts differ by exactly what the rounds
        # between them dispatched: the log says how many executions the
        # trace may hold
        between = [d for d, _t in rounds
                   if start["round"] < d["round"] <= stop["round"]]
        assert len(between) >= 2
        assert (start["prefills"]
                + sum(1 for d in between if d["prefill_rows"])
                == stop["prefills"])
        decodes = decoded = 0
        for e in edge["events"]:
            if e[2] == "decode":
                decoded = 1
            elif e[2] == "round":
                if start["round"] < e[5]["round"] <= stop["round"]:
                    decodes += decoded
                decoded = 0
        assert start["chunks"] + decodes == stop["chunks"]
        assert start["verifies"] == stop["verifies"] == 0
        assert stop["chunks"] > start["chunks"]
    elif case == "start_says_its_cost":
        assert start["wait_s"] >= 0 and start["start_s"] >= 0
        assert "span_s" in stop
    elif case == "prefill_width_is_the_calls_T":
        since, seen = [], set()
        for e in edge["events"]:
            if e[2] == "prefill":
                since.append(max(take for _sid, take in e[5]))
            elif e[2] == "round":
                want = 0
                if since:
                    want = 8                    # page_size: the floor
                    while want < since[0]:
                        want *= 2
                    want = min(want, 16)        # prefill_chunk
                assert e[5]["prefill_width"] == want
                seen.add(want)
                since = []
        assert {0, 8, 16} <= seen
    elif case == "prefill_head_rows_is_the_calls_B":
        # the head is applied to ONE position a row of the [4, T] call
        # (step_programs._jit_prefill asks the model for ``last_idx``
        # alone), dummy rows included; a round without a call says 0
        heads = {(bool(d["prefill_rows"]), d["prefill_head_rows"])
                 for d, _t in rounds}
        assert heads == {(True, 4), (False, 0)}
        assert edge["stats"]["prefill_head_rows"] == \
            4 * edge["stats"]["prefills"]
    elif case == ("no_layer_of_a_model_of_pages_runs_on_the_sampled_"
                  "positions_alone"):
        # every layer of this model keeps an entry (K/V pages): its
        # prefill call gathers ``last_idx`` after the LAST layer, and
        # the counter beside ``prefill_head_rows`` says 0 in every
        # round (tests/test_phi4flash.py has the model where it does
        # not)
        assert {d["prefill_sampled_only_layers"] for d, _t in rounds} == {0}
        assert edge["stats"]["prefill_sampled_only_layers"] == 0
        assert edge["stats"]["prefills"] > 0
    elif case == "no_scan_kernel_walks_a_model_of_pages":
        # no layer of this model keeps a state-space state: the counter
        # of the scan's kernel (ops/selective_scan.py) says 0 in every
        # round (tests/test_phi4flash.py has the model where it does
        # not)
        assert {d["prefill_scan_kernel_positions"] for d, _t in rounds} == {0}
        assert edge["stats"]["prefill_scan_kernel_positions"] == 0
        assert edge["stats"]["prefills"] > 0
    elif case == "cpu_within_wall":
        for d, _t in rounds:
            assert 0 <= d["cpu_s"] <= d["wall_s"] + 2e-3
            assert 0 <= d["readback_cpu_s"]
            assert d["cpu_s"] + d["readback_cpu_s"] <= d["wall_s"] + 2e-3
    elif case == "gc_pass_is_an_event":
        full = [e[5] for e in edge["gc"] if e[5]["generation"] == 2]
        assert all(d["duration_s"] > 0 for d in full)
        # the one the fixture forced after the trace's start
        assert any(d["round"] >= start["round"] for d in full)
        # shorter passes of the young generations are not events
        assert all(e[5]["generation"] == 2
                   or e[5]["duration_s"] >= obs.GC_MIN_S
                   for e in edge["gc"])
    elif case == "gc_pass_is_a_span":
        assert "host.gc" in edge["host_names"]
    elif case == "gc_hook_installed_once":
        import gc
        assert gc.callbacks.count(obs._GC_WATCH) == 1
    else:
        # benchmarks/trace_dispatch.py reads this engine's log as it
        # stands: one execution a dispatch, in order, reconciles with
        # the marks' counts, and every row says what its round made
        from benchmarks import trace_dispatch as td
        made = [d for d in td.rounds_of(edge["events"])
                if d["round"] > start["round"]]
        execs, t = [], 1000
        for d in made:
            for prog in d["programs"]:
                execs.append((prog, t, 500))
                t += 1000
        # (the chip's last execution is left out as cut by the stop)
        last = [("jit_seed", t + 1000, 500)]
        got = td.join(execs + last, td.rounds_of(edge["events"]),
                      *td.marks_of(edge["events"]))
        assert got is not None and len(got["rows"]) == len(execs) > 4
        assert not any(got["tail"].values())
        by = {d["round"]: d for d in made}
        for r in got["rows"]:
            d = by[r["round"]]
            if r["program"] == "jit_prefill":
                assert (r["rows"], r["width"]) == (d["prefill_rows"],
                                                   d["prefill_width"])
            else:
                assert (r["steps"], r["riders"]) == (d["decode_steps"],
                                                     d["decode_riders"])
        # an execution more than the log has dispatches: refused
        assert td.join(execs + [("jit_decode", t, 500)] + last,
                       td.rounds_of(edge["events"]),
                       *td.marks_of(edge["events"])) is None


def test_engine_takes_no_events_argument():
    """``LLMEngine(events=)`` was the A/B arm of a benchmark deleted in
    PR 30: the log is always on."""
    import inspect
    assert "events" not in inspect.signature(LLMEngine.__init__).parameters


# ---------------------------------- the programs' names are an interface

@pytest.mark.parametrize("program", ["jit_decode", "jit_prefill",
                                     "jit_step_fn"])
def test_program_names_the_benchmark_keys_on(program):
    """benchmarks/metrics/decode_step_ms.py, decode_roofline.py,
    flash_roofline.py and the ledger's idle-gap labels find the
    programs by these names: renaming the functions empties a metric
    without failing anything else."""
    if program == "jit_step_fn":
        import optax
        from ray_tpu.train.spmd import TrainState, make_train_step
        opt = optax.sgd(0.1)
        step = make_train_step(lambda p, b: (p["w"] * b["x"]).sum(), opt)
        state = TrainState.create({"w": jnp.ones((4,))}, opt)
        text = step.lower(state, {"x": jnp.ones((4,))}).as_text()
        assert "module @jit_step_fn" in text
        return
    from ray_tpu.serve import step_programs
    model, _ = _model(251)
    fn = {"jit_decode": lambda: step_programs._jit_decode(
              model, 0.0, 128, 4, False, None),
          "jit_prefill": lambda: step_programs._jit_prefill(
              model, 0.0, 4, False, None)}[program]()
    assert "jit_" + fn.__name__ == program


@pytest.mark.parametrize("program", ["jit_decode", "jit_prefill"])
def test_scope_names_the_benchmark_keys_on(program):
    """The family tables of benchmarks/families/ sort a program's
    device time by these names (decode_linear_attn_ms,
    linear_state_roofline, decode_moe_ms, moe_experts_roofline,
    decode_attn_ms ...): a hybrid model's step programs carry every
    one of them in their operations' metadata, the delta-rule layer's
    four, the GQA layer's gate and the shared expert's among them."""
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.solar_open2 import SolarOpen2, solar_open2_tiny
    from ray_tpu.serve import step_programs
    cfg = solar_open2_tiny(dtype=jnp.float32, n_layers=4)
    model = SolarOpen2(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = {"params": params["params"]}
    S, i32 = 4, jnp.int32
    pages = jax.eval_shape(lambda: init_kv_pool(cfg, 17, 8, n_slots=S))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    arr = jax.ShapeDtypeStruct
    if program == "jit_decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        text = fn.lower(params, pages, arr((S, 8), i32), arr((S,), i32),
                        arr((S,), i32), key, arr((), i32)
                        ).as_text(debug_info=True)
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        text = fn.lower(params, pages, arr((4, 16), i32), arr((4,), i32),
                        arr((4,), i32), arr((4, 8), i32), key,
                        arr((4,), i32)).as_text(debug_info=True)
    assert f"module @{program}" in text
    for scope in ("kda_conv", "kda_gates", "kda_recurrence", "kda_out",
                  "attn_gate", "moe_shared", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_stats", "kv_append",
                  "kv_gather", "attn_scores", "attn_pv", "head", "sample"):
        assert f"/{scope}" in text, scope


@pytest.mark.parametrize("program", ["jit_decode", "jit_prefill"])
def test_latent_scope_names_the_benchmark_keys_on(program):
    """benchmarks/families/axk1.py's table sorts a program's device
    time by these names (decode_latent_attn_ms, latent_attn_roofline,
    prefill_attn_share): a latent-attention model's step programs carry
    the three ``mla_*`` scopes beside the page window's shared ones,
    the mixture's and the leading dense layer's module name."""
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    cfg = axk1_tiny(dtype=jnp.float32, n_layers=2)
    model = AXK1(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = {"params": params["params"]}
    S, i32 = 4, jnp.int32
    pages = jax.eval_shape(lambda: init_kv_pool(cfg, 17, 8))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    arr = jax.ShapeDtypeStruct
    if program == "jit_decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        text = fn.lower(params, pages, arr((S, 8), i32), arr((S,), i32),
                        arr((S,), i32), key, arr((), i32)
                        ).as_text(debug_info=True)
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        text = fn.lower(params, pages, arr((4, 16), i32), arr((4,), i32),
                        arr((4,), i32), arr((4, 8), i32), key
                        ).as_text(debug_info=True)
    assert f"module @{program}" in text
    for scope in ("mla_q", "mla_kv", "mla_absorb", "kv_append",
                  "kv_gather", "attn_scores", "attn_pv", "feed_forward",
                  "moe_shared", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_stats", "head",
                  "sample"):
        assert f"/{scope}" in text, scope
    # the low-rank projections lie inside their scopes, the output
    # projection outside them
    assert "mla_q/wq_b" in text and "mla_kv/wkv_a" in text
    assert "mla_absorb/wo" not in text and "attention/wo" in text


@pytest.mark.parametrize("program", ["jit_decode", "jit_prefill"])
def test_recurrent_and_latent_scopes_in_one_program(program):
    """benchmarks/families/kimi_linear.py's table sorts a program's
    device time by these names (the three ``*_roofline.by_kind``): the
    step programs of a model with NO K/V layer carry the delta-rule
    layer's four scopes AND the latent attention's three beside the
    page window's shared ones, the mixture's and the leading dense
    layer's module name."""
    from ray_tpu.models.kimi_linear import KimiLinear, kimi_linear_tiny
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    cfg = kimi_linear_tiny(dtype=jnp.float32, n_layers=4)
    model = KimiLinear(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = {"params": params["params"]}
    S, i32 = 4, jnp.int32
    pages = jax.eval_shape(lambda: init_kv_pool(cfg, 17, 8, n_slots=S))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    arr = jax.ShapeDtypeStruct
    if program == "jit_decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        text = fn.lower(params, pages, arr((S, 8), i32), arr((S,), i32),
                        arr((S,), i32), key, arr((), i32)
                        ).as_text(debug_info=True)
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        text = fn.lower(params, pages, arr((4, 16), i32), arr((4,), i32),
                        arr((4,), i32), arr((4, 8), i32), key,
                        arr((4,), i32)).as_text(debug_info=True)
    assert f"module @{program}" in text
    for scope in ("kda_conv", "kda_gates", "kda_recurrence", "kda_out",
                  "mla_q", "mla_kv", "mla_absorb", "kv_append",
                  "kv_gather", "attn_scores", "attn_pv", "feed_forward",
                  "moe_shared", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_stats", "head",
                  "sample"):
        assert f"/{scope}" in text, scope
    # the direct query lies inside its scope, the output projection
    # outside; no rotation is traced
    assert "mla_q/wq" in text and "mla_kv/wkv_a" in text
    assert "attention/wo" in text and "cos" not in text


# ------------------------------------------- the cost with tracing off

def test_closed_annotations_cost_nothing_measurable():
    """Six a round, each under a microsecond with no trace running
    (0.7 us here, PERF.md section 6): 10,000 stay under 50 ms even on
    a loaded test host."""
    from jax.profiler import TraceAnnotation
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for i in range(10_000):
            with TraceAnnotation("engine.plan", round=i):
                pass
        best = min(best, time.perf_counter() - t)
    assert best < 0.05


# ------------------------- scopes are part of a step program's identity

@pytest.mark.parametrize("keyed", [False, True],
                         ids=["default_key_ignores_scopes",
                              "metadata_keyed_sees_scopes"])
def test_persistent_cache_key_and_named_scopes(keyed):
    """The persistent cache strips debug information from its key, so
    an executable loaded from it names its operations as whoever
    compiled it did; inside ``metadata_keyed`` (the engine's step())
    a changed scope is another program."""
    import contextlib
    import hashlib
    from jax._src import cache_key
    from ray_tpu.util.compile_cache import metadata_keyed

    def build(scope):
        def step(x):
            with jax.named_scope(scope):
                return x * 2
        return step

    def key_of(scope):
        with (metadata_keyed() if keyed else contextlib.nullcontext()):
            module = jax.jit(build(scope)).lower(
                jnp.ones((4,))).compiler_ir()
            h = hashlib.sha256()
            cache_key._hash_computation(
                h, module, cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    assert (key_of("attn_scores") != key_of("attn_pv")) == keyed
