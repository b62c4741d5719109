"""The trace reducer on two traces recorded on a TPU v5e (PR 24, trimmed
to about a second and to the lines the reducer reads), and on synthetic
events for the arithmetic."""
import os
import types

import pytest

from benchmarks import common, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_self_time_gives_enclosed_time_to_the_body():
    evs = [["%while.1 = s32[] while(", 0, 100],
           ["%a.1 = f32[4] fusion(", 10, 30],
           ["%b.2 = f32[4] copy(", 50, 40],
           ["%c.3 = f32[4] fusion(", 120, 10]]
    got = {n.split(" ")[0]: s for n, _s, _d, s in tr.self_times(evs)}
    assert got == {"%while.1": 30, "%a.1": 30, "%b.2": 40, "%c.3": 10}
    assert tr.union_intervals(evs) == [(0, 100), (120, 130)]


def test_names():
    assert tr.module_name("jit_decode(123)") == "jit_decode"
    text = ("%copy.743 = bf16[8,32,64,64,128]{4,3,2,0,1:T(8,128)(2,1)} "
            "copy(bf16[8,32,64,64,128]{4,0,3,2,1} %x)")
    assert tr.op_name(text) == "copy.743"
    assert tr.op_kind(text) == ("copy", "bf16[8,32,64,64,128]")
    tup = ("%attn.67 = (bf16[24,1024,768]{2,1,0:T(8,128)(2,1)}, "
           "bf16[24,1024,768]{2,1,0}) custom-call(bf16[24] %q)")
    assert tr.op_kind(tup) == ("custom-call", "bf16[24,1024,768]")


def test_idle_gaps_and_chips_on_synthetic_planes():
    ir = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_decode(1)", 0, 400_000],
                ["jit_decode(1)", 1_000_000, 400_000]]},
            {"name": "XLA Ops", "events": [
                ["%f.1 = f32[8] fusion(", 0, 300_000],
                ["%all-reduce.2 = f32[8] all-reduce(", 300_000, 100_000],
                ["%f.1 = f32[8] fusion(", 1_000_000, 400_000]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                ["%f.1 = f32[8] fusion(", 0, 1_400_000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["np.asarray(jax.Array)", 420_000, 560_000]]}]}]}
    red = tr.reduce(ir, 2)
    assert red["window_s"] == pytest.approx(1.4e-3)
    assert red["busy_s_per_chip"] == pytest.approx([0.8e-3, 1.4e-3])
    assert red["busy_s"] == pytest.approx(1.1e-3)
    assert red["modules"]["jit_decode"]["runs"] == 2
    assert red["breakdown"]["idle_gaps"] == [
        ["jit_decode->jit_decode | host: np.asarray(jax.Array)",
         pytest.approx(6e-4)]]
    assert tr.reduce(ir, 1)["busy_s"] == pytest.approx(0.8e-3)


def test_recorded_serving_trace():
    red = tr.reduce(tr.load_json(
        os.path.join(DATA, "serve_d16.trace.json.gz")), 1)
    assert red["window_s"] == pytest.approx(1.299528381)
    assert red["busy_s"] == pytest.approx(1.299465882)
    assert red["modules"]["jit_decode"] == {
        "runs": 2, "seconds": pytest.approx(0.694667684)}
    assert red["modules"]["jit_prefill"]["runs"] == 2
    # two runs of 8 and 3 steps: the loop's body ran 11 times
    assert tr.loop_steps(red["module_ops"]["jit_decode"], 2) == 11
    assert tr.loop_step_seconds(red, "jit_decode") == \
        pytest.approx(0.0631516076)
    top = red["breakdown"]["device_ops"][0]
    assert top[0] == "copy bf16[8,32,64,64,128] x504"
    assert top[1] == pytest.approx(0.410590437)
    assert len(red["breakdown"]["device_ops"]) == 10
    read = common.load_metric_reader("decode_step_ms")
    assert read(types.SimpleNamespace(trace=red)) == \
        pytest.approx(63.1516076)


def test_recorded_training_trace_and_the_flash_reader():
    red = tr.reduce(tr.load_json(
        os.path.join(DATA, "train_gpt2.trace.json.gz")), 1)
    assert red["modules"] == {"jit_step_fn": {
        "runs": 1, "seconds": pytest.approx(0.178534692)}}
    kernels = [r for r in red["module_ops"]["jit_step_fn"].values()
               if r[2] == "custom-call" and r[1] > 1e-6]
    assert len(kernels) == 36          # 12 layers x (fwd, dq, dk/dv)
    assert sum(r[1] for r in kernels) == pytest.approx(0.0524770, abs=1e-6)
    cfg = common.load_json("configs", "gpt2-124m.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    run = types.SimpleNamespace(
        kind="train", trace=red, peaks=common.peaks_for("TPU v5 lite"),
        cfg=cfg, family=fam, batch=24, seq=1024, chips=1)
    # 12 layers x 3.5 x 38.65 GFLOP = 1.623 TFLOP a step: 8.24 ms at the
    # peak, against 52.5 ms of kernel time
    assert common.load_metric_reader("flash_roofline")(run) == \
        pytest.approx(15.70, abs=0.01)
    assert common.load_metric_reader("device_idle_share.train")(run) == \
        pytest.approx(100 * (1 - 0.387899947 / 0.388060344))
    assert common.load_metric_reader("train_mfu")(types.SimpleNamespace(
        kind="train", peaks=run.peaks, cfg=cfg, family=fam, seq=1024,
        e2e={"train_tokens_per_s": 134223.85})) == \
        pytest.approx(58.538, abs=0.01)


def test_readers_return_nothing_where_there_is_nothing():
    empty = types.SimpleNamespace(kind="serve", trace=None, peaks=None,
                                  events=[], measured=[], samples=[],
                                  window=(0.0, 1.0), chips=1,
                                  trace_span=None)
    names = [m["name"] for m in common.load_benchmark()["per_layer"]]
    for name in names:
        assert common.load_metric_reader(name)(empty) is None, name
