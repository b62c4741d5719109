"""trace_parts on a trace recorded on a TPU v5e (PR 25: one jit_prefill
run and one 8-step jit_decode run of the d16 engine, operation names cut
to 110 characters), the idle gap of a recorded trace named by the
engine's own annotation, and the new readers on hand-made runs."""
import os
import types

import pytest

from benchmarks import common, trace_parts as tp, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("tf_op,part", [
    ("jit(decode)/while/body/Llama/layers_3/attention/kv_gather/gather:",
     "kv_gather"),
    ("jit(decode)/while/body/Llama/layers_3/attention/attn_pv/dot_general:",
     "attn_pv"),
    ("jit(prefill)/Llama/layers_4/attention/wk/dot_general:",
     "projections"),
    ("jit(decode)/while/body/Llama/layers_2/attention/concatenate:",
     "rope"),
    ("jit(decode)/while/body/Llama/layers_1/feed_forward/w3/dot_general:",
     "mlp"),
    ("jit(decode)/while/body/Llama/layers_1/ffn_norm/mul:", "norms"),
    ("jit(decode)/while/body/Llama/head/dot_general:", "head"),
    ("jit(decode)/while/body/sample/argmax:", "sample"),
    ("jit(decode)/while:", "other"),
    ("", "unnamed"),
    ("jit(step_fn)/transpose(jvp(loss_head))/dot_general:", "loss_head"),
    ("jit(step_fn)/jvp(loss_head)/reduce_sum:", "loss_head"),
    ("jit(step_fn)/jvp(GPT2)/h_6/attn/c_attn/dot_general:", "other"),
])
def test_part_of_a_scope_path(tf_op, part):
    assert tp.part_of(tf_op) == part


def test_recorded_decode_run_splits_by_scope():
    ir = tp.load_json(os.path.join(DATA, "decode_parts.trace.json.gz"))
    dec = tp.split(ir, "jit_decode")
    assert dec["runs"] == 1
    assert dec["module_s"] == pytest.approx(0.536824128)
    parts = dec["parts"]
    assert parts["kv_gather"] == pytest.approx(0.317312872)
    assert parts["attn_scores"] == pytest.approx(0.046634489)
    assert parts["attn_pv"] == pytest.approx(0.045549496)
    assert parts["mlp"] == pytest.approx(0.060419741)
    assert parts["projections"] == pytest.approx(0.005116591)
    assert parts["unnamed"] + parts["other"] == pytest.approx(0.0576911)
    # every nanosecond of the run is some part's or between operations
    assert sum(parts.values()) + dec["gaps_s"] == \
        pytest.approx(dec["module_s"])
    assert dec["gaps_s"] == pytest.approx(1.912e-06, abs=1e-9)
    pre = tp.split(ir, "jit_prefill")
    assert pre["parts"]["attn_scores"] == pytest.approx(0.051081064)
    assert tp.split(ir, "jit_step_fn") is None


def test_decode_readers_add_up_to_the_step(tmp_path):
    ir = tp.load_json(os.path.join(DATA, "decode_parts.trace.json.gz"))
    # the run as --trace 2 hands it over: the reduced trace (steps come
    # from it) and the trace's directory; the recorded split stands in
    # for the .xplane.pb
    ops = {}
    for name, _s, dur, _scope in ir["ops"]:
        rec = ops.setdefault(tr.op_name(name), [0, 0.0, tr.op_kind(name)[0]])
        rec[0] += 1
        rec[1] += dur / 1e9
    run = types.SimpleNamespace(
        kind="serve", trace_dir=str(tmp_path),
        trace={"modules": {"jit_decode": {"runs": 1,
                                          "seconds": 0.536824128}},
               "module_ops": {"jit_decode": ops}})
    run._trace_parts = {"ir": ir}
    attn = common.load_metric_reader("decode_attn_ms")(run)
    dense = common.load_metric_reader("decode_dense_ms")(run)
    parts = tp.decode_step_parts(run)
    assert attn == pytest.approx(1e3 * 0.410276926 / 8)       # 51.28
    assert dense == pytest.approx(1e3 * 0.068854190 / 8)      # 8.61
    assert parts["rest_ms"] == pytest.approx(1e3 * 0.057693012 / 8)
    assert attn + dense + parts["rest_ms"] == \
        pytest.approx(parts["step_ms"]) == pytest.approx(67.103016)
    # no trace directory (--trace 1): nothing to read
    bare = types.SimpleNamespace(kind="serve", trace=run.trace)
    assert common.load_metric_reader("decode_attn_ms")(bare) is None
    assert common.load_metric_reader("decode_dense_ms")(bare) is None


def test_loss_head_share_reader(tmp_path):
    ir = {"modules": [["jit_step_fn(1)", 0, 1000]],
          "ops": [["%a = f32[8] fusion(", 0, 300,
                   "jit(step_fn)/jvp(loss_head)/dot_general:"],
                  ["%b = f32[8] fusion(", 300, 100,
                   "jit(step_fn)/transpose(jvp(loss_head))/dot_general:"],
                  ["%c = f32[8] fusion(", 400, 600,
                   "jit(step_fn)/jvp(GPT2)/h_0/mlp/c_fc/dot_general:"]]}
    run = types.SimpleNamespace(kind="train", trace_dir=str(tmp_path),
                                trace={})
    run._trace_parts = {"ir": ir}
    assert common.load_metric_reader("train_loss_head_share")(run) == \
        pytest.approx(40.0)


def test_recorded_idle_gap_is_named_by_the_engine():
    """Cut from the burst traced in PR 25: the device ran dry for 3.1 ms
    between a seed scatter and the next prefill while the host sat in
    the round's trailing readback."""
    red = tr.reduce(tr.load_json(
        os.path.join(DATA, "serve_gap.trace.json.gz")), 1)
    assert red["breakdown"]["idle_gaps"] == [
        ["jit_seed->jit_prefill | host: engine.readback",
         pytest.approx(0.003104505)]]
    assert red["window_s"] - red["busy_s"] == pytest.approx(0.003108877)


def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.5, "overlap": True}
    base.update(data)
    return (0, t, "round", None, None, base)


def test_event_readers_on_hand_made_rounds():
    new = dict(round=1, admit_s=0.001, plan_s=0.002, dispatch_s=0.003,
               readback_s=0.4, decode_riders=12, decode_steps=8,
               prefill_tokens=256, prefill_budget=256)
    events = [
        _round(1.0, **new),
        _round(2.0, **dict(new, decode_riders=10, prefill_tokens=128)),
        _round(3.0, **dict(new, decode_riders=0, decode_steps=0,
                           prefill_tokens=0)),
        _round(9.0, **dict(new, decode_riders=32)),      # outside
        (1, 1.0, "submit", 7, None, {}), (2, 1.1, "admit", 7, 0, {}),
        (3, 1.6, "first_token", 7, 0, {}),
        (4, 2.0, "submit", 8, None, {}), (5, 2.0, "admit", 8, 1, {}),
        (6, 5.0, "first_token", 8, 1, {}),
        (7, 9.0, "submit", 9, None, {}), (8, 9.0, "admit", 9, 1, {}),
        (9, 9.5, "first_token", 9, 1, {})]
    run = types.SimpleNamespace(kind="serve", window=(0.5, 8.0),
                                events=events)
    read = common.load_metric_reader
    assert read("decode_riders_mean")(run) == pytest.approx(11.0)
    assert read("prefill_budget_share")(run) == pytest.approx(50.0)
    assert read("round_host_ms")(run) == pytest.approx(6.0)
    assert read("prefill_in_slot_p50_ms")(run) == pytest.approx(1750.0)
    # a program whose round events lack the new keys: nothing, no error
    old = types.SimpleNamespace(kind="serve", window=(0.5, 8.0),
                                events=[_round(1.0), _round(2.0)])
    for name in ("decode_riders_mean", "prefill_budget_share",
                 "round_host_ms", "prefill_in_slot_p50_ms"):
        assert read(name)(old) is None, name
