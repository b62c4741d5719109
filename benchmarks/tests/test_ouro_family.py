"""The Ouro family (families/ouro.py, reference/ouro.py,
configs/ouro-2.6b.json, the toy ``rehearsal/toy-ouro.json``,
traffic/chat-sat.json as it stands) on the CPU: the configuration
against its published copy, the program's config the family builds, the
served model against the plain reference at the toy's sizes and the
reference's controls against the margin rule, the byte counts against
hand counts (weights once a PASS, K/V over passes x layers entries),
the two new readers on a hand-made joined trace whose decode program
holds a nested loop, the cell, and the rehearsal cell end to end at
``--trace 0`` and ``--trace 2``."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import (common, parity, trace_parts, trace_reduce,
                        trafficgen, weights)

CONFIG = "ouro-2.6b"
CELL = "ouro-2.6b.chat-sat"
NEW_READERS = ("loop_step_roofline", "loop_attn_share")


@pytest.fixture(scope="module")
def ouro_toy():
    cfg = common.load_json("rehearsal", "toy-ouro.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def ouro_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_ouro_file_holds_the_published_sizes_but_for_reduced(ouro_real):
    cfg, _fam = ouro_real
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "published", CONFIG + ".json")
    with open(path) as f:
        source = json.load(f)
    assert cfg["reduced"] == ["max_position_embeddings"]
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
        else:
            assert cfg[key] == want, key
    # whole: depth, passes, threshold and vocabulary as published
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["early_exit_threshold"], cfg["vocab_size"]) == (
        48, 4, 1, 49152)
    assert source["max_position_embeddings"] == 65536
    assert {"sandwich_norms", "final_norm_every_pass", "exit_gate",
            "float32", "weights"} <= set(cfg["assumed"])
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_ouro_program_config_is_the_published_model_whole(ouro_real):
    cfg, fam = ouro_real
    pcfg = fam.program_config(cfg)
    assert (pcfg.dim, pcfg.n_layers, pcfg.n_heads, pcfg.n_kv_heads,
            pcfg.head_dim, pcfg.hidden_dim, pcfg.vocab_size) == (
        2048, 48, 16, 16, 128, 5632, 49152)
    assert (pcfg.total_ut_steps, pcfg.early_exit_threshold,
            pcfg.rope_theta, pcfg.norm_eps, pcfg.max_seq_len) == (
        4, 1.0, 1e6, 1e-6, 4096)
    assert pcfg.dtype == pcfg.param_dtype == jnp.bfloat16
    assert pcfg.kv_entries_per_layer == 4
    assert type(fam.model(pcfg)).__name__ == "Ouro"


@pytest.mark.parametrize("key,value,named", [
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("sliding_window", 4096, "sliding window"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("layer_types", ["sliding_attention"] * 48, "full_attention"),
    ("head_dim", 64, "head_dim")])
def test_what_the_program_lacks_of_ouro_is_refused(ouro_real, key, value,
                                                   named):
    cfg, fam = ouro_real
    with pytest.raises(SystemExit, match=named):
        fam.program_config({**cfg, key: value})


def test_a_program_without_the_module_is_refused(ouro_real, monkeypatch):
    cfg, fam = ouro_real
    monkeypatch.setitem(sys.modules, "ray_tpu.models.ouro", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.ouro"):
        fam.program_config(cfg)


def test_the_seeded_weights_rule(ouro_toy):
    """Mistral-d16's rule, the gate's vector at 1/sqrt(hidden_size)
    over a bias of 0, every norm's scale one; the same seed the same
    weights, whatever its size."""
    _cfg, fam, pcfg, model, params = ouro_toy
    p = params["params"]
    assert float(p["exit_gate"]["bias"][0]) == 0.0
    gate = np.asarray(p["exit_gate"]["kernel"])
    assert gate.shape == (64, 1)
    assert 0.5 < gate.std() * 8.0 < 1.5              # 1/sqrt(64)
    layer = p["stack"]["layers_1"]
    for norm in ("attention_norm", "attention_post_norm", "ffn_norm",
                 "ffn_post_norm"):
        assert (np.asarray(layer[norm]["scale"]) == 1.0).all()
    assert (np.asarray(p["stack"]["norm"]["scale"]) == 1.0).all()
    w1 = np.asarray(layer["feed_forward"]["w1"]["kernel"])
    assert 0.8 < w1.std() * 8.0 < 1.2
    assert 0.015 < np.asarray(p["lm_head"]).std() < 0.025
    again = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    assert (np.asarray(again["params"]["lm_head"])
            == np.asarray(p["lm_head"])).all()


# ------------------------------------------- the served model, the reference

_APPLY = {}


def _logits(ouro_toy, ids):
    import jax
    _cfg, _fam, _pcfg, model, params = ouro_toy
    # one jitted function a model: a new wrapper a call traces anew
    apply = _APPLY.setdefault(id(model), jax.jit(model.apply))
    return np.asarray(apply(params, jnp.asarray(ids, jnp.int32))[0])


def test_the_ouro_reference_matches_the_served_model(ouro_toy):
    _cfg, fam, pcfg, _model, params = ouro_toy
    ids = np.random.default_rng(3).integers(1, 255, size=(2, 96))
    want = fam.reference_forward(fam.reference_weights(params, pcfg),
                                 jnp.asarray(ids, jnp.int32), pcfg)
    np.testing.assert_allclose(_logits(ouro_toy, ids), want, rtol=1e-4,
                               atol=2e-5)


def test_the_ouro_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(common.HERE, "reference", "ouro.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
        elif isinstance(node, ast.Import):
            names += [a.name for a in node.names]
    assert names and not [n for n in names if n.startswith("ray_tpu")]


@pytest.mark.parametrize("control", [
    dict(passes=3), dict(sandwich=False), dict(norm_every_pass=False)],
    ids=["three-passes", "no-sandwich-norms", "final-norm-once"])
def test_the_margin_rule_refuses_each_control(ouro_toy, control):
    """The served model's greedy tokens pass the harness's margin rule
    (through the family's limits) against the plain reference and FAIL
    it against each control of the reference's STRUCTURE: another number
    of passes, the sandwich norms left out, the final norm once at the
    end. (The control of PRECISION, every matrix in float8, needs the
    published depth to show: at the toy's 12 layer applications it
    reads 0.3 tolerances in the mean; on the chip, at 192, 3.45-7.28
    against a limit of 1.2: PERF.md section 6, PR 46.)"""
    _cfg, fam, pcfg, _model, params = ouro_toy
    rw = fam.reference_weights(params, pcfg)
    P, G = 40, fam.SCORED_TAIL
    rng = np.random.default_rng(11)
    ids = rng.integers(1, 255, size=(2, P + G))
    for g in range(G):                    # the served path's greedy tokens
        # (causal: what follows position P + g - 1 does not reach it,
        # so one shape serves every step)
        ids[:, P + g] = _logits(ouro_toy, ids)[:, P + g - 1].argmax(-1)
    dev = jnp.asarray(ids, jnp.int32)
    right = parity.margin_rule(fam.reference_logits(rw, dev, pcfg), ids, P)
    assert right["ok"] and right["worst_deficit"] <= 1e-4
    wrong = parity.margin_rule(
        fam.reference_logits(rw, dev, pcfg, **control), ids, P)
    assert not wrong["ok"], (control, wrong)


def test_the_scored_tail_is_the_configurations_new_tokens(ouro_toy,
                                                          ouro_real):
    """The harness hands ``reference_logits`` the ids without saying
    where the prompt ends: the family scores each row's last
    ``SCORED_TAIL`` positions, which is every configuration's parity
    ``new_tokens``; the rows it fills are the rows the rule reads."""
    cfg, fam, pcfg, _model, params = ouro_toy
    assert cfg["parity"]["new_tokens"] == fam.SCORED_TAIL == 32
    assert ouro_real[0]["parity"]["new_tokens"] == fam.SCORED_TAIL
    P, G = 20, fam.SCORED_TAIL
    ids = np.random.default_rng(5).integers(1, 255, size=(1, P + G))
    logits = fam.reference_logits(fam.reference_weights(params, pcfg),
                                  jnp.asarray(ids, jnp.int32), pcfg)
    assert logits.shape == (1, P + G, 256)
    filled = np.abs(logits[0]).max(-1) > 0
    assert not filled[:P - 1].any() and not filled[-1]
    full = fam.reference_forward(fam.reference_weights(params, pcfg),
                                 jnp.asarray(ids, jnp.int32), pcfg)
    # random ids are not the served path's: nothing is excused, and the
    # filled rows are the plain reference's as they are
    np.testing.assert_array_equal(logits[0, P - 1:P - 1 + G],
                                  full[0, P - 1:P - 1 + G])


def _synthetic(fam, monkeypatch, deficits):
    """``reference_logits`` over a hand-made window: one row of 32
    positions of 64 tokens whose best logit is 3.2 (token 0; the
    tolerance is 0.1). Where ``deficits[i]`` is 0 the served token IS
    token 0 (and the runner-up lies far under it: a decisive position),
    elsewhere it is token 1, ``deficits[i]`` tolerances under the
    best."""
    G = fam.SCORED_TAIL
    d = np.asarray(deficits, np.float32)
    window = np.zeros((1, G, 64), np.float32)
    window[..., 0] = 3.2
    window[0, :, 1] = np.where(d > 0, 3.2 - 0.1 * d, 0.0)
    window[..., 2] = -3.2
    monkeypatch.setattr(fam.ref, "chosen_state",
                        lambda rw, ids, **kw: (np.zeros((1, G + 4, 8)),
                                               None))
    monkeypatch.setattr(fam.ref, "head", lambda rw, x, lower=False: window)
    ids = np.ones((1, G + 4), np.int32)
    ids[0, 4:] = d > 0
    pcfg = fam.program_config(common.load_json("rehearsal",
                                               "toy-ouro.json"))
    logits = fam.reference_logits(None, ids, pcfg)
    return parity.margin_rule(logits, ids, 4), logits[0, 3:3 + G]


def test_deficits_are_excused_up_to_a_mean_and_a_worst(ouro_real, capsys,
                                                       monkeypatch):
    """Positions over the tolerance are excused (a row of zeros) while
    the deficits' mean is at most 1.2 tolerances AND the largest at most
    8.0; past either limit nothing is excused and the harness's rule
    fails on them."""
    _cfg, fam = ouro_real
    assert (fam.MEAN_DEFICIT_LIMIT, fam.WORST_DEFICIT_LIMIT) == (1.2, 8.0)
    honest = [0.0] * 20 + [0.5] * 6 + [1.5] * 4 + [2.7] * 2
    got, rows = _synthetic(fam, monkeypatch, honest)
    assert got["ok"] and got["steps"] == 32
    assert (np.abs(rows[-6:]).max(-1) == 0).all()       # the six over it
    assert (np.abs(rows[:-6]).max(-1) > 0).all()
    assert "6 positions over it" in capsys.readouterr().out
    # the mean past its limit (the float8 control's least reading, 3.45)
    got, rows = _synthetic(fam, monkeypatch, [3.45] * 32)
    assert not got["ok"] and (np.abs(rows).max(-1) > 0).all()
    assert "a limit passed" in capsys.readouterr().out
    # one position past the worst limit, the mean far under its own
    got, _rows = _synthetic(fam, monkeypatch, [0.0] * 31 + [8.5])
    assert not got["ok"]
    got, _rows = _synthetic(fam, monkeypatch, [0.0] * 31 + [7.5])
    assert got["ok"]
    # about the mean's limit: 1.15 is excused, 1.3 is not
    assert _synthetic(fam, monkeypatch, [0.0] + [1.15 * 32 / 31] * 31)[0][
        "ok"]
    assert not _synthetic(fam, monkeypatch, [0.0] + [1.3 * 32 / 31] * 31)[
        0]["ok"]


# ---------------------------------------------------------- byte counts

def test_ouro_byte_counts_by_hand(ouro_real):
    cfg, fam = ouro_real
    assert fam.n_cache_entries(cfg) == 192
    assert fam.kv_bytes_per_token(cfg) == 192 * 2 * 16 * 128 * 2 \
        == 1_572_864
    stack = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2
    assert fam.stack_weight_bytes(cfg) == stack == 4_932_501_504
    head = 49152 * 2048 * 2
    # an empty batch: the weights four times, the head once
    assert fam.decode_step_bytes(cfg, 0, 0) == 4 * stack + head
    # 16 riders at a mean context of 304: ISSUE 46's reckoning
    full = fam.decode_step_bytes(cfg, 16 * 304, 16)
    assert full == 4 * stack + head + 16 * 2048 * 2 \
        + (16 * 304 + 16) * 1_572_864
    assert 27.5e9 < full < 28.0e9
    # what Llama's count would say of the same keys: the weights once,
    # K/V over 48 entries
    from benchmarks import costs
    assert costs.llama_decode_step_bytes(cfg, 16 * 304, 16) < full / 3.5
    # a pool page of the deployment, by the program's own count
    from ray_tpu.models.kv_cache import kv_pool_page_bytes
    dep = cfg["deployment"]
    page = kv_pool_page_bytes(fam.program_config(cfg), dep["page_size"])
    assert page == dep["page_size"] * fam.kv_bytes_per_token(cfg)
    assert 9.7e9 < dep["n_pages"] * page < 9.8e9


def test_the_loop_and_the_gate_are_parts_of_their_own(ouro_real):
    _cfg, fam = ouro_real
    base = "jit(decode)/while/body/Ouro/while/body/closed_call/stack/"
    layer = base + "ut_pass/layers_7/"
    for scope in fam.ATTENTION_PARTS:
        assert trace_parts.part_of(
            layer + f"attention/while/body/{scope}/dot_general:",
            fam.parts) == scope
    assert trace_parts.part_of(layer + "attention/wq/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(layer + "feed_forward/w2/dot_general:",
                               fam.parts) == "mlp"
    for norm in ("attention_norm", "attention_post_norm", "ffn_norm",
                 "ffn_post_norm"):
        assert trace_parts.part_of(layer + norm + "/mul:",
                                   fam.parts) == "norms"
    assert trace_parts.part_of(base + "ut_pass/norm/mul:",
                               fam.parts) == "norms"
    assert trace_parts.part_of(layer + "attention/mul:",
                               fam.parts) == "rope"
    assert trace_parts.part_of(base + "ut_pass/add:",
                               fam.parts) == "ut_pass"
    gate = "jit(decode)/while/body/Ouro/exit_gate/"
    assert trace_parts.part_of(gate + "exit_gate/dot_general:",
                               fam.parts) == "exit_gate"
    assert trace_parts.part_of(gate + "cumsum:", fam.parts) == "exit_gate"
    assert trace_parts.part_of("jit(decode)/while/body/Ouro/head/"
                               "dot_general:", fam.parts) == "head"


# ----------------------------------------------------- the two new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.6, "overlap": True,
            "decode_riders": 14, "decode_steps": 2,
            "decode_window_tokens": 352, "decode_context_tokens": 14 * 300}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(ouro_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 14 and 16
    riders), a jit_prefill between them, and a THIRD jit_decode that the
    stop cut. A step is FOUR passes of a 48-layer stack: a layer-pass's
    attention 0.2 ms (0.01 append + 0.10 gather + 0.05 scores + 0.04
    read-out), its projections and SwiGLU 0.15 ms, and once a step the
    gate (0.05 ms) and the head (0.3 ms)."""
    cfg, fam = ouro_real
    base = "jit(decode)/while/body/Ouro/"
    stack = base + "while/body/closed_call/stack/ut_pass/"
    attention = (("kv_append/scatter:", 10_000),
                 ("while/body/kv_gather/gather:", 100_000),
                 ("while/body/attn_scores/dot_general:", 50_000),
                 ("while/body/attn_pv/dot_general:", 40_000))
    one_pass = ([(f"{stack}layers_{i}/attention/{s}", d)
                 for i in range(48) for s, d in attention]
                + [(f"{stack}layers_{i}/feed_forward/w2/dot_general:",
                    150_000) for i in range(48)])
    step = one_pass * 4 + [(base + "exit_gate/exit_gate/dot_general:",
                            50_000),
                           (base + "head/dot_general:", 300_000)]
    call = [("jit(prefill)/Ouro/head/dot_general:", 30_000_000)]
    ops, modules, t = [], [], 0
    for n_steps, name in ((2, "jit_decode(1)"), (0, "jit_prefill(2)"),
                          (2, "jit_decode(1)"), (1, "jit_decode(1)")):
        t0 = t
        for scope, dur in (call if not n_steps else step * n_steps):
            ops.append(["%f = f32[8] fusion(", t, dur, scope])
            t += dur
        modules.append([name, t0, t - t0])
        t += 1000
    rows = [{"program": "jit_decode", "round": 11, "steps": 2,
             "start_ns": modules[0][1], "device_ms": modules[0][2] / 1e6},
            {"program": "jit_prefill", "round": 12, "steps": 0,
             "start_ns": modules[1][1], "device_ms": modules[1][2] / 1e6},
            {"program": "jit_decode", "round": 12, "steps": 2,
             "start_ns": modules[2][1], "device_ms": modules[2][2] / 1e6}]
    events = [_round(1.0), _round(11.0, round=11),
              _round(12.0, round=12, decode_riders=16,
                     decode_context_tokens=16 * 320)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"], chips=1,
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={}, samples=[])
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[1:]}}
    return run


def test_the_two_new_readers_on_a_hand_made_run(ouro_real, tmp_path):
    cfg, fam = ouro_real
    run = _joined_run(ouro_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(15.0)
    # the riders' contexts at a dispatch's end, less half a step's growth
    assert got["context_tokens"] == pytest.approx(
        (14 * 300 - 7 + 16 * 320 - 8) / 2)
    step_s = 4 * 48 * 0.35e-3 + 0.35e-3
    assert got["module_s"] == pytest.approx(4 * step_s)
    assert got["parts"]["kv_gather"] == pytest.approx(4 * 192 * 100e-6)
    assert got["parts"]["exit_gate"] == pytest.approx(4 * 50e-6)
    assert fam.attention_s(got) == pytest.approx(4 * 192 * 0.2e-3)
    # the attention is 38.4 ms of a step of 67.55
    assert read("loop_attn_share")(run) == pytest.approx(
        100.0 * 192 * 0.2e-3 / step_s)
    least = fam.decode_step_bytes(cfg, got["context_tokens"], 15.0) / 819e9
    assert read("loop_step_roofline")(run) == pytest.approx(
        100.0 * least / step_s)
    assert 40.0 < read("loop_step_roofline")(run) < 60.0
    # what the older count makes of the nested loop: in the first
    # execution (2 steps) each layer's operations ran once a PASS, 8
    # times, and trace_reduce.loop_steps reads 8 steps where the engine
    # dispatched 2
    start, dur = run._trace_parts["ir"]["modules"][0][1:]
    by_op = {}
    for _name, s, d, tf_op in run._trace_parts["ir"]["ops"]:
        if start <= s < start + dur:
            n, total = by_op.get(tf_op, (0, 0.0))
            by_op[tf_op] = (n + 1, total + d)
    assert trace_reduce.loop_steps(by_op, 1) == 8.0


def test_the_new_readers_find_nothing_where_there_is_nothing(ouro_real,
                                                             tmp_path):
    """Another family, a join that was refused, a program without the
    trace, spans that disagree with the rows, no peaks: None, never an
    error (the parent of PR 46 cannot run the cell at all; a traced run
    of an OLDER cell under this PR's files must not trip on them)."""
    read = common.load_metric_reader
    run = _joined_run(ouro_real, tmp_path)
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("llama", "serve")})
    for name in NEW_READERS:
        assert read(name)(other) is None, name
    refused = _joined_run(ouro_real, tmp_path)
    refused._dispatch = None
    no_trace = _joined_run(ouro_real, tmp_path)
    no_trace.trace_dir = None
    short = _joined_run(ouro_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    for broken in (refused, no_trace, short):
        for name in NEW_READERS:
            assert read(name)(broken) is None, name
    no_peaks = _joined_run(ouro_real, tmp_path)
    no_peaks.peaks = None
    assert read("loop_step_roofline")(no_peaks) is None
    assert read("loop_attn_share")(no_peaks) is not None
    training = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(training) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_chat_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-2:]) == NEW_READERS
    assert bench["per_layer"][-2:] == [
        {"name": "loop_step_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "model step",
         "moves": "serve_tokens_per_s", "workloads": [CELL]},
        {"name": "loop_attn_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "model step",
         "moves": "serve_tokens_per_s", "workloads": [CELL]}]
    for name in NEW_READERS:
        assert callable(common.load_metric_reader(name))
    # the readers that divide by trace_reduce.loop_steps, which counts a
    # nested loop's passes as steps (PERF.md section 7): the cell is on
    # none of them
    assert not per_layer & {"decode_roofline", "decode_step_ms",
                            "decode_attn_ms", "decode_dense_ms"}
    # the traffic is mistral7b-d16.chat-sat's and olmoe-d8.chat-sat's
    # file, unedited
    for other in ("mistral7b-d16.chat-sat", "olmoe-d8.chat-sat"):
        assert common.find_named(bench["workloads"], other, "workload")[
            "traffic"] == cell["traffic"]
    tr = common.load_json("traffic", "chat-sat.json")
    assert (tr["loop"], tr["clients_per_slot"], tr["prefix_cache"],
            tr["ramp_s"]) == ("closed", 2, False, 10.0)
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {256}
    assert {r.output_len for r in reqs} == {96}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 256, cfg["vocab_size"])
    assert len(ids) == 256 and 1 <= min(ids) and max(ids) < 49152
    # 16 slots of six pages, a page table that holds a request
    dep = cfg["deployment"]
    per_slot = -(-(256 + 96) // dep["page_size"])
    assert per_slot == 6
    assert dep["max_slots"] * per_slot == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
    assert dep["batch_wait_timeout_s"] == 0.25
    assert cfg["parity"] == {"prompts": 4, "prompt_len": 320,
                             "new_tokens": 32}


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-ouro.chat-sat", "--seed", str(2**32 + 13),
         "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_ouro_rehearsal_cell_runs(trace):
    """The toy cell borrows ouro-2.6b.chat-sat's metric lists: correct
    against the plain reference through the served path (40 tokens of
    prompt, twelve cache entries a token), no program built in the
    window; at ``--trace 2`` the counter metrics are there; the
    device_trace metrics need a device in the trace, which a CPU has
    not (the hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "[correct] margin rule: {'ok': True" in stdout
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in common.load_rehearsal_cell("toy-ouro.chat-sat")["reports"]:
        assert name in line["metrics"], name
    assert 0.0 < line["metrics"]["kv_peak_share"]["value"] <= 100.0
    for name in NEW_READERS:
        assert name not in line["metrics"]
