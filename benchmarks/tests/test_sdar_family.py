"""The SDAR family (families/sdar.py, reference/sdar.py,
configs/sdar-30b-a3b-chat-d6.json, the toy ``rehearsal/toy-sdar.json``,
traffic/gen-sat.json as it stands) on the CPU: the configuration against
its published copy, the program's config the family builds, the served
path against ``reference.generate`` at the toy's sizes, the replay that
hands the margin rule its logits (no back-track in float32, a
back-track on a planted near-tie, a FAIL on a causal mask, a shifted
read and a float8 mixture), the byte and FLOP counts by hand, the five
new readers on a hand-made joined trace and event log, the cell as it
stands, and the rehearsal cell end to end at ``--trace 0`` and
``--trace 2``."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, parity, trafficgen, weights

CONFIG = "sdar-30b-a3b-chat-d6"
CELL = "sdar-30b-d6.gen-sat"
NEW_READERS = ("denoise_tokens_per_forward", "denoise_commit_share",
               "denoise_idle_share", "denoise_attn_ms",
               "denoise_step_roofline")


@pytest.fixture(scope="module")
def sdar_toy():
    cfg = common.load_json("rehearsal", "toy-sdar.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def sdar_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


@pytest.fixture(scope="module")
def served(sdar_toy):
    """(ids [2, P + G], P): two seeded prompts of 40 and the ENGINE's
    greedy continuation by ``NEW_TOKENS`` tokens (the served path: the
    prefill program and the block program)."""
    from ray_tpu.serve.engine import LLMEngine
    _cfg, fam, _pcfg, model, params = sdar_toy
    P, G = 40, fam.NEW_TOKENS
    prompts = np.random.default_rng(3).integers(1, 250, size=(2, P))
    eng = LLMEngine(model, params, max_slots=2, page_size=8, n_pages=65,
                    chunk=10, prefill_chunk=16)
    handles = [eng.submit(p.tolist(), max_new_tokens=G) for p in prompts]
    while eng.step():
        pass
    ids = np.asarray([p.tolist() + h.result()
                      for p, h in zip(prompts, handles)], np.int32)
    return ids, P


# ------------------------------------------------------ the configuration

def test_the_sdar_file_holds_the_published_sizes_but_for_depth(sdar_real):
    cfg, fam = sdar_real
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "published", CONFIG + ".json")) as f:
        source = json.load(f)
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"SDAR-30B-A3B-Chat"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        assert source == row["config"] and cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 48}
    for key, want in source.items():
        if key == "num_hidden_layers":
            assert (cfg[key], want) == (6, 48)
        else:
            assert cfg[key] == want, key
    assert fam.generation(cfg) == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "mask_token_id": 151669,
        "temperature": 0.0}
    for key in ("weights", "qk_norm", "generation", "mask",
                "masks_as_flags", "replay", "unaccounted_share"):
        assert cfg["assumed"][key]
    assert "EIGHT" in cfg["stands_for"]
    assert cfg["deployment"] == {"max_slots": 128, "page_size": 64,
                                 "n_pages": 4609, "tensor_parallel": 1,
                                 "decode_chunk": 10}
    # (8 prompts, not the issue's 2: 256 positions to place the share
    # of unaccounted positions between its two readings)
    assert cfg["parity"] == {"prompts": 8, "prompt_len": 256,
                             "new_tokens": fam.NEW_TOKENS}
    assert fam.UNACCOUNTED_SHARE == 0.016
    assert int(fam.UNACCOUNTED_SHARE * 8 * fam.NEW_TOKENS) == 4
    bench = common.load_benchmark()
    entry = common.find_named(bench["configs"], CONFIG, "configuration")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_sdar_program_config_is_the_published_model_cut_in_depth(
        sdar_real):
    from ray_tpu.models.sdar import SdarConfig, sdar_param_count
    cfg, fam = sdar_real
    pcfg = fam.program_config(cfg)
    whole = SdarConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert pcfg == SdarConfig(
        n_layers=6, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert pcfg.head_dim == 128 != pcfg.dim // pcfg.n_heads
    # the issue's arithmetic: 4.361 B at six layers, 30.5 B whole
    assert sdar_param_count(pcfg) == pytest.approx(4.361e9, rel=1e-3)
    assert sdar_param_count(whole) == pytest.approx(30.5e9, rel=2e-3)
    bd = pcfg.block_decode
    assert (bd.block_length, bd.denoising_steps, bd.remasking,
            bd.confidence_threshold, bd.mask_token_id) == (
        4, 4, "low_confidence_dynamic", 0.9, 151669)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("hidden_act", "gelu"),
    ("norm_topk_prob", False)])
def test_what_the_program_lacks_of_sdar_is_refused(sdar_real, key, value):
    cfg, fam = sdar_real
    with pytest.raises(SystemExit, match="benchmarks: the program"):
        fam.program_config({**cfg, key: value})


def test_a_sampled_configuration_is_refused(sdar_real):
    cfg, fam = sdar_real
    warm = {**cfg, "generation": {**cfg["generation"], "temperature": 1.0}}
    with pytest.raises(SystemExit, match="greedy"):
        fam.program_config(warm)


def test_the_sdar_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(common.HERE, "reference", "sdar.py")
    tree = ast.parse(open(path).read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert not [n for n in names if n.startswith("ray_tpu")]


# ---------------------------------------------- the replay and its rule

def test_new_tokens_is_the_configurations(sdar_toy, sdar_real):
    cfg, fam = sdar_real
    assert fam.NEW_TOKENS == cfg["parity"]["new_tokens"]
    assert fam.NEW_TOKENS == sdar_toy[0]["parity"]["new_tokens"]
    # twice the rule's tolerance, which is the harness's
    assert fam.ORDER_TOL == 2.0
    assert parity.LOGIT_TOL_FRACTION == 2.0 ** -5


def test_the_served_path_is_the_references_generation(sdar_toy, served):
    _cfg, fam, pcfg, _model, params = sdar_toy
    ids, P = served
    rw = fam.reference_weights(params, pcfg)
    for row in ids:
        toks, _steps, _logits = fam.reference_generate(
            rw, row[:P].tolist(), fam.NEW_TOKENS, pcfg)
        assert row[P:].tolist() == toks.tolist()


def test_the_replay_hands_the_rule_the_logits_each_token_was_chosen_from(
        sdar_toy, served, capsys):
    """In float32 the replay's first path is the served one: no
    back-track, every block accounted for, and row P - 1 + g of its
    result IS ``reference.generate``'s logits for token g."""
    _cfg, fam, pcfg, _model, params = sdar_toy
    ids, P = served
    rw = fam.reference_weights(params, pcfg)
    logits = fam.reference_logits(rw, ids, pcfg)
    line = capsys.readouterr().out
    assert " 0 of 64 positions it cannot account for" in line
    assert "; 0 back-tracks" in line
    check = parity.margin_rule(logits, ids, P)
    assert check["ok"] and check["worst_deficit"] == 0.0
    assert check["same_argmax"] == check["steps"] == 2 * fam.NEW_TOKENS
    _t, _s, chosen = fam.reference_generate(rw, ids[0, :P].tolist(),
                                            fam.NEW_TOKENS, pcfg)
    assert np.allclose(logits[0, P - 1:P - 1 + fam.NEW_TOKENS], chosen,
                       atol=1e-6)
    assert not logits[:, :P - 1].any() and not logits[:, -1].any()


@pytest.mark.parametrize("control", [
    {"block_length": 1}, {"shift": 1}, {"lower_precision": True},
    {"whole_width_norm": True}])
def test_each_control_fails_the_comparison_that_decides_correct(
        sdar_toy, served, control, capsys):
    """A causal mask, a read shifted by one (an autoregressive model's
    convention), every matrix in float8, a whole-width query/key norm:
    the replay cannot account for more positions than the share allows
    and the rule fails."""
    _cfg, fam, pcfg, _model, params = sdar_toy
    ids, P = served
    rw = fam.reference_weights(params, pcfg)
    logits = fam.reference_logits(rw, ids, pcfg, **control)
    assert "too many: scored as they are" in capsys.readouterr().out
    assert not parity.margin_rule(logits, ids, P)["ok"]


def _planted(flip):
    """One block of four masks behind a prompt of four, on a hand-made
    'reference': position i's logits prefer token 10 + i by a margin of
    1, position 0 is the most confident and 3 the least, and positions 1
    and 2 lie ``flip`` of a logit apart (the reference prefers 1). But
    once 1 is revealed with 2 still masked, 2's token of choice CHANGES
    to 22: the served path, at its precision, revealed 2 first and
    chose 12."""
    V = 32
    ids = np.asarray([1, 2, 3, 4, 10, 11, 12, 13], np.int32)
    top = (4.0, 3.0, 3.0 - flip, 1.5)

    def forward(tokens, flags, lo, hi):
        out = np.zeros((hi - lo, V), np.float32)
        for i in range(hi - lo):
            want = 10 + i
            if i == 2 and flags[lo + 2] and not flags[lo + 1]:
                want = 22
            out[i, want], out[i, (want + 1) % V] = top[i], top[i] - 1.0
        return out
    return forward, ids


def test_a_planted_near_tie_is_found_by_a_back_track(sdar_toy):
    """The reference's own order reveals 1 before 2 and then cannot
    account for the served token at 2; within the order tolerance the
    other order is tried, and accounts for the whole block. Outside it
    no path accounts for position 2, and the one that loses least is
    returned with that position marked."""
    _cfg, fam, pcfg, _model, _params = sdar_toy
    bd = pcfg.block_decode._replace(remasking="low_confidence_static")
    forward, ids = _planted(flip=0.05)
    rows, accounted, back, slack = fam.replay_row(
        forward, ids, 4, 4, bd, parity.LOGIT_TOL_FRACTION)
    assert accounted.all() and back == 1 and 0 < slack <= fam.ORDER_TOL
    assert rows.argmax(-1).tolist() == [10, 11, 12, 13]
    forward, ids = _planted(flip=1.5)
    rows, accounted, back, _slack = fam.replay_row(
        forward, ids, 4, 4, bd, parity.LOGIT_TOL_FRACTION)
    assert accounted.tolist() == [True, True, False, True]
    assert rows.argmax(-1).tolist() == [10, 11, 22, 13]


def test_candidate_sets_by_strategy(sdar_toy):
    _cfg, fam, _pcfg, _model, _params = sdar_toy
    masked = np.asarray([False, True, True, True])
    known = np.ones(4, bool)
    ok = np.ones(4, bool)
    lc = np.log(np.asarray([0.5, 0.30, 0.29, 0.05]))
    args = (masked, known, ok, lc, 1)
    assert fam._candidates(*args, "sequential", 0.9, 0.1) == [((1,), ())]
    # the sets the reference accounts for whole come first, its own
    # pick first of all; a member out of order or with a token out of
    # tolerance is what a set loses
    assert fam._candidates(*args, "low_confidence_static", 0.9, 0.1) == [
        ((1,), ()), ((2,), ()), ((3,), (3,))]
    assert fam._candidates(*args, "low_confidence_static", 0.9, 0.0) == [
        ((1,), ()), ((2,), (2,)), ((3,), (3,))]
    assert fam._candidates(masked, known, np.asarray([1, 0, 1, 1], bool),
                           lc, 1, "low_confidence_static", 0.9, 0.1) == [
        ((2,), ()), ((1,), (1,)), ((3,), (3,))]
    # a position whose served token is not known loses nothing by it
    assert fam._candidates(masked, np.asarray([1, 0, 1, 1], bool),
                           np.asarray([1, 0, 1, 1], bool), lc, 1,
                           "low_confidence_static", 0.9, 0.1)[0] == (
        (1,), ())
    # dynamic: everything over the line, each within the slack of it
    # either way; the top-n sets only where too few may be over it
    got = dict(fam._candidates(*args, "low_confidence_dynamic", 0.295,
                               0.1))
    assert {c for c, lost in got.items() if not lost} == {
        (1,), (2,), (1, 2)}
    assert [c for c, _ in fam._candidates(
        *args, "low_confidence_dynamic", 0.9, 0.1)][:2] == [(1,), (2,)]
    assert fam._candidates(*args, "low_confidence_dynamic", 0.0, 0.1) == [
        ((1, 2, 3), ())]


def test_unaccounted_positions_are_excused_up_to_the_share(
        sdar_toy, served, capsys, monkeypatch):
    """A served token moved out of tolerance at ONE position: the replay
    marks that reveal, the rest of the row stands, and the position is
    left unscored while such positions are within the share; past the
    share nothing is excused and the rule fails."""
    _cfg, fam, pcfg, _model, params = sdar_toy
    ids, P = served
    rw = fam.reference_weights(params, pcfg)
    bent = ids.copy()
    bent[0, P + 9] = (bent[0, P + 9] + 97) % 250 + 1
    monkeypatch.setattr(fam, "UNACCOUNTED_SHARE", 0.25)
    logits = fam.reference_logits(rw, bent, pcfg)
    line = capsys.readouterr().out
    assert "positions it cannot account for" in line and "not scored" in line
    check = parity.margin_rule(logits, bent, P)
    lost = int(line.split("reference: ")[1].split(" of ")[0])
    # the bent token, and what it made of the positions that saw it
    assert 1 <= lost <= 16 and check["ok"]
    assert not logits[0, P - 1 + 9].any()
    monkeypatch.setattr(fam, "UNACCOUNTED_SHARE", 0.0)
    logits = fam.reference_logits(rw, bent, pcfg)
    assert "too many: scored as they are" in capsys.readouterr().out
    assert not parity.margin_rule(logits, bent, P)["ok"]


# ------------------------------------------------------- the byte counts

def test_sdar_byte_and_flop_counts_by_hand(sdar_real):
    cfg, fam = sdar_real
    assert fam.expert_bytes(cfg) == 3 * 2048 * 768 * 2 == 9437184
    assert fam.kv_bytes_per_token(cfg) == 6 * 2 * 4 * 128 * 2 == 12288
    assert fam.n_moe_layers(cfg) == 6 and fam.block_length(cfg) == 4
    # one layer's experts at 126 touched and 4,096 pairs
    assert fam.experts_step_bytes(cfg, 126, 4096) == \
        126 * 9437184 + 2 * 4096 * 2048 * 2
    assert fam.experts_step_flops(cfg, 4096) == 2 * 3 * 4096 * 2048 * 768
    attn = (2 * 2048 * 4096 + 2 * 2048 * 512) * 2 + 2 * 128 * 4
    assert fam.attention_weight_bytes(cfg) == attn
    # a forward of 128 riders x 4 positions over 190,000 tokens of
    # context, 126 experts touched a layer
    rows = 512
    layer = attn + 2048 * 128 * 4 + 126 * 9437184 + 2 * rows * 8 * 2048 * 2
    head = 151936 * 2048 * 2 + rows * 2048 * 2
    kv = (190000 + 2 * rows) * 12288
    assert fam.decode_step_bytes(cfg, 190000, 128, experts_touched=126) \
        == 6 * layer + head + kv
    # without a counter: every expert (an upper bound)
    assert fam.decode_step_bytes(cfg, 190000, 128) > \
        fam.decode_step_bytes(cfg, 190000, 128, experts_touched=126)
    per_row = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
               + 3 * 2048 * 8 * 768)
    flops = 6 * (2 * rows * per_row
                 + 2 * 2 * 32 * 128 * (4 * 190000 + rows * 4)) \
        + 2 * rows * 2048 * 151936
    assert fam.decode_step_flops(cfg, 190000, 128) == pytest.approx(flops)
    # the issue's forecast: bytes bound it, 10-13 GB and ~0.7 TFLOP
    assert 10e9 < fam.decode_step_bytes(cfg, 190000, 128,
                                        experts_touched=126) < 13e9
    assert 0.5e12 < flops < 0.9e12


# ---------------------------------------- the readers on a hand-made run

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.3, "overlap": True,
            "decode_riders": 124, "decode_steps": 10,
            "moe_decode_experts_touched": 10 * 6 * 126,
            "moe_decode_pairs": 10 * 6 * 3968,
            "moe_decode_layer_steps": 10 * 6,
            "denoise_rider_forwards": 1240, "denoise_commits": 248,
            "denoise_revealed": 992, "denoise_emitted": 992,
            "denoise_idle_forwards": 0}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(sdar_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 10 forwards (rounds 11 and 12, 124 and 126
    riders), a jit_prefill between them, and a THIRD jit_decode that the
    stop cut. A forward: six layers' gather of 1.6 ms, scores 0.3 and
    values 0.35, experts of 1.7 ms, the head 2.3, the choice 0.9."""
    cfg, fam = sdar_real
    base = "jit(decode)/while/body/Sdar/"
    step = ([(f"layers_{i}/attention/kv_gather/gather:", 1_600_000)
             for i in range(6)]
            + [(f"layers_{i}/attention/attn_scores/dot_general:", 300_000)
               for i in range(6)]
            + [(f"layers_{i}/attention/attn_pv/dot_general:", 350_000)
               for i in range(6)]
            + [(f"layers_{i}/moe/moe_experts/custom-call:", 1_700_000)
               for i in range(6)]
            + [("head/dot_general:", 2_300_000),
               ("sample/reduce:", 900_000)])
    call = [("layers_1/moe/moe_experts/custom-call:", 16_000_000)]
    ops, modules, t = [], [], 0
    for n_steps, name in ((10, "jit_decode(1)"), (0, "jit_prefill(2)"),
                          (10, "jit_decode(1)"), (3, "jit_decode(1)")):
        t0 = t
        for scope, dur in (call if not n_steps else step * n_steps):
            path = ("jit(prefill)/Sdar/" if not n_steps else base)
            ops.append(["%f = f32[8] fusion(", t, dur, path + scope])
            t += dur
        modules.append([name, t0, t - t0])
        t += 1000
    rows = [{"program": "jit_decode", "round": 11, "steps": 10,
             "start_ns": modules[0][1], "device_ms": modules[0][2] / 1e6},
            {"program": "jit_prefill", "round": 12, "steps": 0,
             "start_ns": modules[1][1], "device_ms": modules[1][2] / 1e6},
            {"program": "jit_decode", "round": 12, "steps": 10,
             "start_ns": modules[2][1], "device_ms": modules[2][2] / 1e6}]
    events = [_round(1.0), _round(2.0, denoise_idle_forwards=60,
                                  denoise_rider_forwards=1180,
                                  denoise_commits=295,
                                  denoise_emitted=1180),
              _round(11.0, round=11),
              _round(12.0, round=12, decode_riders=126,
                     moe_decode_experts_touched=10 * 6 * 128)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"], chips=1,
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={},
        samples=[{"t": 1.0, "kv_bytes_in_use": 1.0},
                 {"t": 11.0, "kv_bytes_in_use": 180000 * 12288},
                 {"t": 12.0, "kv_bytes_in_use": 200000 * 12288}])
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[2:]}}
    return run


def test_the_five_readers_on_a_hand_made_run(sdar_real, tmp_path, capsys):
    cfg, fam = sdar_real
    run = _joined_run(sdar_real, tmp_path)
    read = common.load_metric_reader
    # the window's rounds (t 1 and 2)
    assert fam.denoise_counters(run) == {
        "rider_forwards": 2420, "commits": 543, "revealed": 1984,
        "emitted": 2172, "idle_forwards": 60}
    assert read("denoise_tokens_per_forward")(run) == pytest.approx(
        2172 / 2420)
    assert read("denoise_commit_share")(run) == pytest.approx(
        100.0 * 543 / 2420)
    assert read("denoise_idle_share")(run) == pytest.approx(
        100.0 * 60 / 2480)
    got = fam.decode_parts_by_rounds(run)
    # 20 forwards over the two matched executions; the cut one nowhere
    assert got["steps"] == 20 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(125.0)
    assert "[sdar] jit_decode over the 2 matched executions: 20 forwards" \
        in capsys.readouterr().out
    assert read("denoise_attn_ms")(run) == pytest.approx(
        6 * (1.6 + 0.3 + 0.35))
    step_s = got["module_s"] / 20
    assert step_s == pytest.approx(
        (6 * (1.6 + 0.3 + 0.35 + 1.7) + 2.3 + 0.9) * 1e-3)
    # the traced seconds' counters and samples: 127 experts touched a
    # layer-step, 190,000 tokens of context
    assert fam.decode_counters(run)["experts_touched"] == pytest.approx(127)
    want = fam.decode_step_bytes(cfg, 190000, 125.0,
                                 experts_touched=127.0) / 819e9
    assert read("denoise_step_roofline")(run) == pytest.approx(
        100.0 * want / step_s)
    assert 40.0 < read("denoise_step_roofline")(run) < 100.0
    assert read("moe_experts_roofline.by_kind")(run) == pytest.approx(
        100.0 * fam.experts_step_bytes(cfg, 127.0, 3968.0) / 819e9
        / 1.7e-3)


def test_the_new_readers_find_nothing_where_there_is_nothing(
        sdar_real, tmp_path):
    """Another family, a join that was refused, rounds without the
    counters (the parent's traced run of another cell): None, never an
    error."""
    read = common.load_metric_reader
    run = _joined_run(sdar_real, tmp_path)
    for family in ("olmoe", "laguna"):
        other = types.SimpleNamespace(**{
            **vars(run), "family": common.load_family(family, "serve")})
        for name in NEW_READERS:
            assert read(name)(other) is None, (family, name)
    refused = _joined_run(sdar_real, tmp_path)
    refused._dispatch = None
    for name in NEW_READERS[3:]:
        assert read(name)(refused) is None, name
    for name in NEW_READERS[:3]:
        assert read(name)(refused) is not None, name
    old = _joined_run(sdar_real, tmp_path)
    old.events = [(e[0], e[1], e[2], e[3], e[4], {
        k: v for k, v in e[5].items() if not k.startswith("denoise_")})
        for e in old.events]
    for name in NEW_READERS:
        assert read(name)(old) is None, name
    train = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(train) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_gen_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "gen-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    assert len(bench["configs"]) == 13 and len(bench["workloads"]) == 14
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", "moe_dispatch_share",
        "moe_experts_roofline.by_kind", "moe_experts_touched_mean",
        "moe_rows_per_expert_mean", "setup_build_s",
        "setup_program_trace_s", "setup_cold_builds", "engine_init_s",
        *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-5:]) == NEW_READERS
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(common.load_metric_reader(m["name"]))
    assert [(m["unit"], m["better"], m["source"], m["layer"])
            for m in bench["per_layer"][-5:]] == [
        ("tokens/fwd", "higher", "program_counter", "model step"),
        ("%", "lower", "program_counter", "model step"),
        ("%", "lower", "program_counter", "engine"),
        ("ms", "lower", "device_trace", "model step"),
        ("%", "higher", "device_trace", "model step")]
    # the readers that divide by trace_reduce.loop_steps (PERF.md
    # section 7: it read 521 forwards where the rounds dispatched 130):
    # the cell is on none
    assert not per_layer & {
        "decode_moe_ms", "moe_experts_roofline", "decode_attn_ms",
        "decode_dense_ms", "decode_step_ms", "decode_roofline"}
    # the traffic is laguna-xs2-d5.gen-sat's file, unedited
    other = common.find_named(bench["workloads"], "laguna-xs2-d5.gen-sat",
                              "workload")
    assert other["traffic"] == cell["traffic"]
    tr = common.load_json("traffic", "gen-sat.json")
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prefix_cache"] is False and tr["ramp_s"] == 35.0
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {1024}
    assert {r.output_len for r in reqs} == {1024}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 1024, cfg["vocab_size"])
    assert len(ids) == 1024 and 1 <= min(ids) and max(ids) < 151936
    # 128 slots of 32 pages and four of headroom; whole blocks a page
    # and a chunk; ten forwards are two whole blocks
    dep = cfg["deployment"]
    per_slot = -(-(1024 + 1024) // dep["page_size"])
    assert per_slot == 32 and dep["max_slots"] == 128
    assert dep["max_slots"] * (per_slot + 4) == dep["n_pages"] - 1 == 4608
    L, T = (cfg["generation"][k] for k in ("block_length",
                                           "denoising_steps"))
    assert dep["page_size"] % L == 0 and 256 % L == 0
    assert dep["decode_chunk"] == 2 * (T + 1)
    assert 1024 // L * (T + 1) == 128 * dep["decode_chunk"]


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-sdar.gen-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_sdar_rehearsal_cell_runs(trace):
    """The toy cell borrows sdar-30b-d6.gen-sat's metric lists: correct
    by the replay through the served path, no program built in the
    window; at ``--trace 2`` the counter metrics are there, the three
    ``denoise_*`` counters among them (the toy's short outputs end
    inside a dispatch, so some forwards idle and fewer than 0.8 tokens
    come a forward); the device_trace metrics need a device in the
    trace, which a CPU has not (the hand-made run above checks their
    readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "[correct] sdar: replayed 2 x 32 generated tokens" in stdout
    assert " 0 of 64 positions it cannot account for" in stdout
    assert "denoise_rider_forwards" in stdout
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in common.load_rehearsal_cell("toy-sdar.gen-sat")["reports"]:
        assert name in line["metrics"], name
    per = line["metrics"]["denoise_tokens_per_forward"]
    assert per["unit"] == "tokens/fwd" and 0.3 < per["value"] <= 0.8
    # every block runs its 4 + 1 forwards but the first of a request,
    # whose prompt remainder leaves it fewer masks
    assert 20.0 <= line["metrics"]["denoise_commit_share"]["value"] < 34.0
    assert 0.0 <= line["metrics"]["denoise_idle_share"]["value"] < 60.0
    for name in NEW_READERS[3:]:
        assert name not in line["metrics"]
