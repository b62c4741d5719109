"""The Granite-4.0-H family (families/granite_hybrid.py,
reference/granite_hybrid.py, configs/granite-4.0-h-small-d10-ep2.json,
the toy ``rehearsal/toy-granite-hybrid.json``, traffic/gen-sat.json) on
the CPU: the configuration against its published copy (``reduced`` is
the depth, the layer list, the experts held, the vocabulary's slice and
the page table's width; every ``assumed`` item says what follows if it
is wrong), the program's config the family builds, the served model
against the plain reference at the toy's sizes and the reference's
controls against the margin rule, the excusing of flipped positions, the
byte and FLOP counts against ISSUE 65's arithmetic (102.29 M a mixer,
800.9 / 740.6 M a layer whole, 4.76 B as cut, 38.2 MB of state a slot,
4,096 B of K/V a token), the two new readers and the older ones the cell
joins on a hand-made joined trace, the cell and its mix, and the
rehearsal cell end to end at ``--trace 2``."""
import functools
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, parity, trace_parts, trafficgen, weights

CONFIG = "granite-4.0-h-small-d10-ep2"
CELL = "granite4-h-small-d10.gen-sat"
TOY = "toy-granite-hybrid"
NEW_READERS = ("ssd_prefill_roofline", "ssm_moe_step_roofline")
JOINED = ("host_gap_share", "kv_peak_share", "device_idle_share.serve",
          "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
          "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
          "dispatch_prefill_share", "setup_build_s",
          "setup_program_trace_s", "setup_cold_builds", "engine_init_s",
          "moe_dispatch_share", "moe_experts_touched_mean",
          "moe_held_pair_share", "moe_rows_per_expert_mean",
          "moe_experts_roofline.by_kind", "state_peak_share",
          "state_kv_bytes_ratio", "decode_ssm_ms", "prefill_ssm_share",
          "ssm_scan_roofline")


@pytest.fixture(scope="module")
def granite_toy():
    cfg = common.load_json("rehearsal", "toy-granite-hybrid.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def granite_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_granite_file_holds_the_published_sizes_but_for_reduced(
        granite_real):
    """Every key of the catalog row's ``config`` stands under its own
    name and value but the five cuts of scale, and no width is among
    them."""
    cfg, _fam = granite_real
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "published", CONFIG + ".json")
    with open(path) as f:
        source = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_local_experts", "vocab_size",
                              "max_position_embeddings"]
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 40, "layer_types": source["layer_types"],
        "num_local_experts": 72, "vocab_size": 100352,
        "max_position_embeddings": 131072}
    assert len(source) == 33
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
        else:
            assert cfg[key] == want, key
    # the widths, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_width"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (4096, 768, 1536, 10, 72, 32, 8)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_n_groups"], cfg["mamba_expand"],
            cfg["mamba_chunk_size"]) == (128, 64, 128, 4, 1, 2, 256)
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (
        12, 0.22, 0.0078125, 16)
    # the cut: one whole period, a half share of experts and vocabulary
    assert cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"] == source["layer_types"][:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    assert source["layer_types"] == cfg["layer_types"] * 4
    assert (cfg["num_local_experts"], cfg["experts_held_from"],
            cfg["vocab_size"]) == (36, 0, 50176)
    assert cfg["num_local_experts"] >= 8 and \
        8 * cfg["vocab_size"] >= source["vocab_size"]
    for key in cfg["reduced"]:
        assert key in cfg["why_reduced"], key
    for item, text in cfg["assumed"].items():
        if item not in ("conv", "weights", "flipped_share", "multipliers"):
            assert "If wrong" in text or "if wrong" in text \
                or "If it were" in text, item
    assert {"in_projection_order", "gate_and_norm", "dt", "one_norm",
            "no_bias", "weights"} <= set(cfg["assumed"])
    assert "2-chip" in cfg["stands_for"] and "8-chip" in cfg["stands_for"]
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_granite_program_config_is_the_published_model_cut_to_the_share(
        granite_real):
    cfg, fam = granite_real
    pcfg = fam.program_config(cfg)
    assert (pcfg.dim, pcfg.n_layers, pcfg.n_heads, pcfg.n_kv_heads,
            pcfg.head_dim, pcfg.vocab_size, pcfg.max_seq_len) == (
        4096, 10, 32, 8, 128, 50176, cfg["max_position_embeddings"])
    assert pcfg.recurrent_state_shape == (128, 64, 128)
    assert pcfg.recurrent_conv_shape == (3, 8448)
    assert (pcfg.hidden_dim, pcfg.num_experts, pcfg.num_experts_per_tok,
            pcfg.n_shared_experts, pcfg.experts_held) == (
        768, 72, 10, 2, (0, 36))
    assert (pcfg.router, pcfg.norm_topk_prob) == ("softmax", True)
    assert (pcfg.embedding_multiplier, pcfg.residual_multiplier,
            pcfg.attention_multiplier, pcfg.logits_scaling) == (
        12.0, 0.22, 1 / 128, 16.0)
    kinds = pcfg.layer_kinds
    assert kinds.count("recurrent") == 9 == fam.n_ssm_layers(cfg)
    assert kinds.count("kv") == 1 == fam.n_attn_layers(cfg)
    assert kinds[5] == "kv" and fam.n_moe_layers(cfg) == 10
    assert pcfg.mamba_chunk == 256 and pcfg.norm_eps == 1e-5
    assert pcfg.dtype == jnp.bfloat16 and pcfg.tie_word_embeddings
    assert not hasattr(pcfg, "serving_rules")


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("position_embedding_type", "rope"),
    ("model_type", "granitemoe"), ("normalization_function", "layernorm"),
    ("mamba_expand", 4), ("shared_intermediate_size", 1000),
    ("mamba_n_groups", 8), ("num_hidden_layers", 50)])
def test_what_the_program_lacks_of_granite_is_refused(granite_real, key,
                                                      value):
    cfg, fam = granite_real
    with pytest.raises(SystemExit, match="Granite-4.0-H"):
        fam.program_config({**cfg, key: value})


def test_a_program_without_the_granite_module_is_refused(granite_real,
                                                         monkeypatch):
    """The parent of PR 65 given the cell: a SystemExit before a weight
    is made (non-zero, in seconds), not a hang."""
    cfg, fam = granite_real
    monkeypatch.setitem(sys.modules, "ray_tpu.models.granite_hybrid", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.granite_hybrid"):
        fam.program_config(cfg)


# ----------------------------------------- the program and the reference

def test_the_granite_reference_matches_the_served_model(granite_toy):
    _cfg, fam, pcfg, model, params = granite_toy
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 255, (2, 45)),
                      jnp.int32)
    got = np.asarray(model.apply(params, ids)[0])
    rw = fam.reference_weights(params, pcfg)
    want = np.asarray(fam.reference_forward(rw, ids, pcfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)
    # the toy holds experts 4-7 of 8: the reference is handed the share
    assert pcfg.experts_held == (4, 4)
    assert rw["layers"][0]["w_gate"].shape[0] == 4
    assert rw["layers"][0]["router"].shape[1] == 8


def test_the_granite_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(common.HERE, "reference", "granite_hybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
        elif isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
    assert not [m for m in mods if m.startswith("ray_tpu")], mods
    assert mods <= {"__future__", "functools", "jax", "jax.numpy",
                    "benchmarks.reference", "benchmarks.reference.llama"}


def test_the_granite_weights_are_the_seeds_alone(granite_toy):
    """The same seed gives the same bits, another seed others; the
    decays keep ``a`` in (0, 1); the tied embedding's scale by its
    width; a norm's scale is ones."""
    cfg, fam, pcfg, model, params = granite_toy
    again = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    other = fam.init_params(weights.param_shapes(model), 2**32 + 8)
    p, q, r = (t["params"] for t in (params, again, other))
    a = p["layers_0"]["attention"]
    assert np.array_equal(np.asarray(a["w_in"]["kernel"]),
                          np.asarray(q["layers_0"]["attention"]["w_in"][
                              "kernel"]))
    assert not np.array_equal(np.asarray(a["w_in"]["kernel"]),
                              np.asarray(r["layers_0"]["attention"]["w_in"][
                                  "kernel"]))
    assert (np.asarray(p["layers_0"]["attention_norm"]["scale"]) == 1).all()
    assert (np.asarray(a["o_norm"]["scale"]) == 1).all()
    emb = np.asarray(p["tok_embeddings"])
    assert emb.std() == pytest.approx(0.125 / 8, rel=0.05)   # D = 64
    for leaf, centre in ((a["A_log"], 1.0), (a["dt_bias"], -4.0),
                         (a["D"], 1.0)):
        assert abs(float(np.asarray(leaf).mean()) - centre) < 2.0
    assert set(p["layers_5"]["attention"]) == {"wq", "wk", "wv", "wo"}
    assert p["layers_5"]["moe"]["w1"].shape == (4, 64, 32)
    assert p["layers_5"]["moe"]["shared_w1"].shape == (64, 64)


def _served_like(fam, rw, pcfg, seed, miss, G=32, ids=None, start=28):
    """ids [2, 28 + G] whose last ``G`` tokens a row are the reference's
    own greedy continuation of those before (a position at a time, over
    one buffer: the model is causal, so what lies after a position does
    not move its logits), but for ``miss`` of them (a count, or the (row,
    position) pairs), which are the reference's LEAST likely token there
    (and stay in the context of what follows). ``ids``, ``start``: a
    buffer made before, redone from position ``start`` on."""
    ids = (np.random.default_rng(seed).integers(1, 255, size=(2, 28 + G))
           if ids is None else ids.copy())
    wrong = ({(n % 2, 30 + 3 * n) for n in range(miss)}
             if isinstance(miss, int) else set(miss))
    for t in range(start, 28 + G):
        logits = np.asarray(fam.reference_forward(rw, jnp.asarray(ids),
                                                  pcfg))
        for b in range(2):
            last = logits[b, t - 1]
            ids[b, t] = last.argmin() if (b, t) in wrong else last.argmax()
    return ids.astype(np.int32), 28


def test_granite_flipped_positions_are_excused_up_to_a_share(granite_toy,
                                                             monkeypatch):
    """``reference_logits`` hands the margin rule the reference's own
    logits of the scored rows; generated positions whose token lies more
    than the tolerance under the best get a row of zeros while they are
    at most ``FLIPPED_SHARE`` of the generated positions, and none does
    once they are more: the rule then fails on them."""
    _cfg, fam, pcfg, _model, params = granite_toy
    assert fam.SCORED_TAIL == 256 and fam.FLIPPED_SHARE == 0.01
    monkeypatch.setattr(fam, "SCORED_TAIL", 32)       # a short tail here
    monkeypatch.setattr(fam, "FLIPPED_SHARE", 0.10)   # 6 of its 64
    rw = fam.reference_weights(params, pcfg)
    # every token the reference's own: nothing excused, all scored
    ids, P = _served_like(fam, rw, pcfg, 1, miss=0)
    plain = np.asarray(fam.reference_forward(rw, jnp.asarray(ids), pcfg))
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    np.testing.assert_allclose(scored[:, P - 1:-1], plain[:, P - 1:-1],
                               rtol=1e-5, atol=1e-7)
    assert not scored[:, :P - 1].any() and not scored[:, -1].any()
    ok = parity.margin_rule(scored, ids, P)
    assert ok["ok"] and ok["same_argmax"] == 64 and ok["decisive"] > 0
    # 6 of 64 far off (under the limit): excused, zeros there
    ids, _ = _served_like(fam, rw, pcfg, 1, miss=6)
    assert not parity.margin_rule(np.asarray(fam.reference_forward(
        rw, jnp.asarray(ids), pcfg)), ids, P)["ok"]
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    zeroed = ~scored[:, P - 1:-1].any(-1)
    assert zeroed.sum() == 6
    ok = parity.margin_rule(scored, ids, P)
    assert ok["ok"] and ok["worst_deficit"] <= ok["tol"]
    assert 0 < ok["decisive"] <= 58
    # 7 of 64: over the limit, nothing excused, not correct
    ids, _ = _served_like(fam, rw, pcfg, 1, miss=7)
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    assert scored[:, P - 1:-1].any(-1).all()
    assert not parity.margin_rule(scored, ids, P)["ok"]


def test_the_granite_limit_is_five_of_a_runs_512_positions(granite_toy):
    """The family's own limit at the cell's own count (two prompts' 256
    generated positions): 5 of 512 far off are excused, 6 are not. On
    the chip the served path read 0-1 and the reference with its state
    handed on in bfloat16 9-36 (PERF.md section 6, PR 65)."""
    _cfg, fam, pcfg, _model, params = granite_toy
    assert int(fam.FLIPPED_SHARE * 2 * fam.SCORED_TAIL) == 5
    rw = fam.reference_weights(params, pcfg)
    ids, P = _served_like(fam, rw, pcfg, 2, miss=0, G=fam.SCORED_TAIL)
    assert parity.margin_rule(fam.reference_logits(
        rw, jnp.asarray(ids), pcfg), ids, P)["ok"]
    T = ids.shape[1]
    for miss, ok in ((5, True), (6, False)):
        # the misses are each row's last tokens: only they are redone
        wrong = [(n % 2, T - 1 - n // 2) for n in range(miss)]
        got = _served_like(fam, rw, pcfg, 2, miss=wrong, G=fam.SCORED_TAIL,
                           ids=ids, start=T - 3)[0]
        assert (got[:, :T - 3] == ids[:, :T - 3]).all()
        scored = fam.reference_logits(rw, jnp.asarray(got), pcfg)
        assert (~scored[:, P - 1:-1].any(-1)).sum() == (miss if ok else 0)
        assert parity.margin_rule(scored, got, P)["ok"] is ok


def test_the_scored_tail_is_the_configurations_new_tokens(granite_toy,
                                                          granite_real):
    cfg, fam = granite_real
    toy = granite_toy[0]
    assert fam.SCORED_TAIL == cfg["parity"]["new_tokens"] \
        == toy["parity"]["new_tokens"] == 256
    assert cfg["parity"]["prompt_len"] + 256 <= cfg[
        "max_position_embeddings"]


TOLD_AT_THE_TOY = ("residual_one", "scale_sqrt", "rotary",
                   "norm_before_gate", "one_decay", "softmax_all",
                   "lower_precision")


@pytest.mark.parametrize("control", TOLD_AT_THE_TOY)
def test_the_margin_rule_fails_every_control(granite_toy, monkeypatch,
                                             control):
    """What the true model serves, held to the reference under each
    control: not correct, by the harness's own rule with the family's
    excusing of a share of the positions."""
    _cfg, fam, pcfg, _model, params = granite_toy
    assert fam.CONTROLS == TOLD_AT_THE_TOY + ("bf16_state",)
    monkeypatch.setattr(fam, "SCORED_TAIL", 32)
    rw = fam.reference_weights(params, pcfg)
    ids, P = _served_like(fam, rw, pcfg, 9, 0)
    true = parity.margin_rule(
        fam.reference_logits(rw, jnp.asarray(ids), pcfg), ids, P)
    assert true["ok"] is True
    wrong = parity.margin_rule(
        fam.reference_logits(rw, jnp.asarray(ids), pcfg,
                             **{control: True}), ids, P)
    assert wrong["ok"] is False, (control, wrong)


def test_a_bfloat16_state_is_the_eighth_control_and_the_chips_to_tell(
        granite_toy, monkeypatch, capsys):
    """``bf16_state`` goes through the same comparison as the other
    seven (``reference_logits`` hands it on to the reference's scan,
    ``python -m benchmarks.families.granite_hybrid`` runs all eight and
    exits 1 where one reads correct). At the toy's 16 states over 60
    tokens it moves the served tokens' deficits and no token past the
    tolerance; at the real sizes it read 9-36 of 512 positions over it
    at every seed tried against the limit's 5 (PERF.md section 6, PR
    65)."""
    _cfg, fam, pcfg, _model, params = granite_toy
    monkeypatch.setattr(fam, "SCORED_TAIL", 32)
    rw = fam.reference_weights(params, pcfg)
    ids, P = _served_like(fam, rw, pcfg, 9, 0)
    true = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    rounded = fam.reference_logits(rw, jnp.asarray(ids), pcfg,
                                   bf16_state=True)
    assert np.abs(rounded - true).max() > 1e-3 * np.abs(true).max()
    assert (parity.margin_rule(rounded, ids, P)["worst_deficit"]
            > parity.margin_rule(true, ids, P)["worst_deficit"])
    # the controls' driver: all eight by default, one verdict a control
    seen = {}
    monkeypatch.setattr(
        fam.trace_rounds, "controls_main",
        lambda family, config, argv: seen.update(
            family=family, config=config) or 0)
    assert fam.main([]) == 0
    assert seen["family"].CONTROLS[-1] == "bf16_state"
    assert seen["config"] == CONFIG


def test_the_controls_driver_runs_the_served_path_against_the_controls(
        monkeypatch, capsys):
    """benchmarks/trace_rounds.py ``controls_main`` on the toy: the
    deployment serves the parity's tokens once, the reference as it is
    reads correct and under ``rotary`` does not, one ``CONTROL`` line
    each, and the exit code says whether every verdict was the expected
    one."""
    from benchmarks import trace_rounds
    fam = common.load_family("granite_hybrid", "serve")
    toy, load = common.load_json("rehearsal", TOY + ".json"), common.load_json
    monkeypatch.setattr(
        common, "load_json",
        lambda *parts: toy if parts[0] == "configs" else load(*parts))
    assert trace_rounds.controls_main(
        fam, TOY, ["--seeds", "5", "--controls", "rotary"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("CONTROL")]
    assert [ln.split(":")[0] for ln in lines] == [
        "CONTROL seed 5 None", "CONTROL seed 5 rotary",
        "CONTROLS as expected"]
    verdicts = [json.loads(ln.split(": ", 1)[1]) for ln in lines[:2]]
    assert [v["ok"] for v in verdicts] == [True, False]
    assert verdicts[0]["steps"] == 2 * fam.SCORED_TAIL
    # a control that reads correct is the driver's failure
    monkeypatch.setattr(fam, "reference_logits", functools.partial(
        lambda real, rw, ids, pcfg, **_control: real(rw, ids, pcfg),
        fam.reference_logits))
    assert trace_rounds.controls_main(
        fam, TOY, ["--seeds", "5", "--controls", "rotary"]) == 1


# -------------------------------------------------------- the byte counts

def test_granite_byte_counts_by_hand(granite_real):
    cfg, fam = granite_real
    # ISSUE 65's recount: a mixer 102.29 M, the attention 41.94 M, an
    # expert 9.437 M, the shared SwiGLU 18.87 M
    assert fam.mixer_params(cfg) == (4096 * 16768 + 8192 * 4096
                                     + 4 * 8448)
    assert round(fam.mixer_params(cfg) / 1e6, 2) == 102.27
    assert fam.attention_params(cfg) == 41_943_040
    assert fam.expert_bytes(cfg) == 2 * 9_437_184
    assert fam.shared_params(cfg) == 18_874_368
    assert fam.router_bytes(cfg) == 4096 * 72 * 4
    assert fam.kv_bytes_per_token(cfg) == 4096
    # a slot: 4 MiB of state and 50,688 B of tail a layer, nine layers
    assert fam.state_bytes(cfg) == 4 * 2 ** 20
    assert fam.conv_tail_bytes(cfg) == 50_688
    assert fam.state_bytes_per_slot(cfg) == 9 * (4 * 2 ** 20 + 50_688)
    assert round(fam.state_bytes_per_slot(cfg) / 1e6, 1) == 38.2
    assert fam.state_step_bytes(cfg, 100) == 100 * 2 * (4 * 2 ** 20
                                                        + 50_688)
    assert fam.state_step_flops(cfg, 100) == 100 * 4 * 8192 * 128
    # a prefill call: four rows' states both ways and a token's x', B,
    # C, dt in and y out; 8.5 MFLOP a token a layer over the whole
    # chunk, half the intra-chunk part over the positions it can see
    assert fam.scan_call_bytes(cfg, 4, 1024) == (
        4 * 2 * 4 * 2 ** 20 + 1024 * (2 * 8192 + 256 + 128) * 2)
    assert fam.scan_call_flops(cfg, 4, 1024) == 1024 * 2 * (
        128.5 * 128 + 128.5 * 8192 + 2 * 8192 * 128)
    assert 6.3e6 < fam.scan_call_flops(cfg, 4, 1024) / 1024 < 6.4e6
    assert fam.experts_step_bytes(cfg, 36, 600) == (
        36 * 18_874_368 + 2 * 600 * 4096 * 2)
    assert fam.experts_step_flops(cfg, 600) == 2 * 3 * 600 * 4096 * 768
    # an empty batch: the matrices once (all 36 experts where the
    # caller has no counter), the routers, the head's slice
    weights_ = (2 * fam.mixing_params(cfg) + 10 * (
        2 * fam.shared_params(cfg) + fam.router_bytes(cfg))
        + 50176 * 4096 * 2)
    assert fam.decode_step_bytes(cfg, 0, 0, experts_touched=0) == weights_
    # ISSUE 65's step: ~125 riders at a mean context of ~1.5k: weights
    # 9.1 GB, head 0.4, state both ways 9.6, K/V 0.8 = ~19.9 GB
    full = fam.decode_step_bytes(cfg, 125 * 1536, 125, experts_touched=36)
    assert full == (weights_ + 10 * 36 * 18_874_368
                    + 9 * 125 * 2 * (4 * 2 ** 20 + 50_688)
                    + (125 * 1536 + 125) * 4096 + 125 * 4096 * 2)
    assert 19.6e9 < full < 20.2e9
    assert fam.decode_step_bytes(cfg, 125 * 1536, 125) == full
    flops = fam.decode_step_flops(cfg, 125 * 1536, 125, pairs=625)
    assert flops == (
        2 * 125 * (fam.mixing_params(cfg) + 10 * (
            fam.shared_params(cfg) + 4096 * 72) + 50176 * 4096)
        + 10 * fam.experts_step_flops(cfg, 625)
        + 9 * fam.state_step_flops(cfg, 125)
        + 2 * 2 * 4096 * 125 * 1536)
    assert fam.decode_step_flops(cfg, 125 * 1536, 125) == flops
    # bytes bound the step: 24 ms of bandwidth, 2 ms of the matrix unit
    assert full / 819e9 > 5 * flops / 197e12
    # the program's own counts agree
    from ray_tpu.models.granite_hybrid import granite_hybrid_param_count
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         state_bytes_per_slot)
    pcfg = fam.program_config(cfg)
    assert state_bytes_per_slot(pcfg) == fam.state_bytes_per_slot(cfg)
    dep = cfg["deployment"]
    assert kv_pool_page_bytes(pcfg, dep["page_size"]) == \
        dep["page_size"] * fam.kv_bytes_per_token(cfg)
    assert round(granite_hybrid_param_count(pcfg, 36) / 1e9, 2) == 4.76
    assert dep["n_pages"] == dep["max_slots"] * 32 + 1
    resident = (2 * granite_hybrid_param_count(pcfg, 36)
                + dep["n_pages"] * 64 * 4096
                + dep["max_slots"] * fam.state_bytes_per_slot(cfg))
    assert resident < 16.0e9 and resident > 0.25 * 16e9


def test_the_granite_layers_parts(granite_real):
    _cfg, fam = granite_real
    base = "jit(decode)/while/body/GraniteHybrid/"
    ssm, attn = base + "layers_0/attention/", base + "layers_5/attention/"
    part = lambda path: trace_parts.part_of(path, fam.parts)  # noqa: E731
    for scope in ("ssm_conv", "ssm_gates", "ssm_scan", "ssm_out"):
        assert part(ssm + f"{scope}/mul:") == scope
    # the chunked form's two scopes INSIDE ssm_scan are parts of their own
    pre = "jit(prefill)/GraniteHybrid/layers_0/attention/ssm_scan/"
    assert part(pre + "ssd_intra/bhts,bshp->bthp/dot_general:") == \
        "ssd_intra"
    assert part(pre + "ssd_carry/bshp,bsn->bhpn/dot_general:") == \
        "ssd_carry"
    assert part(pre + "gather:") == "ssm_scan"
    assert fam.SCAN_PARTS == ("ssd_intra", "ssd_carry", "ssm_scan")
    assert set(fam.SCAN_PARTS) < set(fam.SSM_SCOPES)
    assert part(ssm + "ssm_out/wo/dot_general:") == "ssm_out"
    assert part(ssm + "w_in/dot_general:") == "ssm_in"
    assert part(attn + "kv_append/scatter:") == "kv_append"
    assert part(attn + "attn_scores/paged_decode:") == "attn_scores"
    assert part(attn + "wq/dot_general:") == "projections"
    moe = base + "layers_3/moe/"
    for scope in fam.MOE_SCOPES:
        assert part(moe + f"{scope}/x:") == scope
    assert part(moe + "moe_shared/dot_general:") == "moe_shared"
    assert part(base + "layers_3/ffn_norm/mul:") == "norms"
    assert part(base + "head/dot_general:") == "head"


# ----------------------------------------------------------- the readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 100, "decode_steps": 2,
            "decode_context_tokens": 100 * 1500,
            "decode_kernel_pages": 100 * 24,
            "moe_decode_experts_touched": 2 * 10 * 36,
            "moe_decode_pairs": 2 * 10 * 500,
            "moe_decode_layer_steps": 2 * 10,
            "moe_pairs": 2 * 10 * 500 + 10 * 5000,
            "moe_pairs_routed": 2 * 10 * 1000 + 10 * 10240,
            "moe_experts_touched": 3 * 10 * 36, "moe_layer_steps": 30,
            "prefill_rows": 4, "prefill_tokens": 1024, "prefill_width": 256}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(granite_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 100 and 104
    riders), a jit_prefill between them, and a THIRD jit_decode that the
    stop cut. A step: each of 9 Mamba-2 layers 1.5 ms under ssm_scan and
    0.05 under each of the three other scopes; the attention layer 0.4
    ms under attn_scores; each of 10 mixtures 0.6 ms under moe_experts,
    0.1 under moe_dispatch and 0.1 under moe_shared; the head 0.6 ms.
    The prefill call: 60 ms, of which 9 layers x (1.0 ms under
    ssd_intra, 0.4 under ssd_carry, 0.1 directly under ssm_scan)."""
    cfg, fam = granite_real
    base = "jit(decode)/while/body/GraniteHybrid/"
    step = []
    for i, t in enumerate(cfg["layer_types"]):
        a = f"{base}layers_{i}/attention/"
        if t == "mamba":
            step.append((a + "ssm_scan/mul:", 1_500_000))
            step += [(a + f"{s}/mul:", 50_000)
                     for s in ("ssm_conv", "ssm_gates", "ssm_out")]
        else:
            step.append((a + "attn_scores/paged_decode:", 400_000))
        m = f"{base}layers_{i}/moe/"
        step += [(m + "moe_experts/gmm:", 600_000),
                 (m + "moe_dispatch/sort:", 100_000),
                 (m + "moe_shared/dot_general:", 100_000)]
    step.append((base + "head/dot_general:", 600_000))
    pre = "jit(prefill)/GraniteHybrid/"
    call = []
    for i, t in enumerate(cfg["layer_types"]):
        if t == "mamba":
            s = f"{pre}layers_{i}/attention/ssm_scan/"
            call += [(s + "ssd_intra/dot_general:", 1_000_000),
                     (s + "ssd_carry/dot_general:", 400_000),
                     (s + "gather:", 100_000)]
    call.append((pre + "head/dot_general:", 60_000_000 - 9 * 1_500_000))
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
              _round(12.0, round=12, decode_riders=104,
                     decode_context_tokens=104 * 1600)]
    slots = cfg["deployment"]["max_slots"]
    samples = [{"t": 1.0 + i, "free_slots": 2 * i,
                "kv_bytes_in_use": (slots - 2 * i) * 1500 * 4096,
                "kv_bytes_total": cfg["deployment"]["n_pages"] * 64 * 4096,
                "queue_depth": 0} for i in range(3)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"], chips=1,
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={}, samples=samples)
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[1:]}}
    return run


def test_the_granite_readers_on_a_hand_made_run(granite_real, tmp_path):
    cfg, fam = granite_real
    run = _joined_run(granite_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(102.0)
    tokens = (100 * 1500 - 50 + 104 * 1600 - 52) / 2
    assert got["context_tokens"] == pytest.approx(tokens)
    step_s = (9 * 1.65e-3 + 0.4e-3 + 10 * 0.8e-3 + 0.6e-3)
    assert got["module_s"] == pytest.approx(4 * step_s)
    # the whole step: the family's bytes at the rounds' own riders and
    # contexts and the counters' 36 experts touched a mixture layer
    least = fam.decode_step_bytes(cfg, tokens, 102.0,
                                  experts_touched=36.0) / 819e9
    assert fam.decode_counters(run) == {
        "experts_touched": 36.0, "pairs": 500.0, "layer_steps": 40}
    assert read("ssm_moe_step_roofline")(run) == pytest.approx(
        100.0 * least / step_s)
    assert 60.0 < read("ssm_moe_step_roofline")(run) < 100.0
    # the Mamba-2 layers by the scopes Phi-4's readers know
    assert read("decode_ssm_ms")(run) == pytest.approx(9 * 1.65)
    assert read("ssm_scan_roofline")(run) == pytest.approx(
        100.0 * 102 * 2 * (4 * 2 ** 20 + 50_688) / 819e9 / 1.5e-3)
    assert 60.0 < read("ssm_scan_roofline")(run) < 100.0
    # the mixture: 0.6 ms of experts a layer-step
    assert read("moe_experts_roofline.by_kind")(run) == pytest.approx(
        100.0 * fam.experts_step_bytes(cfg, 36, 500) / 819e9 / 0.6e-3)
    assert read("moe_dispatch_share")(run) == pytest.approx(
        100.0 * 0.1 / 0.7)
    # the prefill call: 13.5 of 60 ms under the recurrence's scopes,
    # 1.5 ms a layer against the larger of FLOPs and bytes
    assert read("prefill_ssm_share")(run) == pytest.approx(22.5)
    least = max(fam.scan_call_flops(cfg, 4, 1024) / 197e12,
                fam.scan_call_bytes(cfg, 4, 1024) / 819e9)
    assert least == fam.scan_call_bytes(cfg, 4, 1024) / 819e9
    assert read("ssd_prefill_roofline")(run) == pytest.approx(
        100.0 * least / 1.5e-3)
    assert read("ssd_prefill_roofline")(run) < 100.0
    # the counter metrics
    per_slot = fam.state_bytes_per_slot(cfg)
    assert read("state_kv_bytes_ratio")(run) == pytest.approx(
        per_slot / (1500 * 4096))
    assert read("state_peak_share")(run) == pytest.approx(100.0)
    assert read("moe_held_pair_share")(run) == pytest.approx(
        100.0 * (10000 + 50000) / (20000 + 102400))
    assert read("moe_rows_per_expert_mean")(run) == pytest.approx(
        500 / 36)
    assert read("moe_experts_touched_mean")(run) == pytest.approx(36.0)


def test_the_new_readers_find_nothing_where_there_is_nothing(granite_real,
                                                             tmp_path):
    """Another family, a join that was refused, a program without the
    trace or without the scopes, no peaks, rounds without the mixture's
    counters: None, never an error (the parent of PR 65 cannot run the
    cell at all; a traced run of an OLDER cell under this PR's files
    must not trip on them)."""
    read = common.load_metric_reader
    run = _joined_run(granite_real, tmp_path)
    for other_family in ("llama", "laguna", "phi4flash", "kimi_linear"):
        other = types.SimpleNamespace(**{
            **vars(run), "family": common.load_family(other_family,
                                                      "serve")})
        for name in NEW_READERS:
            assert read(name)(other) is None, (other_family, name)
    refused = _joined_run(granite_real, tmp_path)
    refused._dispatch = None
    no_trace = _joined_run(granite_real, tmp_path)
    no_trace.trace_dir = None
    short = _joined_run(granite_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    unnamed = _joined_run(granite_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("ssm_", "xyz_").replace("ssd_", "xyz_")
    for broken in (no_trace, unnamed):
        for name in NEW_READERS:
            assert read(name)(broken) is None, name
    for broken in (refused, short):
        assert read("ssm_moe_step_roofline")(broken) is None
    no_peaks = _joined_run(granite_real, tmp_path)
    no_peaks.peaks = None
    for name in NEW_READERS:
        assert read(name)(no_peaks) is None
    plain = _joined_run(granite_real, tmp_path)
    for e in plain.events:
        for key in [k for k in e[5] if k.startswith("moe_")]:
            del e[5][key]
    assert read("ssm_moe_step_roofline")(plain) is None
    assert read("ssd_prefill_roofline")(plain) is not None
    no_calls = _joined_run(granite_real, tmp_path)
    for e in no_calls.events:
        e[5]["prefill_width"] = 0
    assert read("ssd_prefill_roofline")(no_calls) is None
    training = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(training) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_gen_sat_as_pr_65_left_them():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "gen-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    assert len(bench["configs"]) == 14 and len(bench["workloads"]) == 15
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {*JOINED, *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-2:]) == NEW_READERS
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["unit"], m["better"], m["source"]) == (
            "serve_tokens_per_s", "%", "higher", "device_trace")
        assert callable(common.load_metric_reader(m["name"]))
    assert [m["layer"] for m in bench["per_layer"][-2:]] == [
        "kernels", "model step"]
    # Phi-4's bytes-alone share of a walk of positions is not this rule's
    assert "ssm_prefill_scan_roofline" not in per_layer
    assert "decode_roofline" not in per_layer
    # the traffic is kimi-linear-d8.gen-sat's file, unedited
    other = common.find_named(bench["workloads"], "kimi-linear-d8.gen-sat",
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
    assert len(ids) == 1024 and 1 <= min(ids) and max(ids) < 50176
    # a slot's 2,048 tokens are 32 pages; the slots are one of the
    # three ISSUE 65 names
    dep = cfg["deployment"]
    assert dep["max_slots"] in (128, 112, 96) and dep["page_size"] == 64
    assert dep["n_pages"] == dep["max_slots"] * 32 + 1
    assert cfg["max_position_embeddings"] >= 2048
    assert cfg["max_position_embeddings"] % dep["page_size"] == 0
    # four prefill chunks of 256 = four chunks of the recurrence
    assert 1024 // cfg["mamba_chunk_size"] == 4


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-granite-hybrid.gen-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["2"])
def test_the_granite_rehearsal_cell_runs(trace):
    """The toy cell borrows granite4-h-small-d10.gen-sat's metric lists:
    correct against the plain reference through the served path, no
    program built in the window; at ``--trace 2`` (what the driver runs;
    tests/test_rehearse.py runs every toy cell at ``--trace 0`` too,
    outside tier-1's clock) the counter metrics are there, the share's
    among them (the toy holds experts 4-7 of 8); the device_trace
    metrics need a device in the trace, which a CPU has not (the
    hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "[correct] margin rule: {'ok': True" in stdout
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    reports = common.load_rehearsal_cell(
        "toy-granite-hybrid.gen-sat")["reports"]
    assert {"state_peak_share", "state_kv_bytes_ratio",
            "moe_held_pair_share", "moe_rows_per_expert_mean"} <= set(
        reports)
    for name in reports:
        assert name in line["metrics"], name
    assert 0.0 < line["metrics"]["state_peak_share"]["value"] <= 100.0
    assert 20.0 < line["metrics"]["moe_held_pair_share"]["value"] < 80.0
    assert line["metrics"]["moe_experts_touched_mean"]["value"] <= 4.0
    for name in NEW_READERS + ("decode_ssm_ms", "ssm_scan_roofline"):
        assert name not in line["metrics"]


# ------------------------------------------------------------ the cadence

def _planned_rounds(slots, decode_chunk, rounds=900):
    """The engine's REAL planner over ``gen-sat`` as this cell deploys
    it, without a device or a clock (benchmarks/tests/
    test_deepseek_v32_family.py ``_planned_rounds``'s method): ``slots``
    slots that a waiting client refills the round after a request's last
    step was dispatched (two clients a slot: the queue never runs dry),
    prompts of 1,024 through four rows of 256, 1,024 tokens a request
    (the prefill call's and 1,023 steps). Returns (steps, riders, rows)
    a round."""
    from ray_tpu.serve.scheduler import SlotView, plan_step
    held, seq, out = [None] * slots, 0, []
    for _ in range(rounds):
        for i in range(slots):
            if held[i] is None:
                held[i] = {"rem": 1024, "dec": 0, "cur": False, "seq": seq}
                seq += 1
        plan = plan_step(
            [SlotView(sid=i, admit_seq=s["seq"], prompt_remaining=s["rem"],
                      owed=1023 - s["dec"] if s["cur"] else 0,
                      seeded=s["cur"]) for i, s in enumerate(held)],
            total_slots=slots, prefill_chunk=256, decode_chunk=decode_chunk,
            max_run_ahead=max(decode_chunk, 128), prefill_batch=4,
            eos_bounded=False)
        riders = [i for i, s in enumerate(held) if s["cur"]]
        for g in plan.prefill:
            held[g.sid]["rem"] -= g.tokens
        for i in riders if plan.decode_steps else ():
            held[i]["dec"] += plan.decode_steps
            if held[i]["dec"] >= 1023:
                held[i] = None
        for g in plan.prefill:
            if held[g.sid]["rem"] == 0:
                held[g.sid]["cur"] = True      # rides from the next round
        out.append((plan.decode_steps if riders else 0, len(riders),
                    len(plan.prefill)))
    return out


def test_the_cadence_is_the_least_at_which_no_stream_ever_stalls():
    """``deployment.decode_chunk`` 10 (the file's ``deployment_notes``),
    from the traffic's own numbers: four rows of 256 admit one prompt of
    1,024 a round, and a request then holds its slot for 4 prefill
    rounds and ceil(1,023 / cadence) decode rounds: 132 at the engine's
    default 8, 118 at 9, 107 at 10. Over 112 slots the loop is bound by
    its slots at 8 and 9: no slot is free for a part of every cycle, the
    rows stand empty, and the real planner, with nothing to prefill,
    runs ahead: a dispatch of 128 steps (3.7 s on the chip in which no
    stream gets a token) and one of 31 a cycle at 8, one of 51 at 9. At
    10 admission bounds it: every round past the start-up is 10 steps
    beside a FULL prefill call, 100-104 of the 112 slots ride and
    nothing runs ahead (PERF.md section 6, PR 65, has what either
    cadence read on the chip)."""
    cfg = common.load_json("configs", CONFIG + ".json")
    dep = cfg["deployment"]
    assert dep["decode_chunk"] == 10 and dep["max_slots"] == 112

    def rounds_held(cadence):
        return 4 + -(-1023 // cadence)
    assert [rounds_held(c) for c in (8, 9, 10)] == [132, 118, 107]
    assert min(c for c in range(1, 17)
               if rounds_held(c) <= dep["max_slots"]) == 10
    settled = _planned_rounds(112, 10)[300:]
    assert {steps for steps, _r, _p in settled} == {10}
    assert {rows for _s, _r, rows in settled} == {4}
    assert 100 <= min(r for _s, r, _p in settled)
    assert max(r for _s, r, _p in settled) <= 104
    for cadence, ahead in ((8, {128, 31}), (9, {51})):
        bound = _planned_rounds(112, cadence)[300:]
        assert {rows for _s, _r, rows in bound} == {0, 4}
        assert {s for s, _r, _p in bound} == {cadence} | ahead
        assert max(r for _s, r, _p in bound) == 112      # slot-bound
