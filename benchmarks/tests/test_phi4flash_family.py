"""The Phi-4-mini-flash family (families/phi4flash.py,
reference/phi4flash.py, configs/phi-4-mini-flash-reasoning.json, the toy
``rehearsal/toy-phi4flash.json``, traffic/reason-sat.json) on the CPU:
the configuration against its published copy (``reduced`` is the page
table's width alone; every ``assumed`` item has its line in the family
file's docstring), the program's config the family builds, the served
model against the plain reference at the toy's sizes and the
reference's controls against the margin rule, the byte counts against
ISSUE 60's arithmetic (119.9 / 98.3 / 104.9 / 91.8 M a layer, 3,852.6 M,
5,120 B a token, 34.1 MB of rings and 3.23 MB of states a slot), the
seven new readers and the older ones the cell joins on a hand-made
joined trace, the cell and its mix, and the rehearsal cell end to end at
``--trace 0`` and ``--trace 2``."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, parity, trace_parts, trafficgen, weights

CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi4-mini-flash.reason-sat"
NEW_READERS = ("decode_ssm_ms", "prefill_ssm_share", "ssm_scan_roofline",
               "ssm_prefill_scan_roofline", "decode_shared_attn_ms",
               "shared_attn_roofline", "shared_kv_read_ratio")
JOINED = ("host_gap_share", "kv_peak_share", "device_idle_share.serve",
          "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
          "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
          "dispatch_prefill_share", "state_peak_share",
          "sliding_resident_share", "state_kv_bytes_ratio",
          "decode_sliding_attn_ms", "sliding_attn_roofline",
          "prefill_sliding_attn_share", "hybrid_step_roofline",
          "setup_build_s", "setup_program_trace_s", "setup_cold_builds",
          "engine_init_s")


@pytest.fixture(scope="module")
def phi_toy():
    cfg = common.load_json("rehearsal", "toy-phi4flash.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def phi_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_phi_file_holds_the_published_sizes_but_for_reduced(phi_real):
    """Nothing is cut but the page table's width: ``reduced`` is exactly
    ``["max_position_embeddings"]``, every other key of the catalog
    row's ``config`` stands under its own name."""
    cfg, fam = phi_real
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "published", CONFIG + ".json")
    with open(path) as f:
        source = json.load(f)
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["reduced_from"] == {"max_position_embeddings": 262144}
    assert len(source) == 17
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
        else:
            assert cfg[key] == want, key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["sliding_window"], cfg["mb_per_layer"]) == (
        2560, 10240, 32, 40, 20, 200064, 512, 2)
    assert cfg["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                            "dt_rank": 160}
    # every size the row lacks is under ``assumed``, each with what
    # moves if it is wrong, and each has its line in the family file
    assumed = {"mamba_sizes", "layout", "memory", "differential_pairs",
               "lambdas", "window", "layer_norm", "biases", "nope",
               "swiglu_halves", "weights"}
    assert assumed <= set(cfg["assumed"])
    for item in ("mamba_sizes", "layout", "memory", "differential_pairs",
                 "lambdas", "window", "layer_norm", "biases", "nope"):
        assert "If wrong" in cfg["assumed"][item] \
            or "if wrong" in cfg["assumed"][item], item
    for item in assumed:
        assert f"- ``{item}``" in fam.__doc__, item
    assert "WHOLE" in cfg["stands_for"]
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_phi_program_config_is_the_published_model(phi_real):
    cfg, fam = phi_real
    pcfg = fam.program_config(cfg)
    assert (pcfg.dim, pcfg.n_layers, pcfg.attn_heads, pcfg.attn_kv_heads,
            pcfg.hidden_dim, pcfg.vocab_size, pcfg.max_seq_len,
            pcfg.sliding_window) == (2560, 32, 40, 20, 10240, 200064,
                                     3072, 512)
    # as stored: 10 pairs of 128, pages of 16 head rows
    assert (pcfg.n_heads, pcfg.n_kv_heads, pcfg.head_dim,
            pcfg.kv_page_heads) == (40, 10, 128, 16)
    assert pcfg.recurrent_state_shape == (16, 5120)
    assert pcfg.recurrent_conv_shape == (3, 5120)
    kinds = pcfg.layer_kinds
    assert kinds.count("recurrent") == 9 == fam.n_ssm_layers(cfg)
    assert kinds.count("sliding") == 8 == fam.n_sliding_layers(cfg)
    assert kinds.count("kv") == 1 and kinds.count("borrowed") == 7
    assert fam.n_page_readers(cfg) == 8
    assert kinds.count("stateless") == 7 == fam.n_gmu_layers(cfg)
    assert pcfg.mixers == fam.mixers(cfg)
    assert pcfg.norm_eps == 1e-5 and pcfg.dtype == jnp.bfloat16
    assert pcfg.tie_word_embeddings
    assert not hasattr(pcfg, "serving_rules")


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("mlp_bias", True), ("lm_head_bias", True), ("mb_per_layer", 4),
    ("model_type", "phi3"), ("resid_pdrop", 0.1),
    ("num_hidden_layers", 30), ("num_key_value_heads", 15)])
def test_what_the_program_lacks_of_phi_is_refused(phi_real, key, value):
    cfg, fam = phi_real
    with pytest.raises(SystemExit, match="Phi-4-mini-flash"):
        fam.program_config({**cfg, key: value})


def test_a_program_without_the_phi_module_is_refused(phi_real,
                                                     monkeypatch):
    cfg, fam = phi_real
    monkeypatch.setitem(sys.modules, "ray_tpu.models.phi4flash", None)
    with pytest.raises(SystemExit,
                       match="cannot express Phi-4-mini-flash"):
        fam.program_config(cfg)


# ------------------------------------------- weights and the reference

def test_the_phi_seeded_weights_rule(phi_toy):
    """Matrices at 1/sqrt(fan_in), the convolution by its width, the
    tied embedding at 0.02, biases and the lambda vectors at 0.1, every
    norm's scale one; A = exp(1 + n), b_dt around -4, D around 1; the
    same seed the same bits, another seed others."""
    cfg, fam, pcfg, model, params = phi_toy
    p = params["params"]
    ssm, attn = p["layers_0"]["attention"], p["layers_5"]["attention"]
    gmu, cross = p["layers_6"]["attention"], p["layers_7"]["attention"]
    D, C = cfg["hidden_size"], 2 * cfg["hidden_size"]
    for leaf, want in ((ssm["w_in"]["kernel"], D ** -0.5),
                       (ssm["wo"]["kernel"], C ** -0.5),
                       (ssm["w_x"]["kernel"], C ** -0.5),
                       (ssm["conv"], 0.5), (p["tok_embeddings"], 0.02),
                       (attn["wq"]["kernel"], D ** -0.5),
                       (attn["wq"]["bias"], 0.1),
                       (gmu["w2"]["kernel"], C ** -0.5),
                       (p["layers_0"]["attention_norm"]["bias"], 0.1)):
        assert float(np.std(np.asarray(leaf))) == pytest.approx(
            want, rel=0.3)
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        assert attn[name].shape == cross[name].shape == (8,)
        assert 0.02 < float(np.std(np.asarray(attn[name]))) < 0.25
    for scale in (attn["subln"], p["norm"]["scale"],
                  p["layers_0"]["attention_norm"]["scale"]):
        assert (np.asarray(scale) == 1.0).all()
    assert ssm["A_log"].shape == (8, 128)               # [N, C]
    assert ssm["dt_bias"].shape == ssm["D"].shape == (128,)
    assert 0.5 < float(np.mean(np.asarray(ssm["A_log"]))) < 1.5
    assert -6.0 < float(np.mean(np.asarray(ssm["dt_bias"]))) < -2.0
    assert 0.7 < float(np.mean(np.asarray(ssm["D"]))) < 1.3
    assert "wk" not in cross and "wk" in attn
    again = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    other = fam.init_params(weights.param_shapes(model), 2**32 + 8)
    for tree, same in ((again, True), (other, False)):
        assert bool((np.asarray(tree["params"]["tok_embeddings"])
                     == np.asarray(p["tok_embeddings"])).all()) is same


def test_the_phi_reference_weights_round_trip(phi_toy):
    """Every leaf of the program's tree reaches the reference under its
    name, none twice, none dropped."""
    import jax
    _cfg, fam, pcfg, _model, params = phi_toy
    rw = fam.reference_weights(params, pcfg)
    assert len(rw["layers"]) == pcfg.n_layers
    ours = {id(leaf) for leaf in jax.tree_util.tree_leaves(params)}
    theirs = [id(leaf) for leaf in jax.tree_util.tree_leaves(rw)]
    assert len(theirs) == len(set(theirs)) == len(ours)
    assert set(theirs) == ours
    block = {"ln1", "ln1_bias", "ln2", "ln2_bias", "w_gate", "w_up",
             "w_down"}
    lambdas = {"lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"}
    assert set(rw["layers"][0]) - block == {
        "w_in", "conv", "conv_bias", "w_x", "w_dt", "dt_bias", "A_log",
        "D", "w_out"}
    assert set(rw["layers"][5]) - block - lambdas == {
        "wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo"}
    assert set(rw["layers"][5]) == set(rw["layers"][1])
    assert set(rw["layers"][6]) - block == {"w1", "w2"}
    assert set(rw["layers"][7]) - block - lambdas == {"wq", "bq", "wo",
                                                      "bo"}


def test_the_phi_reference_matches_the_served_model(phi_toy):
    import jax
    cfg, fam, pcfg, model, params = phi_toy
    ids = jnp.asarray(trafficgen.prompt_tokens(5, 1, 90, 256))[None]
    got, _ = jax.jit(model.apply)(params, ids)
    want = fam.reference_forward(fam.reference_weights(params, pcfg), ids,
                                 pcfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_the_phi_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(common.HERE, "reference", "phi4flash.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
        elif isinstance(node, ast.Import):
            names += [a.name for a in node.names]
    assert names and not [n for n in names if n.startswith("ray_tpu")]


def _served_ids(phi_toy, P=40, G=32):
    """Greedy tokens of the program's own cache-less forward pass,
    teacher-forced from seeded prompts (the engine's path is held to the
    same logits in tests/test_phi4flash.py)."""
    import jax
    cfg, fam, pcfg, model, params = phi_toy
    ids = np.asarray([trafficgen.prompt_tokens(9, i, P + G, 256)
                      for i in range(2)], np.int32)
    apply = jax.jit(model.apply)
    for t in range(P, P + G):
        logits, _ = apply(params, jnp.asarray(ids))
        ids[:, t] = np.asarray(logits[:, t - 1].argmax(-1))
    return ids


@pytest.mark.parametrize("control", [None, "cross_from_sliding",
                                     "memory_after_gate", "wide_window",
                                     "lower_precision"])
def test_the_margin_rule_refuses_each_control(phi_toy, control):
    """The comparison that decides ``correct``, as the harness makes it
    (``reference_logits`` into ``parity.margin_rule``): true for the
    reference as it is, false with the cross layers given the last
    sliding layer's keys, the memory taken after the gate, the window
    half as wide again, or computed in the precision below (float8).
    (``lambda_fixed`` moves the toy's logits by less than the rule's
    2**-5 of the logit scale: tests/test_phi4flash.py holds it on
    logits, and PERF.md section 6 has its reading on the chip.)"""
    cfg, fam, pcfg, _model, params = phi_toy
    P, G = cfg["parity"]["prompt_len"], cfg["parity"]["new_tokens"]
    ids = _served_ids(phi_toy, P, G)
    rw = fam.reference_weights(params, pcfg)
    kw = {control: True} if control else {}
    logits = fam.reference_logits(rw, jnp.asarray(ids), pcfg, **kw)
    check = parity.margin_rule(logits, ids, P)
    assert check["ok"] is (control is None), check
    assert check["steps"] == 2 * G


def test_the_scored_tail_is_the_configurations_new_tokens(phi_toy,
                                                          phi_real):
    """``reference_logits`` fills the rows that predict the last
    ``SCORED_TAIL`` tokens and no other: the parity blocks of both
    configurations generate exactly that many (the deficits' mean and
    worst are taken over those rows)."""
    cfg, fam, pcfg, _model, params = phi_toy
    assert fam.SCORED_TAIL == phi_real[0]["parity"]["new_tokens"] == \
        cfg["parity"]["new_tokens"] == 64
    assert phi_real[0]["parity"] == {"prompts": 2, "prompt_len": 2048,
                                     "new_tokens": 64}
    ids = _served_ids(phi_toy, 40, 64)
    rw = fam.reference_weights(params, pcfg)
    logits = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    whole = np.asarray(fam.reference_forward(rw, jnp.asarray(ids), pcfg))
    T, G = ids.shape[1], fam.SCORED_TAIL
    np.testing.assert_allclose(logits[:, T - 1 - G:T - 1],
                               whole[:, T - 1 - G:T - 1], rtol=1e-5,
                               atol=1e-6)
    assert not logits[:, :T - 1 - G].any() and not logits[:, T - 1:].any()


# ---------------------------------------------------------- byte counts

def test_phi_byte_counts_by_hand(phi_real):
    """ISSUE 60's arithmetic: a state-space layer 119.9 M, an attention
    layer that owns K/V 98.3 M, a memory-unit layer 104.9 M, a cross
    layer 91.8 M, the model 3,852.6 M = 7.71 GB; a token 5,120 B in ONE
    layer; a slot eight rings of 832 positions (34.1 MB) and nine states
    (3.23 MB)."""
    cfg, fam = phi_real
    mlp = fam.mlp_params(cfg)
    assert mlp == 3 * 2560 * 10240
    assert round((fam.mixing_params(cfg, fam.SSM) + mlp) / 1e5) == 1199
    assert round((fam.mixing_params(cfg, fam.FULL) + mlp) / 1e5) == 983
    assert fam.mixing_params(cfg, fam.SLIDING) == fam.mixing_params(
        cfg, fam.FULL) == 2 * 2560 * 2560 + 2 * 2560 * 1280
    assert round((fam.mixing_params(cfg, fam.GMU) + mlp) / 1e5) == 1049
    assert round((fam.mixing_params(cfg, fam.CROSS) + mlp) / 1e5) == 918
    assert round(fam.model_params(cfg) / 1e5) == 38520       # matrices
    assert 7.70e9 < 2 * fam.model_params(cfg) < 7.72e9
    assert fam.key_bytes(cfg) == fam.kv_bytes_per_token(cfg) \
        == 2 * 20 * 64 * 2 == 5120
    assert fam.page_token_bytes(cfg) == 2 * 16 * 128 * 2 == 8192
    assert fam.ring_len(cfg) == 832
    assert fam.ring_bytes(cfg) == 832 * 5120
    assert fam.sliding_bytes_per_slot(cfg) == 8 * 832 * 5120
    assert round(fam.sliding_bytes_per_slot(cfg) / 1e5) == 341
    assert fam.state_bytes(cfg) == 16 * 5120 * 4 == 327_680
    assert fam.conv_tail_bytes(cfg) == 3 * 5120 * 2 == 30_720
    assert 9 * (327_680 + 30_720) == 3_225_600             # 3.23 MB
    assert fam.state_bytes_per_slot(cfg) == 3_225_600 + 8 * 832 * 5120
    assert fam.state_step_bytes(cfg, 64) == 64 * 2 * 358_400
    assert fam.scan_call_bytes(cfg, 4, 1024) == (
        4 * 2 * 327_680 + 1024 * (3 * 5120 + 32) * 2)
    assert fam.sliding_step_bytes(cfg, 64 * 512) == 64 * 512 * 5120
    assert fam.sliding_step_flops(cfg, 100) == 2 * 40 * (64 + 128) * 100
    assert fam.shared_step_bytes(cfg, 8 * 1000) == 8000 * 5120
    # what stays of the pages a layer did nothing age: the pool's bytes
    # at 8,192 a token are 5,120 of content a sliding layer
    assert fam.unaged_bytes(cfg, 100 * 8192) == 100 * 5120 * 8
    # an empty batch: the 32 layers and the tied head once
    weights_ = 2 * fam.model_params(cfg)
    assert fam.decode_step_bytes(cfg, 0, 0) == weights_
    # ISSUE 60's step: 64 riders at a mean context of 2,560: weights
    # 7.71 GB, pages x 8 readers 6.7 GB, rings 1.3 GB, states 0.41 GB
    full = fam.decode_step_bytes(cfg, 64 * 2560, 64)
    assert full == (weights_ + 64 * 2560 * 2
                    + (64 * 2560 * 8 + 64) * 5120
                    + 8 * (64 * 512 + 64) * 5120
                    + 9 * 64 * 2 * 358_400)
    assert 16.0e9 < full < 16.3e9
    # contexts under the window: the rings read the contexts
    short = fam.decode_step_bytes(cfg, 64 * 100, 64)
    assert short == (weights_ + 64 * 2560 * 2 + (6400 * 8 + 64) * 5120
                     + 8 * (6400 + 64) * 5120 + 9 * 64 * 2 * 358_400)
    # the program's own counts: a slot's state as declared, the pool's
    # page 16 head rows a token where the arithmetic needs 10
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         sliding_ring_len,
                                         state_bytes_per_slot)
    pcfg = fam.program_config(cfg)
    dep = cfg["deployment"]
    ring = sliding_ring_len(pcfg, dep["page_size"], 256)
    assert ring == fam.ring_len(cfg)
    assert state_bytes_per_slot(pcfg, ring) == fam.state_bytes_per_slot(cfg)
    assert kv_pool_page_bytes(pcfg, dep["page_size"]) == \
        dep["page_size"] * fam.page_token_bytes(cfg)
    assert dep["n_pages"] == dep["max_slots"] * 48 + 1
    resident = (2 * fam.model_params(cfg)
                + dep["n_pages"] * 64 * fam.page_token_bytes(cfg)
                + dep["max_slots"] * fam.state_bytes_per_slot(cfg))
    assert 11.6e9 < resident < 11.8e9


def test_the_phi_layers_parts(phi_real):
    _cfg, fam = phi_real
    base = "jit(decode)/while/body/Phi4Flash/"
    ssm, swa = base + "layers_0/attention/", base + "layers_1/attention/"
    full, cross = (base + "layers_17/attention/",
                   base + "layers_19/attention/")
    part = lambda path: trace_parts.part_of(path, fam.parts)  # noqa: E731
    for scope in fam.SSM_SCOPES:
        assert part(ssm + f"{scope}/mul:") == scope
    # the x, dt and output projections lie inside their scopes
    assert part(ssm + "ssm_gates/w_x/dot_general:") == "ssm_gates"
    assert part(ssm + "ssm_out/wo/dot_general:") == "ssm_out"
    assert part(ssm + "w_in/dot_general:") == "ssm_in"
    assert part(swa + "attn_sliding/ring_window:") == "attn_sliding"
    assert part(swa + "attn_sliding/ring_scores/dot_general:") == \
        "ring_scores"
    assert part(swa + "wq/dot_general:") == "projections"
    for path in (full, cross):
        for scope in fam.SHARED_PARTS[1:4]:
            assert part(path + f"attn_shared/while/body/{scope}/dot:") \
                == scope
        assert part(path + "attn_shared/pad:") == "attn_shared"
        assert part(path + "diff_merge/sub:") == "diff_merge"
    assert part(full + "attn_shared/kv_append/scatter:") == "kv_append"
    assert part(base + "layers_18/attention/gmu/w1/dot_general:") == "gmu"
    layer = base + "layers_3/"
    for norm in ("attention_norm", "ffn_norm"):
        assert part(layer + norm + "/mul:") == "norms"
    assert part(layer + "feed_forward/w2/dot_general:") == "mlp"
    assert part(base + "head/dot_general:") == "head"


# ----------------------------------------------------------- the readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 60, "decode_steps": 2,
            "decode_window_tokens": 3072,
            "decode_context_tokens": 60 * 2500,
            "decode_sliding_keys": 60 * 512,
            "decode_shared_kv_reads": 8 * 60 * 2500,
            "decode_kernel_pages": 60 * 40,
            "prefill_rows": 4, "prefill_tokens": 1024, "prefill_width": 256}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(phi_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 60 and 64
    riders), a jit_prefill between them, and a THIRD jit_decode that the
    stop cut. A step: each of 9 state-space layers 0.2 ms under ssm_scan
    and 0.05 under each of the three other scopes; each of 8 sliding
    layers 0.25 ms in the ring kernel; each of the 8 layers that read
    pages 1.0 ms under attn_scores (the owner 0.01 more in kv_append);
    32 layers of 0.3 ms of SwiGLU; the head 1.0 ms. The prefill call: 80
    ms, of which 9 layers x 2.0 ms under ssm_scan and 8 x 0.5 under
    attn_sliding."""
    cfg, fam = phi_real
    layout = fam.mixers(cfg)
    base = "jit(decode)/while/body/Phi4Flash/"
    step = []
    for i, mixer in enumerate(layout):
        a = f"{base}layers_{i}/attention/"
        if mixer == fam.SSM:
            step.append((a + "ssm_scan/mul:", 200_000))
            step += [(a + f"{s}/mul:", 50_000)
                     for s in ("ssm_conv", "ssm_gates", "ssm_out")]
        elif mixer == fam.SLIDING:
            step.append((a + "attn_sliding/ring_window:", 250_000))
        elif mixer in (fam.FULL, fam.CROSS):
            step.append((a + "attn_shared/attn_scores/paged_decode:",
                         1_000_000))
            if mixer == fam.FULL:
                step.append((a + "attn_shared/kv_append/scatter:", 10_000))
        step.append((f"{base}layers_{i}/feed_forward/w2/dot_general:",
                     300_000))
    step.append((base + "head/dot_general:", 1_000_000))
    pre = "jit(prefill)/Phi4Flash/"
    call = [(f"{pre}layers_{i}/attention/ssm_scan/while/body/mul:",
             2_000_000) for i, m in enumerate(layout) if m == fam.SSM]
    call += [(f"{pre}layers_{i}/attention/attn_sliding/ring_window:",
              500_000) for i, m in enumerate(layout) if m == fam.SLIDING]
    call.append((pre + "head/dot_general:", 58_000_000))
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
              _round(12.0, round=12, decode_riders=64,
                     decode_context_tokens=64 * 2600,
                     decode_sliding_keys=64 * 512,
                     decode_shared_kv_reads=8 * 64 * 2600,
                     decode_kernel_pages=64 * 41)]
    samples = [{"t": 1.0 + i, "free_slots": 2 * i,
                "kv_bytes_in_use": (64 - 2 * i) * 2500 * 8192,
                "kv_bytes_total": 3073 * 64 * 8192, "queue_depth": 0}
               for i in range(3)]
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


def test_the_readers_on_a_hand_made_run(phi_real, tmp_path):
    cfg, fam = phi_real
    run = _joined_run(phi_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(62.0)
    tokens = (60 * 2500 - 30 + 64 * 2600 - 32) / 2
    assert got["context_tokens"] == pytest.approx(tokens)
    assert got["sliding_keys"] == pytest.approx(62 * 512)
    assert got["shared_reads"] == pytest.approx(8 * tokens)
    step_s = (9 * 0.35e-3 + 8 * 0.25e-3 + 8 * 1.0e-3 + 0.01e-3
              + 32 * 0.3e-3 + 1.0e-3)
    assert got["module_s"] == pytest.approx(4 * step_s)
    # the whole step against the family's bytes at the rounds' own
    # riders and contexts
    least = fam.decode_step_bytes(cfg, tokens, 62.0) / 819e9
    assert read("hybrid_step_roofline")(run) == pytest.approx(
        100.0 * least / step_s)
    assert 50.0 < read("hybrid_step_roofline")(run) < 100.0
    # the state-space layers: 0.35 ms a layer-step, 0.2 of it the scan
    assert read("decode_ssm_ms")(run) == pytest.approx(9 * 0.35)
    assert read("ssm_scan_roofline")(run) == pytest.approx(
        100.0 * 62 * 2 * 358_400 / 819e9 / 0.2e-3)
    assert read("ssm_scan_roofline")(run) < 100.0
    # the layers that read pages: 8.01 ms a step for 8 x the contexts
    assert read("decode_shared_attn_ms")(run) == pytest.approx(8.01)
    assert read("shared_attn_roofline")(run) == pytest.approx(
        100.0 * 8 * tokens * 5120 / 819e9 / 8.01e-3)
    assert read("shared_attn_roofline")(run) < 100.0
    # the sliding layers: the older readers through this family's table
    assert read("decode_sliding_attn_ms")(run) == pytest.approx(8 * 0.25)
    assert read("sliding_attn_roofline")(run) == pytest.approx(
        100.0 * 62 * 512 * 5120 / 819e9 / 0.25e-3)
    # the prefill call: 18 of 80 ms under the scan, 4 under the rings
    assert read("prefill_ssm_share")(run) == pytest.approx(22.5)
    assert read("prefill_sliding_attn_share")(run) == pytest.approx(5.0)
    assert read("ssm_prefill_scan_roofline")(run) == pytest.approx(
        100.0 * fam.scan_call_bytes(cfg, 4, 1024) / 819e9 / 2.0e-3)
    # the window's one round: 8 readers x 40 pages of 64 a rider over
    # contexts of 2,500
    assert read("shared_kv_read_ratio")(run) == pytest.approx(
        8 * 40 * 64 / 2500)
    # the counter metrics: slots x a slot's state over the pages in use
    per_slot = fam.state_bytes_per_slot(cfg)
    assert read("state_kv_bytes_ratio")(run) == pytest.approx(
        per_slot / (2500 * 8192))
    assert read("state_peak_share")(run) == pytest.approx(100.0)
    assert read("sliding_resident_share")(run) == pytest.approx(
        100.0 * 8 * 832 * 5120 / (2500 * 5120 * 8))
    assert read("kv_peak_share")(run) == pytest.approx(
        100.0 * 64 * 2500 / (3073 * 64))


def test_the_new_readers_find_nothing_where_there_is_nothing(phi_real,
                                                             tmp_path):
    """Another family, a join that was refused, a program without the
    trace or without the scopes, no peaks, rounds without the counter:
    None, never an error (the parent of PR 60 cannot run the cell at
    all; a traced run of an OLDER cell under this PR's files must not
    trip on them)."""
    read = common.load_metric_reader
    run = _joined_run(phi_real, tmp_path)
    for other_family in ("llama", "laguna", "olmo_hybrid"):
        other = types.SimpleNamespace(**{
            **vars(run), "family": common.load_family(other_family,
                                                      "serve")})
        for name in NEW_READERS[:-1]:
            assert read(name)(other) is None, (other_family, name)
    traced = NEW_READERS[:-1]
    refused = _joined_run(phi_real, tmp_path)
    refused._dispatch = None
    no_trace = _joined_run(phi_real, tmp_path)
    no_trace.trace_dir = None
    short = _joined_run(phi_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    unnamed = _joined_run(phi_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("ssm_", "xyz_").replace(
            "attn_s", "xyz_s")
    for broken in (no_trace, unnamed):
        for name in traced:
            assert read(name)(broken) is None, name
    for broken in (refused, short):
        for name in ("decode_ssm_ms", "ssm_scan_roofline",
                     "decode_shared_attn_ms", "shared_attn_roofline"):
            assert read(name)(broken) is None, name
    no_peaks = _joined_run(phi_real, tmp_path)
    no_peaks.peaks = None
    for name in ("ssm_scan_roofline", "ssm_prefill_scan_roofline",
                 "shared_attn_roofline"):
        assert read(name)(no_peaks) is None
    assert read("decode_ssm_ms")(no_peaks) is not None
    # rounds without the counter (a program with no borrowed layer)
    plain = _joined_run(phi_real, tmp_path)
    for e in plain.events:
        del e[5]["decode_shared_kv_reads"]
    assert read("shared_kv_read_ratio")(plain) is None
    assert read("shared_attn_roofline")(plain) is None
    # the block loop in the kernel's place: every rider's window
    loop = _joined_run(phi_real, tmp_path)
    for e in loop.events:
        e[5]["decode_kernel_pages"] = 0
    assert read("shared_kv_read_ratio")(loop) == pytest.approx(
        8 * 3072 / 2500)
    training = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(training) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_reason_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reason-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {*JOINED, *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-7:]) == NEW_READERS
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(common.load_metric_reader(m["name"]))
    # the whole step's share is the engine's count of steps and riders,
    # not trace_reduce.loop_steps x max_slots
    assert "decode_roofline" not in per_layer
    tr = common.load_json("traffic", "reason-sat.json")
    assert (tr["loop"], tr["clients_per_slot"], tr["prefix_cache"],
            tr["shared_prefix_tokens"], tr["population"]) == (
        "closed", 2, False, 0, 512)
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {2048}
    assert {r.output_len for r in reqs} == {1024}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 2048, cfg["vocab_size"])
    assert len(ids) == 2048 and 1 <= min(ids) and max(ids) < 200064
    # 48 pages a slot hold a request; the page table holds it exactly
    dep = cfg["deployment"]
    per_slot = -(-(2048 + 1024) // dep["page_size"])
    assert per_slot == 48
    assert dep["max_slots"] * per_slot == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] == cfg["max_position_embeddings"]
    assert dep["max_slots"] * tr["clients_per_slot"] == 128
    assert dep["batch_wait_timeout_s"] == 0.25
    assert cfg["parity"]["prompt_len"] + cfg["parity"]["new_tokens"] <= \
        cfg["max_position_embeddings"]


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-phi4flash.reason-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_phi_rehearsal_cell_runs(trace):
    """The toy cell borrows phi4-mini-flash.reason-sat's metric lists:
    correct against the plain reference through the served path, no
    program built in the window; at ``--trace 2`` the counter metrics
    are there, ``shared_kv_read_ratio`` among them (the block loop's on
    a CPU); the device_trace metrics need a device in the trace, which a
    CPU has not (the hand-made run above checks their readers)."""
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
        "toy-phi4flash.reason-sat")["reports"]
    assert {"state_peak_share", "sliding_resident_share",
            "state_kv_bytes_ratio", "shared_kv_read_ratio"} <= set(reports)
    for name in reports:
        assert name in line["metrics"], name
    assert 0.0 < line["metrics"]["state_peak_share"]["value"] <= 100.0
    # two layers read the toy's pages, a block of them at a time
    assert line["metrics"]["shared_kv_read_ratio"]["value"] >= 2.0
    for name in NEW_READERS[:-1]:
        assert name not in line["metrics"]
