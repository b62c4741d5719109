"""The Olmo-Hybrid family (families/olmo_hybrid.py,
reference/olmo_hybrid.py, configs/olmo-hybrid-7b-d16.json, the toy
``rehearsal/toy-olmo-hybrid.json``, traffic/sample-sat.json) on the CPU:
the configuration against its published copy, the program's config the
family builds, the served model against the plain reference at the
toy's sizes and the reference's controls against the margin rule, the
byte counts against ISSUE 49's arithmetic (215.6 M, 185.8 M, 4,101 M,
27.4 MB, 61,440 B), the four new readers and the older ones the cell
joins on a hand-made joined trace, the cell and its mix, and the
rehearsal cell end to end at ``--trace 0`` and ``--trace 2``."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, parity, trace_parts, trafficgen, weights

CONFIG = "olmo-hybrid-7b-d16"
CELL = "olmo-hybrid-d16.sample-sat"
NEW_READERS = ("hybrid_step_roofline", "prefill_linear_attn_share",
               "state_kv_bytes_ratio", "kda_step_packed_roofline")
JOINED = ("host_gap_share", "kv_peak_share", "state_peak_share",
          "device_idle_share.serve", "decode_riders_mean",
          "round_host_ms", "prefill_rows_mean", "dispatch_prefill_call_ms",
          "dispatch_decode_step_ms", "dispatch_prefill_share",
          "decode_linear_attn_ms", "decode_full_attn_ms",
          "linear_state_roofline.by_kind")


@pytest.fixture(scope="module")
def olmo_toy():
    cfg = common.load_json("rehearsal", "toy-olmo-hybrid.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def olmo_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_olmo_file_holds_the_published_sizes_but_for_reduced(olmo_real):
    cfg, _fam = olmo_real
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "published", CONFIG + ".json")
    with open(path) as f:
        source = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers",
                              "max_position_embeddings"]
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
        else:
            assert cfg[key] == want, key
    # every width as published; four whole periods; layer_types whole
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["vocab_size"]) == (
        3840, 11008, 30, 30, 30, 96, 192, 100352)
    assert cfg["num_hidden_layers"] == 16 and len(cfg["layer_types"]) == 32
    assert cfg["layer_types"][:4] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert {"block_norms", "qk_norm", "nope", "gate", "short_conv",
            "l2norm_eps", "output_norm_and_gate", "state_dtype",
            "weights"} <= set(cfg["assumed"])
    assert "pipeline" in cfg["stands_for"]
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_olmo_program_config_is_the_published_model(olmo_real):
    cfg, fam = olmo_real
    pcfg = fam.program_config(cfg)
    assert (pcfg.dim, pcfg.n_layers, pcfg.n_heads, pcfg.n_kv_heads,
            pcfg.head_dim, pcfg.hidden_dim, pcfg.vocab_size,
            pcfg.max_seq_len) == (3840, 16, 30, 30, 128, 11008, 100352,
                                  1024)
    assert pcfg.recurrent_state_shape == (15, 96, 384)     # two heads a row
    assert pcfg.recurrent_conv_shape == (3, 11520)
    assert pcfg.layer_kinds.count("recurrent") == 12 == \
        fam.n_kda_layers(cfg)
    assert pcfg.layer_kinds.count("kv") == 4 == fam.n_full_layers(cfg)
    assert pcfg.linear_allow_neg_eigval and pcfg.norm_eps == 1e-6
    assert pcfg.dtype == jnp.bfloat16 and not pcfg.tie_word_embeddings
    assert not hasattr(pcfg, "serving_rules")


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("attention_bias", True), ("rope_parameters", {"rope_theta": 1e4}),
    ("linear_num_value_heads", 60), ("num_key_value_heads", 10),
    ("layer_types", ["sliding_attention"] * 32),
    ("layer_types", ["full_attention"] * 8)])
def test_what_the_program_lacks_of_olmo_is_refused(olmo_real, key, value):
    cfg, fam = olmo_real
    with pytest.raises(SystemExit, match="Olmo-Hybrid has no"):
        fam.program_config({**cfg, key: value})


def test_a_program_without_the_olmo_module_is_refused(olmo_real,
                                                      monkeypatch):
    cfg, fam = olmo_real
    monkeypatch.setitem(sys.modules, "ray_tpu.models.olmo_hybrid", None)
    with pytest.raises(SystemExit, match="cannot express Olmo-Hybrid"):
        fam.program_config(cfg)


# ------------------------------------------- weights and the reference

def test_the_olmo_seeded_weights_rule(olmo_toy):
    """Solar-Open2's rule of scales by the leaves' names: matrices at
    1/sqrt(fan_in), the convolution by its width, the embedding at 1,
    the head at 0.02, every norm's scale one, A = 0.5 n, b_dt around
    -4; the same seed the same bits, another seed others."""
    cfg, fam, pcfg, model, params = olmo_toy
    p = params["params"]
    lin, full = p["layers_0"]["attention"], p["layers_3"]["attention"]
    D = cfg["hidden_size"]
    for leaf, want in ((lin["wq"]["kernel"], D ** -0.5),
                       (lin["wz"]["kernel"], D ** -0.5),
                       (lin["wa"]["kernel"], D ** -0.5),
                       (lin["conv"], 0.5), (p["tok_embeddings"], 1.0),
                       (p["lm_head"], 0.02),
                       (full["wo"]["kernel"], D ** -0.5)):
        assert float(np.std(np.asarray(leaf))) == pytest.approx(
            want, rel=0.25)
    for scale in (lin["o_norm"]["scale"], full["q_norm"]["scale"],
                  p["layers_0"]["attention_post_norm"]["scale"],
                  p["norm"]["scale"]):
        assert (np.asarray(scale) == 1.0).all()
    assert lin["A_log"].shape == lin["dt_bias"].shape == (6,)
    assert -8.0 < float(np.mean(np.asarray(lin["dt_bias"]))) < 0.0
    again = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    other = fam.init_params(weights.param_shapes(model), 2**32 + 8)
    assert (np.asarray(again["params"]["lm_head"])
            == np.asarray(p["lm_head"])).all()
    assert not (np.asarray(other["params"]["lm_head"])
                == np.asarray(p["lm_head"])).all()


def test_the_olmo_reference_weights_round_trip(olmo_toy):
    """Every leaf of the program's tree reaches the reference under its
    name, none twice, none dropped."""
    import jax
    _cfg, fam, pcfg, _model, params = olmo_toy
    rw = fam.reference_weights(params, pcfg)
    assert len(rw["layers"]) == pcfg.n_layers
    ours = {id(leaf) for leaf in jax.tree_util.tree_leaves(params)}
    theirs = [id(leaf) for leaf in jax.tree_util.tree_leaves(rw)]
    assert len(theirs) == len(set(theirs)) == len(ours)
    assert set(theirs) == ours
    assert set(rw["layers"][0]) - set(rw["layers"][3]) == {
        "conv", "wa", "A_log", "dt_bias", "wb", "wz", "o_norm"}
    assert set(rw["layers"][3]) - set(rw["layers"][0]) == {"q_norm",
                                                           "k_norm"}


def test_the_olmo_reference_matches_the_served_model(olmo_toy):
    import jax
    cfg, fam, pcfg, model, params = olmo_toy
    ids = jnp.asarray(trafficgen.prompt_tokens(5, 1, 90, 256))[None]
    got, _ = jax.jit(model.apply)(params, ids)
    want = fam.reference_forward(fam.reference_weights(params, pcfg), ids,
                                 pcfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_the_olmo_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(common.HERE, "reference", "olmo_hybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
        elif isinstance(node, ast.Import):
            names += [a.name for a in node.names]
    assert names and not [n for n in names if n.startswith("ray_tpu")]


def _served_ids(olmo_toy, P=40, G=32):
    """Greedy tokens of the program's own cache-less forward pass,
    teacher-forced from seeded prompts (the engine's path is held to the
    same logits in tests/test_olmo_hybrid.py)."""
    import jax
    cfg, fam, pcfg, model, params = olmo_toy
    ids = np.asarray([trafficgen.prompt_tokens(9, i, P + G, 256)
                      for i in range(2)], np.int32)
    apply = jax.jit(model.apply)
    for t in range(P, P + G):
        logits, _ = apply(params, jnp.asarray(ids))
        ids[:, t] = np.asarray(logits[:, t - 1].argmax(-1))
    return ids


@pytest.mark.parametrize("control", [None, "no_qk_norm", "pre_norm",
                                     "beta_one", "mean_gate",
                                     "lower_precision"])
def test_the_margin_rule_refuses_each_control(olmo_toy, control):
    """The comparison that decides ``correct``, as the harness makes it
    (``reference_logits`` into ``parity.margin_rule``): true for the
    reference as it is, false with the q/k norm, the branch-output
    norms, the factor 2 on beta or the gate a head dropped from it, or
    computed in the precision below (float8)."""
    cfg, fam, pcfg, _model, params = olmo_toy
    P, G = cfg["parity"]["prompt_len"], cfg["parity"]["new_tokens"]
    ids = _served_ids(olmo_toy, P, G)
    rw = fam.reference_weights(params, pcfg)
    kw = {control: True} if control else {}
    logits = fam.reference_logits(rw, jnp.asarray(ids), pcfg, **kw)
    check = parity.margin_rule(logits, ids, P)
    assert check["ok"] is (control is None), check
    assert check["steps"] == 2 * G


def test_the_scored_tail_is_the_configurations_new_tokens(olmo_toy,
                                                          olmo_real):
    """``reference_logits`` fills the rows that predict the last
    ``SCORED_TAIL`` tokens and no other: the parity blocks of both
    configurations generate exactly that many."""
    cfg, fam, pcfg, _model, params = olmo_toy
    assert fam.SCORED_TAIL == cfg["parity"]["new_tokens"] == \
        olmo_real[0]["parity"]["new_tokens"]
    assert olmo_real[0]["parity"] == {"prompts": 2, "prompt_len": 320,
                                      "new_tokens": 32}
    ids = _served_ids(olmo_toy)
    rw = fam.reference_weights(params, pcfg)
    logits = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    whole = np.asarray(fam.reference_forward(rw, jnp.asarray(ids), pcfg))
    T, G = ids.shape[1], fam.SCORED_TAIL
    np.testing.assert_allclose(logits[:, T - 1 - G:T - 1],
                               whole[:, T - 1 - G:T - 1], rtol=1e-5,
                               atol=1e-6)
    assert not logits[:, :T - 1 - G].any() and not logits[:, T - 1:].any()


# ---------------------------------------------------------- byte counts

def test_olmo_byte_counts_by_hand(olmo_real):
    """ISSUE 49's arithmetic: a linear layer 215.6 M, a full one
    185.8 M, the cut 4,101 M = 8.20 GB; a slot's state 27.4 MB; a
    token's K/V 61,440 B."""
    cfg, fam = olmo_real
    assert fam.mixing_params(cfg, True) == (
        2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520)
    assert round(fam.mixing_params(cfg, True) / 1e4) == 8875
    assert round(fam.layer_params(cfg, True) / 1e5) == 2156
    assert round(fam.layer_params(cfg, False) / 1e5) == 1858
    assert round(fam.model_params(cfg) / 1e6) == 4101
    assert 8.19e9 < 2 * fam.model_params(cfg) < 8.21e9
    assert fam.state_bytes(cfg) == 30 * 96 * 192 * 4 == 2_211_840
    assert fam.conv_tail_bytes(cfg) == 11520 * 3 * 2 == 69_120
    assert fam.state_bytes_per_slot(cfg) == 12 * (2_211_840 + 69_120)
    assert round(fam.state_bytes_per_slot(cfg) / 1e5) == 274
    assert fam.kv_bytes_per_token(cfg) == 4 * 2 * 30 * 128 * 2 == 61_440
    assert fam.state_step_bytes(cfg, 96) == 96 * 2 * (2_211_840 + 69_120)
    # the packed kernel's own call: the riders' states alone, each way
    assert fam.step_kernel_bytes(cfg, 96) == 96 * 2 * 2_211_840
    assert fam.step_kernel_flops(cfg, 96) == 96 * 7 * 30 * 96 * 192
    assert (fam.step_kernel_flops(cfg, 96) / 197e12
            < fam.step_kernel_bytes(cfg, 96) / 819e9 / 100)
    # an empty batch: the 16 layers and the head once
    weights_ = 2 * (fam.model_params(cfg) - 100352 * 3840)
    assert fam.decode_step_bytes(cfg, 0, 0) == weights_
    assert 7.42e9 < weights_ < 7.44e9
    # 96 riders at a mean context of 384: weights 7.43 GB, state each
    # way 5.3 GB (the tails with it), K/V 2.3 GB
    full = fam.decode_step_bytes(cfg, 96 * 384, 96)
    assert full == weights_ + 96 * 3840 * 2 + (96 * 384 + 96) * 61_440 \
        + 12 * 96 * 2 * (2_211_840 + 69_120)
    assert 14.9e9 < full < 15.1e9
    # a context under 864 tokens keeps a period's state above its K/V
    assert 3 * 2_211_840 // (2 * 30 * 128 * 2) == 432
    # the program's own counts: the state as declared; the pool's page
    # 32 head rows a token where the arithmetic needs 30
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         state_bytes_per_slot)
    pcfg = fam.program_config(cfg)
    dep = cfg["deployment"]
    assert state_bytes_per_slot(pcfg) == fam.state_bytes_per_slot(cfg)
    page = kv_pool_page_bytes(pcfg, dep["page_size"])
    assert page == dep["page_size"] * fam.kv_bytes_per_token(cfg) * 32 // 30
    assert dep["n_pages"] == dep["max_slots"] * 8 + 1


def test_the_olmo_layers_parts(olmo_real):
    _cfg, fam = olmo_real
    base = "jit(decode)/while/body/OlmoHybrid/"
    lin, full = base + "layers_1/attention/", base + "layers_3/attention/"
    for scope in fam.KDA_SCOPES:
        assert trace_parts.part_of(lin + f"{scope}/mul:",
                                   fam.parts) == scope
    # the decay's and the gate's projections lie inside their scopes
    assert trace_parts.part_of(lin + "kda_gates/wa/dot_general:",
                               fam.parts) == "kda_gates"
    assert trace_parts.part_of(lin + "kda_out/wz/dot_general:",
                               fam.parts) == "kda_out"
    assert trace_parts.part_of(lin + "wq/dot_general:",
                               fam.parts) == "projections"
    for scope in fam.FULL_PARTS[:4]:
        assert trace_parts.part_of(
            full + f"attn_full/while/body/{scope}/dot_general:",
            fam.parts) == scope
    assert trace_parts.part_of(full + "attn_full/pad:",
                               fam.parts) == "attn_full"
    assert trace_parts.part_of(full + "q_norm/mul:",
                               fam.parts) == "qk_norm"
    assert trace_parts.part_of(full + "wo/dot_general:",
                               fam.parts) == "projections"
    layer = base + "layers_3/"
    for norm in ("attention_post_norm", "ffn_post_norm"):
        assert trace_parts.part_of(layer + norm + "/mul:",
                                   fam.parts) == "norms"
    assert trace_parts.part_of(layer + "feed_forward/w2/dot_general:",
                               fam.parts) == "mlp"
    assert trace_parts.part_of(base + "head/dot_general:",
                               fam.parts) == "head"


# ----------------------------------------------------------- the readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 90, "decode_steps": 2,
            "decode_window_tokens": 512, "decode_context_tokens": 90 * 380}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(olmo_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 90 and 94
    riders), a jit_prefill between them, and a THIRD jit_decode that the
    stop cut. A step: each of 12 linear layers 1.0 ms under
    kda_recurrence (0.8 of them the packed kernel's call), 0.1 under
    each of the three other scopes; each of 4
    full layers 0.3 ms of attention (0.02 append + 0.28 scores); 16
    layers of 0.5 ms of SwiGLU; the head 1.0 ms. The prefill call: 40
    ms of which 12 layers x 0.5 ms under the four scopes."""
    cfg, fam = olmo_real
    base = "jit(decode)/while/body/OlmoHybrid/"
    step = []
    for i in range(16):
        layer = f"{base}layers_{i}/"
        if i % 4 == 3:
            step += [(layer + "attention/attn_full/kv_append/scatter:",
                      20_000),
                     (layer + "attention/attn_full/attn_scores/custom:",
                      280_000)]
        else:
            step += [(layer + "attention/kda_recurrence/kda_step_packed:",
                      800_000),
                     (layer + "attention/kda_recurrence/mul:", 200_000)]
            step += [(layer + f"attention/{s}/mul:", 100_000)
                     for s in ("kda_conv", "kda_gates", "kda_out")]
        step.append((layer + "feed_forward/w2/dot_general:", 500_000))
    step.append((base + "head/dot_general:", 1_000_000))
    pre = "jit(prefill)/OlmoHybrid/"
    call = [(f"{pre}layers_{i}/attention/kda_recurrence/dot_general:",
             500_000) for i in range(16) if i % 4 != 3]
    call.append((pre + "head/dot_general:", 34_000_000))
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
              _round(12.0, round=12, decode_riders=94,
                     decode_context_tokens=94 * 390)]
    per_slot = fam.state_bytes_per_slot(cfg)
    samples = [{"t": 1.0 + i, "free_slots": 2 * i,
                "kv_bytes_in_use": (96 - 2 * i) * per_slot // (1 + i),
                "kv_bytes_total": 1 << 32, "queue_depth": 0}
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


def test_the_readers_on_a_hand_made_run(olmo_real, tmp_path):
    cfg, fam = olmo_real
    run = _joined_run(olmo_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(92.0)
    assert got["context_tokens"] == pytest.approx(
        (90 * 380 - 45 + 94 * 390 - 47) / 2)
    step_s = 12 * 1.3e-3 + 4 * 0.3e-3 + 16 * 0.5e-3 + 1.0e-3
    assert got["module_s"] == pytest.approx(4 * step_s)
    # the whole step against the family's bytes at the rounds' own
    # riders and contexts
    least = fam.decode_step_bytes(cfg, got["context_tokens"], 92.0) / 819e9
    assert read("hybrid_step_roofline")(run) == pytest.approx(
        100.0 * least / step_s)
    assert 50.0 < read("hybrid_step_roofline")(run) < 100.0
    # the state step alone: 1.0 ms a layer-step for 92 riders' bytes
    assert read("linear_state_roofline.by_kind")(run) == pytest.approx(
        100.0 * fam.state_step_bytes(cfg, 92.0) / 819e9 / 1.0e-3)
    # the kernel alone: 0.8 ms a layer-step for 92 riders' states
    assert got["kernel_s"] == pytest.approx(4 * 12 * 0.8e-3)
    assert read("kda_step_packed_roofline")(run) == pytest.approx(
        100.0 * 92.0 * 2 * 2211840 / 819e9 / 0.8e-3)
    assert read("kda_step_packed_roofline")(run) < 100.0
    assert read("decode_full_attn_ms")(run) == pytest.approx(4 * 0.3)
    assert fam.under(got, fam.KDA_SCOPES) == pytest.approx(
        4 * 12 * 1.3e-3)
    # 6 of the call's 40 ms under the delta rule's scopes
    assert read("prefill_linear_attn_share")(run) == pytest.approx(15.0)
    # slots x a slot's state over the pages in use: 1, 2, 3 -> median 2
    assert read("state_kv_bytes_ratio")(run) == pytest.approx(2.0,
                                                              rel=1e-6)
    assert read("state_peak_share")(run) == pytest.approx(100.0)


def test_the_new_readers_find_nothing_where_there_is_nothing(olmo_real,
                                                             tmp_path):
    """Another family, a join that was refused, a program without the
    trace or without the scopes, no peaks, no page in use: None, never
    an error (the parent of PR 49 cannot run the cell at all; a traced
    run of an OLDER cell under this PR's files must not trip on
    them)."""
    read = common.load_metric_reader
    run = _joined_run(olmo_real, tmp_path)
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("llama", "serve")})
    for name in NEW_READERS:
        assert read(name)(other) is None, name
    refused = _joined_run(olmo_real, tmp_path)
    refused._dispatch = None
    assert read("hybrid_step_roofline")(refused) is None
    no_trace = _joined_run(olmo_real, tmp_path)
    no_trace.trace_dir = None
    short = _joined_run(olmo_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    unnamed = _joined_run(olmo_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("kda_", "xyz_")
    for broken in (no_trace, short, unnamed):
        assert read("hybrid_step_roofline")(broken) is None
        assert read("kda_step_packed_roofline")(broken) is None
    # a program whose state steps without the kernel (the CPU, a mesh)
    no_kernel = _joined_run(olmo_real, tmp_path)
    for op in no_kernel._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("kda_step_packed", "mul")
    assert read("hybrid_step_roofline")(no_kernel) is not None
    assert read("kda_step_packed_roofline")(no_kernel) is None
    for broken in (no_trace, unnamed):
        assert read("prefill_linear_attn_share")(broken) is None
    no_peaks = _joined_run(olmo_real, tmp_path)
    no_peaks.peaks = None
    assert read("hybrid_step_roofline")(no_peaks) is None
    assert read("kda_step_packed_roofline")(no_peaks) is None
    assert read("prefill_linear_attn_share")(no_peaks) is not None
    empty = _joined_run(olmo_real, tmp_path)
    for s in empty.samples:
        s["kv_bytes_in_use"] = 0
    assert read("state_kv_bytes_ratio")(empty) is None
    training = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(training) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_sample_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "sample-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {*JOINED, *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-4:]) == NEW_READERS
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(common.load_metric_reader(m["name"]))
    # the whole step's share is the engine's count of steps and riders,
    # not trace_reduce.loop_steps x max_slots
    assert "decode_roofline" not in per_layer
    tr = common.load_json("traffic", "sample-sat.json")
    assert (tr["loop"], tr["clients_per_slot"], tr["prefix_cache"],
            tr["shared_prefix_tokens"], tr["population"]) == (
        "closed", 2, False, 0, 512)
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {256}
    assert {r.output_len for r in reqs} == {256}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 256, cfg["vocab_size"])
    assert len(ids) == 256 and 1 <= min(ids) and max(ids) < 100352
    # eight pages a slot hold a request; the page table holds it
    dep = cfg["deployment"]
    per_slot = -(-(256 + 256) // dep["page_size"])
    assert per_slot == 8
    assert dep["max_slots"] * per_slot == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
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
         "--workload", "toy-olmo-hybrid.sample-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_olmo_rehearsal_cell_runs(trace):
    """The toy cell borrows olmo-hybrid-d16.sample-sat's metric lists:
    correct against the plain reference through the served path, no
    program built in the window; at ``--trace 2`` the counter metrics
    are there, ``state_kv_bytes_ratio`` among them; the device_trace
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
        "toy-olmo-hybrid.sample-sat")["reports"]
    assert {"state_peak_share", "state_kv_bytes_ratio"} <= set(reports)
    for name in reports:
        assert name in line["metrics"], name
    assert 0.0 < line["metrics"]["state_peak_share"]["value"] <= 100.0
    assert line["metrics"]["state_kv_bytes_ratio"]["value"] > 0.0
    for name in ("hybrid_step_roofline", "prefill_linear_attn_share",
                 "kda_step_packed_roofline"):
        assert name not in line["metrics"]
