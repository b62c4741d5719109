"""The A.X-K1 family (families/axk1.py, reference/axk1.py,
configs/a.x-k1-d5-ep16.json, the toy ``rehearsal/toy-axk1.json``,
traffic/longdoc-sat.json) on the CPU: the configuration against its
published copy, the program's config the family builds, the served
model against the plain reference at the toy's sizes (a SHARE: 4 of 16
experts held, from expert 4), the seeded weights, the near-tie rule,
the byte counts against hand counts, the three new readers on a
hand-made trace and hand-made rounds, the traffic mix, and the
rehearsal cell end to end."""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, trace_parts, trafficgen, weights

CONFIG = "a.x-k1-d5-ep16"
CELL = "axk1-d5.longdoc-sat"
# what a chip's share of a stated deployment may cut (model-configs,
# section 4), beside depth and the page table's width
REDUCIBLE = {"num_hidden_layers", "max_position_embeddings",
             "n_routed_experts", "vocab_size"}


@pytest.fixture(scope="module")
def axk1_toy():
    cfg = common.load_json("rehearsal", "toy-axk1.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def axk1_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_axk1_file_holds_the_published_sizes_but_for_reduced(axk1_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; no width is reduced."""
    cfg, _fam = axk1_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 33 and source["model_type"] == "axk1"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
            assert key in REDUCIBLE and key in cfg["why_reduced"], key
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == REDUCIBLE
    # the floors: the leading dense layer and four after it, 8 experts,
    # an eighth of the vocabulary; the router keeps its width
    assert cfg["num_hidden_layers"] == cfg["first_k_dense_replace"] + 4
    assert cfg["n_routed_experts"] == 12 >= 8
    assert cfg["router_width"] == source["n_routed_experts"] == 192
    assert cfg["vocab_size"] * 8 == source["vocab_size"]
    assert "16-chip" in cfg["stands_for"]
    assert "SIXTEENTH" in cfg["stands_for"]
    for key in ("attention", "yarn", "rope_pairing", "router",
                "n_group_topk_group", "routers_not_balanced", "weights",
                "near_ties"):
        assert key in cfg["assumed"], key
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]


def test_the_axk1_program_config_is_the_published_model_cut_to_the_share(
        axk1_real):
    import dataclasses
    from ray_tpu.models.axk1 import axk1, axk1_param_count
    cfg, fam = axk1_real
    want = axk1(n_layers=5, vocab_size=20480, max_seq_len=16384,
                experts_held=(0, 12), param_dtype=jnp.bfloat16)
    pcfg = fam.program_config(cfg)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)
    # ISSUE 34's arithmetic: 3.491 B parameters = 6.98 GB in bf16
    n = axk1_param_count(pcfg, experts=12)
    assert round(n / 1e9, 3) == 3.491 and round(2 * n / 1e9, 2) == 6.98
    shapes = weights.param_shapes(fam.model(pcfg))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == n
    assert shapes["layers_0"]["feed_forward"]["w1"]["kernel"].shape == \
        (7168, 18432)
    assert shapes["layers_1"]["moe"]["w1"].shape == (12, 7168, 2048)
    assert shapes["layers_1"]["moe"]["router"].shape == (7168, 192)
    assert shapes["layers_1"]["attention"]["wkv_b"].shape == (512, 16384)


def test_a_program_that_cannot_express_axk1_is_refused(axk1_toy,
                                                       monkeypatch):
    """The parent has no ray_tpu.models.axk1, and a program whose
    config lacks a field the model needs is no better: the family exits
    before a weight is made (this is how the parent commit fails on the
    new cell, cleanly and at once)."""
    import dataclasses
    import ray_tpu.models.axk1 as ax
    cfg, fam, *_ = axk1_toy

    @dataclasses.dataclass(frozen=True)
    class Lesser:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(ax, "AXK1Config", Lesser)
    with pytest.raises(SystemExit, match="cannot express A.X-K1"):
        fam.program_config(cfg)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "ray_tpu.models.axk1", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.axk1"):
        fam.program_config(cfg)


def test_what_the_program_lacks_of_axk1_is_refused(axk1_toy):
    cfg, fam, *_ = axk1_toy
    for wrong in ({"attention_bias": True}, {"tie_word_embeddings": True},
                  {"scoring_func": "softmax"}, {"topk_method": "noaux_tc"},
                  {"topk_method": "group_limited_greedy"},
                  {"moe_layer_freq": 2}, {"num_key_value_heads": 1},
                  {"rope_scaling": {**cfg["rope_scaling"],
                                    "type": "linear"}}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


# ---------------------------------------------- program against reference

def test_the_axk1_reference_matches_the_served_model(axk1_toy):
    """Float32 both sides, full forward logits, the SAME SHARE both
    sides (experts 4-7 of 16), 150 positions (past the toy's 64
    original positions): rtol 1e-4 (tests/test_axk1.py says why). A
    reference handed another share, 2 experts a token, gates that are
    not renormalised, another scaling factor or an unroped key is far
    outside."""
    _cfg, fam, pcfg, model, params = axk1_toy
    assert pcfg.experts_held == (4, 4)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 150)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_forward(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=2e-5)
    from benchmarks.reference import axk1 as ref
    scale = float(np.abs(np.asarray(want)).max())
    sizes = fam._sizes(pcfg)
    for wrong in (dict(lo=0), dict(top_k=2), dict(norm_topk=False),
                  dict(scaling=1.0), dict(unroped_key=True)):
        out = ref.forward(rw, ids, **{**sizes, **wrong})
        gap = float(np.abs(out - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (wrong, gap, scale)


def test_the_axk1_reference_imports_nothing_of_the_program():
    with open(os.path.join(common.HERE, "reference", "axk1.py")) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    import benchmarks.reference.axk1 as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


def test_yarn_frequencies_by_hand(axk1_real):
    """A.X-K1's 32 frequencies: the original ones up to dimension 10,
    those over 32 from 23 on, a ramp of 13 steps between; and the
    program's own table agrees with the reference's."""
    from benchmarks.reference import axk1 as ref
    from ray_tpu.models import axk1 as prog
    got = np.asarray(ref.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0))
    orig = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], orig[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], orig[23:] / 32, rtol=1e-6)
    mid = got[11:23] / orig[11:23]
    assert (np.diff(mid) < 0).all() and 1 / 32 < mid.min() < mid.max() < 1
    np.testing.assert_allclose(
        np.asarray(prog.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)),
        got, rtol=1e-6)
    assert abs(ref.yarn_mscale(32.0, 1.0) - 1.3466) < 1e-4
    cfg, fam = axk1_real
    assert abs(fam.program_config(cfg).softmax_scale - 0.1309) < 1e-4


# ------------------------------------------------------ seeded weights

def test_the_weights_are_the_seeds_alone(axk1_toy):
    """The same seed gives the same bits twice, another seed other
    weights; the embedding at 1.0, the router and head at 0.02, every
    matrix by its fan-in, every norm's scale one; and the family makes
    no weight through the program under test."""
    _cfg, fam, pcfg, model, params = axk1_toy
    shapes = weights.param_shapes(model)
    again = fam.init_params(shapes, 2**31 + 7)
    other = fam.init_params(shapes, 2**31 + 8)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        params, again)
    assert all(jax.tree_util.tree_leaves(same))
    p, o = params["params"], other["params"]
    assert not np.array_equal(np.asarray(p["layers_1"]["moe"]["router"]),
                              np.asarray(o["layers_1"]["moe"]["router"]))
    std = lambda a: float(np.asarray(a, np.float32).std())
    assert abs(std(p["tok_embeddings"]) - 1.0) < 0.05
    assert abs(std(p["layers_1"]["moe"]["router"]) - 0.02) < 0.003
    assert abs(std(p["layers_1"]["moe"]["w1"]) - 64 ** -0.5) < 0.01
    assert abs(std(p["layers_1"]["attention"]["wkv_b"]) - 16 ** -0.5) < 0.02
    assert (np.asarray(p["layers_0"]["attention"]["kv_norm"]["scale"])
            == 1).all()
    assert "router_bias" not in p["layers_1"]["moe"]


# ---------------------------------------------------------- near ties

def test_axk1_near_ties_of_the_choice_are_not_scored(axk1_toy):
    """``reference_logits`` hands the margin rule a row of zeros at
    every position whose choice of held experts a relative error of
    the hidden state under ``NEAR_TIE`` changes in some mixture layer
    (the dense layer has no choice: its margin is infinite), and the
    reference's own logits everywhere else."""
    from benchmarks import parity
    from benchmarks.reference import axk1 as ref
    _cfg, fam, pcfg, _model, params = axk1_toy
    rw = fam.reference_weights(params, pcfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        1, 255, size=(4, 96)), jnp.int32)
    logits, margin = fam.reference_forward(rw, ids, pcfg, margins=True)
    scored = fam.reference_logits(rw, ids, pcfg)
    unsure = margin < fam.NEAR_TIE
    assert 0 < unsure.sum() < unsure.size and np.isfinite(margin).all()
    assert (scored[unsure] == 0).all()
    assert (scored[~unsure] == logits[~unsure]).all()
    P = 64
    ok = parity.margin_rule(
        scored, np.concatenate([np.asarray(ids)[:, :P],
                                scored[:, P - 1:-1].argmax(-1)], 1), P)
    assert ok["ok"] and ok["decisive"] <= int((~unsure[:, P - 1:-1]).sum())
    # by hand: 6 candidates, 2 chosen, experts 2-3 held, no bias
    h = jnp.eye(6)[None, :1]                        # picks row 0 of W_r
    s = jnp.asarray([.9, .8, .75, .6, .5, .4])
    w = {"router": jnp.zeros((6, 6)).at[0].set(jnp.log(s / (1 - s))),
         "w_gate": jnp.zeros((2, 6, 3))}
    # the boundary lies between .8 and .75, at .775; held expert 2
    # (s .75) is .025 from it and an error of h reaches
    # s (1 - s) |W_2| |h| / sqrt(6) = .1875 x 1.0986 / 2.449
    got = float(ref.choice_margin(h, w, top_k=2, lo=2)[0, 0])
    want = 0.025 / (0.1875 * np.log(3.0) / np.sqrt(6.0))
    assert abs(got - want) < 1e-4 * want
    # a dense layer scores no choice
    x = jnp.ones((1, 3, 6))
    dense = {"ffn_norm": jnp.ones((6,)), "w_gate": jnp.ones((6, 4)),
             "w_up": jnp.ones((6, 4)), "w_down": jnp.ones((4, 6))}
    _y, m = ref.feed_forward(x, dense, eps=1e-6, top_k=2, lo=0,
                             norm_topk=True, scaling=1.0)
    assert np.isinf(np.asarray(m)).all()


# ---------------------------------------------------------- byte counts

def test_axk1_byte_counts_by_hand(axk1_real, axk1_toy):
    cfg, fam = axk1_real
    assert fam.n_moe_layers(cfg) == 4
    # a token's latent entry: (512 + 64) x 2 B a layer, five layers
    assert fam.latent_entry_bytes(cfg) == 1152
    assert fam.kv_bytes_per_token(cfg) == 5760
    assert fam.expert_bytes(cfg) == 3 * 7168 * 2048 * 2 == 88_080_384
    # 101.12 M parameters of attention a layer, less the two norms
    assert round(fam.mla_weight_bytes(cfg) / 2e6, 2) == 101.12
    # 8 riders at 8,704 tokens: bytes bound the absorbed attention
    tokens = 8 * 8704.0
    assert fam.latent_step_bytes(cfg, tokens) == tokens * 1152
    assert fam.latent_step_flops(cfg, tokens) == \
        2 * 64 * (576 + 512) * tokens
    assert fam.latent_step_bytes(cfg, tokens) / 819e9 > \
        fam.latent_step_flops(cfg, tokens) / 197e12
    # the mixture's counts are means over ALL five layers, of which
    # four are mixtures (the readers divide the time by five)
    assert fam.experts_step_bytes(cfg, 3.5, 5.0) == pytest.approx(
        0.8 * (3.5 * 88_080_384 + 2 * 5 * 7168 * 2))
    assert fam.experts_step_flops(cfg, 5.0) == pytest.approx(
        0.8 * 2 * 3 * 5 * 7168 * 2048)
    # ISSUE 34's decode step: ~3.7 GB of weights at 3.5 experts touched
    step = fam.decode_step_bytes(cfg, 0.0, 0, experts_touched=3.5)
    assert round(step / 1e9, 1) == 3.7
    # and 32 rows' contexts of 8,704 tokens: 1.6 GB of latent entries
    full = fam.decode_step_bytes(cfg, 32 * 8704.0, 32, experts_touched=3.5)
    assert round((full - step) / 1e9, 1) == 1.6
    assert fam.decode_step_bytes(cfg, 0.0, 32) > \
        fam.decode_step_bytes(cfg, 0.0, 32, experts_touched=11.0)
    tcfg, *_ = axk1_toy
    assert fam.n_moe_layers(tcfg) == 2
    assert fam.kv_bytes_per_token(tcfg) == 3 * (16 + 16) * 2


def test_the_latent_scopes_are_parts_of_their_own(axk1_real):
    _cfg, fam = axk1_real
    base = "jit(decode)/while/body/AXK1/layers_1/"
    for scope in fam.MLA_SCOPES:
        assert trace_parts.part_of(
            base + f"attention/{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(base + "attention/mla_q/wq_b/dot_general:",
                               fam.parts) == "mla_q"
    assert trace_parts.part_of(base + "attention/wo/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(base + "attention/kv_gather/gather:",
                               fam.parts) == "kv_gather"
    dense = "jit(prefill)/AXK1/layers_0/"
    assert trace_parts.part_of(dense + "feed_forward/w1/dot_general:",
                               fam.parts) == "mlp"
    for scope in fam.MOE_SCOPES + ("moe_shared",):
        assert trace_parts.part_of(base + f"moe/{scope}/dot_general:",
                                   fam.parts) == scope
    assert set(fam.LATENT_WINDOW_SCOPES) < set(fam.LATENT_ATTN_SCOPES) == \
        set(fam.parts["attention"])


# ------------------------------------------------ the three new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.1, "overlap": True,
            "decode_riders": 8, "decode_steps": 8,
            "decode_window_tokens": 8704}
    base.update(data)
    return (0, t, "round", None, None, base)


def _traced_run(axk1_real, tmp_path):
    """A hand-made --trace 2 run: one jit_decode run of 4 steps and one
    jit_prefill run whose operations are named by scope; rounds of 8
    riders under a window of 8,704 tokens (6 riders in the traced
    seconds)."""
    cfg, fam = axk1_real
    dec = "jit(decode)/while/body/AXK1/layers_1/"
    body = [("attention/mla_q/wq_b/dot_general:", 100),
            ("attention/mla_kv/wkv_a/dot_general:", 50),
            ("attention/mla_absorb/dot_general:", 150),
            ("attention/kv_append/scatter:", 20),
            ("attention/kv_gather/gather:", 3000),
            ("attention/attn_scores/dot_general:", 1200),
            ("attention/attn_pv/dot_general:", 800),
            ("attention/wo/dot_general:", 300),
            ("moe/moe_experts/custom-call:", 900)]
    ops, t = [], 0
    for _step in range(4):
        for i, (scope, dur) in enumerate(body):
            ops.append([f"%f.{i} = f32[8] fusion(", t, dur, dec + scope])
            t += dur
        # the window loop's second block in each of two layers: an
        # operation run more often than a step
        for _block in range(2):
            ops.append(["%g = f32[8] fusion(", t, 0,
                        dec + "attention/kv_gather/gather:"])
        ops.append(["%h = f32[8] fusion(", t, 40,
                    "jit(decode)/while/body/AXK1/head/dot_general:"])
        t += 40
    t_decode = t
    pre = "jit(prefill)/AXK1/layers_1/"
    t += 1000
    p0 = t
    for scope, dur in (("attention/mla_q/wq_b/dot_general:", 2000),
                       ("attention/attn_scores/dot_general:", 5000),
                       ("attention/wo/dot_general:", 1000),
                       ("moe/moe_shared/dot_general:", 2000)):
        ops.append(["%p = f32[8] fusion(", t, dur, pre + scope])
        t += dur
    ir = {"modules": [["jit_decode(1)", 0, t_decode],
                      ["jit_prefill(2)", p0, t - p0]], "ops": ops}
    module_ops = {f"f.{i}": [4, 4 * dur / 1e9, "fusion"]
                  for i, (_s, dur) in enumerate(body)}
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=[_round(1.0), _round(2.0),
                _round(11.0, decode_riders=6)],
        trace={"modules": {"jit_decode": {"runs": 1,
                                          "seconds": t_decode / 1e9}},
               "module_ops": {"jit_decode": module_ops}})
    run._trace_parts = {"ir": ir}
    return run


def test_the_three_latent_readers_on_a_hand_made_run(axk1_real, tmp_path):
    run = _traced_run(axk1_real, tmp_path)
    read = common.load_metric_reader
    # a step holds 100 + 50 + 150 + 20 + 3000 + 1200 + 800 ns under
    # the seven scopes (not the output projection's 300)
    assert read("decode_latent_attn_ms")(run) == pytest.approx(5320e-6)
    # the block loop took 5000 ns a step = 1000 ns a layer-step of the
    # five layers; the traced seconds' 6 riders under a window of 8,704
    # tokens must move 6 x 8,704 x 1,152 B (bytes bound it)
    least_s = 6 * 8704 * 1152 / 819e9
    assert read("latent_attn_roofline")(run) == pytest.approx(
        100.0 * least_s / 1000e-9)
    # the prefill call: 7,000 of its 10,000 ns under the scopes
    assert read("prefill_attn_share")(run) == pytest.approx(70.0)


def test_the_latent_readers_find_nothing_where_there_is_nothing(
        axk1_real, tmp_path):
    """Another family, a program without the scopes (the parent, with
    this PR's readers laid over it), a run without a trace: None, never
    an error."""
    run = _traced_run(axk1_real, tmp_path)
    read = common.load_metric_reader
    names = ("decode_latent_attn_ms", "latent_attn_roofline",
             "prefill_attn_share")
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("olmoe", "serve")})
    for name in names:
        assert read(name)(other) is None, name
    bare = types.SimpleNamespace(**vars(run))
    del bare.trace_dir, bare._trace_parts
    for name in names:
        assert read(name)(bare) is None, name
    unnamed = _traced_run(axk1_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("mla_", "x_")
    for name in names:
        assert read(name)(unnamed) is None, name
    headless = _traced_run(axk1_real, tmp_path)
    for op in headless._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("/head/", "/tail/")
    for name in names[:2]:
        assert read(name)(headless) is None, name
    assert axk1_real[1].decode_steps_traced(run) == 4.0
    no_rounds = types.SimpleNamespace(**{**vars(run), "events": []})
    assert read("latent_attn_roofline")(no_rounds) is None


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_longdoc_sat():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-sat", 1)
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "moe_dispatch_share", "moe_experts_touched_mean",
        "moe_held_pair_share",
        "decode_latent_attn_ms", "latent_attn_roofline",
        "prefill_attn_share"}
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
    # decode_moe_ms and moe_experts_roofline divide by
    # trace_reduce.loop_steps, which counts this cell's window blocks
    # for decode steps (PERF.md section 7): the cell is on neither list
    assert "decode_moe_ms" not in per_layer
    tr = common.load_json("traffic", "longdoc-sat.json")
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prefix_cache"] is False and tr["shared_prefix_tokens"] == 0
    assert 15.0 <= tr["ramp_s"] <= 30.0
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {8192}
    assert {r.output_len for r in reqs} == {512}
    # ids come from the configuration's vocabulary slice
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 8192, cfg["vocab_size"])
    assert len(ids) == 8192 and 1 <= min(ids) and max(ids) < 20479
    # 32 chunks a prompt, a page table and a pool that hold 32 slots
    dep = cfg["deployment"]
    per_slot = -(-(8192 + 512) // dep["page_size"])
    assert dep["max_slots"] * per_slot == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
    # the parity prompt runs past the original positions and over nine
    # 512-token blocks of the window loop
    assert cfg["parity"] == {"prompts": 2, "prompt_len": 4400,
                             "new_tokens": 32}
    assert 4400 > cfg["rope_scaling"]["original_max_position_embeddings"]


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-axk1.longdoc-sat", "--seed",
         str(2**31 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_the_axk1_rehearsal_cell_runs():
    """The toy cell borrows axk1-d5.longdoc-sat's metric lists: correct
    against the plain reference through the served path (100 tokens of
    prompt in chunks, past the toy's original positions), no program
    built in the window, the counter metrics there; the device_trace
    metrics need a device in the trace, which a CPU has not (the
    hand-made run above checks their readers)."""
    line, stdout = _rehearse("2")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    share = line["metrics"]["moe_held_pair_share"]
    # 4 of 16 experts held: a quarter of the pairs under even routing
    assert share["unit"] == "%" and 15.0 <= share["value"] <= 35.0
    touched = line["metrics"]["moe_experts_touched_mean"]["value"]
    assert 0.0 < touched <= 4.0             # counted over HELD experts
    for name in common.load_rehearsal_cell(
            "toy-axk1.longdoc-sat")["reports"]:
        assert name in line["metrics"], name
    assert "latent_attn_roofline" not in line["metrics"]
    assert "[correct] axk1:" in stdout and "moe_pairs_routed" in stdout
