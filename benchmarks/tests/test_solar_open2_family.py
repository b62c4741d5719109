"""The Solar-Open2 family (families/solar_open2.py,
reference/solar_open2.py, configs/solar-open2-250b-d4-ep8.json, the toy
``rehearsal/toy-solar-open2.json``, traffic/doc-sat.json) on the CPU:
the configuration against its published copy, the program's config the
family builds, the served model against the plain reference at the
toy's sizes (a SHARE: 4 of 16 experts held, from expert 4), the byte
counts against hand counts, the three new readers on a hand-made trace
and hand-made rounds, the traffic mix, and the rehearsal cell end to
end."""
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

CONFIG = "solar-open2-250b-d4-ep8"
CELL = "solar-open2-d4.doc-sat"
# what a chip's share of a stated deployment may cut (model-configs,
# section 4), beside depth and the page table's width
REDUCIBLE = {"num_hidden_layers", "max_position_embeddings",
             "n_routed_experts", "vocab_size"}


@pytest.fixture(scope="module")
def solar_toy():
    cfg = common.load_json("rehearsal", "toy-solar-open2.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def solar_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_file_holds_the_published_sizes_but_for_reduced(solar_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; no width is reduced."""
    cfg, _fam = solar_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 27 and source["model_type"] == "solar_open2"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
            assert key in REDUCIBLE and key in cfg["why_reduced"], key
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == REDUCIBLE
    # the floors: a whole period and four layers, 8 experts, an eighth
    # of the vocabulary; the router keeps its width
    assert cfg["num_hidden_layers"] == cfg["gqa_interval"] + 1 == 4
    assert cfg["n_routed_experts"] == 40 >= 8
    assert cfg["router_width"] == source["n_routed_experts"] == 320
    assert cfg["vocab_size"] * 8 == source["vocab_size"]
    assert "8-chip" in cfg["stands_for"]
    assert "EIGHTH" in cfg["stands_for"]
    for key in ("router", "kda_low_rank", "kda_heads", "l2norm_eps",
                "gqa_gate", "state_dtype", "weights"):
        assert key in cfg["assumed"], key
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]


def test_the_program_config_is_the_published_model_cut_to_the_share(
        solar_real):
    import dataclasses
    from ray_tpu.models.solar_open2 import (solar_open2_250b,
                                            solar_open2_param_count)
    cfg, fam = solar_real
    want = solar_open2_250b(n_layers=4, vocab_size=24576,
                            max_seq_len=4096, experts_held=(0, 40),
                            param_dtype=jnp.bfloat16)
    pcfg = fam.program_config(cfg)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)
    # ISSUE 32's arithmetic: 3.308 B parameters = 6.62 GB in bf16
    n = solar_open2_param_count(pcfg, experts=40)
    assert round(n / 1e9, 3) == 3.308 and round(2 * n / 1e9, 2) == 6.62
    shapes = weights.param_shapes(fam.model(pcfg))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == n
    assert shapes["layers_1"]["moe"]["w1"].shape == (40, 4096, 1280)
    assert shapes["layers_1"]["moe"]["router"].shape == (4096, 320)


def test_a_program_that_cannot_express_it_is_refused(solar_toy,
                                                     monkeypatch):
    """The parent has no ray_tpu.models.solar_open2, and a program
    whose config lacks a field the model needs is no better: the family
    exits before a weight is made (this is how the parent commit fails
    on the new cell, cleanly and at once)."""
    import dataclasses
    import ray_tpu.models.solar_open2 as so
    cfg, fam, *_ = solar_toy

    @dataclasses.dataclass(frozen=True)
    class Lesser:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(so, "SolarOpen2Config", Lesser)
    with pytest.raises(SystemExit, match="cannot express Solar-Open2"):
        fam.program_config(cfg)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "ray_tpu.models.solar_open2", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.solar_open2"):
        fam.program_config(cfg)


def test_what_the_program_lacks_of_solar_open2_is_refused(solar_toy):
    cfg, fam, *_ = solar_toy
    for wrong in ({"use_rope": True}, {"first_k_dense_replace": 1},
                  {"kda_use_full_proj": True}, {"use_gqa_gate": False},
                  {"kda_allow_neg_eigval": False},
                  {"tie_word_embeddings": True}, {"gqa_layers": [0, 3]}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


# ---------------------------------------------- program against reference

def test_the_reference_matches_the_served_model(solar_toy):
    """Float32 both sides, full forward logits, the SAME SHARE both
    sides (experts 4-7 of 16): the two differ only in the order of their
    sums, rtol 1e-4 (tests/test_solar_open2.py says why). A reference
    handed another share, 2 experts a token or gates that are not
    renormalised is far outside."""
    _cfg, fam, pcfg, model, params = solar_toy
    assert pcfg.experts_held == (4, 4)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 70)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_forward(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 70, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)
    from benchmarks.reference import solar_open2 as ref
    scale = float(np.abs(np.asarray(want)).max())
    sizes = dict(n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
                 eps=pcfg.norm_eps)
    for wrong in (dict(top_k=4, lo=0), dict(top_k=2, lo=4),
                  dict(top_k=4, lo=4, norm_topk=False)):
        out = ref.forward(rw, ids, **sizes, **wrong)
        gap = float(np.abs(np.asarray(out) - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (wrong, gap, scale)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(common.HERE, "reference",
                           "solar_open2.py")) as f:
        assert "ray_tpu" not in f.read()
    import benchmarks.reference.solar_open2 as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


def test_the_seeded_decays_span_what_the_file_says(solar_real):
    """A = 0.5 n and b_dt = -4 + 1.5 n: with the decay projection's own
    spread of about 1, most channels' step decay exp(g) lies in
    0.5-0.999 and a few decay hard."""
    cfg, fam = solar_real
    rng = np.random.default_rng(0)
    a, b, f = (rng.standard_normal(200_000) for _ in range(3))
    g = -np.exp(0.5 * a) * np.log1p(np.exp(f + 1.5 * b - 4.0))
    decay = np.exp(g)
    assert np.mean((decay > 0.5) & (decay < 0.9995)) > 0.8
    assert 1e-4 < np.mean(decay < 0.1) < 0.05
    assert "0.5-0.999" in cfg["assumed"]["weights"]
    shapes = {"params": {"attention": {
        "A_log": jax.ShapeDtypeStruct((64,), jnp.float32),
        "dt_bias": jax.ShapeDtypeStruct((4096,), jnp.float32),
        "o_norm": {"scale": jax.ShapeDtypeStruct((8,), jnp.float32)}}}}
    p = fam.seeded(shapes, 5)["params"]["attention"]
    assert abs(float(p["dt_bias"].mean()) + 4.0) < 0.1
    assert abs(float(p["dt_bias"].std()) - 1.5) < 0.1
    assert abs(float(p["A_log"].std()) - 0.5) < 0.15
    assert (np.asarray(p["o_norm"]["scale"]) == 1.0).all()


def test_the_routers_are_balanced_at_set_up(solar_toy):
    """``init_params`` is ``seeded`` then ``balanced``: the choice
    biases, and nothing else, are moved until the experts of the
    router's whole width are chosen about equally often on seeded
    tokens. The seeded biases give the loads of a random router:
    uneven. The served model's own routing (float32 here) shows it,
    though the balance was fitted on the plain reference's."""
    from ray_tpu.models.mixtral import MOE_STATS
    _cfg, fam, pcfg, model, params = solar_toy
    plain = fam.seeded(weights.param_shapes(model), 2**31 + 7)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        1, 255, size=(64, 128)), jnp.int32)
    look = jax.jit(lambda p: model.apply(p, ids, mutable=[MOE_STATS])[1])

    def loads(p):
        topk = jax.tree_util.tree_leaves(look(p)[MOE_STATS])
        counts = np.stack([np.bincount(np.asarray(t).ravel(), minlength=16)
                           for t in topk])
        return counts / counts.mean(axis=1, keepdims=True)
    uneven, even = loads(plain), loads(params)
    assert uneven.std() > 0.2 and even.std() < 0.1
    assert even.min() > 0.7 and even.max() < 1.3
    # the held experts 4-7 see their even quarter of the routing
    assert abs(even[:, 4:8].mean() - 1.0) < 0.05
    same = jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        plain, params)
    moved = [jax.tree_util.keystr(k) for k, v in
             jax.tree_util.tree_flatten_with_path(same)[0] if not v]
    assert moved and all("router_bias" in m for m in moved)


def test_the_balance_is_the_references_and_the_seeds_alone(
        solar_toy, monkeypatch):
    """Both sides of a comparison must get the same bits from
    ``--seed``: ``balanced`` runs the plain reference on the seeded
    weights and nothing of the program under test (its forward pass
    raises here), and the shapes ``model(pcfg).init`` gives carry the
    config ``init_params`` needs for that."""
    import ray_tpu.models.solar_open2 as so
    _cfg, fam, pcfg, model, params = solar_toy
    shapes = weights.param_shapes(model)
    assert isinstance(shapes, fam.Variables) and shapes.pcfg == pcfg
    # a pytree like any dict, under the same key paths
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names == [jax.tree_util.keystr(k) for k, _ in
                     jax.tree_util.tree_flatten_with_path(dict(shapes))[0]]

    def refuse(*_a, **_k):
        raise AssertionError("the program's forward pass was called")
    monkeypatch.setattr(so.SolarOpen2, "apply", refuse)
    again = fam.init_params(shapes, 2**31 + 7)
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
    with open(fam.__file__) as f:
        assert "balance_bias_step" not in f.read()


def test_near_ties_of_the_choice_are_not_scored(solar_toy):
    """``reference_logits`` hands the margin rule a row of zeros at
    every position whose choice of held experts a relative error of
    the hidden state under ``NEAR_TIE`` changes in some layer, and the
    reference's own logits everywhere else; ``choice_margin`` is the
    least distance of a HELD expert's s + b from the midpoint of the
    k-th and next candidates, over what a unit of such an error moves
    it."""
    from benchmarks import parity
    from benchmarks.reference import solar_open2 as ref
    _cfg, fam, pcfg, _model, params = solar_toy
    rw = fam.reference_weights(params, pcfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        1, 255, size=(4, 96)), jnp.int32)
    logits, margin = (np.asarray(a) for a in fam.reference_forward(
        rw, ids, pcfg, margins=True))
    scored = np.asarray(fam.reference_logits(rw, ids, pcfg))
    unsure = margin < fam.NEAR_TIE
    assert 0 < unsure.sum() < unsure.size
    assert (scored[unsure] == 0).all()
    assert (scored[~unsure] == logits[~unsure]).all()
    # the rule neither fails such a position nor counts it decisive
    P = 64
    ok = parity.margin_rule(
        scored, np.concatenate([np.asarray(ids)[:, :P],
                                scored[:, P - 1:-1].argmax(-1)], 1), P)
    assert ok["ok"] and ok["decisive"] <= int((~unsure[:, P - 1:-1]).sum())
    # by hand: 6 candidates, 2 chosen, experts 2-3 held
    h = jnp.eye(6)[None, :1]                        # picks row 0 of W_r
    logit = jnp.log(jnp.asarray([.9, .8, .7, .6, .5, .4])
                    / (1 - jnp.asarray([.9, .8, .7, .6, .5, .4])))
    w = {"router": jnp.zeros((6, 6)).at[0].set(logit),
         "router_bias": jnp.asarray([0., 0., .05, 0., 0., 0.]),
         "w_gate": jnp.zeros((2, 6, 3))}
    # s + b = .9 .8 .75 .6 .5 .4: the boundary lies between .8 and .75,
    # at .775; held expert 2 (s .7) is .025 from it and an error of h
    # reaches s (1 - s) |W_2| |h| / sqrt(6) = .21 x .8473 / 2.449; held
    # expert 3 (s .6) is .175 away and far less within reach
    got = float(ref.choice_margin(h, w, top_k=2, lo=2)[0, 0])
    want = 0.025 / (0.21 * np.log(0.7 / 0.3) / np.sqrt(6.0))
    assert abs(got - want) < 1e-4 * want and 0.34 < want < 0.35


# ---------------------------------------------------------- byte counts

def test_solar_open2_byte_counts_by_hand(solar_real, solar_toy):
    cfg, fam = solar_real
    assert fam.n_kda_layers(cfg) == 3
    # ONE layer's K/V: 2 x 8 heads x 128 x 2 B (Mistral-d16's: 65,536)
    assert fam.kv_bytes_per_token(cfg) == 4096
    assert fam.expert_bytes(cfg) == 3 * 4096 * 1280 * 2 == 31_457_280
    # 64 heads x 128 x 128 float32 = 4 MiB; the tail 3 x 24,576 bf16
    assert fam.state_bytes(cfg) == 4 * 2**20
    assert fam.conv_tail_bytes(cfg) == 147_456
    assert fam.state_step_bytes(cfg, 28.5) == 28.5 * 2 * (4 * 2**20
                                                          + 147_456)
    # token mixing: 109.1 M (GQA) and 137.7 M (KDA) parameters
    assert round(fam.layer_weight_bytes(cfg, False) / 2e6, 1) == 109.1
    assert round(fam.layer_weight_bytes(cfg, True) / 2e6, 1) == 137.7
    assert fam.experts_step_bytes(cfg, 22.0, 28.0) == \
        22 * 31_457_280 + 2 * 28 * 4096 * 2
    assert fam.experts_step_flops(cfg, 28.0) == 2 * 3 * 28 * 4096 * 1280
    # ISSUE 32's 32-rider step at 1,150 tokens a slot and 22 experts
    # touched a layer: 5.1 GB, of which state 0.8 and experts 2.8
    step = fam.decode_step_bytes(cfg, 32 * 1150.0, 32,
                                 experts_touched=22.0)
    assert round(step / 1e9, 1) == 5.1
    assert round(3 * fam.state_step_bytes(cfg, 32) / 1e9, 1) == 0.8
    assert round(4 * 22 * fam.expert_bytes(cfg) / 1e9, 1) == 2.8
    # without a counter: the most 32 rows x 8 can touch, all 40 held
    assert fam.decode_step_bytes(cfg, 0.0, 32) > \
        fam.decode_step_bytes(cfg, 0.0, 32, experts_touched=39.0)
    tcfg, *_ = solar_toy
    assert fam.n_kda_layers(tcfg) == 6
    assert fam.kv_bytes_per_token(tcfg) == 2 * 2 * 16 * 2 * 2


def test_the_new_scopes_are_parts_of_their_own(solar_real):
    _cfg, fam = solar_real
    base = "jit(decode)/while/body/SolarOpen2/layers_1/"
    for scope in fam.KDA_SCOPES:
        assert trace_parts.part_of(
            base + f"attention/{scope}/mul:", fam.parts) == scope
    assert trace_parts.part_of(base + "attention/kda_gates/f_b/dot_general:",
                               fam.parts) == "kda_gates"
    assert trace_parts.part_of(base + "attention/wq/dot_general:",
                               fam.parts) == "projections"
    gqa = "jit(prefill)/SolarOpen2/layers_0/"
    assert trace_parts.part_of(gqa + "attention/attn_gate/w_gate/dot_general:",
                               fam.parts) == "attn_gate"
    assert trace_parts.part_of(gqa + "attention/kv_gather/gather:",
                               fam.parts) == "kv_gather"
    for scope in fam.MOE_SCOPES + ("moe_shared",):
        assert trace_parts.part_of(base + f"moe/{scope}/dot_general:",
                                   fam.parts) == scope
    assert trace_parts.part_of(base + "moe/convert:", fam.parts) == "moe"


# ------------------------------------------------ the three new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.1, "overlap": True,
            "decode_riders": 28, "decode_steps": 8}
    base.update(data)
    return (0, t, "round", None, None, base)


def _traced_run(solar_real, tmp_path):
    """A hand-made --trace 2 run: one jit_decode run of 4 steps whose
    operations are named by scope; rounds of 28 riders (24 in the
    traced seconds) whose counters say 1 pair in 8 landed here."""
    cfg, fam = solar_real
    dec = "jit(decode)/while/body/SolarOpen2/layers_1/"
    body = [("attention/kda_conv/add:", 10),
            ("attention/kda_gates/f_b/dot_general:", 20),
            ("attention/kda_recurrence/reduce:", 1200),
            ("attention/kda_out/mul:", 30),
            ("attention/wq/dot_general:", 300),
            ("moe/moe_experts/custom-call:", 900)]
    ops, t = [], 0
    for _step in range(4):
        for i, (scope, dur) in enumerate(body):
            ops.append([f"%f.{i} = f32[8] fusion(", t, dur, dec + scope])
            t += dur
    ir = {"modules": [["jit_decode(1)", 0, t]], "ops": ops}
    module_ops = {f"f.{i}": [4, 4 * dur / 1e9, "fusion"]
                  for i, (_s, dur) in enumerate(body)}
    counters = dict(moe_pairs=100, moe_pairs_routed=800)
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=[_round(1.0, **counters), _round(2.0, **counters),
                _round(11.0, decode_riders=24, **counters)],
        trace={"modules": {"jit_decode": {"runs": 1, "seconds": t / 1e9}},
               "module_ops": {"jit_decode": module_ops}})
    run._trace_parts = {"ir": ir}
    return run


def test_the_three_readers_on_a_hand_made_run(solar_real, tmp_path):
    cfg, fam = solar_real
    run = _traced_run(solar_real, tmp_path)
    read = common.load_metric_reader
    # a step holds 10 + 20 + 1200 + 30 ns under the four kda scopes
    assert read("decode_linear_attn_ms")(run) == pytest.approx(1260e-6)
    # the recurrence took 1200 ns a step = 400 ns a layer-step of the
    # three KDA layers; the traced seconds' 24 riders must move
    # 24 x 2 x (4 MiB + 147,456 B)
    least_s = 24 * 2 * (4 * 2**20 + 147_456) / 819e9
    assert read("linear_state_roofline")(run) == pytest.approx(
        100.0 * least_s / 400e-9)
    # the window's two rounds: 200 of 1,600 pairs
    assert read("moe_held_pair_share")(run) == pytest.approx(12.5)


def test_the_new_readers_find_nothing_where_there_is_nothing(solar_real,
                                                             tmp_path):
    """Another family, a program without the scopes or the counter (the
    parent, with this PR's readers laid over it), a run without a
    trace: None, never an error."""
    run = _traced_run(solar_real, tmp_path)
    read = common.load_metric_reader
    names = ("decode_linear_attn_ms", "linear_state_roofline",
             "moe_held_pair_share")
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("olmoe", "serve"),
        "events": [_round(1.0)]})
    for name in names:
        assert read(name)(other) is None, name
    bare = types.SimpleNamespace(**{**vars(run), "events": [_round(1.0)]})
    del bare.trace_dir, bare._trace_parts
    for name in names:
        assert read(name)(bare) is None, name
    unnamed = _traced_run(solar_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("kda_", "x_")
    for name in names[:2]:
        assert read(name)(unnamed) is None, name


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_doc_sat():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "doc-sat", 1)
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "decode_moe_ms", "moe_dispatch_share", "moe_experts_roofline",
        "moe_experts_touched_mean", "decode_linear_attn_ms",
        "linear_state_roofline", "moe_held_pair_share"}
    tr = common.load_json("traffic", "doc-sat.json")
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prefix_cache"] is False and tr["shared_prefix_tokens"] == 0
    assert tr["ramp_s"] == 10.0
    reqs = trafficgen.closed_population(tr)
    assert len(reqs) == 512
    assert {r.prompt_len for r in reqs} == {1024}
    assert {r.output_len for r in reqs} == {256}
    # ids come from the configuration's vocabulary slice
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 1024, cfg["vocab_size"])
    assert len(ids) == 1024 and 1 <= min(ids) and max(ids) < 24575
    # four chunks a prompt, and a deployment whose pages hold 32 slots
    dep = cfg["deployment"]
    assert dep["max_slots"] * -(-(1024 + 256) // dep["page_size"]) \
        <= dep["n_pages"] - 1
    assert cfg["parity"] == {"prompts": 2, "prompt_len": 320,
                             "new_tokens": 32}


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-solar-open2.doc-sat", "--seed",
         str(2**31 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_the_solar_open2_rehearsal_cell_runs():
    """The toy cell borrows solar-open2-d4.doc-sat's metric lists:
    correct against the plain reference through the served path (40
    tokens of prompt in chunks, the state carried between rounds), no
    program built in the window, the counter metrics there; the
    device_trace metrics need a device in the trace, which a CPU has
    not (the hand-made run above checks their readers)."""
    line, stdout = _rehearse("2")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    share = line["metrics"]["moe_held_pair_share"]
    # 4 of 16 experts held: a quarter of the pairs under even routing
    assert share["unit"] == "%" and 15.0 <= share["value"] <= 35.0
    touched = line["metrics"]["moe_experts_touched_mean"]["value"]
    assert 0.0 < touched <= 4.0             # counted over HELD experts
    for name in ("host_gap_share", "decode_riders_mean", "round_host_ms",
                 "kv_peak_share", "prefill_rows_mean"):
        assert name in line["metrics"], name
    assert "linear_state_roofline" not in line["metrics"]
    assert "state_slots" in stdout and "moe_pairs_routed" in stdout
