"""The Laguna family (families/laguna.py, reference/laguna.py,
configs/laguna-xs.2-d5.json, the toy ``rehearsal/toy-laguna.json``,
traffic/gen-sat.json as it stands) on the CPU: the configuration against
its published copy, the program's config the family builds, the served
model against the plain reference at the toy's sizes with every control,
the reference against a second, quadratic-mask form of itself, the
scored tail and the share of flipped positions, the byte and FLOP
counts against hand counts BY LAYER TYPE, the ring copies BY OPCODE,
the three new readers and the older ones on a hand-made joined trace,
the cell, and the rehearsal cell end to end at ``--trace 0`` and
``--trace 2``."""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, parity, trace_parts, trafficgen, weights

CONFIG = "laguna-xs.2-d5"
CELL = "laguna-xs2-d5.gen-sat"
REDUCIBLE = {"num_hidden_layers", "max_position_embeddings"}
NEW_READERS = ("swa_moe_step_roofline", "decode_attn_gate_ms",
               "moe_rows_per_expert_mean")
CONTROLS = ("no_gate", "heads_swapped", "rotate_whole_head", "one_theta",
            "window_511", "no_shared", "scale_one", "lower_precision")


@pytest.fixture(scope="module")
def laguna_toy():
    cfg = common.load_json("rehearsal", "toy-laguna.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def laguna_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


@pytest.fixture(scope="module")
def greedy(laguna_toy):
    """(ids [2, P + G], P): two seeded prompts of 40 and the model's own
    greedy continuation by ``SCORED_TAIL`` tokens (the cache-less
    forward pass over a padded row, one token a call: the engine's path
    is tests/test_laguna.py's)."""
    _cfg, fam, _pcfg, model, params = laguna_toy
    P, G = 40, fam.SCORED_TAIL
    ids = np.zeros((2, P + G), np.int32)
    ids[:, :P] = np.random.default_rng(3).integers(1, 255, size=(2, P))
    step = jax.jit(lambda p, i, t: jnp.argmax(
        jnp.take(model.apply(p, i)[0], t - 1, axis=1), -1))
    for t in range(P, P + G):
        ids[:, t] = np.asarray(step(params, jnp.asarray(ids), t))
    return ids, P


# ------------------------------------------------------ the configuration

def test_the_laguna_file_holds_the_published_sizes_but_for_reduced(
        laguna_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; depth and the page table's
    width alone are cut, and the nested groups are copied whole."""
    cfg, _fam = laguna_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 25 and source["model_type"] == "laguna"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
            assert key in REDUCIBLE and key in cfg["why_reduced"], key
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == REDUCIBLE
    # the dense layer and one whole period, every expert, the whole
    # vocabulary, every published width
    assert cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"][:5] == (
        ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"])
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(
        cfg["num_attention_heads_per_layer"]) == 40
    assert (cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["shared_expert_intermediate_size"],
            cfg["intermediate_size"], cfg["vocab_size"]) == (
        2048, 8, 128, 512, 256, 512, 8, 512, 8192, 100352)
    for key in ("gating", "router", "no_keys_for", "partial_rotary",
                "attention_factor", "window", "float32", "weights",
                "scored_tail", "flipped_share"):
        assert key in cfg["assumed"] and len(cfg["assumed"][key]) > 80, key
    assert "PIPELINE STAGE" in cfg["stands_for"]
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_laguna_program_config_is_the_published_model_cut_in_depth(
        laguna_real):
    import dataclasses
    from ray_tpu.models.kv_cache import (KIND_KV, KIND_SLIDING,
                                         kv_pool_page_bytes,
                                         kv_query_heads, sliding_ring_len,
                                         state_bytes_per_slot)
    from ray_tpu.models.laguna import laguna_param_count, laguna_xs2
    cfg, fam = laguna_real
    want = laguna_xs2(n_layers=5, max_seq_len=4096,
                      param_dtype=jnp.bfloat16)
    pcfg = fam.program_config(cfg)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)
    assert (kv_query_heads(pcfg, KIND_KV),
            kv_query_heads(pcfg, KIND_SLIDING)) == (48, 64)
    # ISSUE 53's arithmetic: 3.870 B parameters = 7.74 GB in bf16
    n = laguna_param_count(pcfg)
    assert round(n / 1e9, 3) == 3.870 and round(2 * n / 1e9, 2) == 7.74
    shapes = weights.param_shapes(fam.model(pcfg))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == n
    attn = [shapes[f"layers_{i}"]["attention"] for i in range(5)]
    assert attn[0]["wq"]["kernel"].shape == (2048, 6144)
    assert attn[1]["wq"]["kernel"].shape == (2048, 8192)
    assert attn[0]["wg"]["kernel"].shape == (2048, 48)
    assert attn[3]["wg"]["kernel"].shape == (2048, 64)
    assert attn[4]["wo"]["kernel"].shape == (6144, 2048)
    assert {a["wk"]["kernel"].shape for a in attn} == {(2048, 1024)}
    assert shapes["layers_0"]["feed_forward"]["w1"]["kernel"].shape == \
        (2048, 8192)
    assert shapes["layers_1"]["moe"]["w1"].shape == (256, 2048, 512)
    assert shapes["layers_4"]["moe"]["shared_w2"].shape == (512, 2048)
    assert shapes["layers_2"]["moe"]["router"].shape == (2048, 256)
    assert shapes["lm_head"].shape == (100352, 2048)
    # the deployment: 128 slots of 10.2 MB of rings, 4,609 pages of
    # 524,288 B: 1.31 GB and 2.42 GB beside the weights, 11.5 GB in all
    dep = cfg["deployment"]
    ring = sliding_ring_len(pcfg, dep["page_size"], 256)
    assert ring == 832 == fam.ring_len(cfg)
    per_slot = state_bytes_per_slot(pcfg, ring)
    assert per_slot == fam.sliding_bytes_per_slot(cfg) == 3 * 832 * 4096
    page = kv_pool_page_bytes(pcfg, dep["page_size"])
    assert page == 524288 == 64 * fam.kv_bytes_per_token(cfg)
    state, pool = dep["max_slots"] * per_slot, dep["n_pages"] * page
    assert round(state / 1e9, 2) == 1.31 and round(pool / 1e9, 2) == 2.42
    assert round((2 * n + state + pool) / 1e9, 1) == 11.5


def test_a_program_that_cannot_express_laguna_is_refused(laguna_toy,
                                                         monkeypatch):
    """The parent has no ray_tpu.models.laguna, and a program whose
    config lacks a field the model needs is no better: the family exits
    before a weight is made (this is how the parent commit fails on the
    new cell, cleanly and at once)."""
    import dataclasses
    import ray_tpu.models.laguna as lm
    cfg, fam, *_ = laguna_toy

    @dataclasses.dataclass(frozen=True)
    class Lesser:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(lm, "LagunaConfig", Lesser)
    with pytest.raises(SystemExit, match="cannot express Laguna"):
        fam.program_config(cfg)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "ray_tpu.models.laguna", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.laguna"):
        fam.program_config(cfg)


def test_what_the_program_lacks_of_laguna_is_refused(laguna_toy):
    cfg, fam, *_ = laguna_toy
    rp = cfg["rope_parameters"]
    for wrong in ({"attention_bias": True}, {"tie_word_embeddings": True},
                  {"gating": False}, {"gating": "per-channel"},
                  {"moe_apply_router_weight_on_input": True},
                  {"shared_expert_intermediate_size": 30},
                  {"layer_types": cfg["layer_types"][:4]},
                  {"num_attention_heads": 6},
                  {"partial_rotary_factor": 1.0},
                  {"rope_parameters": {**rp, "full_attention": {
                      **rp["full_attention"], "rope_type": "default"}}},
                  {"rope_parameters": {**rp, "sliding_attention": {
                      **rp["sliding_attention"], "rope_type": "yarn"}}}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


# ---------------------------------------------- program against reference

def test_the_laguna_reference_matches_the_served_model(laguna_toy):
    """Float32 both sides, full forward logits, 150 positions: rtol
    1e-4 (tests/test_laguna.py says why). The reference under any of
    its eight controls is far outside."""
    _cfg, fam, pcfg, model, params = laguna_toy
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 150)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_forward(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=2e-5)
    scale = float(np.abs(np.asarray(want)).max())
    assert tuple(fam.CONTROLS) == CONTROLS
    for control in CONTROLS:
        out = fam.reference_forward(rw, ids, pcfg, **{control: True})
        gap = float(np.abs(out - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (control, gap, scale)


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_shows_in_the_comparison_that_decides_correct(
        laguna_toy, greedy, control, capsys):
    """The comparison that decides ``correct``, as the harness makes it:
    the model's own greedy continuation of two prompts, teacher-forced
    through ``reference_logits`` and ``parity.margin_rule``. In float32
    nothing flips: against the plain reference NO position lies past
    the tolerance; against the reference under a control at least a
    tenth do, and the verdict is NOT correct wherever they are more
    than ``FLIPPED_SHARE`` (the chip's bfloat16 limit: the two weakest
    controls, one rope base and the window a key short, read under it
    at this size and are held by the logits' rtol above and in
    tests/test_laguna.py)."""
    import re
    _cfg, fam, pcfg, _model, params = laguna_toy
    served, P = greedy
    G = fam.SCORED_TAIL
    rw = fam.reference_weights(params, pcfg)

    def past(**kw):
        check = parity.margin_rule(
            fam.reference_logits(rw, jnp.asarray(served), pcfg, **kw),
            served, P)
        n = int(re.search(r"at (\d+) of 256 generated positions",
                          capsys.readouterr().out).group(1))
        return check, n
    right, n = past()
    assert right["ok"] is True and right["decisive"] > G and n == 0, right
    off, n = past(**{control: True})
    assert n > 2 * G // 10, (control, n)
    assert off["ok"] is (n <= int(fam.FLIPPED_SHARE * 2 * G)), (control, n)
    if control not in ("one_theta", "window_511"):
        assert off["ok"] is False, (control, n, off)


def test_the_blocked_reference_is_its_quadratic_form(laguna_toy,
                                                     monkeypatch):
    """The reference attends ``Q_BLOCK`` queries and computes
    ``EXPERT_BLOCK`` experts at a time; with one [T, T] mask over the
    whole sequence and other blocks it gives the same logits (query
    blocks of 64: three, the last ragged, every window crossing an
    edge; experts 4 at a time where the toy's 16 went in one)."""
    from benchmarks.reference import laguna as ref
    _cfg, fam, pcfg, _model, params = laguna_toy
    ids = jnp.asarray(np.random.default_rng(1).integers(
        1, 255, size=(1, 150)), jnp.int32)
    rw = fam.reference_weights(params, pcfg)
    whole = fam.reference_forward(rw, ids, pcfg, quadratic=True)
    monkeypatch.setattr(ref, "Q_BLOCK", 64)
    monkeypatch.setattr(ref, "EXPERT_BLOCK", 4)
    ref.layer.clear_cache()
    blocked = fam.reference_forward(rw, ids, pcfg)
    ref.layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=2e-6)


def test_the_laguna_reference_imports_nothing_of_the_program():
    for name in ("laguna", "llama"):
        with open(os.path.join(common.HERE, "reference",
                               name + ".py")) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
    import benchmarks.reference.laguna as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


def test_the_scored_tail_is_the_configurations_new_tokens(laguna_toy,
                                                          laguna_real):
    """``reference_logits`` applies the head to the rows that predict
    each prompt's last ``SCORED_TAIL`` tokens alone: the rows the
    comparison reads are the whole reference's, and both
    configurations' ``parity`` generate exactly that many; the real
    one's prompt is past two windows and one turn of the ring, with
    decode across the next wrap's approach."""
    cfg, fam, pcfg, _model, params = laguna_toy
    real, _ = laguna_real
    assert fam.SCORED_TAIL == cfg["parity"]["new_tokens"] == \
        real["parity"]["new_tokens"] == 128
    assert real["parity"] == {"prompts": 2, "prompt_len": 1280,
                              "new_tokens": 128}
    assert 1280 > 2 * real["sliding_window"]
    assert 1280 // fam.ring_len(real) == 1 and \
        (1280 + 128) // real["deployment"]["page_size"] == 22
    P, G = 40, 128
    ids = jnp.asarray(np.random.default_rng(2).integers(
        1, 255, size=(2, P + G)), jnp.int32)
    rw = fam.reference_weights(params, pcfg)
    whole = fam.reference_forward(rw, ids, pcfg)
    # (random "served" tokens: all of them lie under the best, which is
    # too many to excuse, so the rows come back as the reference's)
    tail = fam.reference_logits(rw, ids, pcfg)
    assert tail.shape == whole.shape
    np.testing.assert_allclose(tail[:, P - 1:-1], whole[:, P - 1:-1],
                               rtol=1e-5, atol=1e-6)
    assert not tail[:, :P - 1].any() and not tail[:, -1].any()
    served = np.asarray(ids)
    assert parity.margin_rule(tail, served, P)["ok"] is False
    assert parity.margin_rule(whole, served, P)["ok"] is False


def test_flipped_positions_are_excused_up_to_the_share(laguna_toy, capsys,
                                                       monkeypatch):
    """A served path whose tokens lie far under the reference's best at
    ``FLIPPED_SHARE`` of the generated positions is excused there (rows
    of zeros: not failed, not decisive) and reads correct; at one
    position more nothing is excused and the rule fails on them. (The
    reference's head is stood in for by logits made by hand.)"""
    _cfg, fam, pcfg, _model, params = laguna_toy
    P, G, V = 40, 128, 256
    rng = np.random.default_rng(5)
    served = rng.integers(1, 255, size=(2, P + G))
    rw = fam.reference_weights(params, pcfg)
    most = int(fam.FLIPPED_SHARE * 2 * G)
    for n, ok in ((most, True), (most + 1, False), (2 * most, False)):
        rows, cols = np.divmod(rng.choice(2 * G, size=n, replace=False), G)

        def head(_rw, x, **_kw):
            assert x.shape[:2] == (2, G)
            logits = np.zeros((2, G, V), np.float32)
            np.put_along_axis(logits, served[:, P:, None], 1.0, axis=-1)
            logits[rows, cols] = 0.0
            logits[rows, cols, (served[rows, P + cols] + 1) % V] = 1.0
            return logits
        monkeypatch.setattr(fam.ref, "head", head)
        check = parity.margin_rule(
            fam.reference_logits(rw, jnp.asarray(served, jnp.int32), pcfg),
            served, P)
        assert check["ok"] is ok, (n, check)
        assert check["decisive"] == (2 * G - n if ok else 2 * G)
        assert f"[correct] laguna: at {n} of 256 generated positions" \
            in capsys.readouterr().out


# ---------------------------------------------------------- byte counts

def test_laguna_byte_counts_by_hand(laguna_real, laguna_toy):
    cfg, fam = laguna_real
    assert (fam.n_sliding_layers(cfg), fam.n_full_layers(cfg),
            fam.n_moe_layers(cfg), fam.n_dense_layers(cfg)) == (3, 2, 4, 1)
    assert fam.heads_by_type(cfg) == {"full_attention": 48,
                                      "sliding_attention": 64}
    assert fam.key_bytes(cfg) == 2 * 8 * 128 * 2 == 4096
    assert fam.kv_bytes_per_token(cfg) == 2 * 4096 == 8192
    assert fam.ring_len(cfg) == 512 + 256 + 64
    assert fam.state_bytes(cfg) == 832 * 4096
    assert fam.sliding_bytes_per_slot(cfg) == 3 * 832 * 4096 == 10223616
    # 125 riders past the window: 125 x 512 keys a sliding layer-step
    assert fam.sliding_step_bytes(cfg, 125 * 512) == 125 * 512 * 4096
    assert fam.sliding_step_flops(cfg, 125 * 512) == \
        2.0 * 2 * 64 * 128 * 125 * 512
    assert fam.full_step_flops(cfg, 1000) == 2.0 * 2 * 48 * 128 * 1000
    # un-aged, three layers would hold 1.5 times the two full layers'
    assert fam.unaged_bytes(cfg, 1000.0) == 1500.0
    assert fam.expert_bytes(cfg) == 3 * 2048 * 512 * 2 == 6291456
    assert fam.shared_expert_bytes(cfg) == 6291456
    assert fam.experts_step_bytes(cfg, 250, 1000) == \
        250 * 6291456 + 2 * 1000 * 2048 * 2
    assert fam.experts_step_flops(cfg, 1000) == 2.0 * 3 * 1000 * 2048 * 512
    assert fam.attention_weights(cfg, "full_attention") == 29458432
    assert fam.attention_weights(cfg, "sliding_attention") == 37879808
    assert fam.dense_ffn_bytes(cfg) == 3 * 2048 * 8192 * 2
    # ISSUE 53's step at 128 riders of ~1,540 tokens: experts 6.3 GB at
    # 252 touched a layer, windows 0.81 GB, contexts 1.61 GB, 9.6 GB
    # (9.68 with the routed rows, the shared experts and the routers)
    ctx, riders = 128 * 1536, 128
    got = fam.decode_step_bytes(cfg, ctx, riders, experts_touched=252,
                                sliding_keys=riders * 512)
    attention = 2 * (2 * 29458432 + 3 * 37879808)
    dense = 3 * 2048 * 8192 * 2
    experts = 4 * 252 * 6291456
    rows = 4 * 2 * 1024 * 2048 * 2
    shared, router = 4 * 6291456, 4 * 2048 * 256 * 4
    pages = (ctx + riders) * 8192
    window = 3 * riders * 512 * 4096
    head = 100352 * 2048 * 2 + riders * 2048 * 2
    assert got == (attention + dense + experts + rows + shared + router
                   + pages + window + head)
    assert round(experts / 1e9, 2) == 6.34 and round(window / 1e9, 2) == 0.81
    assert round(pages / 1e9, 2) == 1.61 and round(got / 1e9, 1) == 9.7
    # without the program's counters: the most the riders can touch and
    # at most a window a slot
    assert fam.decode_step_bytes(cfg, ctx, riders) == got + 4 * 4 * 6291456
    assert fam.decode_step_bytes(cfg, 128 * 100, riders) < got
    # FLOPs: two a weight a rider in what a rider passes, and the scores
    per_rider = (2 * 29458432 + 3 * 37879808 + 3 * 2048 * 8192
                 + 4 * (3 * 2048 * (8 * 512 + 512) + 2048 * 256)
                 + 100352 * 2048)
    assert fam.decode_step_flops(cfg, ctx, riders, riders * 512) == (
        2.0 * riders * per_rider + 2 * 2.0 * 2 * 48 * 128 * ctx
        + 3 * 2.0 * 2 * 64 * 128 * riders * 512)
    # bytes bound the step: 11.7 ms against 0.8
    assert got / 819e9 > 10 * fam.decode_step_flops(
        cfg, ctx, riders, riders * 512) / 197e12
    # the toy's ring: a window of 12 and the default chunk in pages of 8
    toy = laguna_toy[0]
    assert fam.ring_len(toy) == 272 + 8
    assert fam.heads_by_type(toy) == {"full_attention": 4,
                                      "sliding_attention": 6}


def test_the_gate_and_both_layer_types_are_parts_of_their_own(laguna_real):
    """The gate is looked for first (it lies inside either type's
    scope), an inner scope is its own part, what is left under an outer
    one goes to the outer; the dense layer and the shared expert have
    theirs."""
    _cfg, fam = laguna_real
    sliding = "jit(decode)/while/body/Laguna/layers_1/attention/"
    full = "jit(prefill)/Laguna/layers_4/attention/"
    for path in (sliding + "attn_sliding/attn_gate/wg/dot_general:",
                 sliding + "attn_sliding/attn_gate/mul:",
                 full + "attn_full/attn_gate/logistic:"):
        assert trace_parts.part_of(path, fam.parts) == "attn_gate"
    for scope in ("ring_append", "ring_scores", "ring_pv"):
        assert trace_parts.part_of(
            sliding + f"attn_sliding/{scope}/dot_general:", fam.parts
        ) == scope
    for scope in ("kv_append", "kv_gather", "attn_scores", "attn_pv"):
        assert trace_parts.part_of(
            full + f"attn_full/{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(
        sliding + "attn_sliding/jit(ring_window_kernel)/ring_window:",
        fam.parts) == "attn_sliding"
    assert trace_parts.part_of(full + "attn_full/transpose:",
                               fam.parts) == "attn_full"
    assert trace_parts.part_of(sliding + "wq/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(sliding + "mul:", fam.parts) == "rope"
    assert trace_parts.part_of(
        "jit(decode)/while/body/Laguna/layers_0/feed_forward/w1/"
        "dot_general:", fam.parts) == "mlp"
    moe = "jit(prefill)/Laguna/layers_1/moe/"
    for scope in fam.MOE_SCOPES + ("moe_shared",):
        assert trace_parts.part_of(moe + f"{scope}/dot_general:",
                                   fam.parts) == scope
    assert set(fam.SLIDING_PARTS) | set(fam.FULL_PARTS) | {fam.GATE} == \
        set(fam.parts["attention"]) | {fam.RING_COPIES}
    assert not set(fam.SLIDING_PARTS) & set(fam.FULL_PARTS)


def test_ring_copies_are_copies_by_opcode(laguna_real):
    """A whole ring (or a quarter) moved by a copy's opcode is a ring
    copy; the decode loop's ``while``, whose name holds a ring's shape
    first, a fusion of that shape and a copy of another shape are not
    (families/mellum2.py matches by shape alone and counts the loop's
    own self time: PERF.md section 7 after PR 52 (b))."""
    cfg, fam = laguna_real
    ring = "bf16[128,8,832,128]"
    yes = (f"%copy-done.7 = {ring}{{3,2,1,0:T(8,128)(2,1)}} copy-done(",
           f"%copy-start.7 = ({ring}, {ring}, u32[]) copy-start(",
           f"%slice-done.2 = bf16[32,8,832,128]{{3,2,1,0}} slice-done(",
           f"%copy.3 = {ring}{{3,2,1,0}} copy(")
    no = (f"%while.3 = (s32[], {ring}, {ring}) while(",
          f"%fusion.9 = {ring}{{3,2,1,0}} fusion(",
          f"%ring_window.1 = ({ring}, {ring}) custom-call(",
          "%copy-done.8 = bf16[4609,64,8,128]{3,2,1,0} copy-done(",
          "%copy.4 = bf16[128,2048]{1,0} copy(", "copy.4", "")
    for name in yes:
        assert fam.is_ring_copy(cfg, name), name
    for name in no:
        assert not fam.is_ring_copy(cfg, name), name
    # Mellum 2's rule by shape takes the loop (its own shape, 32 x 4 x
    # 1,344 x 128): the fault this rule does not have
    mellum = common.load_family("mellum2", "serve")
    assert mellum._result_elements(
        "%while.3 = (s32[], bf16[32,4,1344,128]) while(") == \
        32 * 4 * 1344 * 128


# ------------------------------------------------ the readers on a run

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 120, "decode_steps": 2,
            "decode_window_tokens": 2048, "decode_context_tokens": 180000,
            "decode_sliding_keys": 120 * 512,
            "decode_kernel_pages": 2900, "sliding_kernel_keys": 120 * 832,
            "moe_decode_experts_touched": 2 * 4 * 250,
            "moe_decode_pairs": 2 * 4 * 960,
            "moe_decode_layer_steps": 2 * 4}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(laguna_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 120 and 128
    riders), a jit_prefill between them, and a THIRD jit_decode that
    the stop cut. A step: 3 sliding layers' kernel of 0.6 ms each and
    a gate of 0.004, 2 full layers' kernel of 1.2 ms and a gate of
    0.003, 4 mixture layers' experts of 2.1 ms and shared expert of
    0.01, the dense layer 0.08, the head 0.6, the loop's own ``while``
    (a ring's shape first in its name) 0.2 unnamed, and one unnamed
    asynchronous whole-ring copy of 0.05. The prefill call: 3 x 1.0 ms
    sliding, 2 x 1.5 ms full, 14 ms of the rest."""
    cfg, fam = laguna_real
    base = "jit(decode)/while/body/Laguna/"
    ring = "bf16[128,8,832,128]"
    loop = (f"!%while.3 = (s32[], {ring}, {ring}) while(", 200_000)
    ring_wait = (f"!%copy-done.7 = {ring}{{3,2,1,0}} copy-done(", 50_000)
    step = ([(f"layers_{i}/attention/attn_sliding/"
              "jit(ring_window_kernel)/ring_window:", 600_000)
             for i in (1, 2, 3)]
            + [(f"layers_{i}/attention/attn_sliding/attn_gate/wg/"
                "dot_general:", 4_000) for i in (1, 2, 3)]
            + [(f"layers_{i}/attention/attn_full/attn_scores/"
                "paged_decode:", 1_200_000) for i in (0, 4)]
            + [(f"layers_{i}/attention/attn_full/attn_gate/mul:", 3_000)
               for i in (0, 4)]
            + [(f"layers_{i}/moe/moe_experts/custom-call:", 2_100_000)
               for i in (1, 2, 3, 4)]
            + [(f"layers_{i}/moe/moe_shared/dot_general:", 10_000)
               for i in (1, 2, 3, 4)]
            + [("layers_0/feed_forward/w1/dot_general:", 80_000),
               ("head/dot_general:", 600_000), loop, ring_wait])
    call = ([(f"layers_{i}/attention/attn_sliding/"
              "jit(ring_window_kernel)/ring_window:", 1_000_000)
             for i in (1, 2, 3)]
            + [(f"layers_{i}/attention/attn_full/attn_scores/"
                "dot_general:", 1_500_000) for i in (0, 4)]
            + [("layers_1/moe/moe_experts/custom-call:", 14_000_000)])
    ops, modules, t = [], [], 0
    for n_steps, name in ((2, "jit_decode(1)"), (0, "jit_prefill(2)"),
                          (2, "jit_decode(1)"), (1, "jit_decode(1)")):
        t0 = t
        for scope, dur in (call if not n_steps else step * n_steps):
            path = ("jit(prefill)/Laguna/" if not n_steps else base)
            if scope[0] == "!":
                ops.append([scope[1:], t, dur, ""])
            else:
                ops.append(["%f = f32[8] fusion(", t, dur, path + scope])
            t += dur
        modules.append([name, t0, t - t0])
        t += 1000
    rows = [{"program": "jit_decode", "round": 11, "steps": 2,
             "start_ns": modules[0][1], "device_ms": modules[0][2] / 1e6},
            {"program": "jit_prefill", "round": 12, "steps": 0,
             "start_ns": modules[1][1], "device_ms": modules[1][2] / 1e6},
            {"program": "jit_decode", "round": 12, "steps": 2,
             "start_ns": modules[2][1], "device_ms": modules[2][2] / 1e6}]
    events = [_round(1.0), _round(2.0), _round(11.0, round=11),
              _round(12.0, round=12, decode_riders=128,
                     decode_context_tokens=196000,
                     decode_sliding_keys=128 * 512,
                     moe_decode_experts_touched=2 * 4 * 254,
                     moe_decode_pairs=2 * 4 * 1024)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"], chips=1,
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={},
        samples=[{"t": 1.0, "free_slots": 8,
                  "kv_bytes_in_use": 120 * 1500 * 8192},
                 {"t": 2.0, "free_slots": 0,
                  "kv_bytes_in_use": 128 * 1600 * 8192},
                 {"t": 9.0, "free_slots": 128, "kv_bytes_in_use": 0}])
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[2:]}}
    return run


def test_the_readers_on_a_hand_made_run(laguna_real, tmp_path, capsys):
    cfg, fam = laguna_real
    run = _joined_run(laguna_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(124.0)
    assert got["sliding_keys"] == pytest.approx(124 * 512)
    assert got["context_tokens"] == pytest.approx(
        (180000 - 60 + 196000 - 64) / 2)
    assert got["parts"]["attn_sliding"] == pytest.approx(4 * 3 * 600e-6)
    assert got["parts"]["attn_gate"] == pytest.approx(
        4 * (3 * 4e-6 + 2 * 3e-6))
    # the asynchronous whole-ring copy is a ring copy; the loop's own
    # ``while`` stays unnamed
    assert got["parts"]["ring_copies"] == pytest.approx(4 * 50e-6)
    assert got["parts"]["unnamed"] == pytest.approx(4 * 200e-6)
    assert got["parts"]["moe_shared"] == pytest.approx(4 * 4 * 10e-6)
    assert got["parts"]["mlp"] == pytest.approx(4 * 80e-6)
    line = capsys.readouterr().out
    assert "[laguna] jit_decode over the 2 matched executions: 4 steps" \
        in line
    assert "decode_kernel_pages 5800 and sliding_kernel_keys 199680" in line
    # the gate: 18 us a step
    assert read("decode_attn_gate_ms")(run) == pytest.approx(0.018)
    # three sliding layers of 0.6 ms and the copy of 0.05; two full
    # layers of 1.2 ms
    assert read("decode_sliding_attn_ms")(run) == pytest.approx(1.85)
    assert read("decode_full_attn_ms")(run) == pytest.approx(2.4)
    assert read("sliding_attn_roofline")(run) == pytest.approx(
        100.0 * (124 * 512 * 4096 / 819e9) / (1.85e-3 / 3))
    # the traced seconds' counters: (250 + 254) / 2 experts touched and
    # (960 + 1,024) / 2 pairs a mixture layer-step
    counters = fam.decode_counters(run)
    assert counters["experts_touched"] == pytest.approx(252.0)
    assert counters["pairs"] == pytest.approx(992.0)
    assert read("moe_experts_roofline.by_kind")(run) == pytest.approx(
        100.0 * fam.experts_step_bytes(cfg, 252.0, 992.0) / 819e9 / 2.1e-3)
    step_s = got["module_s"] / 4
    assert step_s == pytest.approx(
        (3 * .6 + 3 * .004 + 2 * 1.2 + 2 * .003 + 4 * 2.1 + 4 * .01 + .08
         + .6 + .2 + .05) * 1e-3)
    want = fam.decode_step_bytes(
        cfg, got["context_tokens"], 124.0, experts_touched=252.0,
        sliding_keys=124 * 512) / 819e9
    assert read("swa_moe_step_roofline")(run) == pytest.approx(
        100.0 * want / step_s)
    assert 60.0 < read("swa_moe_step_roofline")(run) < 100.0
    # the window's rounds (t 1 and 2): 960 pairs over 250 touched
    assert read("moe_rows_per_expert_mean")(run) == pytest.approx(
        960 / 250)
    # the call: 3 ms sliding and 3 ms full of 20.0 ms
    assert read("prefill_sliding_attn_share")(run) == pytest.approx(15.0)
    assert read("prefill_full_attn_share")(run) == pytest.approx(15.0)
    held = (120 + 128) * 3 * 832 * 4096
    unaged = 1.5 * (120 * 1500 + 128 * 1600) * 8192
    assert read("sliding_resident_share")(run) == pytest.approx(
        100.0 * held / unaged)
    assert read("state_peak_share")(run) == pytest.approx(100.0)


def test_the_new_readers_find_nothing_where_there_is_nothing(
        laguna_real, tmp_path):
    """Another family, a join that was refused, a program without the
    scope (the parent of a later cell's traced run), rounds without the
    counters: None, never an error."""
    read = common.load_metric_reader
    run = _joined_run(laguna_real, tmp_path)
    for family in ("olmoe", "mellum2"):
        other = types.SimpleNamespace(**{
            **vars(run), "family": common.load_family(family, "serve")})
        for name in NEW_READERS[:2]:
            assert read(name)(other) is None, (family, name)
    refused = _joined_run(laguna_real, tmp_path)
    refused._dispatch = None
    for name in NEW_READERS[:2]:
        assert read(name)(refused) is None, name
    assert read("moe_rows_per_expert_mean")(refused) is not None
    ungated = _joined_run(laguna_real, tmp_path)
    for op in ungated._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("attn_gate", "x")
    assert read("decode_attn_gate_ms")(ungated) is None
    assert read("swa_moe_step_roofline")(ungated) is not None
    old = _joined_run(laguna_real, tmp_path)
    old.events = [(e[0], e[1], e[2], e[3], e[4], {
        k: v for k, v in e[5].items() if not k.startswith("moe_decode")})
        for e in old.events]
    assert read("swa_moe_step_roofline")(old) is None
    assert read("moe_rows_per_expert_mean")(old) is None
    assert read("decode_attn_gate_ms")(old) is not None
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
    assert len(bench["configs"]) == 10 and len(bench["workloads"]) == 11
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    mellum = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", "mellum2-d8.longdoc-sat")}
    # every list Mellum 2's cell is on, and the three new readers
    assert per_layer == mellum | set(NEW_READERS)
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "moe_dispatch_share", "moe_experts_touched_mean",
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", "moe_experts_roofline.by_kind",
        "state_peak_share", "decode_sliding_attn_ms",
        "decode_full_attn_ms", "sliding_attn_roofline",
        "prefill_sliding_attn_share", "prefill_full_attn_share",
        "sliding_resident_share", "setup_build_s",
        "setup_program_trace_s", "setup_cold_builds", "engine_init_s",
        *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-3:]) == NEW_READERS
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["layer"] == "model step"
        assert callable(common.load_metric_reader(m["name"]))
    assert [(m["unit"], m["better"], m["source"])
            for m in bench["per_layer"][-3:]] == [
        ("%", "higher", "device_trace"), ("ms", "lower", "device_trace"),
        ("rows", "higher", "program_counter")]
    # the readers that divide by trace_reduce.loop_steps or by
    # num_hidden_layers (PERF.md section 7): the cell is on none
    assert not per_layer & {
        "decode_moe_ms", "moe_experts_roofline", "decode_attn_ms",
        "decode_dense_ms", "decode_step_ms", "decode_roofline",
        "moe_held_pair_share"}
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
    assert len(ids) == 1024 and 1 <= min(ids) and max(ids) < 100352
    # 128 slots of 32 pages and four of headroom, a page table that
    # holds them
    dep = cfg["deployment"]
    per_slot = -(-(1024 + 1024) // dep["page_size"])
    assert per_slot == 32 and dep["max_slots"] == 128
    assert dep["max_slots"] * (per_slot + 4) == dep["n_pages"] - 1 == 4608
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
    assert set(dep) == {"max_slots", "page_size", "n_pages",
                        "tensor_parallel"}


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-laguna.gen-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_laguna_rehearsal_cell_runs(trace):
    """The toy cell borrows laguna-xs2-d5.gen-sat's metric lists:
    correct against the plain reference through the served path (100
    tokens of prompt in chunks, both kinds of entry under both query
    widths), no program built in the window; at ``--trace 2`` the
    counter metrics are there, ``moe_rows_per_expert_mean`` among them;
    the device_trace metrics need a device in the trace, which a CPU
    has not (the hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "decode_sliding_keys" in stdout and "state_slots" in stdout
    assert "[correct] laguna: at 0 of 256 generated positions" in stdout
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in common.load_rehearsal_cell(
            "toy-laguna.gen-sat")["reports"]:
        assert name in line["metrics"], name
    touched = line["metrics"]["moe_experts_touched_mean"]["value"]
    assert 0.0 < touched <= 16.0
    rows = line["metrics"]["moe_rows_per_expert_mean"]
    assert rows["unit"] == "rows" and 1.0 <= rows["value"] <= 4.0
    assert line["metrics"]["sliding_resident_share"]["value"] > 100.0
    for name in NEW_READERS[:2]:
        assert name not in line["metrics"]
