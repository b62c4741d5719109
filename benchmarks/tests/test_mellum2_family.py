"""The Mellum 2 family (families/mellum2.py, reference/mellum2.py,
configs/mellum2-12b-a2.5b-d8.json, the toy ``rehearsal/toy-mellum2.json``,
traffic/longdoc-sat.json as it stands) on the CPU: the configuration
against its published copy, the program's config the family builds, the
served model against the plain reference at the toy's sizes, the
reference against a second, quadratic-mask form of itself, the scored
tail, the byte counts against hand counts BY KIND of layer, the six new
readers on a hand-made joined trace and hand-made samples, the cell,
and the rehearsal cell end to end at ``--trace 0`` and ``--trace 2``."""
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

CONFIG = "mellum2-12b-a2.5b-d8"
CELL = "mellum2-d8.longdoc-sat"
REDUCIBLE = {"num_hidden_layers", "max_position_embeddings"}
NEW_READERS = ("decode_sliding_attn_ms", "decode_full_attn_ms",
               "sliding_attn_roofline", "prefill_sliding_attn_share",
               "prefill_full_attn_share", "sliding_resident_share")


@pytest.fixture(scope="module")
def mellum_toy():
    cfg = common.load_json("rehearsal", "toy-mellum2.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def mellum_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_mellum_file_holds_the_published_sizes_but_for_reduced(
        mellum_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; depth and the page table's
    width alone are cut, and the nested groups are copied whole."""
    cfg, _fam = mellum_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 23 and source["model_type"] == "mellum"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
            assert key in REDUCIBLE and key in cfg["why_reduced"], key
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == REDUCIBLE
    # two whole periods, every expert, the whole vocabulary
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"][:8] == (["sliding_attention"] * 3
                                      + ["full_attention"]) * 2
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert cfg["num_experts"] == 64 and cfg["vocab_size"] == 98304
    assert "harness_keys" not in cfg
    for key in ("qk_norm", "window", "truncate", "attention_factor",
                "float32", "intermediate_size", "mtp", "weights",
                "scored_tail", "flipped_share"):
        assert key in cfg["assumed"], key
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_mellum_program_config_is_the_published_model_cut_in_depth(
        mellum_real):
    import dataclasses
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         sliding_ring_len,
                                         state_bytes_per_slot)
    from ray_tpu.models.mellum import mellum2_12b, mellum_param_count
    cfg, fam = mellum_real
    want = mellum2_12b(n_layers=8, max_seq_len=16384,
                       param_dtype=jnp.bfloat16)
    pcfg = fam.program_config(cfg)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)
    # ISSUE 42's arithmetic: 3.795 B parameters = 7.59 GB in bf16
    n = mellum_param_count(pcfg)
    assert round(n / 1e9, 3) == 3.795 and round(2 * n / 1e9, 2) == 7.59
    shapes = weights.param_shapes(fam.model(pcfg))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == n
    assert shapes["layers_0"]["attention"]["wq"]["kernel"].shape == \
        (2304, 4096)
    assert shapes["layers_3"]["attention"]["wk"]["kernel"].shape == \
        (2304, 512)
    assert shapes["layers_1"]["moe"]["w1"].shape == (64, 2304, 896)
    assert shapes["lm_head"].shape == (98304, 2304)
    # the deployment: 32 slots of 16.5 MB of rings, 4,481 pages of
    # 262,144 B: 0.53 GB and 1.17 GB beside the weights, 9.3 GB in all
    dep = cfg["deployment"]
    ring = sliding_ring_len(pcfg, dep["page_size"], 256)
    assert ring == 1344 == fam.ring_len(cfg)
    per_slot = state_bytes_per_slot(pcfg, ring)
    assert per_slot == fam.sliding_bytes_per_slot(cfg) == 6 * 1344 * 2048
    page = kv_pool_page_bytes(pcfg, dep["page_size"])
    assert page == 262144 == 64 * fam.kv_bytes_per_token(cfg)
    state, pool = dep["max_slots"] * per_slot, dep["n_pages"] * page
    assert round(state / 1e9, 2) == 0.53 and round(pool / 1e9, 2) == 1.17
    assert round((2 * n + state + pool) / 1e9, 1) == 9.3


def test_a_program_that_cannot_express_mellum_is_refused(mellum_toy,
                                                         monkeypatch):
    """The parent has no ray_tpu.models.mellum, and a program whose
    config lacks a field the model needs is no better: the family exits
    before a weight is made (this is how the parent commit fails on the
    new cell, cleanly and at once)."""
    import dataclasses
    import ray_tpu.models.mellum as mm
    cfg, fam, *_ = mellum_toy

    @dataclasses.dataclass(frozen=True)
    class Lesser:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(mm, "MellumConfig", Lesser)
    with pytest.raises(SystemExit, match="cannot express Mellum 2"):
        fam.program_config(cfg)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "ray_tpu.models.mellum", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.mellum"):
        fam.program_config(cfg)


def test_what_the_program_lacks_of_mellum_is_refused(mellum_toy):
    cfg, fam, *_ = mellum_toy
    rp = cfg["rope_parameters"]
    for wrong in ({"attention_bias": True}, {"tie_word_embeddings": True},
                  {"hidden_act": "gelu"}, {"use_sliding_window": False},
                  {"norm_topk_prob": False},
                  {"mlp_layer_types": ["dense"] + ["sparse"] * 7},
                  {"layer_types": cfg["layer_types"][:4]},
                  {"rope_parameters": {**rp, "full_attention": {
                      **rp["full_attention"], "rope_type": "default"}}},
                  {"rope_parameters": {**rp, "sliding_attention": {
                      "rope_type": "yarn", "rope_theta": 10000}}},
                  {"rope_parameters": {**rp, "sliding_attention": {
                      "rope_type": "default", "rope_theta": 5e5}}}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


# ---------------------------------------------- program against reference

def test_the_mellum_reference_matches_the_served_model(mellum_toy):
    """Float32 both sides, full forward logits, 150 positions: rtol
    1e-4 (tests/test_mellum.py says why). A reference with a sliding
    layer attended as a full one, the window a key short, the full
    layers' rope without YaRN, two experts a token or matrices rounded
    to float8 is far outside."""
    _cfg, fam, pcfg, model, params = mellum_toy
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 150)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_forward(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=2e-5)
    scale = float(np.abs(np.asarray(want)).max())
    for wrong in (dict(sliding_as_full=True), dict(window=11),
                  dict(plain_full_rope=True), dict(top_k=2),
                  dict(lower_precision=True)):
        out = fam.reference_forward(rw, ids, pcfg, **wrong)
        gap = float(np.abs(out - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (wrong, gap, scale)


def test_the_blocked_reference_is_its_quadratic_form(mellum_toy,
                                                     monkeypatch):
    """The reference attends ``Q_BLOCK`` queries at a time; with one
    [T, T] mask over the whole sequence it gives the same logits (blocks
    of 64 here: three blocks, the last ragged, every window crossing a
    block's edge)."""
    from benchmarks.reference import mellum2 as ref
    _cfg, fam, pcfg, _model, params = mellum_toy
    ids = jnp.asarray(np.random.default_rng(1).integers(
        1, 255, size=(1, 150)), jnp.int32)
    rw = fam.reference_weights(params, pcfg)
    whole = fam.reference_forward(rw, ids, pcfg, quadratic=True)
    monkeypatch.setattr(ref, "Q_BLOCK", 64)
    ref.layer.clear_cache()
    blocked = fam.reference_forward(rw, ids, pcfg)
    ref.layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=2e-6)


def test_the_mellum_reference_imports_nothing_of_the_program():
    for name in ("mellum2", "llama"):
        with open(os.path.join(common.HERE, "reference",
                               name + ".py")) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
    import benchmarks.reference.mellum2 as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


def test_the_scored_tail_is_the_configurations_new_tokens(mellum_toy,
                                                          mellum_real):
    """``reference_logits`` applies the head to the rows that predict
    each prompt's last ``SCORED_TAIL`` tokens alone: the rows the
    comparison reads are the whole reference's, the rule's verdict is
    the same, and both configurations' ``parity`` generate exactly that
    many."""
    cfg, fam, pcfg, _model, params = mellum_toy
    real, _ = mellum_real
    assert fam.SCORED_TAIL == cfg["parity"]["new_tokens"] == \
        real["parity"]["new_tokens"] == 128
    # past YaRN's original 8,192, after six turns of the ring
    assert real["parity"]["prompt_len"] == 8320 > real["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]
    assert 8320 // fam.ring_len(real) == 6
    P, G = 40, 128
    ids = jnp.asarray(np.random.default_rng(2).integers(
        1, 255, size=(2, P + G)), jnp.int32)
    rw = fam.reference_weights(params, pcfg)
    whole = fam.reference_forward(rw, ids, pcfg)
    # (random "served" tokens: all of them lie under the best, which is
    # too many to excuse, so the rows come back as the reference's)
    tail = fam.reference_logits(rw, ids, pcfg)
    assert tail.shape == whole.shape
    # the head of a block of positions sums in another order than the
    # head of all of them
    np.testing.assert_allclose(tail[:, P - 1:-1], whole[:, P - 1:-1],
                               rtol=1e-5, atol=1e-6)
    assert not tail[:, :P - 1].any() and not tail[:, -1].any()
    served = np.asarray(ids)
    assert parity.margin_rule(tail, served, P)["ok"] is False
    assert parity.margin_rule(whole, served, P)["ok"] is False


def test_flipped_positions_are_excused_up_to_a_share(mellum_toy, capsys,
                                                     monkeypatch):
    """A served path whose tokens lie far under the reference's best at
    a TENTH of the generated positions is excused there (rows of zeros:
    not failed, not decisive) and reads correct; at an EIGHTH nothing is
    excused and the rule fails on them. (The reference's head is stood
    in for by logits made by hand: the served token best by 1.0 but at
    the chosen positions, where another is.)"""
    _cfg, fam, pcfg, _model, params = mellum_toy
    P, G, V = 40, 128, 256
    rng = np.random.default_rng(5)
    served = rng.integers(1, 255, size=(2, P + G))
    rw = fam.reference_weights(params, pcfg)
    for n, ok in ((25, True), (26, False), (32, False)):  # of 256: 10 %
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
        assert f"at {n} of 256 generated positions" in capsys.readouterr().out


# ---------------------------------------------------------- byte counts

def test_mellum_byte_counts_by_hand(mellum_real, mellum_toy):
    cfg, fam = mellum_real
    assert (fam.n_sliding_layers(cfg), fam.n_full_layers(cfg),
            fam.n_moe_layers(cfg)) == (6, 2, 8)
    assert fam.key_bytes(cfg) == 2 * 4 * 128 * 2 == 2048
    assert fam.kv_bytes_per_token(cfg) == 2 * 2048 == 4096
    assert fam.ring_len(cfg) == 1024 + 256 + 64
    assert fam.state_bytes(cfg) == 1344 * 2048
    assert fam.sliding_bytes_per_slot(cfg) == 6 * 1344 * 2048 == 16515072
    # 24 riders past the window: 24 x 1,024 keys a sliding layer-step
    assert fam.sliding_step_bytes(cfg, 24 * 1024) == 24 * 1024 * 2048
    assert fam.sliding_step_flops(cfg, 24 * 1024) == \
        2.0 * 2 * 32 * 128 * 24 * 1024
    # un-aged, six layers would hold three times the two full layers'
    assert fam.unaged_bytes(cfg, 1000.0) == 3000.0
    assert fam.expert_bytes(cfg) == 3 * 2304 * 896 * 2 == 12386304
    assert fam.experts_step_bytes(cfg, 60, 192) == \
        60 * 12386304 + 2 * 192 * 2304 * 2
    assert fam.experts_step_flops(cfg, 192) == 2.0 * 3 * 192 * 2304 * 896
    assert fam.attention_weight_bytes(cfg) == 2 * 21233664
    # ISSUE 42's step at ~24 riders of ~8,450 tokens: experts 6.4 GB at
    # 64 touched a layer, the head 0.45, the full layers' pages 0.83,
    # the sliding layers' windows 0.30
    ctx = 24 * 8450
    got = fam.decode_step_bytes(cfg, ctx, 24, experts_touched=64)
    experts = 8 * 64 * 12386304
    head = 98304 * 2304 * 2 + 24 * 2304 * 2
    pages = (ctx + 24) * 4096
    window = 6 * 24 * 1024 * 2048
    router = 8 * 2304 * 64 * 4
    assert got == 8 * 2 * 21233664 + experts + router + pages + window + head
    assert round(experts / 1e9, 1) == 6.3 and round(head / 1e9, 2) == 0.45
    assert round(pages / 1e9, 2) == 0.83 and round(window / 1e9, 2) == 0.30
    # the toy's ring: a window of 12 and the default chunk in pages of 8
    toy = mellum_toy[0]
    assert fam.ring_len(toy) == 272 + 8


def test_both_layer_types_are_parts_of_their_own(mellum_real):
    """An inner scope is its own part, what is left under an outer one
    goes to the outer; the two tuples split either program by type."""
    _cfg, fam = mellum_real
    sliding = "jit(decode)/while/body/Mellum/layers_1/attention/"
    full = "jit(prefill)/Mellum/layers_3/attention/"
    for scope in ("ring_append", "ring_scores", "ring_pv"):
        assert trace_parts.part_of(
            sliding + f"attn_sliding/{scope}/dot_general:", fam.parts
        ) == scope
    for scope in ("kv_append", "kv_gather", "attn_scores", "attn_pv"):
        assert trace_parts.part_of(
            full + f"attn_full/{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(sliding + "attn_sliding/reshape:",
                               fam.parts) == "attn_sliding"
    assert trace_parts.part_of(full + "attn_full/transpose:",
                               fam.parts) == "attn_full"
    assert trace_parts.part_of(sliding + "wq/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(sliding + "mul:", fam.parts) == "rope"
    for scope in fam.MOE_SCOPES:
        assert trace_parts.part_of(
            f"jit(prefill)/Mellum/layers_1/moe/{scope}/dot_general:",
            fam.parts) == scope
    assert set(fam.SLIDING_PARTS) | set(fam.FULL_PARTS) == \
        set(fam.parts["attention"]) | {fam.RING_COPIES}
    assert not set(fam.SLIDING_PARTS) & set(fam.FULL_PARTS)


# --------------------------------------------------- the six new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 20, "decode_steps": 2,
            "decode_window_tokens": 8704, "decode_context_tokens": 170000,
            "decode_sliding_keys": 20 * 1024}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(mellum_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 20 and 24
    riders), a jit_prefill between them, and a THIRD jit_decode that
    the stop cut. A step: 6 sliding layers' ring of 0.15 ms each (0.02
    append + 0.08 scores + 0.04 read-out + 0.01 left under the outer
    scope) and two unnamed whole-ring copies of 0.03 ms, 2 full layers'
    block loop of 1.0 ms each, 8 mixture layers' experts of 1.0 ms each,
    the head. The prefill call: 6 x 1.2 ms sliding and four unnamed ring
    scatters of 0.2 ms, 2 x 6 ms full, 20 ms of the rest."""
    cfg, fam = mellum_real
    base = "jit(decode)/while/body/Mellum/"
    sliding = (("attn_sliding/ring_append/scatter:", 20_000),
               ("attn_sliding/ring_scores/dot_general:", 80_000),
               ("attn_sliding/ring_pv/dot_general:", 40_000),
               ("attn_sliding/reshape:", 10_000))
    full = (("attn_full/kv_append/scatter:", 50_000),
            ("attn_full/kv_gather/gather:", 500_000),
            ("attn_full/attn_scores/dot_general:", 250_000),
            ("attn_full/attn_pv/dot_general:", 200_000))
    # an asynchronous whole-ring copy the compiler leaves without a
    # scope, two a step, and a prefill call's flattened ring scatter
    ring_wait = ("!%copy-done.7 = bf16[32,4,1344,128]{3,2,1,0:T(8,128)"
                 "(2,1)} copy-done(", 30_000)
    ring_scatter = ("!%fusion.9 = bf16[172032,128]{1,0:T(8,128)(2,1)} "
                    "fusion(", 200_000)
    # (one of the two a step carries a scope of no part: "other")
    ring_wait_other = ("?%copy-done.8 = bf16[32,4,1344,128]{3,2,1,0} "
                       "copy-done(", 30_000)
    other_unnamed = ("!%while.3 = (s32[], bf16[32,2304]) while(", 5_000)
    step = ([(f"layers_{i}/attention/{s}", d)
             for i in (0, 1, 2, 4, 5, 6) for s, d in sliding]
            + [ring_wait, ring_wait_other, other_unnamed]
            + [(f"layers_{i}/attention/{s}", d)
               for i in (3, 7) for s, d in full]
            + [(f"layers_{i}/moe/moe_experts/custom-call:", 1_000_000)
               for i in range(8)]
            + [("head/dot_general:", 600_000)])
    call = ([(f"layers_{i}/attention/attn_sliding/ring_scores/"
              "dot_general:", 1_200_000) for i in (0, 1, 2, 4, 5, 6)]
            + [(f"layers_{i}/attention/attn_full/attn_scores/"
                "dot_general:", 6_000_000) for i in (3, 7)]
            + [("layers_0/moe/moe_experts/custom-call:", 20_000_000)]
            + [ring_scatter] * 4)
    ops, modules, t = [], [], 0
    for n_steps, name in ((2, "jit_decode(1)"), (0, "jit_prefill(2)"),
                          (2, "jit_decode(1)"), (1, "jit_decode(1)")):
        t0 = t
        for scope, dur in (call if not n_steps else step * n_steps):
            path = ("jit(prefill)/Mellum/" if not n_steps else base)
            if scope[0] in "!?":
                ops.append([scope[1:], t, dur, "" if scope[0] == "!"
                            else "jit(decode)/while/body/closed_call"])
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
              _round(12.0, round=12, decode_riders=24,
                     decode_context_tokens=204000,
                     decode_sliding_keys=24 * 1024)]
    per_token = 4096
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"],
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={},
        samples=[{"t": 1.0, "free_slots": 2,
                  "kv_bytes_in_use": 30 * 5000 * per_token},
                 {"t": 2.0, "free_slots": 0,
                  "kv_bytes_in_use": 32 * 5500 * per_token},
                 {"t": 9.0, "free_slots": 32, "kv_bytes_in_use": 0}])
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[2:]}}
    return run


def test_the_six_new_readers_on_a_hand_made_run(mellum_real, tmp_path):
    cfg, fam = mellum_real
    run = _joined_run(mellum_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(22.0)
    assert got["sliding_keys"] == pytest.approx(22 * 1024)
    assert got["parts"]["ring_scores"] == pytest.approx(4 * 6 * 80e-6)
    assert got["parts"]["attn_sliding"] == pytest.approx(4 * 6 * 10e-6)
    # the unnamed whole-ring copies are a part of their own, the other
    # unnamed operation stays unnamed
    assert got["parts"]["ring_copies"] == pytest.approx(4 * 2 * 30e-6)
    assert got["parts"]["unnamed"] == pytest.approx(4 * 5e-6)
    # a step: six sliding layers of 0.15 ms and 0.06 ms of ring copies,
    # two full layers of 1.0 ms
    assert read("decode_sliding_attn_ms")(run) == pytest.approx(0.96)
    assert read("decode_full_attn_ms")(run) == pytest.approx(2.0)
    # a sliding layer-step took 0.16 ms for 22 riders' 1,024 keys of
    # 2,048 B
    assert read("sliding_attn_roofline")(run) == pytest.approx(
        100.0 * (22 * 1024 * 2048 / 819e9) / 0.16e-3)
    assert 0.0 < read("sliding_attn_roofline")(run) < 100.0
    # the call: 7.2 + 0.8 ms sliding and 12 ms full of 40.0 ms
    assert read("prefill_sliding_attn_share")(run) == pytest.approx(
        100.0 * 8.0 / 40.0)
    assert read("prefill_full_attn_share")(run) == pytest.approx(
        100.0 * 12.0 / 40.0)
    # 30 and 32 slots' rings over three times the full layers' pages
    held = (30 + 32) * 6 * 1344 * 2048
    unaged = 3 * (30 * 5000 + 32 * 5500) * 4096
    assert read("sliding_resident_share")(run) == pytest.approx(
        100.0 * held / unaged)
    assert 20.0 < read("sliding_resident_share")(run) < 30.0
    # the mixture's reader takes the family's count of layers
    assert read("moe_experts_roofline.by_kind")(run) is None   # no counters
    assert read("state_peak_share")(run) == pytest.approx(100.0)


def test_the_new_readers_find_nothing_where_there_is_nothing(
        mellum_real, tmp_path):
    """Another family, a join that was refused, a program without the
    scopes, rounds without the counter, spans that disagree with the
    rows: None, never an error."""
    read = common.load_metric_reader
    run = _joined_run(mellum_real, tmp_path)
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("olmoe", "serve")})
    for name in NEW_READERS:
        assert read(name)(other) is None, name
    refused = _joined_run(mellum_real, tmp_path)
    refused._dispatch = None
    for name in NEW_READERS[:3]:
        assert read(name)(refused) is None, name
    for name in NEW_READERS[3:]:
        assert read(name)(refused) is not None, name
    unnamed = _joined_run(mellum_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = (op[3].replace("attn_sliding", "x").replace("ring_", "x_")
                 .replace("attn_full", "y").replace("kv_", "y_")
                 .replace("attn_", "y_"))
    for name in NEW_READERS[:5]:
        assert read(name)(unnamed) is None, name
    old = _joined_run(mellum_real, tmp_path)
    for d in old._dispatch["by_round"].values():
        del d["decode_sliding_keys"]
    assert read("sliding_attn_roofline")(old) is None
    assert read("decode_sliding_attn_ms")(old) is not None
    short = _joined_run(mellum_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    for name in NEW_READERS[:3]:
        assert read(name)(short) is None, name
    idle = types.SimpleNamespace(**{**vars(run), "samples": []})
    assert read("sliding_resident_share")(idle) is None


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_longdoc_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-sat", 1)
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
        "moe_dispatch_share", "moe_experts_touched_mean",
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", "moe_experts_roofline.by_kind",
        "state_peak_share", *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-6:]) == NEW_READERS
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(common.load_metric_reader(m["name"]))
    assert [(m["unit"], m["better"], m["source"], m["layer"])
            for m in bench["per_layer"][-6:]] == [
        ("ms", "lower", "device_trace", "model step"),
        ("ms", "lower", "device_trace", "model step"),
        ("%", "higher", "device_trace", "kernels"),
        ("%", "lower", "device_trace", "model step"),
        ("%", "lower", "device_trace", "model step"),
        ("%", "lower", "program_counter", "KV pages")]
    # the readers that divide by trace_reduce.loop_steps or by
    # num_hidden_layers (PERF.md section 7): the cell is on none
    assert not per_layer & {
        "decode_moe_ms", "moe_experts_roofline", "decode_attn_ms",
        "decode_dense_ms", "decode_step_ms", "decode_roofline",
        "moe_held_pair_share"}
    # the traffic is a.x-k1-d5.longdoc-sat's file, unedited
    other = common.find_named(bench["workloads"], "axk1-d5.longdoc-sat",
                              "workload")
    assert other["traffic"] == cell["traffic"]
    tr = common.load_json("traffic", "longdoc-sat.json")
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {8192}
    assert {r.output_len for r in reqs} == {512}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 8192, cfg["vocab_size"])
    assert len(ids) == 8192 and 1 <= min(ids) and max(ids) < 98304
    # 32 slots of 136 pages and four of headroom, a page table that
    # holds them
    dep = cfg["deployment"]
    per_slot = -(-(8192 + 512) // dep["page_size"])
    assert per_slot == 136
    assert dep["max_slots"] * (per_slot + 4) == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
    assert dep["batch_wait_timeout_s"] == 0.25
    assert cfg["parity"] == {"prompts": 2, "prompt_len": 8320,
                             "new_tokens": 128}


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-mellum2.longdoc-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_mellum_rehearsal_cell_runs(trace):
    """The toy cell borrows mellum2-d8.longdoc-sat's metric lists:
    correct against the plain reference through the served path (100
    tokens of prompt in chunks, both kinds of entry), no program built
    in the window; at ``--trace 2`` the counter metrics are there,
    ``state_peak_share`` and ``sliding_resident_share`` among them; the
    device_trace metrics need a device in the trace, which a CPU has
    not (the hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "decode_sliding_keys" in stdout and "state_slots" in stdout
    assert "[correct] mellum2: at 0 of 256 generated positions" in stdout
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in common.load_rehearsal_cell(
            "toy-mellum2.longdoc-sat")["reports"]:
        assert name in line["metrics"], name
    touched = line["metrics"]["moe_experts_touched_mean"]["value"]
    assert 0.0 < touched <= 8.0
    state = line["metrics"]["state_peak_share"]
    assert state["unit"] == "%" and 25.0 <= state["value"] <= 100.0
    # contexts far under the toy's ring: the rings hold MORE than the
    # contexts' pages would (the metric reads under 100 % only past it)
    assert line["metrics"]["sliding_resident_share"]["value"] > 100.0
    for name in NEW_READERS[:5]:
        assert name not in line["metrics"]
