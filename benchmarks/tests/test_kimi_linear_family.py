"""The Kimi-Linear family (families/kimi_linear.py,
reference/kimi_linear.py, configs/kimi-linear-48b-a3b-d8-ep4.json, the
toy ``rehearsal/toy-kimi-linear.json``, traffic/gen-sat.json) on the
CPU: the configuration against its published copy, the program's config
the family builds, the served model against the plain reference at the
toy's sizes (a SHARE: 4 of 16 experts held, from expert 4), the seeded
and balanced weights, the flipped-share rule, the byte counts against hand
counts BY KIND of layer, the four new readers on a hand-made joined
trace and hand-made rounds, the traffic mix, and the rehearsal cell end
to end."""
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

CONFIG = "kimi-linear-48b-a3b-d8-ep4"
CELL = "kimi-linear-d8.gen-sat"
# what a chip's share of a stated deployment may cut (model-configs,
# section 4), beside depth and the page table's width
REDUCIBLE = {"num_hidden_layers", "model_max_length", "num_experts",
             "vocab_size"}
NEW_READERS = ("state_peak_share", "linear_state_roofline.by_kind",
               "latent_attn_roofline.by_kind",
               "moe_experts_roofline.by_kind")


@pytest.fixture(scope="module")
def kimi_toy():
    cfg = common.load_json("rehearsal", "toy-kimi-linear.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**32 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def kimi_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_kimi_file_holds_the_published_sizes_but_for_reduced(kimi_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; no width is reduced, and
    the nested group is copied whole."""
    cfg, _fam = kimi_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 34 and source["model_type"] == "kimi_linear"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
            assert key in REDUCIBLE and key in cfg["why_reduced"], key
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == REDUCIBLE
    # the floors: two whole periods, the dense layer and seven after it
    # (at least four), 64 experts (at least 8), a quarter of the
    # vocabulary (at least an eighth); the router keeps its width
    assert cfg["num_hidden_layers"] == 8 >= cfg["first_k_dense_replace"] + 4
    assert cfg["num_experts"] == 64 >= 8
    assert cfg["router_width"] == source["num_experts"] == 256
    assert cfg["vocab_size"] * 4 == source["vocab_size"]
    assert cfg["num_experts_per_tok"] == source["num_experts_per_token"]
    assert "4-chip" in cfg["stands_for"] and "QUARTER" in cfg["stands_for"]
    for key in ("layer_lists", "kda_low_rank", "kda_beta", "l2norm_eps",
                "state_dtype", "attention", "head_dim_72", "router",
                "balanced_bias", "weights", "flipped_share"):
        assert key in cfg["assumed"], key
    bench = common.load_benchmark()
    conf = common.find_named(bench["configs"], CONFIG, "configuration")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_kimi_program_config_is_the_published_model_cut_to_the_share(
        kimi_real):
    import dataclasses
    from ray_tpu.models.kimi_linear import (kimi_linear_48b,
                                            kimi_linear_param_count)
    from ray_tpu.models.kv_cache import (KIND_KV, kv_pool_page_bytes,
                                         state_bytes_per_slot)
    cfg, fam = kimi_real
    want = kimi_linear_48b(n_layers=8, vocab_size=40960, max_seq_len=4096,
                           experts_held=(0, 64), param_dtype=jnp.bfloat16)
    pcfg = fam.program_config(cfg)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)
    assert KIND_KV not in pcfg.layer_kinds
    # ISSUE 39's arithmetic: 3.772 B parameters = 7.54 GB in bf16
    n = kimi_linear_param_count(pcfg, experts=64)
    assert round(n / 1e9, 3) == 3.772 and round(2 * n / 1e9, 2) == 7.54
    shapes = weights.param_shapes(fam.model(pcfg))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == n
    assert shapes["layers_0"]["feed_forward"]["w1"]["kernel"].shape == \
        (2304, 9216)
    assert shapes["layers_1"]["moe"]["w1"].shape == (64, 2304, 1024)
    assert shapes["layers_1"]["moe"]["router"].shape == (2304, 256)
    assert shapes["layers_3"]["attention"]["wkv_b"].shape == (512, 8192)
    # the deployment: 128 slots of 12.4 MiB of state, 4,609 pages of
    # 163,840 B: 1.67 GB and 0.76 GB beside the weights
    dep = cfg["deployment"]
    state = dep["max_slots"] * state_bytes_per_slot(pcfg)
    pool = dep["n_pages"] * kv_pool_page_bytes(pcfg, dep["page_size"])
    assert round(state / 1e9, 2) == 1.67 and round(pool / 1e9, 2) == 0.76
    assert round((2 * n + state + pool) / 1e9, 1) == 10.0


def test_a_program_that_cannot_express_kimi_linear_is_refused(
        kimi_toy, monkeypatch):
    """The parent has no ray_tpu.models.kimi_linear, and a program
    whose config lacks a field the model needs is no better: the family
    exits before a weight is made (this is how the parent commit fails
    on the new cell, cleanly and at once)."""
    import dataclasses
    import ray_tpu.models.kimi_linear as kl
    cfg, fam, *_ = kimi_toy

    @dataclasses.dataclass(frozen=True)
    class Lesser:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(kl, "KimiLinearConfig", Lesser)
    with pytest.raises(SystemExit, match="cannot express Kimi-Linear"):
        fam.program_config(cfg)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "ray_tpu.models.kimi_linear", None)
    with pytest.raises(SystemExit, match="no ray_tpu.models.kimi_linear"):
        fam.program_config(cfg)


def test_what_the_program_lacks_of_kimi_linear_is_refused(kimi_toy):
    cfg, fam, *_ = kimi_toy
    lin = cfg["linear_attn_config"]
    for wrong in ({"mla_use_nope": False}, {"q_lora_rank": 24},
                  {"rope_scaling": {"type": "yarn"}},
                  {"tie_word_embeddings": True},
                  {"moe_router_activation_func": "softmax"},
                  {"num_expert_group": 8}, {"topk_group": 4},
                  {"moe_layer_freq": 2}, {"num_key_value_heads": 1},
                  {"num_nextn_predict_layers": 1},
                  {"linear_attn_config": {**lin, "kda_layers": [1, 2, 3]}},
                  {"linear_attn_config": {**lin, "full_attn_layers":
                                          [0, 4]}}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


# ---------------------------------------------- program against reference

def test_the_kimi_reference_matches_the_served_model(kimi_toy):
    """Float32 both sides, full forward logits, the SAME SHARE both
    sides (experts 4-7 of 16), 150 positions: rtol 1e-4
    (tests/test_kimi_linear.py says why). A reference handed another
    share, 2 experts a token, gates that are not renormalised, another
    scaling factor, a doubled beta, no shared key or matrices rounded
    to float8 is far outside."""
    _cfg, fam, pcfg, model, params = kimi_toy
    assert pcfg.experts_held == (4, 4)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 150)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_forward(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=2e-5)
    from benchmarks.reference import kimi_linear as ref
    scale = float(np.abs(np.asarray(want)).max())
    sizes = fam._sizes(pcfg)
    for wrong in (dict(lo=0), dict(top_k=2), dict(norm_topk=False),
                  dict(scaling=1.0), dict(doubled_beta=True),
                  dict(unshared_key=True), dict(lower_precision=True)):
        out = ref.forward(rw, ids, **{**sizes, **wrong})
        gap = float(np.abs(out - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (wrong, gap, scale)


def test_the_kimi_reference_imports_nothing_of_the_program():
    for name in ("kimi_linear", "solar_open2", "llama"):
        with open(os.path.join(common.HERE, "reference",
                               name + ".py")) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
    import benchmarks.reference.kimi_linear as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


# ------------------------------------------------------ seeded weights

def test_the_kimi_weights_are_the_seeds_alone_then_balanced(kimi_toy):
    """The same seed gives the same bits twice (a seed past 2**32 goes
    through every place the family makes numbers from it), another seed
    other weights; the embedding at 1.0, the router and head at 0.02,
    every matrix by its fan-in, every norm's scale one, the decays
    moved; balancing moves the mixture layers' choice biases alone and
    leaves the held experts an even share of the routing."""
    _cfg, fam, pcfg, model, params = kimi_toy
    shapes = weights.param_shapes(model)
    again = fam.init_params(shapes, 2**32 + 7)
    other = fam.init_params(shapes, 2**32 + 8)
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
    assert abs(std(p["layers_3"]["attention"]["wkv_b"]) - 16 ** -0.5) < 0.02
    assert abs(std(p["layers_0"]["feed_forward"]["w1"]["kernel"])
               - 64 ** -0.5) < 0.01
    assert (np.asarray(p["layers_3"]["attention"]["kv_norm"]["scale"])
            == 1).all()
    assert float(np.asarray(p["layers_0"]["attention"]["dt_bias"]).mean()) \
        < -3.0
    before = fam.seeded(shapes, 2**32 + 7)["params"]
    for i in range(pcfg.n_layers):
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                before[f"layers_{i}"]):
            name = jax.tree_util.keystr(path)
            now = p[f"layers_{i}"]
            for k in path:
                now = now[k.key]
            moved = not np.array_equal(np.asarray(leaf), np.asarray(now))
            assert moved == ("router_bias" in name), (i, name)
    assert "moe" not in p["layers_0"]
    # the routing over fresh random tokens: each expert's share of the
    # pairs near 1/16 in every mixture layer
    from benchmarks.reference import solar_open2 as sref
    rw = fam.reference_weights(params, pcfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(
        1, 255, size=(8, 128)), jnp.int32)
    from benchmarks.reference import kimi_linear as ref
    from benchmarks.reference import llama as lref
    x = lref._embed(rw["embed"], ids)
    sizes = fam._sizes(pcfg)
    for w in rw["layers"]:
        w32 = {k: jnp.asarray(a, jnp.float32) for k, a in w.items()}
        if "router" in w:
            mixed = ref.mix(x, w32, n_heads=sizes["n_heads"],
                            nope=sizes["nope"], eps=sizes["eps"])
            h = lref.rms_norm(mixed, w32["ffn_norm"], sizes["eps"])
            chosen = np.asarray(sref.route(
                h.reshape(-1, h.shape[-1]), w32, 4, True, 1.0)) > 0
            share = chosen.sum(0) / chosen.sum()
            assert abs(share[4:8].sum() - 0.25) < 0.04, share
        x = ref.layer(x, w, **sizes)


# ------------------------------------------------- the flipped share

def _served_like(fam, rw, pcfg, seed, miss):
    """ids [2, 60] whose last ``SCORED_TAIL`` tokens a row are the
    reference's own greedy continuation of those before (a position at
    a time), but for ``miss`` of them, which are the reference's LEAST
    likely token there (and stay in the context of what follows)."""
    from benchmarks import parity
    G = fam.SCORED_TAIL
    ids = np.random.default_rng(seed).integers(1, 255, size=(2, 28 + G))
    wrong = {(n % 2, 30 + 3 * n) for n in range(miss)}
    for t in range(28, 28 + G):
        logits = fam.reference_forward(rw, jnp.asarray(ids[:, :t]), pcfg)
        for b in range(2):
            last = logits[b, -1]
            ids[b, t] = last.argmin() if (b, t) in wrong else last.argmax()
    return ids, parity


def test_kimi_flipped_positions_are_excused_up_to_a_share(kimi_toy,
                                                          monkeypatch):
    """``reference_logits`` hands the margin rule the reference's own
    logits; generated positions whose token lies more than the
    tolerance under the best get a row of zeros while they are at most
    ``FLIPPED_SHARE`` of the generated positions, and none does once
    they are more: the rule then fails on them."""
    _cfg, fam, pcfg, _model, params = kimi_toy
    assert fam.SCORED_TAIL == 256 and fam.FLIPPED_SHARE == 0.10
    monkeypatch.setattr(fam, "SCORED_TAIL", 32)       # a short tail here
    rw = fam.reference_weights(params, pcfg)
    P = 28
    # every token the reference's own: nothing excused, all scored
    ids, parity = _served_like(fam, rw, pcfg, 1, miss=0)
    plain = fam.reference_forward(rw, jnp.asarray(ids), pcfg)
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    assert (scored == plain).all()
    ok = parity.margin_rule(scored, ids, P)
    assert ok["ok"] and ok["same_argmax"] == 64 and ok["decisive"] > 0
    # 6 of 64 far off (under the limit): excused, zeros there, the
    # rest scored
    ids, _ = _served_like(fam, rw, pcfg, 1, miss=6)
    plain = fam.reference_forward(rw, jnp.asarray(ids), pcfg)
    assert not parity.margin_rule(plain, ids, P)["ok"]
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    zeroed = ~scored.any(-1)
    assert zeroed.sum() == 6 and not zeroed[:, :P - 1].any()
    assert (scored[~zeroed] == plain[~zeroed]).all()
    ok = parity.margin_rule(scored, ids, P)
    assert ok["ok"] and ok["worst_deficit"] <= ok["tol"]
    assert 0 < ok["decisive"] <= 58
    # 7 of 64: over the limit, nothing excused, not correct
    ids, _ = _served_like(fam, rw, pcfg, 1, miss=7)
    scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
    assert scored.any(-1).all()
    assert not parity.margin_rule(scored, ids, P)["ok"]


def test_kimi_excusing_takes_the_tolerance_as_the_rule_does(kimi_toy,
                                                            monkeypatch):
    """The tolerance is 2**-5 of the largest logit among the positions
    that STAY scored (the rule takes it over what it is handed): when
    the excused row held that largest logit the tolerance falls, and a
    position between the two tolerances is excused too, not left to
    fail."""
    from benchmarks import parity
    _cfg, fam, pcfg, _model, params = kimi_toy
    monkeypatch.setattr(fam, "SCORED_TAIL", 32)
    rw = fam.reference_weights(params, pcfg)
    ids, _ = _served_like(fam, rw, pcfg, 2, miss=0)
    real = fam.reference_forward

    def scaled(rw_, ids_, pcfg_, **kw):
        out = real(rw_, ids_, pcfg_, **kw).copy()
        out[0, -2] *= 10.0    # row 0's last scored position: the largest
        return out
    fam.reference_forward = scaled
    try:
        logits = scaled(rw, jnp.asarray(ids), pcfg)
        # row 0's last token a far miss; row 1's a miss between the
        # tolerance with that row scored and the tolerance without it
        # (last tokens: nothing follows them)
        ids[0, -1] = logits[0, -2].argmin()
        window = np.abs(logits[:, 27:59]).max(-1)
        high = 2.0 ** -5 * window.max()
        low = 2.0 ** -5 * np.delete(window.reshape(-1), 31).max()
        assert window.argmax() == 31 and low < 0.2 * high
        gap = logits[1, -2].max() - logits[1, -2]
        between = np.flatnonzero((gap > 1.05 * low) & (gap < 0.95 * high))
        assert between.size
        ids[1, -1] = between[0]
        scored = fam.reference_logits(rw, jnp.asarray(ids), pcfg)
        assert not scored[0, -2].any() and not scored[1, -2].any()
        assert scored[:, 27:58].any(-1).all()
        assert parity.margin_rule(scored, ids, 28)["ok"]
    finally:
        fam.reference_forward = real


def test_the_scored_tail_is_the_configurations_new_tokens(kimi_toy,
                                                          kimi_real):
    """The harness hands ``reference_logits`` the ids without saying
    where the prompt ends: the family counts the last ``SCORED_TAIL``
    positions a row, which is what both configurations generate."""
    tcfg, fam, *_ = kimi_toy
    assert kimi_real[0]["parity"]["new_tokens"] == fam.SCORED_TAIL == 256
    assert tcfg["parity"]["new_tokens"] == fam.SCORED_TAIL


# ---------------------------------------------------------- byte counts

def test_kimi_byte_counts_by_hand(kimi_real, kimi_toy):
    cfg, fam = kimi_real
    assert (fam.n_kda_layers(cfg), fam.n_mla_layers(cfg),
            fam.n_moe_layers(cfg)) == (6, 2, 7)
    # a token's latent entry: (512 + 64) x 2 B MUST be read a layer;
    # the pool stores it as 640 x 2 B in each of the two latent layers
    assert fam.latent_entry_bytes(cfg) == 1152
    assert fam.kv_bytes_per_token(cfg) == 2560
    # a slot's state a KDA layer: 32 x 128 x 128 x 4 B + 3 x 12,288 x 2 B
    assert fam.state_bytes(cfg) == 2_097_152
    assert fam.conv_tail_bytes(cfg) == 73_728
    assert fam.state_step_bytes(cfg, 124.0) == 124 * 2 * 2_170_880
    assert fam.expert_bytes(cfg) == 3 * 2304 * 1024 * 2 == 14_155_776
    # 39.51 M and 29.11 M parameters of token mixing a layer
    assert round(fam.layer_weight_bytes(cfg, False) / 2e6, 2) == 39.51
    assert round(fam.layer_weight_bytes(cfg, True) / 2e6, 2) == 29.11
    # 124 riders at 1,536 tokens: bytes bound the absorbed attention
    tokens = 124 * 1536.0
    assert fam.latent_step_bytes(cfg, tokens) == tokens * 1152
    assert fam.latent_step_flops(cfg, tokens) == \
        2 * 32 * (576 + 512) * tokens
    assert fam.latent_step_bytes(cfg, tokens) / 819e9 > \
        fam.latent_step_flops(cfg, tokens) / 197e12
    # the mixture's counts are a MIXTURE layer's, no mean over layers
    assert fam.experts_step_bytes(cfg, 63.0, 256.0) == \
        63 * 14_155_776 + 2 * 256 * 2304 * 2
    assert fam.experts_step_flops(cfg, 256.0) == 2 * 3 * 256 * 2304 * 1024
    # ISSUE 39's decode step: ~7.3 GB of weights, 3.3 GB of state read
    # and written, ~0.45 GB of latent entries
    weights_only = fam.decode_step_bytes(cfg, 0.0, 0, experts_touched=63)
    assert round(weights_only / 1e9, 1) == 7.3
    full = fam.decode_step_bytes(cfg, tokens, 128, experts_touched=63)
    state = 6 * fam.state_step_bytes(cfg, 128)
    assert round(state / 1e9, 1) == 3.3
    assert round((full - weights_only - state) / 1e9, 2) == 0.44
    assert 13.0 < 1e3 * full / 819e9 < 14.0            # ms at the peak
    assert fam.decode_step_bytes(cfg, 0.0, 128) > \
        fam.decode_step_bytes(cfg, 0.0, 128, experts_touched=60.0)
    tcfg, *_ = kimi_toy
    assert (fam.n_kda_layers(tcfg), fam.n_mla_layers(tcfg)) == (6, 2)
    assert fam.kv_bytes_per_token(tcfg) == 2 * 128 * 2


def test_both_kinds_of_scope_are_parts_of_their_own(kimi_real):
    _cfg, fam = kimi_real
    kda = "jit(decode)/while/body/KimiLinear/layers_1/"
    mla = "jit(decode)/while/body/KimiLinear/layers_3/"
    for scope in fam.KDA_SCOPES:
        assert trace_parts.part_of(
            kda + f"attention/{scope}/mul:", fam.parts) == scope
    for scope in fam.MLA_SCOPES + fam.LATENT_WINDOW_SCOPES:
        assert trace_parts.part_of(
            mla + f"attention/{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(mla + "attention/mla_q/wq/dot_general:",
                               fam.parts) == "mla_q"
    assert trace_parts.part_of(kda + "attention/wq/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(mla + "attention/wo/dot_general:",
                               fam.parts) == "projections"
    dense = "jit(prefill)/KimiLinear/layers_0/"
    assert trace_parts.part_of(dense + "feed_forward/w1/dot_general:",
                               fam.parts) == "mlp"
    for scope in fam.MOE_SCOPES + ("moe_shared",):
        assert trace_parts.part_of(kda + f"moe/{scope}/dot_general:",
                                   fam.parts) == scope


# -------------------------------------------------- the four new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.2, "overlap": True,
            "decode_riders": 100, "decode_steps": 2,
            "decode_window_tokens": 2048, "decode_context_tokens": 150000}
    base.update(data)
    return (0, t, "round", None, None, base)


def _joined_run(kimi_real, tmp_path):
    """A hand-made --trace 2 run as the join leaves it: two matched
    jit_decode executions of 2 steps (rounds 11 and 12, 100 and 120
    riders), a jit_prefill between them, and a THIRD jit_decode that
    the stop cut (the chip's last execution: the join has no row for
    it and its operations count nowhere). A step: 6 KDA layers' state
    update of 1 ms each, 2 MLA layers' block loop of 0.4 ms each
    (0.3 + 0.05 + 0.05), 7 mixture layers' experts of 1.5 ms each, the
    head."""
    cfg, fam = kimi_real
    base = "jit(decode)/while/body/KimiLinear/"
    step = ([(f"layers_{i}/attention/kda_recurrence/mul:", 1_000_000)
             for i in (0, 1, 2, 4, 5, 6)]
            + [(f"layers_{i}/attention/{scope}/dot_general:", dur)
               for i in (3, 7) for scope, dur in (
                   ("kv_gather", 300_000), ("attn_scores", 50_000),
                   ("attn_pv", 50_000), ("mla_absorb", 30_000))]
            + [(f"layers_{i}/moe/moe_experts/custom-call:", 1_500_000)
               for i in range(1, 8)]
            + [("head/dot_general:", 40_000)])
    ops, modules, t = [], [], 0
    for n_steps, name in ((2, "jit_decode(1)"), (0, "jit_prefill(2)"),
                          (2, "jit_decode(1)"), (1, "jit_decode(1)")):
        t0 = t
        if not n_steps:
            ops.append(["%p = f32[8] fusion(", t, 5_000_000,
                        "jit(prefill)/KimiLinear/layers_1/attention/"
                        "kda_recurrence/mul:"])
            t += 5_000_000
        for _ in range(n_steps):
            for scope, dur in step:
                ops.append(["%f = f32[8] fusion(", t, dur, base + scope])
                t += dur
        modules.append([name, t0, t - t0])
        t += 1000
    rows = [{"program": "jit_decode", "round": 11, "steps": 2,
             "start_ns": modules[0][1], "device_ms": modules[0][2] / 1e6},
            {"program": "jit_prefill", "round": 12, "steps": 0,
             "start_ns": modules[1][1], "device_ms": modules[1][2] / 1e6},
            {"program": "jit_decode", "round": 12, "steps": 2,
             "start_ns": modules[2][1], "device_ms": modules[2][2] / 1e6}]
    events = [_round(1.0), _round(2.0),
              _round(11.0, round=11,
                     moe_decode_experts_touched=63 * 14,
                     moe_decode_pairs=256 * 14, moe_decode_layer_steps=14),
              _round(12.0, round=12, decode_riders=120,
                     decode_context_tokens=200000,
                     moe_decode_experts_touched=63 * 14,
                     moe_decode_pairs=256 * 14, moe_decode_layer_steps=14)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        deployment=cfg["deployment"],
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events, trace={},
        samples=[{"t": 1.0, "free_slots": 40}, {"t": 2.0, "free_slots": 4},
                 {"t": 3.0, "free_slots": 6}, {"t": 9.0, "free_slots": 0}])
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {"rows": rows,
                     "by_round": {e[5]["round"]: e[5] for e in events[2:]}}
    return run


def test_the_four_new_readers_on_a_hand_made_run(kimi_real, tmp_path):
    cfg, fam = kimi_real
    run = _joined_run(kimi_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    # 4 steps over the two matched executions; the cut one counts nowhere
    assert got["steps"] == 4 and got["rounds"] == [11, 12]
    assert got["riders"] == pytest.approx(110.0)
    assert got["parts"]["kda_recurrence"] == pytest.approx(4 * 6 * 1e-3)
    # the riders' own contexts a step: the sum after the dispatch less
    # half a step's growth a step before its last
    tokens = ((150000 - 100 * 0.5) + (200000 - 120 * 0.5)) / 2
    assert got["context_tokens"] == pytest.approx(tokens)
    # 128 slots, at most 124 of them busy in the window
    assert read("state_peak_share")(run) == pytest.approx(
        100.0 * 124 / 128)
    # a KDA layer-step took 1 ms; 110 riders must move 2 x 2,170,880 B
    assert read("linear_state_roofline.by_kind")(run) == pytest.approx(
        100.0 * (110 * 2 * 2_170_880 / 819e9) / 1e-3)
    # an MLA layer-step's block loop took 0.4 ms (not the absorb's 0.03)
    assert read("latent_attn_roofline.by_kind")(run) == pytest.approx(
        100.0 * (tokens * 1152 / 819e9) / 0.4e-3)
    # a mixture layer-step's experts took 1.5 ms for 63 experts touched
    # and 256 pairs (the traced seconds' counters)
    least = (63 * 14_155_776 + 2 * 256 * 2304 * 2) / 819e9
    assert read("moe_experts_roofline.by_kind")(run) == pytest.approx(
        100.0 * least / 1.5e-3)
    for name in NEW_READERS[1:]:
        assert 0.0 < read(name)(run) < 100.0, name


def test_the_new_readers_find_nothing_where_there_is_nothing(
        kimi_real, tmp_path):
    """Another family, a join that was refused, a program without the
    scopes, rounds without the counter, spans that disagree with the
    rows: None, never an error."""
    read = common.load_metric_reader
    run = _joined_run(kimi_real, tmp_path)
    other = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("axk1", "serve")})
    for name in NEW_READERS:
        assert read(name)(other) is None, name
    refused = _joined_run(kimi_real, tmp_path)
    refused._dispatch = None
    for name in NEW_READERS[1:]:
        assert read(name)(refused) is None, name
    assert read("state_peak_share")(refused) is not None
    unnamed = _joined_run(kimi_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = (op[3].replace("kda_", "x_").replace("kv_gather", "x")
                 .replace("attn_", "x_").replace("moe_experts", "x"))
    for name in NEW_READERS[1:]:
        assert read(name)(unnamed) is None, name
    old = _joined_run(kimi_real, tmp_path)
    for d in old._dispatch["by_round"].values():
        del d["decode_context_tokens"]
    assert read("latent_attn_roofline.by_kind")(old) is None
    assert read("linear_state_roofline.by_kind")(old) is not None
    short = _joined_run(kimi_real, tmp_path)
    del short._trace_parts["ir"]["modules"][0]
    for name in NEW_READERS[1:]:
        assert read(name)(short) is None, name
    no_window = types.SimpleNamespace(**{**vars(run), "samples": []})
    assert read("state_peak_share")(no_window) is None


# ------------------------------------------------- the cell and its mix

def test_the_cell_and_gen_sat():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "gen-sat", 1)
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
        "moe_held_pair_share", "dispatch_prefill_call_ms",
        "dispatch_decode_step_ms", "dispatch_prefill_share", *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-4:]) == NEW_READERS
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["better"] == "higher"
        assert callable(common.load_metric_reader(m["name"]))
    assert [m["layer"] for m in bench["per_layer"][-4:]] == \
        ["KV pages", "kernels", "kernels", "kernels"]
    # the older readers divide by num_hidden_layers or by
    # trace_reduce.loop_steps (PERF.md section 7): the cell is on none
    assert not per_layer & {
        "latent_attn_roofline", "linear_state_roofline",
        "decode_linear_attn_ms", "decode_latent_attn_ms", "decode_moe_ms",
        "moe_experts_roofline"}
    tr = common.load_json("traffic", "gen-sat.json")
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prefix_cache"] is False and tr["shared_prefix_tokens"] == 0
    assert tr["population"] == 512 and 30.0 <= tr["ramp_s"] <= 45.0
    assert "backlog_rounds" in tr["note"]
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {1024}
    assert {r.output_len for r in reqs} == {1024}
    # ids come from the configuration's vocabulary slice
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**32 + 5, 7, 1024, cfg["vocab_size"])
    assert len(ids) == 1024 and 1 <= min(ids) and max(ids) < 40959
    # 128 slots (256 clients), a page table and a pool in which pages
    # never bound the slots: 32 pages a request, 36 a slot
    dep = cfg["deployment"]
    assert dep["max_slots"] in (128, 96)
    per_slot = -(-(1024 + 1024) // dep["page_size"])
    assert dep["max_slots"] * (per_slot + 4) == dep["n_pages"] - 1
    assert per_slot * dep["page_size"] <= cfg["model_max_length"]
    # the parity prompt crosses four chunks, sixteen pages and the
    # 512-token edge of the window loop's block
    assert cfg["parity"] == {"prompts": 2, "prompt_len": 1024,
                             "new_tokens": 256}


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-kimi-linear.gen-sat", "--seed",
         str(2**32 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_the_kimi_rehearsal_cell_runs():
    """The toy cell borrows kimi-linear-d8.gen-sat's metric lists:
    correct against the plain reference through the served path (100
    tokens of prompt in chunks, both kinds of state), no program built
    in the window, the counter metrics there, ``state_peak_share``
    among them; the device_trace metrics need a device in the trace,
    which a CPU has not (the hand-made run above checks their
    readers)."""
    line, stdout = _rehearse("2")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    share = line["metrics"]["moe_held_pair_share"]
    # 4 of 16 experts held, the routers balanced: a quarter of the pairs
    assert share["unit"] == "%" and 20.0 <= share["value"] <= 30.0
    touched = line["metrics"]["moe_experts_touched_mean"]["value"]
    assert 0.0 < touched <= 4.0             # counted over HELD experts
    state = line["metrics"]["state_peak_share"]
    assert state["unit"] == "%" and 25.0 <= state["value"] <= 100.0
    for name in common.load_rehearsal_cell(
            "toy-kimi-linear.gen-sat")["reports"]:
        assert name in line["metrics"], name
    for name in NEW_READERS[1:]:
        assert name not in line["metrics"]
    assert "[correct] kimi_linear:" in stdout
    assert "decode_context_tokens" in stdout and "state_slots" in stdout
