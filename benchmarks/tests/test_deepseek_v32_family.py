"""The DeepSeek-V3.2 family (families/deepseek_v32.py,
reference/deepseek_v32.py, configs/deepseek-v3.2-d5-ep32.json, the toy
``rehearsal/toy-deepseek-v32.json``, traffic/longdoc-sat.json) on the
CPU: the configuration against its published copy, the program's config
the family builds, the served model against the plain reference at the
toy's sizes (a SHARE: 4 of 16 experts held), the near-tie rule with the
groups' boundary among the ties, the byte counts against hand counts,
the seven new readers on a hand-made joined trace, the cell on
longdoc-sat as it stands, and the rehearsal cell at --trace 0 and 2."""
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

CONFIG = "deepseek-v3.2-d5-ep32"
CELL = "dsv32-d5.longdoc-sat"
NEW_READERS = ("decode_index_ms", "decode_sparse_attn_ms",
               "index_roofline", "sparse_attn_roofline",
               "prefill_sparse_attn_share", "sparse_read_ratio",
               "sparse_step_roofline")
REDUCIBLE = {"num_hidden_layers", "first_k_dense_replace",
             "max_position_embeddings", "n_routed_experts", "vocab_size",
             "num_nextn_predict_layers"}


@pytest.fixture(scope="module")
def dsv32_toy():
    cfg = common.load_json("rehearsal", "toy-deepseek-v32.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    return cfg, fam, pcfg, model, params


@pytest.fixture(scope="module")
def dsv32_real():
    cfg = common.load_json("configs", CONFIG + ".json")
    return cfg, common.load_family(cfg["family"], cfg["kind"])


# ------------------------------------------------------ the configuration

def test_the_dsv32_file_holds_the_published_sizes_but_for_reduced(
        dsv32_real):
    """Every key of the source's config.json as the catalog gives it
    (tests/published/): equal, or listed in ``reduced`` with the
    published value under ``reduced_from``; no width is reduced."""
    cfg, _fam = dsv32_real
    with open(os.path.join(common.HERE, "tests", "published",
                           CONFIG + ".json")) as f:
        source = json.load(f)
    assert len(source) == 36 and source["model_type"] == "deepseek_v32"
    for key, want in source.items():
        if key in cfg["reduced"]:
            assert cfg[key] != want and cfg["reduced_from"][key] == want
        else:
            assert cfg[key] == want, key
    assert set(cfg["reduced"]) == set(cfg["reduced_from"]) \
        == set(cfg["why_reduced"]) == REDUCIBLE
    bench = common.load_benchmark()
    entry = common.find_named(bench["configs"], CONFIG, "configuration")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for width in ("hidden_size", "intermediate_size",
                  "moe_intermediate_size", "kv_lora_rank", "q_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "index_head_dim", "index_n_heads", "index_topk",
                  "num_experts_per_tok", "num_attention_heads", "n_group",
                  "topk_group"):
        assert width not in cfg["reduced"]
    # the share: 8 of 256 held from expert 0, the router at its width
    assert (cfg["n_routed_experts"], cfg["router_width"],
            cfg["experts_held_from"]) == (8, 256, 0)
    assert cfg["vocab_size"] * 8 == 129280
    for key in ("attention", "yarn", "rope_pairing", "indexer_norm",
                "indexer_rope", "indexer_precision", "indexer_weight",
                "selection_ties", "router", "router_bias", "weights",
                "near_ties"):
        assert cfg["assumed"][key], key


def test_the_dsv32_program_config_is_the_published_model_cut_to_the_share(
        dsv32_real):
    from ray_tpu.models.deepseek_v32 import deepseek_v32_param_count
    from ray_tpu.models.kv_cache import KIND_INDEXED
    cfg, fam = dsv32_real
    pcfg = fam.program_config(cfg)
    assert (pcfg.n_layers, pcfg.first_k_dense, pcfg.n_heads) == (5, 1, 128)
    assert (pcfg.num_experts, pcfg.experts_held) == (256, (0, 8))
    assert (pcfg.n_group, pcfg.topk_group, pcfg.router) == \
        (8, 4, "sigmoid_bias")
    assert (pcfg.index_n_heads, pcfg.index_head_dim, pcfg.index_topk) == \
        (64, 128, 2048)
    assert pcfg.layer_kinds == (KIND_INDEXED,) * 5
    assert pcfg.rope_factor == 40.0 and abs(
        pcfg.softmax_scale - 0.13523) < 5e-6
    # ISSUE 56's arithmetic: 3.226 B parameters = 6.45 GB
    n = deepseek_v32_param_count(pcfg, experts=8)
    assert abs(n / 3.226e9 - 1.0) < 1e-3 and abs(2 * n / 6.45e9 - 1) < 2e-3


def test_what_the_program_lacks_of_dsv32_is_refused(dsv32_toy):
    cfg, fam, *_ = dsv32_toy
    for key, value in (("topk_method", "none"),
                       ("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax")):
        with pytest.raises(SystemExit, match="has no"):
            fam.program_config(dict(cfg, **{key: value}))


def test_the_dsv32_reference_imports_nothing_of_the_program():
    path = os.path.join(common.HERE, "reference", "deepseek_v32.py")
    with open(path) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("benchmarks", "")
    assert 'default_matmul_precision("highest")' in text


def test_the_dsv32_reference_matches_the_served_model(dsv32_toy):
    """The toy through the chunked-prefill and decode programs' own
    path (both pools) against the plain reference handed the same
    share, on logits; and the reference's controls move them."""
    from ray_tpu.models.kv_cache import (init_kv_pool, kv_layer_store,
                                         kv_layer_view)
    cfg, fam, pcfg, model, params = dsv32_toy
    ids = np.random.default_rng(5).integers(1, 255, size=(1, 140))
    rw = fam.reference_weights(params, pcfg)
    want, margin = fam.reference_forward(
        rw, jnp.asarray(ids, jnp.int32), pcfg, margins=True)
    pool = init_kv_pool(pcfg, 24, 8)
    table = jnp.asarray(1 + np.arange(20)[None], jnp.int32)

    @jax.jit
    def call(pool, chunk, pos):
        views = [kv_layer_view(layer, table) for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    got = []
    for start, n in ((0, 64), (64, 64)) + tuple(
            (128 + i, 1) for i in range(12)):
        logits, pool = call(pool, jnp.asarray(ids[:, start:start + n],
                                              jnp.int32),
                            jnp.asarray([start], jnp.int32))
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), want[0], rtol=1e-4,
                               atol=2e-5)
    assert margin.shape == (1, 140) and np.isfinite(margin).any()
    for control in ("no_selection", "recent", "no_group_limit", "no_bias",
                    "lower_precision"):
        assert control in fam.CONTROLS
        moved = fam.reference_forward(rw, jnp.asarray(ids, jnp.int32),
                                      pcfg, **{control: True})
        assert np.abs(moved - want).max() > 0.02, control


def test_the_weights_are_the_seeds_alone_and_the_routers_balanced(dsv32_toy):
    _cfg, fam, pcfg, model, params = dsv32_toy
    shapes = weights.param_shapes(model)
    assert shapes.pcfg == pcfg
    again = fam.init_params(shapes, 2**31 + 7)
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                  params, again)
    assert all(jax.tree_util.tree_leaves(same))
    p = params["params"]
    raw = fam.seeded(shapes, 2**31 + 7)["params"]
    norm = p["layers_0"]["indexer"]["k_norm"]
    assert (np.asarray(norm["scale"]) == 1).all()
    assert not np.asarray(norm["bias"]).any()
    wq = np.asarray(p["layers_0"]["indexer"]["wq_b"]["kernel"])
    assert abs(wq.std() * wq.shape[0] ** 0.5 - 1.0) < 0.1
    # only the routers' biases differ from the seeded weights: zeros
    # before, a balance after
    assert not np.asarray(raw["layers_1"]["moe"]["router_bias"]).any()
    bias = np.asarray(p["layers_1"]["moe"]["router_bias"])
    assert 0.0 < np.abs(bias).max() < 0.5
    assert (np.asarray(raw["layers_1"]["moe"]["router"])
            == np.asarray(p["layers_1"]["moe"]["router"])).all()
    # balanced: on fresh random tokens every expert of the router's
    # width is chosen about equally often through the group limit,
    # where the zero bias leaves the loads uneven
    from benchmarks.reference import deepseek_v32 as ref
    rw = fam.reference_weights(params, pcfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(
        1, 255, size=(8, 128)), jnp.int32)
    x = rw["embed"][ids].astype(jnp.float32)
    sizes = fam._sizes(pcfg)
    x, *_ = ref.layer_and_margin(x, rw["layers"][0], **sizes)
    w = {k: jnp.asarray(v, jnp.float32)
         for k, v in rw["layers"][1].items()}
    x = ref.dsa(x, w, **{k: sizes[k] for k in (
        "n_heads", "nope", "rope", "eps", "yarn", "idx_heads", "topk")})
    h = ref.llama.rms_norm(x, w["ffn_norm"], sizes["eps"]).reshape(-1, 64)

    def spread(bias):
        weight = ref.route(h, dict(w, router_bias=bias), top_k=4,
                           norm_topk=True, scaling=2.5, n_group=4,
                           topk_group=2)
        load = np.asarray((weight > 0).sum(0))
        return load.std() / load.mean()
    assert spread(w["router_bias"]) < 0.5 * spread(jnp.zeros(16))


def test_dsv32_near_ties_of_the_choice_are_not_scored(dsv32_toy,
                                                      monkeypatch, capsys):
    """``reference_logits`` zeroes the rows the rule calls near-ties,
    and the rule sees the GROUPS' boundary: a tie between the last
    group that stays and the first that does not counts where the held
    experts' choice differs across it, and nowhere else."""
    from benchmarks.reference import deepseek_v32 as ref
    cfg, fam, pcfg, _model, params = dsv32_toy
    ids = jnp.asarray(np.random.default_rng(9).integers(
        1, 255, size=(2, 60)), jnp.int32)
    rw = fam.reference_weights(params, pcfg)
    plain, margin = fam.reference_forward(rw, ids, pcfg, margins=True)
    # (ids whose every token is the one the reference would serve)
    served = np.concatenate([np.asarray(ids)[:, :1],
                             plain[:, :-1].argmax(-1)], axis=1)
    scored = fam.judged(plain, margin, served)
    unsure = margin < fam.NEAR_TIE
    assert 0 < unsure.sum() < unsure.size
    assert not scored[unsure].any()
    assert (scored[~unsure] == plain[~unsure]).all()
    assert "near-ties of the choice of held experts" in \
        capsys.readouterr().out
    # the family's own limits: where the scored positions' deficits
    # break one (their mean, their worst, the share of them over one
    # tolerance), the logits come back not finite (the rule reads that
    # as not correct) and the line says which; inside all three, the
    # few scored positions over one tolerance are excused
    logits = np.zeros((1, 130, 8), np.float32)
    logits[..., 0] = 1.0
    served = np.zeros((1, 130), np.int64)
    sure = np.full((1, 130), np.inf, np.float32)
    assert fam.SCORED_TAIL == 128 and fam.deficits(
        logits, sure < 1, served) == (0.0, 0.0, 128)
    from benchmarks import parity
    assert parity.margin_rule(fam.judged(logits, sure, served), served,
                              20)["ok"]
    once = served.copy()
    once[0, 30] = 3                      # one position a whole logit off
    mean, worst, n = fam.deficits(logits, sure < 1, once)
    assert (round(mean * 128), round(worst), n) == (32, 32, 128)
    capsys.readouterr()
    assert not np.isfinite(fam.judged(logits, sure, once)).any()
    assert "NOT correct by the mean over 0.05, the worst over 2.5" in \
        capsys.readouterr().out
    # the same position 1.25 tolerances off: one of 128 scored, under
    # every limit, excused; at 2.75 the worst alone refuses it; and
    # three such positions are more than may
    near = logits.copy()
    near[0, 29, 3] = 1.0 - 1.25 / 32
    excused = fam.judged(near, sure, once)
    assert not excused[0, 29].any() and excused[0, 28].any()
    assert parity.margin_rule(excused, once, 20)["ok"]
    assert "1 of them over one tolerance (limit 2): inside" in \
        capsys.readouterr().out
    far = logits.copy()
    far[0, 29, 3] = 1.0 - 2.75 / 32
    assert not np.isfinite(fam.judged(far, sure, once)).any()
    assert "NOT correct by the worst over 2.5\n" in capsys.readouterr().out
    thrice = once.copy()
    thrice[0, 40], thrice[0, 50] = 3, 3
    for at in (39, 49):
        near[0, at, 3] = 1.0 - 1.25 / 32
    monkeypatch.setattr(fam, "MEAN_DEFICIT_LIMIT", 1.0)
    assert not np.isfinite(fam.judged(near, sure, thrice)).any()
    assert "NOT correct by more than 2 over one tolerance" in \
        capsys.readouterr().out
    # a hand-made router: 8 experts in 4 groups of 2, two groups stay,
    # 2 experts a token, experts 0-1 (group 0) held. Group scores 1.0,
    # 0.98, 0.4, 0.2: groups 0 and 1 stay, far from the boundary
    h = jnp.ones((1, 1, 8))

    def margin_of(s):
        logit = jnp.log(jnp.asarray(s)) - jnp.log1p(-jnp.asarray(s))
        w = {"router": jnp.diag(logit / 1.0) / 1.0,
             "router_bias": jnp.zeros((8,)),
             "w_gate": jnp.zeros((2, 8, 4))}
        # h @ diag(logit) = logit
        return float(ref.choice_margin(h, w, top_k=2, lo=0, n_group=4,
                                       topk_group=2)[0, 0])
    clear = margin_of([0.9, 0.1, 0.5, 0.48, 0.3, 0.1, 0.15, 0.05])
    assert clear > 1.0
    # groups 1 and 2 tie (0.98 against 0.979), but whichever stays, the
    # held experts' choice (expert 0 in, expert 1 out) is the same
    unfelt = margin_of([0.9, 0.1, 0.5, 0.48, 0.6, 0.379, 0.15, 0.05])
    assert unfelt > 1.0
    # group 0 itself ties with group 2 for the last place: its expert 0
    # is chosen on one side of the tie and cannot be on the other
    felt = margin_of([0.5, 0.1, 0.9, 0.48, 0.45, 0.149, 0.15, 0.05])
    assert felt < fam.NEAR_TIE


def test_dsv32_byte_counts_by_hand(dsv32_real, dsv32_toy):
    cfg, fam = dsv32_real
    # a token stores 640 x 2 B and 128 x 2 B a layer, five layers
    assert fam.latent_entry_bytes(cfg) == 1280
    assert fam.index_key_bytes(cfg) == 256
    assert fam.kv_bytes_per_token(cfg) == 7680
    dep = cfg["deployment"]
    pool = dep["n_pages"] * dep["page_size"] * fam.kv_bytes_per_token(cfg)
    assert abs(pool / 2.14e9 - 1.0) < 5e-3        # ISSUE 56: 2.14 GB
    # ISSUE 56: at 8.5k of context a rider reads 2,048 x 1,280 B +
    # 8,500 x 256 B = 4.8 MB a layer against 10.9 MB dense
    sparse = (fam.sparse_attn_step_bytes(cfg, 2048)
              + fam.index_step_bytes(cfg, 8500))
    assert abs(sparse / 4.8e6 - 1.0) < 0.01
    assert abs(8500 * 1280 / 10.9e6 - 1.0) < 0.01
    assert fam.chosen_entries(cfg, 24 * 8500, 24) == 24 * 2048
    assert fam.chosen_entries(cfg, 3000, 24) == 3000
    assert fam.index_step_flops(cfg, 1000) == 2.0 * 64 * 129 * 1000
    assert fam.sparse_attn_step_flops(cfg, 2048) == \
        2.0 * 128 * (576 + 512) * 2048
    # the matrices: 187.11 M of attention and 13.96 M of indexer (less
    # the norms) a layer
    assert fam.attention_weight_bytes(cfg) == 2 * (
        187_107_328 - 2048 + 13_959_424 - 256)
    assert fam.expert_bytes(cfg) == 3 * 7168 * 2048 * 2
    assert fam.n_moe_layers(cfg) == 4
    assert fam.experts_step_bytes(cfg, 3.0, 5.0) == \
        3 * fam.expert_bytes(cfg) + 2 * 5 * 7168 * 2
    whole = fam.decode_step_bytes(cfg, 24 * 8500.0, 24,
                                  experts_touched=4.0, chosen=24 * 2048.0)
    by_hand = (5 * fam.attention_weight_bytes(cfg)
               + 3 * 7168 * 18432 * 2
               + 4 * (5 * fam.expert_bytes(cfg) + 7169 * 256 * 4)
               + 5 * (24 * 8500 * 256 + 24 * 2048 * 1280 + 24 * 1536)
               + 16160 * 7168 * 2 + 24 * 7168 * 2)
    assert whole == by_hand
    # a walk over the whole context would move more
    assert fam.decode_step_bytes(cfg, 24 * 8500.0, 24, experts_touched=4.0,
                                 chosen=24 * 8500.0) > whole
    tcfg, *_ = dsv32_toy
    assert fam.kv_bytes_per_token(tcfg) == 3 * (128 + 32) * 2


def test_the_selection_scopes_are_parts_of_their_own(dsv32_real):
    _cfg, fam = dsv32_real
    base = "jit(decode)/while/body/DeepSeekV32/layers_1/"
    for scope in fam.DSA_SCOPES:
        where = "indexer/" if scope.startswith("dsa_index") else \
            "attention/"
        assert trace_parts.part_of(
            base + where + f"{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(
        base + "attention/dsa_attn/attn_scores/latent_window:",
        fam.parts) == "dsa_attn"
    assert trace_parts.part_of(base + "attention/mla_q/wq_b/dot_general:",
                               fam.parts) == "mla_q"
    assert trace_parts.part_of(base + "attention/wo/dot_general:",
                               fam.parts) == "projections"
    for scope in fam.MOE_SCOPES + ("moe_shared",):
        assert trace_parts.part_of(base + f"moe/{scope}/dot_general:",
                                   fam.parts) == scope
    assert set(fam.INDEX_SCOPES) < set(fam.DSA_SCOPES) < \
        set(fam.LATENT_ATTN_SCOPES) == set(fam.parts["attention"])


# ------------------------------------------------- the seven new readers

def _round(t, n, **data):
    base = {"round": n, "host_gap_s": 1e-4, "wall_s": 0.1, "overlap": True}
    base.update(data)
    return (n, t, "round", None, None, base)


def _joined_run(dsv32_real, tmp_path):
    """A hand-made --trace 2 run: two matched jit_decode executions of
    2 steps each, five layers' operations named by scope, and one
    jit_prefill run; the rounds of the traced seconds carry 24 riders
    of 8,500 tokens and the selection's counters."""
    cfg, fam = dsv32_real
    dec = "jit(decode)/while/body/DeepSeekV32/layers_%d/"
    body = [("indexer/dsa_index_q/dot_general:", 10),
            ("indexer/dsa_index_k/scatter:", 5),
            ("indexer/dsa_index_scores/dot_general:", 200),
            ("attention/dsa_topk/reduce:", 300),
            ("attention/dsa_attn/latent_window:", 1500),
            ("attention/mla_q/wq_b/dot_general:", 100),
            ("attention/wo/dot_general:", 300),
            ("moe/moe_experts/custom-call:", 400)]
    ops, modules, t = [], [], 0
    for run_i in range(2):
        start = t
        for _step in range(2):
            for layer in range(5):
                for i, (scope, dur) in enumerate(body):
                    ops.append([f"%f.{i} = f32[8] fusion(", t, dur,
                                dec % layer + scope])
                    t += dur
            ops.append(["%h = f32[8] fusion(", t, 40,
                        "jit(decode)/while/body/DeepSeekV32/head/dot:"])
            t += 40
        modules.append([f"jit_decode({run_i})", start, t - start])
        t += 1000
    pre = "jit(prefill)/DeepSeekV32/layers_1/"
    p0 = t
    for scope, dur in (("indexer/dsa_index_scores/dot_general:", 500),
                       ("attention/dsa_topk/reduce:", 1500),
                       ("attention/dsa_attn/latent_window:", 4000),
                       ("attention/mla_q/wq_b/dot_general:", 1000),
                       ("attention/wo/dot_general:", 1000),
                       ("moe/moe_shared/dot_general:", 2000)):
        ops.append(["%p = f32[8] fusion(", t, dur, pre + scope])
        t += dur
    modules.append(["jit_prefill(2)", p0, t - p0])
    # the join drops the trace's last module (cut by the stop)
    modules.append(["jit_decode(9)", t + 10, 5])
    steps = 2
    per_round = dict(
        decode_riders=24, decode_steps=steps,
        decode_context_tokens=24 * 8500,
        moe_decode_layer_steps=4 * steps,
        moe_decode_experts_touched=4 * steps * 3, moe_decode_pairs=40,
        decode_index_keys_scored=5 * steps * 24 * 8500,
        decode_sparse_entries_chosen=5 * steps * 24 * 2048,
        decode_sparse_entries_read=5 * steps * 24 * 8704,
        index_keys_scored=5 * steps * 24 * 8500 + 5 * 1024 * 4000,
        sparse_entries_chosen=5 * steps * 24 * 2048 + 5 * 1024 * 2000,
        sparse_entries_read=5 * steps * 24 * 8704 + 5 * 1024 * 4608)
    events = [_round(1.0, 1, **per_round), _round(2.0, 2, **per_round),
              _round(11.0, 11, **per_round), _round(12.0, 12, **per_round)]
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, chips=1,
        trace_dir=str(tmp_path), window=(0.5, 8.0),
        trace_span=(10.0, 14.0), deployment=cfg["deployment"],
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=events)
    run._trace_parts = {"ir": {"modules": modules, "ops": ops}}
    run._dispatch = {
        "rows": [{"program": "jit_decode", "steps": steps, "round": 11},
                 {"program": "jit_decode", "steps": steps, "round": 12},
                 {"program": "jit_prefill", "steps": 0, "round": 12}],
        "by_round": {e[5]["round"]: e[5] for e in events[2:]}}
    return run


def test_the_seven_readers_on_a_hand_made_run(dsv32_real, tmp_path, capsys):
    cfg, fam = dsv32_real
    run = _joined_run(dsv32_real, tmp_path)
    read = common.load_metric_reader
    got = fam.decode_parts_by_rounds(run)
    assert got["steps"] == 4 and got["riders"] == pytest.approx(24.0)
    # a dispatch of 2 steps ends at 8,500 tokens a rider: its steps'
    # mean context is half a token less
    assert got["context_tokens"] == pytest.approx(24 * 8500 - 12)
    assert "[dsv32] jit_decode over the 2 matched executions: 4 steps" \
        in capsys.readouterr().out
    # a step: five layers of 200 ns of scores and 300 ns of choice
    assert read("decode_index_ms")(run) == pytest.approx(5 * 500e-6)
    assert read("decode_sparse_attn_ms")(run) == pytest.approx(5 * 1500e-6)
    counted = fam.selection_counters(run)
    assert counted["layer_steps"] == 2 * 2 * 5
    assert counted["sparse_entries_chosen"] / counted["layer_steps"] == \
        24 * 2048
    # 24 riders' 8,500 index keys of 256 B in 200 ns a layer-step; their
    # 2,048 chosen entries of 1,280 B in 1,500 ns (bytes bound both)
    assert read("index_roofline")(run) == pytest.approx(
        100.0 * (24 * 8500 * 256 / 819e9) / 200e-9)
    assert read("sparse_attn_roofline")(run) == pytest.approx(
        100.0 * (24 * 2048 * 1280 / 819e9) / 1500e-9)
    # the prefill call: 6,000 of its 10,000 ns under the dsa scopes
    assert read("prefill_sparse_attn_share")(run) == pytest.approx(60.0)
    assert read("prefill_attn_share")(run) == pytest.approx(70.0)
    # the window's two rounds: read over chosen
    chosen = 5 * 2 * 24 * 2048 + 5 * 1024 * 2000
    fetched = 5 * 2 * 24 * 8704 + 5 * 1024 * 4608
    assert read("sparse_read_ratio")(run) == pytest.approx(fetched / chosen)
    line = capsys.readouterr().out
    # (a decode step walks its riders' 17 blocks of 512: 4.25 x chosen)
    assert "decode steps read 4177920 of 983040 chosen = 4.2500" in line
    assert "= 2.3040" in line
    # the whole step: the family's bytes at 3 experts touched and the
    # counted entries, over (5 x 2,815 + 40) ns
    least = fam.decode_step_bytes(
        cfg, 24 * 8500 - 12, 24.0, experts_touched=3.0,
        chosen=24 * 2048.0) / 819e9
    assert read("sparse_step_roofline")(run) == pytest.approx(
        100.0 * least / (5 * 2815e-9 + 40e-9))
    assert read("moe_experts_roofline.by_kind")(run) is not None
    # two older readers the cell is on: the latent attention WITH its
    # selector a step (the head's executions count the steps: 4, and the
    # trace's last, cut module has none), and the rows a touched expert
    assert fam.decode_steps_traced(run) == 4.0
    assert read("decode_latent_attn_ms")(run) == pytest.approx(
        5 * (10 + 5 + 200 + 300 + 1500 + 100) * 1e-6)
    assert read("moe_rows_per_expert_mean")(run) == pytest.approx(
        40 / (4 * 2 * 3))


def test_the_readers_find_nothing_where_there_is_nothing(dsv32_real,
                                                         tmp_path):
    """Another family, a program without the scopes or the counters
    (the parent, with this PR's readers laid over it), a run without a
    trace or a join: None, never an error."""
    run = _joined_run(dsv32_real, tmp_path)
    read = common.load_metric_reader
    for family in ("axk1", "olmoe"):
        other = types.SimpleNamespace(**{
            **vars(run), "family": common.load_family(family, "serve")})
        for name in NEW_READERS:
            if name != "sparse_read_ratio":
                assert read(name)(other) is None, (family, name)
    bare = types.SimpleNamespace(**vars(run))
    del bare.trace_dir, bare._trace_parts, bare._dispatch
    for name in NEW_READERS:
        if name != "sparse_read_ratio":
            assert read(name)(bare) is None, name
    refused = _joined_run(dsv32_real, tmp_path)
    refused._dispatch = None
    for name in ("decode_index_ms", "decode_sparse_attn_ms",
                 "index_roofline", "sparse_attn_roofline",
                 "sparse_step_roofline"):
        assert read(name)(refused) is None, name
    assert read("prefill_sparse_attn_share")(refused) is not None
    unnamed = _joined_run(dsv32_real, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("dsa_", "x_")
    for name in NEW_READERS:
        if name != "sparse_read_ratio":
            assert read(name)(unnamed) is None, name
    old = _joined_run(dsv32_real, tmp_path)
    old.events = [(e[0], e[1], e[2], e[3], e[4], {
        k: v for k, v in e[5].items()
        if "sparse" not in k and "index" not in k}) for e in old.events]
    for name in ("index_roofline", "sparse_attn_roofline",
                 "sparse_read_ratio", "sparse_step_roofline"):
        assert read(name)(old) is None, name
    assert read("decode_index_ms")(old) is not None
    train = types.SimpleNamespace(kind="train", family=None, peaks=None)
    for name in NEW_READERS:
        assert read(name)(train) is None, name


# ------------------------------------------------- the cell and its mix

def fam_tail():
    return common.load_family("deepseek_v32", "serve").SCORED_TAIL


def test_the_dsv32_cell_and_longdoc_sat_as_it_stands():
    bench = common.load_benchmark()
    cell = common.find_named(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-sat", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1][
        "name"] == CONFIG
    assert len(bench["configs"]) == 11 and len(bench["workloads"]) == 12
    e2e = {m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in common.metrics_of_cell(
        bench, "per_layer", CELL)}
    assert per_layer == {
        "host_gap_share", "kv_peak_share", "device_idle_share.serve",
        "decode_riders_mean", "round_host_ms", "prefill_rows_mean",
        "moe_dispatch_share", "moe_experts_touched_mean",
        "moe_held_pair_share", "prefill_attn_share",
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", "moe_experts_roofline.by_kind",
        "decode_latent_attn_ms", "moe_rows_per_expert_mean",
        "setup_build_s", "setup_program_trace_s", "setup_cold_builds",
        "engine_init_s", *NEW_READERS}
    assert tuple(m["name"] for m in bench["per_layer"][-7:]) == NEW_READERS
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(common.load_metric_reader(m["name"]))
    assert [(m["unit"], m["better"], m["source"], m["layer"])
            for m in bench["per_layer"][-7:]] == [
        ("ms", "lower", "device_trace", "model step"),
        ("ms", "lower", "device_trace", "model step"),
        ("%", "higher", "device_trace", "kernels"),
        ("%", "higher", "device_trace", "kernels"),
        ("%", "lower", "device_trace", "model step"),
        ("ratio", "lower", "program_counter", "model step"),
        ("%", "higher", "device_trace", "model step")]
    # the readers that divide by trace_reduce.loop_steps (the index
    # scores' loop over blocks misleads it) or by num_hidden_layers
    # (one of the five layers has no mixture), and the dense latent
    # walk's rooflines (every entry of the context priced over
    # kv_gather / attn_scores / attn_pv, which this model's attention
    # does not run: sparse_attn_roofline is theirs here): the cell is
    # on none (PERF.md section 3 has the list)
    assert not per_layer & {
        "decode_moe_ms", "moe_experts_roofline", "decode_attn_ms",
        "decode_dense_ms", "decode_step_ms", "decode_roofline",
        "latent_attn_roofline", "latent_attn_roofline.by_kind"}
    # the traffic is axk1-d5.longdoc-sat's file, unedited
    other = common.find_named(bench["workloads"], "axk1-d5.longdoc-sat",
                              "workload")
    assert other["traffic"] == cell["traffic"]
    tr = common.load_json("traffic", "longdoc-sat.json")
    assert tr["loop"] == "closed" and tr["clients_per_slot"] == 2
    assert tr["prefix_cache"] is False and tr["ramp_s"] == 26.0
    assert tr["traffic_seed"] == 4
    reqs = trafficgen.closed_population(tr)
    assert {r.prompt_len for r in reqs} == {8192}
    assert {r.output_len for r in reqs} == {512}
    cfg = common.load_json("configs", CONFIG + ".json")
    ids = trafficgen.prompt_tokens(2**31 + 5, 7, 8192, cfg["vocab_size"])
    assert len(ids) == 8192 and 1 <= min(ids) and max(ids) < 16160
    # contexts of 4.0-4.25 index_topk; ISSUE 56's 32 slots (64 clients)
    # of 136 pages and the null page; a page table that holds them
    assert 8192 == 4 * cfg["index_topk"] and 8704 == 4.25 * cfg["index_topk"]
    dep = cfg["deployment"]
    per_slot = -(-(8192 + 512) // dep["page_size"])
    assert dep["max_slots"] == 32
    assert dep["max_slots"] * tr["clients_per_slot"] == 64
    assert dep["max_slots"] * per_slot == dep["n_pages"] - 1 == 4352
    assert per_slot * dep["page_size"] <= cfg["max_position_embeddings"]
    assert set(dep) == {"max_slots", "page_size", "n_pages",
                        "tensor_parallel", "batch_wait_timeout_s",
                        "decode_chunk"}
    # the parity prompts run past index_topk: their generated positions
    # choose under half of their context; 128 of them a prompt, the
    # family's scored tail, so that a mean over them is a mean
    assert cfg["parity"] == {"prompts": 2, "prompt_len": 4400,
                             "new_tokens": fam_tail()}
    assert 4400 > 2 * cfg["index_topk"]


def _planned_rounds(decode_chunk, rounds=1200):
    """The engine's REAL planner over ``longdoc-sat`` as this cell
    deploys it, without a device or a clock: 32 slots that a waiting
    client refills the round after a request's last step was dispatched
    (64 clients: the queue never runs dry; the engine retires by
    dispatch-time arithmetic, serve/engine.py ``_retire_planned_locked``),
    prompts of 8,192 through four rows of 256, 512 tokens a request
    (the prefill call's and 511 steps). Returns (steps, riders, backlog)
    a round."""
    from ray_tpu.serve.scheduler import SlotView, plan_step
    cfg = common.load_json("configs", CONFIG + ".json")
    S = cfg["deployment"]["max_slots"]
    slots, seq, out = [None] * S, 0, []
    for _ in range(rounds):
        for i in range(S):
            if slots[i] is None:
                slots[i] = {"rem": 8192, "dec": 0, "cur": False, "seq": seq}
                seq += 1
        plan = plan_step(
            [SlotView(sid=i, admit_seq=s["seq"], prompt_remaining=s["rem"],
                      owed=511 - s["dec"] if s["cur"] else 0,
                      seeded=s["cur"]) for i, s in enumerate(slots)],
            total_slots=S, prefill_chunk=256, decode_chunk=decode_chunk,
            max_run_ahead=max(decode_chunk, 128), prefill_batch=4,
            eos_bounded=False)
        riders = [i for i, s in enumerate(slots) if s["cur"]]
        for g in plan.prefill:
            slots[g.sid]["rem"] -= g.tokens
        for i in riders if plan.decode_steps else ():
            slots[i]["dec"] += plan.decode_steps
            if slots[i]["dec"] >= 511:
                slots[i] = None
        for g in plan.prefill:
            if slots[g.sid]["rem"] == 0:
                slots[g.sid]["cur"] = True     # rides from the next round
        out.append((plan.decode_steps if riders else 0, len(riders),
                    plan.backlog))
    return out


def test_the_cadence_is_the_one_at_which_this_closed_loop_stands_still():
    """``deployment.decode_chunk`` 3 (the file's ``deployment_notes``):
    four rows admit a request in 8 rounds, 32 slots give it 256 rounds,
    32 of them in a row, so 511 steps in 224 rounds: the least whole
    cadence is 3. Under the real planner every round past the start-up
    is then 3 steps beside a full prefill call, without a backlog round
    and without a run-ahead, 20-24 riders; at the engine's default 8 the
    planner swings between its backlog cadence and 8 for ever (what
    PR 56's first check read as two groups 0.9 % apart), and at 2 the
    rows run dry and it runs ahead."""
    cfg = common.load_json("configs", CONFIG + ".json")
    assert cfg["deployment"]["decode_chunk"] == 3 == -(-511 // (256 - 32))
    settled = _planned_rounds(3)[400:]
    assert {steps for steps, _r, _b in settled} == {3}
    assert {backlog for _s, _r, backlog in settled} == {0}
    assert {riders for _s, riders, _b in settled} == {20, 24}
    assert 21 < np.mean([r for _s, r, _b in settled]) < 22
    swinging = _planned_rounds(8)[400:]
    assert {steps for steps, _r, _b in swinging} == {2, 8}
    assert sum(b > 0 for _s, _r, b in swinging) > 0.85 * len(swinging)
    dry = _planned_rounds(2)[400:]
    assert max(steps for steps, _r, _b in dry) > 32


def test_the_controls_entry_on_the_toy(tmp_path, capsys):
    """``python -m benchmarks.families.deepseek_v32`` at the rehearsal
    sizes: the parity prompts served through the deployment as they
    are read correct; with every entry attended (a) they do not, by all
    three of the family's limits; ``--dump`` keeps each generated
    position's margin and deficit."""
    fam = common.load_family("deepseek_v32", "serve")
    assert fam.main(["--config", "rehearsal/toy-deepseek-v32.json",
                     "--seeds", "3", "--controls", "a",
                     "--dump", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "seed 3 as it is: correct True" in out
    assert "seed 3 (a) {'no_selection': True}: correct False" in out
    assert "NOT correct by the mean over 0.05, the worst over 2.5, " \
        "more than 2 over one tolerance" in out
    kept = np.load(tmp_path / "controls_3.npz")
    assert kept["ids"].shape == (2, 228)
    for name in ("", "a"):
        assert kept["margin_" + name].shape == kept[
            "deficit_" + name].shape == (2, 128)
    assert kept["deficit_"].max() == 0 < kept["deficit_a"].max()


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-deepseek-v32.longdoc-sat", "--seed",
         str(2**31 + 13), "--seconds", "3", "--trace", trace],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_dsv32_rehearsal_cell_runs(trace):
    """The toy cell borrows dsv32-d5.longdoc-sat's metric lists: correct
    against the plain reference through the served path (100 tokens of
    prompt, four of the toy's ``index_topk``), no program built in the
    window; at --trace 2 the counter metrics are there (the
    device_trace ones need a device in the trace, which a CPU has not:
    the hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    cell = common.load_rehearsal_cell("toy-deepseek-v32.longdoc-sat")
    assert set(cell["reports"]) <= set(line["metrics"])
    share = line["metrics"]["moe_held_pair_share"]
    # 4 of 16 experts held, one group of the four: a quarter of the
    # pairs under even routing
    assert share["unit"] == "%" and 12.0 <= share["value"] <= 38.0
    ratio = line["metrics"]["sparse_read_ratio"]
    # the prefill calls walk their whole window under the mask
    assert ratio["unit"] == "ratio" and ratio["value"] > 2.0
    assert "[selection] window: decode steps read" in stdout
