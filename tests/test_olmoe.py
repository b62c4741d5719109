"""OLMoE on the normal path (ray_tpu.models.mixtral with its three
declared differences) against the plain float32 reference
(benchmarks/reference/olmoe.py), on the CPU at ``olmoe_tiny``.

Tolerances. Both sides compute in float32 on the same weights and
differ only in the order of their sums (the program sorts the pairs by
expert and multiplies group by group; the reference computes every
expert on every token and weights by the gate or by zero): logits of
the order of 1 agree to rtol 1e-4 / atol 2e-5, as the Mixtral family's
test found. Each wrong rule below (gates renormalised, no query/key
norm, a tied head, a dropped pair) moves logits by a hundred times that
or more, and the tests say so.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.mixtral import (MOE_STATS, Mixtral, MoEFeedForward,
                                    active_params_per_token,
                                    mixtral_param_count, mixtral_tiny,
                                    olmoe_1b_7b, olmoe_tiny)

RTOL, ATOL = 1e-4, 2e-5


def _family():
    from benchmarks import common
    return common.load_family("olmoe", "serve")


def _seeded(cfg, seed=0, router_scale=1.0):
    """The model's own initialisers, then every norm's scale (the
    query/key norms' too) away from one and the router sharpened by
    ``router_scale``, so that a scale or a gate left out shows."""
    model = Mixtral(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        params["params"])
    rng = np.random.default_rng(seed + 1)
    out = []
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 1:
            leaf = leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        elif "router" in name:
            leaf = leaf * router_scale
        out.append(leaf)
    return model, {"params": jax.tree_util.tree_unflatten(treedef, out)}


@pytest.fixture(scope="module")
def tiny():
    cfg = olmoe_tiny(dtype=jnp.float32)
    # (a router this mild keeps the 3 gates' sum well under 1, so that
    # renormalising them shows)
    model, params = _seeded(cfg, router_scale=4.0)
    return cfg, model, params


def _ids(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 255, size=shape), jnp.int32)


def _reference(params, ids, cfg):
    fam = _family()
    return np.asarray(fam.reference_logits(
        fam.reference_weights(params, cfg), ids, cfg))


def test_forward_matches_the_reference(tiny):
    cfg, model, params = tiny
    ids = _ids((2, 40))
    got, _ = jax.jit(model.apply)(params, ids)
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL,
                               atol=ATOL)


def test_no_pair_is_dropped_in_a_large_call():
    """2 x 704 tokens x 3 = 4,224 pairs, over the 4,096 at which the
    parent's mixture switched to capacity buffers of 1.25 x the mean
    load. The router is sharp here, so the fullest expert of a layer
    holds well over that capacity: the parent dropped those pairs, the
    reference never does, and the program must equal the reference."""
    cfg = olmoe_tiny(dtype=jnp.float32, max_seq_len=1024)
    model, params = _seeded(cfg, router_scale=20.0)
    ids = _ids((2, 704), seed=3)
    (got, _), sown = jax.jit(
        lambda p, i: model.apply(p, i, mutable=[MOE_STATS]))(params, ids)
    N, K, E = ids.size, cfg.num_experts_per_tok, cfg.num_experts
    assert N * K > 4096
    capacity = int(1.25 * K * N / E)
    fullest = max(np.bincount(np.asarray(t).ravel(), minlength=E).max()
                  for t in jax.tree_util.tree_leaves(sown[MOE_STATS]))
    assert fullest > 1.2 * capacity, (fullest, capacity)
    want = _reference(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL,
                               atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(norm_topk_prob=True), dict(qk_norm=False),
    dict(tie_word_embeddings=True)],
    ids=["gates-renormalised", "no-query-key-norm", "tied-head"])
def test_each_declared_difference_shows(tiny, wrong):
    """The same weights under Mixtral's rule for one field at a time
    are far from the OLMoE reference: the comparison can tell."""
    cfg, _model, params = tiny
    ids = _ids((2, 40))
    want = _reference(params, ids, cfg)
    got, _ = jax.jit(Mixtral(dataclasses.replace(cfg, **wrong)).apply)(
        params, ids)
    gap = float(np.abs(np.asarray(got) - want).max())
    scale = float(np.abs(want).max())
    assert gap > 100 * RTOL * scale, (wrong, gap, scale)


def test_rows_without_a_request_get_no_expert(tiny):
    """``live`` false: the row is routed nowhere and comes back zero;
    the live rows' outputs are what they are without the mask."""
    cfg, _model, _params = tiny
    moe = MoEFeedForward(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, cfg.dim))
    v = jax.jit(moe.init)(jax.random.PRNGKey(3), x)
    every = moe.apply(v, x)
    some = moe.apply(v, x, jnp.asarray([True, False, True]))
    np.testing.assert_allclose(np.asarray(some[0]), np.asarray(every[0]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(some[2]), np.asarray(every[2]),
                               rtol=1e-6, atol=1e-7)
    assert not np.asarray(some[1]).any()


# ------------------------------------------------------ the paged engine

@pytest.fixture(scope="module")
def served(tiny):
    """One request through LLMEngine: a 40-token prompt prefilled in
    chunks of 16, then 12 greedy tokens through the paged cache."""
    from ray_tpu.serve.engine import LLMEngine
    cfg, model, params = tiny
    eng = LLMEngine(model, params, max_slots=4, page_size=8, n_pages=64,
                    chunk=4, prefill_chunk=16, temperature=0.0, seed=0)
    eng.start()
    prompt = _ids((40,), seed=5).tolist()
    out = eng.submit(prompt, max_new_tokens=12).result()
    report = eng.load_report()
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    stats = dict(eng.stats)
    eng.shutdown()
    return prompt, out, rounds, stats, report


def test_chunked_prefill_then_paged_decode_matches_the_reference(
        tiny, served):
    """Teacher-forced: the reference's full forward over prompt +
    generated must choose the engine's token at every generated
    position where its own top-2 margin is decisive (float32 both
    sides, so the margin needed is the rtol of the logits)."""
    cfg, _model, params = tiny
    prompt, out, _rounds, _stats, _report = served
    assert len(prompt) == 40 and len(out) == 12
    logits = _reference(params, jnp.asarray([prompt + out], jnp.int32),
                        cfg)[0]
    steps = logits[39:51]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= 8
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]
            ).all()


def test_round_events_count_the_routing(tiny, served):
    """moe_pairs = live tokens x k x layers; one rider's decode step
    touches exactly k distinct experts a layer, and a prefill call the
    distinct experts of its tokens, which a forward pass of the same
    chunk over the same context counts by hand."""
    cfg, model, params = tiny
    prompt, out, rounds, stats, report = served
    K, L = cfg.num_experts_per_tok, cfg.n_layers
    live = sum(r["decode_riders"] * r["decode_steps"]
               + r["prefill_tokens"] for r in rounds)
    assert live >= 40 + 11
    # counters reach the round that reads them back: all but the last
    # dispatch's are in the events, and the running total holds all
    assert report["moe_pairs_total"] == live * K * L
    reported = sum(r["moe_pairs"] for r in rounds)
    assert 0 < reported <= live * K * L
    assert reported == stats["moe_pairs"]
    assert abs(sum(report["moe_expert_share"]) - 1.0) < 1e-9
    # by hand: the whole prompt's routing, chunk by chunk of 16
    _, sown = jax.jit(lambda p, i: model.apply(p, i, mutable=[MOE_STATS])
                      )(params, jnp.asarray([prompt], jnp.int32))
    topk = [np.asarray(t)[0] for t in
            jax.tree_util.tree_leaves(sown[MOE_STATS])]     # [T, K] each
    prefill_touched = sum(
        len(np.unique(t[a:a + 16])) for t in topk for a in (0, 16, 32))
    decode_steps = sum(r["decode_steps"] for r in rounds)
    prefill_rounds = [r for r in rounds if r["prefill_tokens"]]
    assert len(prefill_rounds) == 3
    want = prefill_touched + K * L * decode_steps
    have = sum(r["moe_experts_touched"] for r in rounds)
    pending = stats.get("moe_layer_steps", 0)
    assert sum(r["moe_layer_steps"] for r in rounds) == pending
    # the events lack at most the last decode dispatch (k a layer-step)
    last = rounds[-1]["decode_steps"] * K * L
    assert want - last <= have <= want
    for r in rounds:
        assert r["moe_load_max"] <= r["moe_pairs"]
        assert r["moe_decode_layer_steps"] <= r["moe_layer_steps"]


def test_a_dense_model_reports_no_routing():
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=2, page_size=8, n_pages=32,
                    chunk=4, prefill_chunk=16, temperature=0.0, seed=0)
    eng.start()
    eng.submit([3, 4, 5, 6], max_new_tokens=5).result()
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    report = eng.load_report()
    eng.shutdown()
    assert rounds and not any(k.startswith("moe_") for r in rounds
                              for k in r)
    assert not any(k.startswith("moe_") for k in report)


# ------------------------------------------------------ parameter trees

def _tree(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return {jax.tree_util.keystr(p): tuple(leaf.shape) for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}


def test_default_configs_keep_their_parameter_trees():
    """The new fields at their defaults add no parameter: Llama's and
    Mixtral's trees are what they were before OLMoE came."""
    from ray_tpu.models.llama import Llama, LlamaConfig
    llama = _tree(Llama(LlamaConfig(n_layers=1)))
    assert llama == {
        "['tok_embeddings']": (32000, 4096),
        "['norm']['scale']": (4096,),
        "['layers_0']['attention_norm']['scale']": (4096,),
        "['layers_0']['ffn_norm']['scale']": (4096,),
        "['layers_0']['attention']['wq']['kernel']": (4096, 4096),
        "['layers_0']['attention']['wk']['kernel']": (4096, 4096),
        "['layers_0']['attention']['wv']['kernel']": (4096, 4096),
        "['layers_0']['attention']['wo']['kernel']": (4096, 4096),
        "['layers_0']['feed_forward']['w1']['kernel']": (4096, 11008),
        "['layers_0']['feed_forward']['w3']['kernel']": (4096, 11008),
        "['layers_0']['feed_forward']['w2']['kernel']": (11008, 4096)}
    mix = _tree(Mixtral(mixtral_tiny(n_layers=1)))
    assert mix == {
        "['tok_embeddings']": (256, 64),
        "['norm']['scale']": (64,),
        "['layers_0']['attention_norm']['scale']": (64,),
        "['layers_0']['ffn_norm']['scale']": (64,),
        "['layers_0']['attention']['wq']['kernel']": (64, 64),
        "['layers_0']['attention']['wk']['kernel']": (64, 32),
        "['layers_0']['attention']['wv']['kernel']": (64, 32),
        "['layers_0']['attention']['wo']['kernel']": (64, 64),
        "['layers_0']['moe']['router']": (64, 4),
        "['layers_0']['moe']['w1']": (4, 64, 128),
        "['layers_0']['moe']['w3']": (4, 64, 128),
        "['layers_0']['moe']['w2']": (4, 128, 64)}
    olmoe = _tree(Mixtral(olmoe_tiny(n_layers=1)))
    assert set(olmoe) - set(mix) == {
        "['lm_head']", "['layers_0']['attention']['q_norm']['scale']",
        "['layers_0']['attention']['k_norm']['scale']"}
    # init gives weights and the sown losses, not the routing statistics
    full = jax.eval_shape(Mixtral(mixtral_tiny()).init,
                          jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert set(full) == {"params", "losses"}


def test_param_counts_hold_the_head_and_the_norms():
    for cfg in (olmoe_tiny(), mixtral_tiny()):
        tree = _tree(Mixtral(cfg))
        assert mixtral_param_count(cfg) == sum(
            int(np.prod(s)) for s in tree.values())
    full = olmoe_1b_7b()
    assert abs(mixtral_param_count(full) - 6.92e9) < 0.01e9
    assert abs(active_params_per_token(full) - 1.28e9) < 0.01e9
    # the benchmark's cut: 8 layers are 7.13 GB in bf16
    assert abs(2 * mixtral_param_count(olmoe_1b_7b(n_layers=8))
               - 7.13e9) < 0.01e9


def test_sharding_rules_name_the_new_parameters():
    from jax.sharding import PartitionSpec as P
    from ray_tpu.models.llama import llama_sharding_rules
    from ray_tpu.models.mixtral import mixtral_sharding_rules
    vec, mat = np.zeros((64,)), np.zeros((256, 64))
    for rules in (mixtral_sharding_rules(fsdp=False),
                  llama_sharding_rules(fsdp=False)):
        assert rules.spec_for("layers_0/attention/q_norm/scale",
                              vec) == P("tensor")
        assert rules.spec_for("layers_3/attention/k_norm/scale",
                              vec) == P("tensor")
        assert rules.spec_for("lm_head", mat) == P("tensor", None)
        assert rules.spec_for("tok_embeddings", mat) == P("tensor", None)


def test_grouped_matmul_takes_no_kernel_off_the_chip_or_under_a_mesh(
        monkeypatch, cpu_mesh_devices):
    """The Pallas kernel serves one TPU device; the CPU, and a replica
    sharded over a mesh (which GSPMD cannot partition a Mosaic kernel
    for), take jax.lax.ragged_dot. The backend and the ambient mesh
    decide, nothing else."""
    from jax.sharding import Mesh
    from ray_tpu.ops import grouped_matmul as gm
    assert not gm._use_kernel()                       # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm._use_kernel()
    mesh = Mesh(np.asarray(cpu_mesh_devices[:4]).reshape(2, 2),
                ("expert", "tensor"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert not gm._use_kernel()
    one = Mesh(np.asarray(cpu_mesh_devices[:1]), ("tensor",))
    with jax.sharding.use_abstract_mesh(one.abstract_mesh):
        assert gm._use_kernel()
    monkeypatch.undo()
    rows = jax.random.normal(jax.random.PRNGKey(0), (12, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
    sizes = jnp.asarray([5, 0, 4], jnp.int32)     # 3 rows in no group
    got = np.asarray(gm.grouped_matmul(rows, w, sizes))
    np.testing.assert_allclose(got[:5], np.asarray(rows[:5] @ w[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[5:9], np.asarray(rows[5:9] @ w[2]),
                               rtol=1e-5, atol=1e-6)


def test_serve_run_serves_olmoe_through_the_engine(tiny, rt):
    """serve.run() of a LlamaDeployment holding an OLMoE config: the
    normal path, no deployment class of its own. The greedy tokens are
    the ones the model's own dense-cache ``generate`` gives, and the
    engine's load report carries the experts' shares."""
    from ray_tpu import serve
    from ray_tpu.models.llama import generate
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, model, params = tiny
    holder = {}

    @serve.deployment
    class OlmoeLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_new_tokens=6,
                             max_slots=2, page_size=8, n_pages=32)
            holder["dep"] = self

    try:
        h = serve.run(OlmoeLLM.bind(), timeout_s=300)
        prompt = _ids((9,), seed=7).tolist()
        full = rt.get(h.remote(prompt), timeout=300)
        want = np.asarray(generate(model, params,
                                   jnp.asarray([prompt], jnp.int32), 6))[0]
        assert full == want.tolist()
        report = holder["dep"].engine().load_report()
        assert report["moe_pairs_total"] > 0
        assert len(report["moe_expert_share"]) == cfg.num_experts
    finally:
        serve.shutdown()


def test_olmoe_on_an_expert_x_tensor_mesh_matches_one_chip(
        tiny, cpu_mesh_devices):
    """ep=2 x tp=2: the new parameters shard by their rules (the
    query/key norm over a tensor-sharded width needs its mean across
    shards; the untied head is vocab-parallel), the mixture runs
    expert-sharded through the same sorted dispatch, and the tokens
    are the one-chip engine's."""
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.sharding import EngineSharding
    cfg, model, params = tiny
    sh = EngineSharding.build(cfg, tp=2, ep=2,
                              devices=cpu_mesh_devices[:4])
    prompt = _ids((10,), seed=9).tolist()

    def run(sharding):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=32, chunk=4, prefill_chunk=16,
                        temperature=0.0, seed=0, sharding=sharding)
        eng.start()
        out = eng.submit(prompt, max_new_tokens=12).result()
        eng.shutdown()
        return out

    assert run(None) == run(sh)
