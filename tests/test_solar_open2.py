"""Solar-Open2 on the normal path (ray_tpu.models.solar_open2 through
LLMEngine and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/solar_open2.py), on the CPU at
``solar_open2_tiny``: two periods of (GQA, KDA, KDA, KDA), 16 experts of
which 4 a token and one shared.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums (the program solves a chunk of the
delta rule at once and sorts the mixture's pairs by expert; the
reference scans positions and computes every expert on every token):
logits of the order of 1 agree to rtol 1e-4 / atol 2e-5, as OLMoE's do
(tests/test_olmoe.py). Each wrong rule below (a softmax router, gates
not renormalised, another scaling factor, no choice bias) moves logits
by a hundred times that or more, and a state carried in bfloat16 misses
the same tolerance in tests/test_linear_attention.py. The engine's
tokens are held to the reference's full forward pass teacher-forced:
at every generated position where the reference's top-2 margin exceeds
ten times the rtol of the logits, the engine's token is the reference's
argmax.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT,
                                     RecurrentState, init_kv_pool,
                                     kv_pool_page_bytes,
                                     state_bytes_per_slot)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.models.solar_open2 import (SolarOpen2, solar_open2_250b,
                                        solar_open2_param_count,
                                        solar_open2_tiny)
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.faults import FaultInjector

RTOL, ATOL = 1e-4, 2e-5


def _family():
    from benchmarks import common
    return common.load_family("solar_open2", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights (decays from 0.999 down
    to hard ones, a router bias that changes choices; not balanced:
    the tests want uneven loads too), then every
    norm's scale away from one so that a scale left out shows."""
    from benchmarks import weights
    model = SolarOpen2(cfg)
    params = _family().seeded(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        if "scale" in jax.tree_util.keystr(path):
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = solar_open2_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg):
    fam = _family()
    return np.asarray(fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg))


def _held_to_the_reference(params, cfg, prompt, out, least=None):
    """The teacher-forced rule of the module docstring."""
    P, G = len(prompt), len(out)
    logits = _reference(params, [list(prompt) + list(out)], cfg)[0]
    steps = logits[P - 1:P - 1 + G]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= (G * 2 // 3 if least is None else least)
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]).all()


def _drive(eng, max_rounds=5000):
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not quiesce")


def _engine(tiny, **kw):
    _cfg, model, params = tiny
    opts = dict(max_slots=4, page_size=8, n_pages=64, chunk=4,
                prefill_chunk=16, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass, 70 positions (the delta rule in a
    chunk of 64 and one of 6)."""
    cfg, model, params = tiny
    ids = _ids((2, 70))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 70, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(router="softmax"), dict(norm_topk_prob=False),
    dict(routed_scaling_factor=2.0), dict(n_shared_experts=0),
    dict(experts_held=(0, 16), _drop="router_bias")],
    ids=lambda w: "-".join(k for k in w))
def test_each_declared_rule_shows(tiny, wrong):
    """A softmax router, gates that are not renormalised, another
    scaling factor, no shared expert, no choice bias: each is far
    outside the tolerance."""
    cfg, _model, params = tiny
    wrong = dict(wrong)
    if wrong.pop("_drop", None):
        params = jax.tree_util.tree_map_with_path(
            lambda p, leaf: jnp.zeros_like(leaf)
            if "router_bias" in jax.tree_util.keystr(p) else leaf, params)
        wrong = {}
    ids = _ids((2, 40), seed=1)
    got, _ = jax.jit(SolarOpen2(dataclasses.replace(cfg, **wrong)).apply)(
        params, jnp.asarray(ids, jnp.int32))
    want = _reference(tiny[2], ids, cfg)
    gap = float(np.abs(np.asarray(got) - want).max())
    assert gap > 100 * RTOL * float(np.abs(want).max()), gap


def test_layer_kinds_and_the_published_count():
    cfg = solar_open2_250b()
    kinds = cfg.layer_kinds
    assert len(kinds) == 48 and kinds.count(KIND_KV) == 12
    assert [i for i, k in enumerate(kinds) if k == KIND_KV] == \
        list(range(0, 48, 4))
    # the published 250B, from the equations
    assert round(solar_open2_param_count(cfg) / 1e9, 2) == 250.29
    # one chip's share of one period (ISSUE 32): 3.308 B parameters
    share = dataclasses.replace(cfg, n_layers=4, vocab_size=24576)
    assert round(solar_open2_param_count(share, experts=40) / 1e9,
                 3) == 3.308
    tiny_cfg = solar_open2_tiny()
    shapes = jax.eval_shape(SolarOpen2(tiny_cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes["params"])) == \
        solar_open2_param_count(tiny_cfg)


# ------------------------------------------------ the mixture's new rules

def _moe_weights(cfg, params, layer=1):
    fam = _family()
    return fam.reference_weights(params, cfg)["layers"][layer]


def test_the_routers_rule_matches_the_reference(tiny):
    """sigmoid, the bias in the choice only, gates renormalised over
    the chosen: the module alone against the reference's routed +
    shared parts; and the bias does change choices here."""
    from benchmarks.reference import solar_open2 as ref
    cfg, _model, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 9, cfg.dim))
    moe_params = {"params": params["params"]["layers_1"]["moe"]}
    got = MoEFeedForward(cfg).apply(moe_params, x)
    w = {k: jnp.asarray(v, jnp.float32)
         for k, v in _moe_weights(cfg, params).items()}
    with jax.default_matmul_precision("highest"):
        want = ref.routed(x, w, top_k=4, lo=0, norm_topk=True,
                          scaling=1.0) + ref.shared(x, w)
        tokens = x.reshape(-1, cfg.dim)
        with_bias = ref.route(tokens, w, 4, True, 1.0) > 0
        without = ref.route(tokens, {**w, "router_bias": jnp.zeros(16)},
                            4, True, 1.0) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    assert (np.asarray(with_bias) != np.asarray(without)).any()
    # every token's weights are those of 4 experts and sum to 1
    weights = np.asarray(ref.route(tokens, w, 4, True, 1.0))
    assert ((weights > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


def _share_case(family, tiny):
    """(reference module, config, params, mixture layer, experts a
    share, experts in all, the reference's routing arguments) of a
    family's tiny model."""
    if family == "solar_open2":
        from benchmarks.reference import solar_open2 as ref
        cfg, _model, params = tiny
        return ref, cfg, params, 2, 4, 16, dict(top_k=4, scaling=1.0)
    if family == "kimi_linear":
        # Kimi-Linear: the choice bias AND gates times 2.446; 64 toy
        # experts of which 8 a token, FOUR shares of 16, as its
        # benchmark cut has them (64 of 256); layer 3 is a latent layer
        from benchmarks import common, weights
        from benchmarks.reference import kimi_linear as ref
        from ray_tpu.models.kimi_linear import KimiLinear, kimi_linear_tiny
        cfg = kimi_linear_tiny(dtype=jnp.float32, num_experts=64,
                               num_experts_per_tok=8, n_layers=4)
        params = common.load_family("kimi_linear", "serve").seeded(
            weights.param_shapes(KimiLinear(cfg)), 0)
        return ref, cfg, params, 3, 16, 64, dict(top_k=8, scaling=2.446)
    # A.X-K1: a sigmoid router without a choice bias, gates times 2.5;
    # SIXTEEN shares of one expert each, as its benchmark cut has them
    from benchmarks import common, weights
    from benchmarks.reference import axk1 as ref
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    cfg = axk1_tiny(dtype=jnp.float32)
    params = common.load_family("axk1", "serve").seeded(
        weights.param_shapes(AXK1(cfg)), 0)
    return ref, cfg, params, 1, 1, 16, dict(top_k=4, scaling=2.5)


@pytest.mark.parametrize("family", ["solar_open2", "axk1", "kimi_linear"])
def test_the_shares_add_up_to_the_whole_layer(tiny, family):
    """THE SHARE TEST (model-configs section 4): the chips of a group
    hold a share of the experts each (four of 4 of 16; sixteen of 1;
    four of 16 of 64).
    What each computes for the same tokens (its own experts' part, the
    router at its full width, the gates normalised over all chosen)
    plus the shared expert, which every chip computes alike, counted
    ONCE, is what the uncut reference gives for the whole layer."""
    from benchmarks import common
    ref, cfg, params, layer, held, E, routing = _share_case(family, tiny)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 31, cfg.dim))
    whole = params["params"][f"layers_{layer}"]["moe"]
    w = {k: jnp.asarray(v, jnp.float32) for k, v in common.load_family(
        family, "serve").reference_weights(params, cfg)["layers"][
            layer].items()}
    with jax.default_matmul_precision("highest"):
        shared = ref.shared(x, w)
        want = ref.routed(x, w, lo=0, norm_topk=True, **routing) + shared
    total, landed = jnp.zeros_like(x), 0
    for lo in range(0, E, held):
        share_cfg = dataclasses.replace(cfg, experts_held=(lo, held))
        share = {k: (v[lo:lo + held] if k in ("w1", "w2", "w3") else v)
                 for k, v in whole.items()}
        part = MoEFeedForward(share_cfg).apply({"params": share}, x)
        total = total + (part - shared)
        landed += float(jnp.abs(part - shared).max() > 1e-3)
    assert landed == E // held              # every share does some work
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=RTOL, atol=ATOL)


def test_a_mixture_that_holds_every_expert_keeps_its_vector(tiny):
    """Mixtral's and OLMoE's step programs return the [E + 4] vector
    of a mixture with no share declared (what the router made is what
    is counted); only a declared share appends the routed pairs, and
    a share of the whole width counts what no share counts."""
    from ray_tpu.models.mixtral import MOE_STATS, moe_stats_vector
    cfg, _model, _params = tiny
    moe = MoEFeedForward(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.dim))
    v = jax.jit(moe.init)(jax.random.PRNGKey(6), x)
    _, sown = moe.apply(v, x, mutable=[MOE_STATS])
    live = jnp.ones((2, 16), bool).at[1, 8:].set(False)
    E = cfg.num_experts
    whole = np.asarray(moe_stats_vector(sown[MOE_STATS], live, E))
    share = np.asarray(moe_stats_vector(sown[MOE_STATS], live, E, (0, E)))
    assert whole.shape == (E + 4,) and share.shape == (E + 5,)
    assert whole.tolist() == share[:-1].tolist()
    assert whole[:E].sum() == share[-1] == 24 * cfg.num_experts_per_tok


def test_a_share_routes_over_the_whole_width(tiny):
    """A pair whose expert is not held gets no group, as a free slot's
    row does: a share's parameter tree holds only its experts, and the
    counters tell held pairs from routed ones."""
    from ray_tpu.models.mixtral import MOE_STATS, moe_stats_vector
    cfg, _model, _params = tiny
    share_cfg = dataclasses.replace(cfg, experts_held=(4, 4))
    moe = MoEFeedForward(share_cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.dim))
    v = jax.jit(moe.init)(jax.random.PRNGKey(6), x)
    assert v["params"]["w1"].shape == (4, cfg.dim, cfg.hidden_dim)
    assert v["params"]["router"].shape == (cfg.dim, 16)
    _, sown = moe.apply(v, x, mutable=[MOE_STATS])
    live = jnp.ones((2, 16), bool).at[1, 8:].set(False)
    vec = np.asarray(moe_stats_vector(sown[MOE_STATS], live, 16, (4, 4)))
    topk = np.asarray(jax.tree_util.tree_leaves(sown[MOE_STATS])[0])
    chosen = topk[np.asarray(live)]
    want = [(chosen == e).sum() for e in range(4, 8)]
    assert vec[:4].tolist() == want
    assert vec[4] == sum(c > 0 for c in want) and vec[5] == max(want)
    assert vec[6] == 1 and vec[8] == 24 * 4
    # the grouped matmul's visits over those pairs: 96 sorted rows lie
    # in ONE row tile, so each touched expert is visited once
    assert vec[7] == vec[4]


# ------------------------------------------- the pool, by kind of layer

def test_the_pool_holds_each_layer_by_its_kind(tiny):
    cfg, _model, _params = tiny
    pool = init_kv_pool(cfg, 16, 8, n_slots=4)
    assert len(pool) == 8
    for kind, entry in zip(cfg.layer_kinds, pool):
        if kind == KIND_RECURRENT:
            assert isinstance(entry, RecurrentState)
            assert entry.state.shape == (4, 4, 16, 16)
            assert entry.state.dtype == jnp.float32
            assert entry.conv.shape == (4, 3, 3 * 64)
        else:
            assert entry[0].shape == (16, 8, 2, 16)
    # 2 of 8 layers have pages; 6 keep 4x16x16 float32 + a 3x192 tail
    assert kv_pool_page_bytes(cfg, 8) == 2 * 2 * 2 * 8 * 16 * 4
    assert state_bytes_per_slot(cfg) == 6 * (4 * 4 * 16 * 16 + 4 * 3 * 192)
    from ray_tpu.models.llama import llama_tiny
    dense = llama_tiny()
    assert state_bytes_per_slot(dense) == 0
    assert kv_pool_page_bytes(dense, 8) == \
        dense.n_layers * 2 * dense.n_kv_heads * 8 * dense.head_dim * 2
    int8 = init_kv_pool(cfg, 16, 8, "int8", n_slots=4)
    assert len(int8[0]) == 4 and isinstance(int8[1], RecurrentState)


# ------------------------------------------------------ the paged engine

def test_mixed_rows_through_both_kinds_of_cache(tiny):
    """Three prompts of 40, 7 and 21 tokens in a prefill call of four
    rows (one a padding row) of chunks of 16: the longest crosses three
    rounds with its state handed over, rows carry padding inside, and
    12 tokens each are decoded through the pages of the GQA layers and
    the slots' state of the KDA layers."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (40, 7, 21)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert max(r["prefill_rows"] for r in rounds) == 3
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        _held_to_the_reference(params, cfg, p, out)
    # the counters: a share of all experts holds every routed pair
    routed = sum(r["moe_pairs_routed"] for r in rounds)
    assert routed == sum(r["moe_pairs"] for r in rounds) > 0
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        eng.stats["state_slots"] > 0
    assert eng.load_report()["state_bytes_in_use"] == 0


def test_a_reused_slot_starts_from_zeros(tiny):
    """One slot, two requests in turn: the second finds the first's
    state and convolution tail in its slot and must not see them (a
    row whose start is 0 begins from zeros, in the program)."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1)
    first, second = _ids((30,), seed=20).tolist(), _ids((19,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    state = [np.asarray(e.state) for e in eng.pages
             if isinstance(e, RecurrentState)]
    assert all(np.abs(s).max() > 0 for s in state)   # left behind
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_free_slots_ride_without_moving_their_state(tiny):
    """One request in an engine of four slots: the other three slots
    ride every decode call (and the fourth row of every prefill call is
    padding), and their state stays what it was, bit for bit."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    marked = []
    for entry in eng.pages:
        if isinstance(entry, RecurrentState):
            entry = RecurrentState(entry.state.at[1:].set(7.0),
                                   entry.conv.at[1:].set(3.0))
        marked.append(entry)
    eng.pages = marked
    prompt = _ids((25,), seed=30).tolist()
    h = eng.submit(prompt, max_new_tokens=9)
    _drive(eng)
    _held_to_the_reference(params, cfg, prompt, h.result())
    for entry in eng.pages:
        if isinstance(entry, RecurrentState):
            assert (np.asarray(entry.state[1:]) == 7.0).all()
            assert (np.asarray(entry.conv[1:]) == 3.0).all()
            assert np.abs(np.asarray(entry.state[0])).max() > 0


def test_preemption_recomputes_the_state(tiny):
    """A pool too small for two growing requests: the younger is
    evicted and requeued with prompt + generated, prefilled again from
    position 0 (its state rebuilt from zeros) and gives the tokens it
    would have given alone."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2, page_size=4, n_pages=14, chunk=2,
                  prefill_chunk=8)
    prompts = [_ids((12,), seed=40).tolist(), _ids((11,), 41).tolist()]
    handles = [eng.submit(p, max_new_tokens=22) for p in prompts]
    _drive(eng)
    assert eng.stats["preemptions"] > 0
    for p, h in zip(prompts, handles):
        alone = _engine(tiny, max_slots=1, page_size=4, n_pages=14,
                        chunk=2, prefill_chunk=8)
        ha = alone.submit(p, max_new_tokens=22)
        _drive(alone)
        assert h.result() == ha.result()
        _held_to_the_reference(params, cfg, p, h.result())


def test_a_requeue_after_a_fault_gives_the_same_tokens(tiny):
    """A fault attributable to slot 1's decode dispatch fails that
    request; the innocent co-rider is requeued, prefilled again from
    position 0 and still gives the reference's tokens."""
    cfg, _model, params = tiny
    inj = FaultInjector()
    inj.inject("dispatch_decode", sid=1, round=4)
    eng = _engine(tiny, chunk=2, fault_injector=inj,
                  retry_backoff_s=0.005)
    p1, p2 = _ids((18,), seed=50).tolist(), _ids((9,), 51).tolist()
    h1 = eng.submit(p1, max_new_tokens=14)
    h2 = eng.submit(p2, max_new_tokens=14)
    _drive(eng)
    with pytest.raises(RuntimeError, match="injected fault"):
        h2.result()
    assert eng.stats["retries"] == 1
    _held_to_the_reference(params, cfg, p1, h1.result())


def test_load_report_counts_both_kinds_of_state(tiny):
    cfg, _model, _params = tiny
    eng = _engine(tiny)
    per_slot = state_bytes_per_slot(cfg)
    eng.submit(_ids((20,), seed=60).tolist(), max_new_tokens=30)
    for _ in range(3):
        eng.step()
    report = eng.load_report()
    assert report["state_bytes_total"] == 4 * per_slot
    assert report["state_bytes_in_use"] == per_slot
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8)
    assert report["kv_bytes_total"] == 64 * report["kv_page_bytes"]
    assert 0 < report["kv_bytes_in_use"] < report["kv_bytes_total"]
    _drive(eng)


def test_int8_pages_beside_the_float32_state(tiny):
    """kv_dtype="int8" quantizes the GQA layers' pages and leaves the
    KDA layers' state alone. The K/V layer itself (no rope, an output
    gate) over int8 pages agrees with its cache-less self to 2 % of its
    scale (an int8 page carries 7 bits; Llama's layer reads the same);
    the engine serves from the mixed pool. The engine's TOKENS are not
    held to the float reference here: at this size a 1 % change of a
    layer's output re-routes tokens (16 experts, 4 a token, near-ties),
    which moves logits by their whole scale."""
    from ray_tpu.models.kv_cache import kv_layer_view
    from ray_tpu.models.llama import LlamaAttention
    cfg, _model, _params = tiny
    attn = LlamaAttention(cfg, rope=False, out_gate=True)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 16, cfg.dim))
    v = jax.jit(attn.init)(jax.random.PRNGKey(8), x, None, None)
    want, _ = attn.apply(v, x, None, None)
    table = jnp.zeros((1, 8), jnp.int32).at[0, :2].set(jnp.asarray([1, 2]))
    pool = init_kv_pool(cfg, 16, 8, "int8", n_slots=1)
    got, cache = attn.apply(v, x, None, None, kv_layer_view(pool[0], table),
                            jnp.zeros((1,), jnp.int32))
    assert cache.pages_k.dtype == jnp.int8 and cache.quantized
    gap = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    assert 0 < gap < 0.02 * float(np.abs(np.asarray(want)).max())
    eng = _engine(tiny, kv_dtype="int8")
    h = eng.submit(_ids((37,), seed=70).tolist(), max_new_tokens=12)
    _drive(eng)
    assert len(h.result()) == 12
    kinds = [type(e).__name__ if isinstance(e, RecurrentState)
             else str(e[0].dtype) for e in eng.pages]
    assert kinds == ["int8", "RecurrentState", "RecurrentState",
                     "RecurrentState"] * 2
    assert eng.pages[1].state.dtype == jnp.float32
    report = eng.load_report()
    assert report["kv_dtype"] == "int8"
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8, "int8")


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=2), "spec_len.*recurrent state"),
    (dict(sharding=object()), "sharding.*recurrent state")],
    ids=["prefix_cache", "spec_len", "sharding"])
def test_the_engine_refuses_what_the_state_cannot_do(tiny, option, match):
    with pytest.raises(ValueError, match=match):
        _engine(tiny, **option)


@pytest.mark.parametrize("option,match", [
    (dict(disaggregate=True, prefix_cache=True), "disaggregate"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_len=3), "spec_len"),
    (dict(tensor_parallel=2), "sharding")],
    ids=["disaggregate", "prefix_cache", "spec_len", "tensor_parallel"])
def test_the_deployment_refuses_at_construction(tiny, option, match):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=match + ".*recurrent state"):
        LlamaDeployment(config=cfg, params=params, **option)


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="kv_migration.*recurrent state"):
        eng.kv_export_pages([1])
    assert eng.kv_pin_prefix([1, 2]) == []      # no prefix cache to pin


def test_a_dense_model_is_refused_nothing():
    """The same options on a model with pages only are what they
    were."""
    from ray_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=2, page_size=8, n_pages=32,
                    prefix_cache=True, spec_len=2)
    report = eng.load_report()
    assert report["state_bytes_total"] == 0
    assert report["state_bytes_in_use"] == 0
    h = eng.submit([3, 4, 5, 6], max_new_tokens=5)
    _drive(eng)
    assert len(h.result()) == 5
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert rounds and all("state_slots" not in r for r in rounds)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    holder = {}

    @serve.deployment
    class SolarLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=16)
            holder["dep"] = self

    try:
        handle = serve.run(SolarLLM.bind(), timeout_s=300)
        prompt = _ids((33,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:33] == prompt and len(out) == 43
        _held_to_the_reference(params, cfg, prompt, out[33:])
        report = holder["dep"].engine().load_report()
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg)
        assert len(report["moe_expert_share"]) == 16
    finally:
        serve.shutdown()
