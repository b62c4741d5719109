"""Laguna on the normal path (ray_tpu.models.laguna through LLMEngine
and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/laguna.py: no cache, no ring, every expert on
every token; the masks, YaRN over the rotated half and the gate written
out from the equations), on the CPU at ``laguna_tiny``: the dense layer
and one period after it (full, sliding, sliding, sliding, full), 4 full
and 6 sliding query heads over 2 K/V heads of 16, a window of 12, half
a full head rotated under YaRN over 32 original positions, 16 experts
of which 4 a token and one shared. The first model whose QUERY differs
by layer type over one K/V pool.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums and in the FORM of both attentions:
logits of the order of 1 agree to rtol 1e-4 / atol 2e-5, as the other
families' do. Each wrong rule and each control below moves logits by a
thousand times that or more. The engine's tokens are held to the
reference's full forward pass teacher-forced: at every generated
position where the reference's top-2 margin exceeds ten times the rtol
of the logits, the engine's token is the reference's argmax.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.kv_cache import (KIND_KV, KIND_SLIDING, SlidingRing,
                                     init_kv_pool, kv_layer_store,
                                     kv_layer_view, kv_query_heads,
                                     layer_kinds, refuse_unsupported,
                                     sliding_ring_len,
                                     state_bytes_per_slot)
from ray_tpu.models.laguna import (DENSE, FULL, SLIDING, SPARSE, Laguna,
                                   LagunaConfig, laguna_param_count,
                                   laguna_tiny, laguna_xs2, rope_by_type)
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5
PAGE, CHUNK = 4, 16                 # a ring of 12 + 16 + 4 = 32 positions


def _family():
    from benchmarks import common
    return common.load_family("laguna", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale
    away from one so that a scale left out shows."""
    from benchmarks import weights
    model = Laguna(cfg)
    params = _family().init_params(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        if "scale" in jax.tree_util.keystr(path):
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = laguna_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


@pytest.fixture(params=["form", "kernel"])
def ring_form(request, monkeypatch):
    """The sliding layers through ``ring_append`` + ``ring_attention``
    (what the CPU runs) and through the Pallas kernel of
    ops/ring_window_attention.py in interpret mode (tests/test_mellum.py
    has the fixture's story)."""
    from ray_tpu.ops import ring_window_attention as rw
    from ray_tpu.serve import step_programs
    programs = (step_programs._jit_prefill, step_programs._jit_decode)
    if request.param == "kernel":
        calls = []
        monkeypatch.setattr(rw, "applies", lambda *a: True)
        kernel = rw.ring_window_kernel
        monkeypatch.setattr(
            rw, "ring_window_kernel", lambda *a, **kw: calls.append(
                a[0].shape) or kernel(*a, interpret=True, **kw))
        for program in programs:
            program.cache_clear()
    yield request.param
    if request.param == "kernel":
        assert calls, "the kernel was never traced"
        for program in programs:
            program.cache_clear()


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg, **control):
    fam = _family()
    return np.asarray(fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg, **control))


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
    opts = dict(max_slots=4, page_size=PAGE, n_pages=200, chunk=4,
                prefill_chunk=CHUNK, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


def _rounds(eng):
    return [e[5] for e in eng.events.snapshot() if e[2] == "round"]


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass, 150 positions: twelve windows and
    past YaRN's 32 original positions, ON LOGITS."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(gating=False),
    dict(partial_rotary_factor=1.0),
    dict(sliding_partial_rotary_factor=0.5),
    dict(sliding_rope_theta=10000.0),
    dict(sliding_window=11),
    dict(layer_types=(SLIDING, FULL, FULL, FULL, SLIDING)),
    dict(yarn_attention_factor=1.0),
    dict(yarn_factor=1.0),
    dict(n_shared_experts=0),
    dict(routed_scaling_factor=1.0),
    dict(norm_topk_prob=False),
    dict(router="softmax"),
    dict(num_experts_per_tok=3)],
    ids=["no_gate", "whole_head_rotated", "half_a_sliding_head_rotated",
         "one_theta", "window_one_short", "types_swapped",
         "no_attention_factor", "no_yarn", "no_shared_expert",
         "gates_not_scaled", "gates_not_renormalised", "softmax_router",
         "three_experts_a_token"])
def test_each_declared_rule_shows(tiny, wrong):
    """A program that read one declared rule differently is far outside
    the tolerance that holds the right one."""
    cfg, _model, params = tiny
    ids = _ids((1, 120), seed=3)
    want = _reference(params, ids, cfg)
    got, _ = jax.jit(Laguna(dataclasses.replace(cfg, **wrong)).apply)(
        params, jnp.asarray(ids, jnp.int32))
    assert np.abs(np.asarray(got) - want).max() > 1e3 * ATOL


def test_the_controls_are_the_issues_eight():
    assert list(_family().CONTROLS) == [
        "no_gate", "heads_swapped", "rotate_whole_head", "one_theta",
        "window_511", "no_shared", "scale_one", "lower_precision"]


@pytest.mark.parametrize("control", [
    "no_gate", "heads_swapped", "rotate_whole_head", "one_theta",
    "window_511", "no_shared", "scale_one", "lower_precision"])
def test_the_reference_shows_its_controls(tiny, control):
    """Each control the cell's ``correct`` must read FALSE under, as a
    failing case at this size: a reference without the gate, with a
    full layer's heads grouped by the sliding count, the whole head
    rotated, one rope base, the window one key short, no shared expert,
    unscaled gates or every matrix in float8 e4m3 is far from what the
    program computes."""
    cfg, model, params = tiny
    ids = _ids((1, 120), seed=4)
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    got = np.asarray(got)
    np.testing.assert_allclose(got, _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    off = _reference(params, ids, cfg, **{control: True})
    assert np.abs(got - off).max() > 1e3 * ATOL
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, off, rtol=RTOL, atol=ATOL)


def test_yarn_over_the_rotated_half_at_the_published_numbers():
    """A full layer rotates 64 of 128 columns, and YaRN's correction
    range is computed for THOSE 64 (Hugging Face's ``dim`` = head_dim x
    partial_rotary_factor): low = floor(32 ln(4096 / (128 pi)) /
    ln 5e5) = 5, high = ceil(32 ln(4096 / (2 pi)) / ln 5e5) = 16; for
    the whole head they would be 11 and 32. The model's frequencies are
    the reference's; a sliding layer's are plain at ITS base over the
    whole head."""
    from benchmarks.reference import laguna as ref
    cfg = laguna_xs2()

    def turning(width, turns):
        return (width / 2) * math.log(4096 / (2 * math.pi * turns)) \
            / math.log(5e5)
    assert (math.floor(turning(64, 64)), math.ceil(turning(64, 1))) == \
        (5, 16)
    assert (math.floor(turning(128, 64)), math.ceil(turning(128, 1))) == \
        (11, 32)
    width, inv, scale = rope_by_type(cfg, FULL)
    assert (width, inv.shape, scale) == (64, (32,), 1.4158883083359672)
    want = ref.yarn_inv_freq(64, 5e5, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want),
                               rtol=1e-6)
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(np.asarray(inv[:6]), plain[:6], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[16:]), plain[16:] / 64,
                               rtol=1e-6)
    assert abs(scale - (0.1 * math.log(64) + 1)) < 1e-12
    width, inv, scale = rope_by_type(cfg, SLIDING)
    assert (width, inv.shape, scale) == (128, (64,), 1.0)
    np.testing.assert_allclose(
        np.asarray(inv), 1e4 ** (-2.0 * np.arange(64) / 128), rtol=1e-6)


def test_the_unrotated_half_passes_as_it_is(tiny):
    """Columns 8..15 of a full layer's head of 16 carry no position:
    ``_rotate`` leaves them bit for bit, and rotates the first 8 as a
    head of 8 is rotated."""
    from ray_tpu.models.axk1 import _rope
    from ray_tpu.models.laguna import _rotate
    cfg = tiny[0]
    width, inv, scale = rope_by_type(cfg, FULL)
    assert width == 8
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 5, 3, 16)), jnp.float32)
    pos = jnp.asarray([[40, 41, 42, 43, 44], [0, 1, 2, 3, 4]])
    got = _rotate(x, width, inv, pos, scale)
    assert (np.asarray(got[..., 8:]) == np.asarray(x[..., 8:])).all()
    np.testing.assert_array_equal(
        np.asarray(got[..., :8]),
        np.asarray(_rope(x[..., :8], inv, pos, scale)))
    assert np.abs(np.asarray(got[0, :, :, :8] - x[0, :, :, :8])).max() > .1


def test_layer_kinds_heads_and_the_published_count():
    """The published keys close on 33.44 B parameters and 3.0 B active
    (ISSUE 53's arithmetic, published 33.4B-A3B); the cut of five
    layers is 3.870 B; a kind of request state has ONE query width."""
    cfg = laguna_xs2()
    kinds = layer_kinds(cfg)
    assert kinds == (KIND_KV, KIND_SLIDING, KIND_SLIDING,
                     KIND_SLIDING) * 10
    assert cfg.query_heads_by_kind == {KIND_KV: 48, KIND_SLIDING: 64}
    assert kv_query_heads(cfg, KIND_KV) == 48
    assert kv_query_heads(cfg, KIND_SLIDING) == 64
    assert cfg.mlp_layer_types[:3] == (DENSE, SPARSE, SPARSE)
    n = laguna_param_count(cfg)
    assert round(n / 1e9, 2) == 33.44
    assert round(laguna_param_count(cfg, experts=8) / 1e9, 1) == 3.0
    # by hand: a full layer's attention 29.46 M, a sliding one's 37.88
    # M, a mixture 808.98 M, the dense SwiGLU 50.33 M, 411.04 M of
    # embedding and head
    D = 2048
    full = 2 * D * 6144 + 2 * D * 1024 + D * 48
    slide = 2 * D * 8192 + 2 * D * 1024 + D * 64
    moe = 257 * 3 * D * 512 + D * 256
    assert (round(full / 1e6, 2), round(slide / 1e6, 2),
            round(moe / 1e6, 2)) == (29.46, 37.88, 808.98)
    want = (2 * 100352 * D + D + 40 * 2 * D + 10 * full + 30 * slide
            + 3 * D * 8192 + 39 * moe)
    assert n == want
    d5 = laguna_xs2(n_layers=5)
    assert round(laguna_param_count(d5) / 1e9, 3) == 3.870
    assert layer_kinds(d5) == (KIND_KV, KIND_SLIDING, KIND_SLIDING,
                               KIND_SLIDING, KIND_KV)
    tiny_cfg = laguna_tiny()
    shapes = jax.eval_shape(Laguna(tiny_cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == laguna_param_count(
                   tiny_cfg)
    assert shapes["layers_0"]["attention"]["wq"]["kernel"].shape == (48, 64)
    assert shapes["layers_1"]["attention"]["wq"]["kernel"].shape == (48, 96)
    assert shapes["layers_1"]["attention"]["wg"]["kernel"].shape == (48, 6)
    assert shapes["layers_1"]["attention"]["wk"]["kernel"].shape == \
        shapes["layers_0"]["attention"]["wk"]["kernel"].shape == (48, 32)
    assert "feed_forward" in shapes["layers_0"]
    assert shapes["layers_4"]["moe"]["shared_w1"].shape == (48, 24)
    # a config whose layers of one type disagree is no model here
    with pytest.raises(ValueError, match="ONE query shape"):
        laguna_tiny(n_heads_per_layer=(4, 6, 6, 8, 4))
    with pytest.raises(ValueError, match="whole groups"):
        laguna_tiny(n_heads_per_layer=(4, 5, 5, 5, 4))
    with pytest.raises(ValueError, match="whole number of pairs"):
        laguna_tiny(partial_rotary_factor=0.3)
    # a config that declares no heads by kind answers with n_heads
    from ray_tpu.models.mellum import mellum_tiny
    assert kv_query_heads(mellum_tiny(), KIND_SLIDING) == 4


# ------------------------------------ the paged path against the reference

def _call(model, params, table, slots):
    @jax.jit
    def call(pool, chunk, pos, n_real):
        def valid():
            return jnp.arange(chunk.shape[1])[None] < n_real[:, None]
        views = [kv_layer_view(layer, table, slots, valid)
                 for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    return call


def test_paged_logits_match_the_reference(tiny, ring_form):
    """Chunked prefill of 600 tokens in chunks of 16 (eighteen turns of
    the 32-position ring, 150 pages of 4, across the 512-token edge of
    the page loop's first block, fifty windows and far past YaRN's 32
    original positions), then six decode steps, through BOTH kinds of
    entry under BOTH query widths (the ring of slot 2 of 3 under 6
    heads, the K/V pages under 4), against the plain reference's full
    forward pass, ON LOGITS."""
    cfg, model, params = tiny
    P, G = 600, 6
    ids = _ids((1, P + G), seed=6)
    want = _reference(params, ids, cfg)[0]
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    assert L == 32
    pool = init_kv_pool(cfg, 160, PAGE, n_slots=3, ring_len=L)
    # one pool shape under both query widths
    assert len(pool) == 5
    table = jnp.asarray(1 + np.arange(155)[None], jnp.int32)
    call = _call(model, params, table, jnp.asarray([2], jnp.int32))
    got = []
    for start in list(range(0, P, CHUNK)) + list(range(P, P + G)):
        n = min(CHUNK, P - start) if start < P else 1
        chunk = jnp.asarray(ids[:, start:start + n], jnp.int32)
        if n < CHUNK and start < P:
            chunk = jnp.pad(chunk, ((0, 0), (0, CHUNK - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32),
                            jnp.asarray([n], jnp.int32))
        got.append(np.asarray(logits[0, :n]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=RTOL,
                               atol=ATOL)
    rings = [e for e in pool if isinstance(e, SlidingRing)]
    assert len(rings) == 3
    for entry in rings:
        assert entry.k.shape == (3, cfg.n_kv_heads, L, cfg.head_dim)
        assert not np.asarray(entry.k[:2]).any()
        assert np.abs(np.asarray(entry.k[2])).max() > 0


def test_the_engine_serves_requests_held_to_the_reference(tiny,
                                                          ring_form):
    """Five requests on four slots, prompts in chunks of 16 that cross
    the ring's wrap (32), page edges (4) and the window (12), then
    decode in dispatches of 4: every request's tokens are the
    reference's wherever the reference decides."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    lens = (70, 37, 5, 50, 33)
    prompts = [_ids((n,), seed=40 + n).tolist() for n in lens]
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    _drive(eng)
    for p, r in zip(prompts, reqs):
        out = r.result()
        assert len(out) == 14
        _held_to_the_reference(params, cfg, p, out)
    rep = eng.load_report()
    per_slot = 3 * 2 * cfg.n_kv_heads * 32 * cfg.head_dim * 4
    assert rep["sliding_bytes_per_slot"] == per_slot == \
        state_bytes_per_slot(cfg, 32)
    rounds = _rounds(eng)
    assert any(r["moe_experts_touched"] for r in rounds)
    # four mixture layers of five: the dense layer sows no choice
    steps = [r for r in rounds if r.get("moe_decode_layer_steps")]
    assert steps and all(r["moe_decode_layer_steps"]
                         == 4 * r["decode_steps"] for r in steps)
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_preemption_recomputes_both_kinds_of_entry(tiny):
    """A pool too small for two long requests preempts one; recomputed
    from its prompt through both query widths, its tokens are what an
    undisturbed engine gives."""
    _cfg, _model, _params = tiny
    prompts = [_ids((40,), seed=80).tolist(), _ids((44,), seed=81).tolist()]
    calm = _engine(tiny)
    want = [calm.submit(p, max_new_tokens=24) for p in prompts]
    _drive(calm)
    tight = _engine(tiny, n_pages=28)
    got = [tight.submit(p, max_new_tokens=24) for p in prompts]
    _drive(tight)
    assert tight.stats["preemptions"] >= 1
    for w, g in zip(want, got):
        assert w.result() == g.result()


# --------------------------- the engine's two questions, by layer type

def test_the_engine_asks_each_kernel_with_its_layer_types_heads(
        tiny, monkeypatch):
    """``decode_kernel_pages`` and ``sliding_kernel_keys`` ask the two
    decode kernels' rules of the query each layer type REALLY hands
    them: 4 heads over the K/V pages, 6 over the rings, never
    ``cfg.n_heads`` for both (set to a third number here to show that
    the engine does not read it)."""
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.ops import ring_window_attention as rw
    cfg = dataclasses.replace(tiny[0], n_heads=10)
    model = Laguna(cfg)
    eng = _engine((cfg, model, tiny[2]))
    eng.submit(_ids((9,), seed=70).tolist(), max_new_tokens=9)
    eng.submit(_ids((40,), seed=71).tolist(), max_new_tokens=9)
    _drive(eng)                 # the CPU's programs: loop and pair
    assert eng.stats["decode_steps"]
    assert not eng.stats["decode_kernel_pages"]
    assert not eng.stats["sliding_kernel_keys"]
    before = len(_rounds(eng))
    paged, ring = [], []
    monkeypatch.setattr(pd, "applies",
                        lambda *a: paged.append(a) or True)
    monkeypatch.setattr(rw, "applies", lambda *a: ring.append(a) or True)
    eng.submit(_ids((9,), seed=70).tolist(), max_new_tokens=9)
    eng.submit(_ids((40,), seed=71).tolist(), max_new_tokens=9)
    _drive(eng)                 # no retrace: only the counters ask
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    q, pk, pv, sk, table, value_dim, _block_len = paged[0]
    assert q.shape == (4, 1, 4, cfg.head_dim)
    assert pk.shape == pv.shape == (1, PAGE, cfg.n_kv_heads, cfg.head_dim)
    assert sk is None and value_dim is None
    assert table.shape == (4, eng.max_pages)
    q, k, v, ring_k, ring_v, window = ring[0]
    assert q.shape == (4, 1, 6, cfg.head_dim)
    assert k.shape == v.shape == (4, 1, cfg.n_kv_heads, cfg.head_dim)
    assert ring_k.shape == ring_v.shape == (4, cfg.n_kv_heads, L,
                                            cfg.head_dim)
    assert window == cfg.sliding_window
    both = [r for r in _rounds(eng)[before:] if r["decode_riders"] == 2]
    assert both
    for r in both:
        assert r["sliding_kernel_keys"] == 2 * L
        assert r["decode_kernel_pages"] > 0
    assert eng.load_report()["sliding_kernel_keys"] == \
        eng.stats["sliding_kernel_keys"] > 0


def test_the_published_shapes_are_shapes_both_kernels_rules_take(
        monkeypatch):
    """By the rules alone, nothing compiled: at the cell's deployment
    (128 slots, pages of 64, a ring of 512 + 256 + 64 = 832) the ring
    kernel's rule takes 64 heads over 8 of 128 for one token and for a
    chunk of 256, and the paged decode kernel's takes 48 over the same
    pages; a TPU alone is what the CPU lacks."""
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.ops import ring_window_attention as rw
    cfg = laguna_xs2(n_layers=5, max_seq_len=4096)
    L = sliding_ring_len(cfg, 64, 256)
    assert L == 832 and rw.write_rows(L, 256) == 64
    bf = jnp.bfloat16
    ring = jax.ShapeDtypeStruct((128, 8, L, 128), bf)

    def asks(T, heads, rows):
        q = jax.ShapeDtypeStruct((rows, T, heads, 128), bf)
        k = jax.ShapeDtypeStruct((rows, T, 8, 128), bf)
        return q, k
    pages = jax.ShapeDtypeStruct((1, 64, 8, 128), bf)
    table = jax.ShapeDtypeStruct((128, 64), jnp.int32)
    for one_tpu in (False, True):
        monkeypatch.setattr(rw, "_on_one_tpu", lambda: one_tpu)
        monkeypatch.setattr(pd, "_on_one_tpu", lambda: one_tpu)
        for T, rows in ((1, 128), (256, 4)):
            q, k = asks(T, kv_query_heads(cfg, KIND_SLIDING), rows)
            assert rw.applies(q, k, k, ring, ring, 512) is one_tpu
        q, _k = asks(1, kv_query_heads(cfg, KIND_KV), 128)
        assert q.shape[2] == 48
        assert pd.applies(q, pages, pages, None, table) is one_tpu
    assert pd.pages_per_visit(48, 64, 8, 64) == 4


# ---------------------------------------------------------------- scopes

def test_the_scopes_reach_both_programs(tiny):
    """``attn_gate`` inside ``attn_sliding`` and inside ``attn_full``,
    the dense layer's ``feed_forward`` and the shared expert's
    ``moe_shared``, in the lowering of both step programs: what the
    benchmark's readers split a device trace by."""
    from ray_tpu.serve import step_programs
    cfg, model, params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    pool = init_kv_pool(cfg, 16, PAGE, n_slots=2, ring_len=L)
    i32 = jnp.int32
    key = jax.random.PRNGKey(0)
    table = jnp.zeros((2, 8), i32)
    decode = step_programs._jit_decode(model, 0.0, 8, 2, False, None).lower(
        params, pool, table, jnp.zeros((2,), i32), jnp.zeros((2,), i32),
        key, jnp.int32(1))
    prefill = step_programs._jit_prefill(model, 0.0, 2, False, None).lower(
        params, pool, jnp.zeros((2, CHUNK), i32), jnp.zeros((2,), i32),
        jnp.zeros((2,), i32), table, key, jnp.zeros((2,), i32))
    for lowered in (decode, prefill):
        text = lowered.as_text(debug_info=True)
        for scope in ("attn_sliding/ring_append", "attn_sliding/ring_scores",
                      "attn_sliding/attn_gate/wg", "attn_full/kv_append",
                      "attn_full/attn_scores", "attn_full/attn_gate/wg",
                      "layers_0/feed_forward", "moe_experts",
                      "moe_shared"):
            assert scope in text, scope
        assert "layers_0/moe" not in text


# ------------------------------------------------------------- refusals

_OPTIONS = ("prefix_cache", "spec_len", "kv_dtype", "kv_migration",
            "sharding")


@pytest.mark.parametrize("option", _OPTIONS)
def test_the_sliding_rows_refusals_reach_this_config(option):
    from ray_tpu.models.kv_cache import KIND_REFUSALS
    keeps, why = KIND_REFUSALS[KIND_SLIDING]
    cfg = laguna_tiny()
    with pytest.raises(ValueError) as refused:
        refuse_unsupported(cfg, **{option: "asked"})
    assert str(refused.value) == (
        f"{option}='asked' is not supported for LagunaConfig: it has "
        f"layers that keep {keeps}; {why[option]}")
    refuse_unsupported(cfg, **dict.fromkeys(_OPTIONS, False))


@pytest.mark.parametrize("option,name", [
    (dict(disaggregate=True), "disaggregate"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_len=3), "spec_len"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tensor_parallel=2), "sharding")],
    ids=["disaggregate", "prefix_cache", "spec_len", "int8",
         "tensor_parallel"])
def test_the_deployment_refuses_at_construction(tiny, option, name):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=name + ".*ring of their window"):
        LlamaDeployment(config=cfg, params=params, **option)


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    caches = [(jnp.zeros((1, 16, 2, 16)),) * 2] * cfg.n_layers
    with pytest.raises(TypeError, match="K/V pages in the model's type"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    assert cfg.model_class is Laguna and isinstance(cfg, LagunaConfig)
    assert not hasattr(cfg, "serving_rules")

    @serve.deployment
    class GatedLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=32)

    try:
        handle = serve.run(GatedLLM.bind(), timeout_s=120)
        prompt = _ids((45,), seed=90).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=120)
        assert out[:45] == prompt and len(out) == 55
        _held_to_the_reference(params, cfg, prompt, out[45:], least=5)
    finally:
        serve.shutdown()
