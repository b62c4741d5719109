"""A.X-K1 on the normal path (ray_tpu.models.axk1 through LLMEngine and
LlamaDeployment) against the plain float32 reference
(benchmarks/reference/axk1.py: the EXPANDED form, never the absorbed
one), on the CPU at ``axk1_tiny``: a dense layer and two mixture
layers of latent attention, 16 experts of which 4 a token and one
shared, YaRN over 64 original positions.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums and in the FORM of the attention (the
program folds the key up-projection into the query and reads the
latent pool a block at a time with an online softmax; the reference
expands K and V a head and takes one softmax): logits of the order of
1 agree to rtol 1e-4 / atol 2e-5, as the other families' do. Each
wrong rule below moves logits by a hundred times that or more. The
engine's tokens are held to the reference's full forward pass
teacher-forced: at every generated position where the reference's
top-2 margin exceeds ten times the rtol of the logits, the engine's
token is the reference's argmax.
"""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.axk1 import (AXK1, AXK1Config, AXK1DenseBlock,
                                 MLAttention, axk1, axk1_param_count,
                                 axk1_tiny, mla_param_count)
from ray_tpu.models.kv_cache import (KIND_KV, KIND_LATENT,
                                     export_page_bytes, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes, latent_page_width,
                                     page_cols_from_bytes)
from ray_tpu.models.llama import (LlamaAttention, LlamaMLP, block_forward,
                                  transformer_forward)
from ray_tpu.ops.paged_attention import PagedShapeError
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5


def _family():
    from benchmarks import common
    return common.load_family("axk1", "serve")


def _seeded(cfg, seed=0, model_cls=AXK1):
    """The benchmark family's seeded weights (not centered: the tests
    want uneven loads too), then every norm's scale away from one so
    that a scale left out shows."""
    from benchmarks import weights
    model = model_cls(cfg)
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
    cfg = axk1_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


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
    opts = dict(max_slots=4, page_size=8, n_pages=160, chunk=4,
                prefill_chunk=32, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass (the program's expanded form), 150
    positions: past the 64 original positions, so YaRN's blended
    frequencies differ from the original ones."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(rope_factor=1.0),                  # no YaRN: original frequencies
    dict(rope_mscale_all_dim=0.0),          # the scale without m^2
    dict(rope_beta_fast=2.0),               # another ramp
    dict(norm_topk_prob=False),
    dict(routed_scaling_factor=1.0),
    dict(router="softmax"),
    dict(first_k_dense=0)],
    ids=["no_yarn", "no_mscale", "other_ramp", "gates_not_renormalised",
         "no_scaling_factor", "softmax_router", "no_dense_layer"])
def test_each_declared_rule_shows(tiny, wrong):
    """A program that read one declared rule differently is far outside
    the tolerance that holds the right one."""
    cfg, _model, params = tiny
    ids = _ids((1, 150), seed=3)
    want = _reference(params, ids, cfg)
    other = dataclasses.replace(cfg, **wrong)
    if "first_k_dense" in wrong:
        # layer 0 a mixture: it needs that layer's weights
        _m, p2 = _seeded(other)
        got, _ = jax.jit(AXK1(other).apply)(p2, jnp.asarray(ids, jnp.int32))
    else:
        got, _ = jax.jit(AXK1(other).apply)(params,
                                            jnp.asarray(ids, jnp.int32))
    assert np.abs(np.asarray(got) - want).max() > 100 * (
        ATOL + RTOL * np.abs(want).max())


def test_the_reference_shows_an_unroped_key(tiny):
    """The control of the chip's comparison (PERF.md section 6, PR 34):
    a reference whose shared rope key is left as projected is far from
    the program."""
    cfg, model, params = tiny
    ids = _ids((1, 150), seed=4)
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    wrong = _reference(params, ids, cfg, unroped_key=True)
    assert np.abs(np.asarray(got) - wrong).max() > 100 * (
        ATOL + RTOL * np.abs(wrong).max())


def test_layer_kinds_and_the_published_count():
    cfg = axk1()
    assert cfg.layer_kinds == (KIND_LATENT,) * 61
    assert (cfg.latent_dim, cfg.qk_head_dim) == (576, 192)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.3466 ** 2) < 1e-4
    assert round(mla_param_count(cfg) / 1e6, 2) == 101.12
    # the catalog's "519B"
    assert round(axk1_param_count(cfg) / 1e9, 1) == 519.0
    # the benchmark's cut: five layers, 12 of 192 experts, 1/8 vocabulary
    cut = axk1(n_layers=5, vocab_size=20480, experts_held=(0, 12))
    assert round(axk1_param_count(cut, 12) / 1e9, 3) == 3.491
    shapes = jax.eval_shape(AXK1(cut).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == axk1_param_count(cut, 12)
    assert "feed_forward" in shapes["layers_0"]
    assert "moe" in shapes["layers_1"]
    assert "router_bias" not in shapes["layers_1"]["moe"]


# ----------------------------------- absorbed = expanded, in float32

def _attention(cfg, seed=5):
    attn = MLAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 40, cfg.dim))
    params = jax.jit(attn.init)(jax.random.PRNGKey(seed + 1), x, None,
                                jnp.arange(40))
    return attn, params, x


def _paged(attn, params, x, cfg, chunks, page_size=8, n_pages=24):
    """x [B, T, D] through a pool of latent pages in ``chunks`` calls
    (the absorbed form), each row on its own pages."""
    B, T, _ = x.shape
    (pool,) = init_kv_pool(dataclasses.replace(cfg, n_layers=1), n_pages,
                           page_size)
    per_row = -(-T // page_size)
    table = jnp.asarray(1 + np.arange(B * per_row).reshape(B, per_row),
                        jnp.int32)
    outs, start = [], 0
    for n in chunks:
        pos = jnp.full((B,), start, jnp.int32)
        positions = pos[:, None] + jnp.arange(n)[None]
        view = kv_layer_view(pool, table)
        out, view = jax.jit(attn.apply)(params, x[:, start:start + n],
                                        None, positions, view, pos)
        pool = kv_layer_store(view)
        outs.append(out)
        start += n
    return jnp.concatenate(outs, axis=1), pool


@pytest.mark.parametrize("chunks", [(40,), (24, 16), (13,) + (1,) * 27],
                         ids=["one_call", "two_chunks", "decode_steps"])
def test_absorbed_equals_expanded(chunks):
    """One layer of latent attention on 40 positions: the expanded form
    (no cache: K and V a head from every position's latent) and the
    absorbed form over the latent pool, in one prefill call, in two
    chunks across a page boundary, and as a prefill followed by decode
    steps of one token."""
    cfg = axk1_tiny(dtype=jnp.float32)
    attn, params, x = _attention(cfg)
    want, _ = jax.jit(attn.apply)(params, x, None, jnp.arange(40))
    got, pool = _paged(attn, params, x, cfg, chunks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    # no per-head K or V of a cached token is stored: the pool is one
    # entry a token, [c | k_r] and zeros up to whole 128-lane tiles
    (pages,) = pool
    width = latent_page_width(cfg)
    assert pages.shape == (24, 8, width) and width % 128 == 0
    stored = np.asarray(pages[1:6]).reshape(40, width)
    assert np.abs(stored[:, :cfg.latent_dim]).min() > 0
    assert not stored[:, cfg.latent_dim:].any()


# ------------------------------------ the paged path against the reference

def test_paged_logits_match_the_reference(tiny):
    """Chunked prefill of 600 tokens in chunks of 64 (across chunk
    boundaries, pages of 8 and the 512-token edge of the window loop's
    first block), then six decode steps through the latent pool,
    against the plain reference's full forward pass, ON LOGITS."""
    cfg, model, params = tiny
    P, G, C, page = 600, 6, 64, 8
    ids = _ids((1, P + G), seed=6)
    want = _reference(params, ids, cfg)[0]
    pool = init_kv_pool(cfg, 80, page)
    table = jnp.asarray(1 + np.arange(76)[None], jnp.int32)

    @jax.jit
    def call(pool, chunk, pos):
        views = [kv_layer_view(layer, table) for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    got = []
    for start in list(range(0, P, C)) + list(range(P, P + G)):
        n = min(C, P - start) if start < P else 1
        chunk = jnp.asarray(ids[:, start:start + n], jnp.int32)
        if n < C and start < P:
            chunk = jnp.pad(chunk, ((0, 0), (0, C - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32))
        got.append(np.asarray(logits[0, :n]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=RTOL,
                               atol=ATOL)


# ------------------------------------------------------------ the router

def test_the_routers_rule_matches_the_reference(tiny):
    """sigmoid scores over the whole width, the 4 largest (no bias),
    renormalised, times 2.5."""
    from benchmarks.reference import axk1 as ref
    cfg, _model, params = tiny
    w = {"router": jnp.asarray(
        params["params"]["layers_1"]["moe"]["router"], jnp.float32)}
    tokens = jax.random.normal(jax.random.PRNGKey(7), (50, cfg.dim))
    weights = np.asarray(ref.route(tokens, w, 4, True, 2.5))
    assert ((weights > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(tokens @ w["router"]))
    chosen = np.sort(np.argsort(-s, axis=-1)[:, :4], axis=-1)
    assert (chosen == np.sort(np.nonzero(weights > 0)[1].reshape(50, 4),
                              axis=-1)).all()


# ------------------------------------------------------------- the pool

def test_the_pool_holds_one_latent_entry_a_token(tiny):
    cfg, _model, _params = tiny
    pool = init_kv_pool(cfg, 16, 8)
    width = latent_page_width(cfg)
    assert len(pool) == 3 and all(len(layer) == 1 for layer in pool)
    assert pool[0][0].shape == (16, 8, width)
    assert kv_pool_page_bytes(cfg, 8) == 3 * 8 * width * 4
    # A.X-K1 itself: 576 of content in 640 stored columns, bfloat16
    real = axk1(n_layers=5)
    assert latent_page_width(real) == 640
    assert kv_pool_page_bytes(real, 64) == 5 * 64 * 640 * 2
    with pytest.raises(ValueError, match="int8.*latent"):
        init_kv_pool(cfg, 16, 8, "int8")
    # a page's frames: one tensor a layer, and back
    blobs = export_page_bytes(pool, 3)
    assert [len(layer) for layer in blobs] == [1, 1, 1]
    cols = page_cols_from_bytes(cfg, 8, "fp", blobs)
    assert cols[0][0].shape == (8, width)
    with pytest.raises(ValueError, match="tensors"):
        page_cols_from_bytes(cfg, 8, "fp", [[b, b] for (b,) in blobs])


def test_append_and_attend_refuse_mismatched_kinds():
    from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                             paged_append)
    pages = jnp.zeros((4, 8, 128))
    table = jnp.ones((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    entry = jnp.zeros((2, 3, 1, 128))
    with pytest.raises(PagedShapeError, match="without V pages"):
        paged_append(pages, None, table, pos, entry, entry)
    with pytest.raises(PagedShapeError, match="rank-3"):
        paged_append(pages[:, :, None], None, table, pos, entry, None)
    with pytest.raises(PagedShapeError, match="value_dim"):
        _paged_window_attention(jnp.zeros((2, 3, 4, 128)), pages, None,
                                None, None, table, pos)


# ----------------------------- a latent layer beside a K/V layer, one pool

@dataclasses.dataclass(frozen=True)
class _MixedConfig(AXK1Config):
    """A toy of two kinds of PAGED layer: layer 0 latent attention,
    layer 1 plain grouped-query attention (no positions)."""
    n_kv_heads: int = 2
    head_dim: int = 16
    qk_norm: bool = False
    attention_impl: str = "auto"

    @property
    def layer_kinds(self):
        return (KIND_LATENT, KIND_KV)


class _KVBlock(nn.Module):
    config: _MixedConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None, cache_len=None):
        cfg = self.config
        return block_forward(
            cfg, LlamaAttention(cfg, rope=False, name="attention"),
            LlamaMLP(cfg.dense_config(), name="feed_forward"),
            x, freqs, positions, kv_cache, cache_len)


class _Mixed(nn.Module):
    config: _MixedConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(
            self, self.config,
            lambda i: AXK1DenseBlock if i == 0 else _KVBlock,
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def test_a_latent_layer_beside_a_kv_layer_in_one_pool():
    """One allocator, one page table, two kinds of page: the mixed
    pool allocates, appends through both layers and frees right."""
    tiny_ = axk1_tiny(dtype=jnp.float32, n_layers=2)
    # no mixture in the toy: the engine asks a model with experts for
    # its routing counters
    cfg = _MixedConfig(**dict(dataclasses.asdict(tiny_), num_experts=0))
    model, params = _seeded(cfg, seed=2, model_cls=_Mixed)
    eng = LLMEngine(model, params, max_slots=3, page_size=8, n_pages=40,
                    chunk=4, prefill_chunk=16)
    width = latent_page_width(cfg)
    assert [len(layer) for layer in eng.pages] == [1, 2]
    assert eng.pages[0][0].shape == (40, 8, width)
    assert eng.pages[1][0].shape == (40, 8, 2, 16)
    assert eng.page_bytes == 8 * 4 * (width + 2 * 2 * 16)
    prompts = [_ids((n,), seed=20 + n).tolist() for n in (37, 5, 18)]
    handles = [eng.submit(p, max_new_tokens=9) for p in prompts]
    for _ in range(3):
        eng.step()
    assert eng.alloc.occupancy() >= sum(-(-len(p) // 8) for p in prompts[1:])
    _drive(eng)
    for p, h in zip(prompts, handles):
        out = h.result()
        full = jnp.asarray([p + list(out)], jnp.int32)
        logits, _ = jax.jit(model.apply)(params, full)
        steps = np.asarray(logits[0, len(p) - 1:-1])
        top2 = np.sort(steps, axis=-1)[:, -2:]
        sure = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
        assert sure.sum() >= 6
        assert (steps.argmax(-1)[sure] == np.asarray(out)[sure]).all()
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []
    report = eng.load_report()
    assert report["kv_bytes_per_token"] == eng.page_bytes / 8
    assert report["kv_bytes_in_use"] == 0


# ------------------------------------------------------ the paged engine

def test_mixed_rows_through_the_latent_pool(tiny):
    """Prompts of 150, 7 and 70 tokens in a prefill call of four rows
    of chunks of 32: the longest crosses five rounds, rows carry
    padding inside, and 12 tokens each are decoded through the latent
    pages; contexts run past the 64 original positions."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (150, 7, 70)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert max(r["prefill_rows"] for r in rounds) == 3
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        _held_to_the_reference(params, cfg, p, out)
    routed = sum(r["moe_pairs_routed"] for r in rounds)
    assert routed == sum(r["moe_pairs"] for r in rounds) > 0
    assert eng.alloc.occupancy() == 0


def test_a_share_of_the_experts_serves(tiny):
    """One chip's share (4 of 16 experts held, the router at its full
    width) through the engine against the reference handed the same
    share; the counters tell held pairs from routed ones."""
    cfg, _model, params = tiny
    share_cfg = dataclasses.replace(cfg, experts_held=(4, 4))
    p = jax.tree_util.tree_map(lambda a: a, params)
    for i in (1, 2):
        moe = dict(p["params"][f"layers_{i}"]["moe"])
        for k in ("w1", "w2", "w3"):
            moe[k] = moe[k][4:8]
        p["params"][f"layers_{i}"] = dict(p["params"][f"layers_{i}"],
                                          moe=moe)
    eng = LLMEngine(AXK1(share_cfg), p, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, prefill_chunk=32)
    prompt = _ids((90,), seed=31).tolist()
    h = eng.submit(prompt, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(p, share_cfg, prompt, h.result(), least=5)
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    held = sum(r["moe_pairs"] for r in rounds)
    routed = sum(r["moe_pairs_routed"] for r in rounds)
    assert 0 < held < routed


def test_preemption_recomputes_the_latent_pages(tiny):
    """A pool too small for two growing requests: the younger is
    evicted, its pages freed, and requeued with prompt + generated; both
    end as the reference has them."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2, page_size=4, n_pages=14, chunk=2,
                  prefill_chunk=8)
    prompts = [_ids((12,), seed=40).tolist(), _ids((11,), 41).tolist()]
    handles = [eng.submit(p, max_new_tokens=22) for p in prompts]
    _drive(eng)
    assert eng.stats["preemptions"] > 0
    for p, h in zip(prompts, handles):
        _held_to_the_reference(params, cfg, p, h.result())
    assert eng.alloc.occupancy() == 0


def test_the_prefix_cache_shares_latent_pages(tiny):
    """The prefix cache deals in page ids only: a second prompt that
    shares 64 tokens skips their prefill, a repeat of a whole prompt of
    full pages goes through the one copy-on-write page copy, and the
    tokens are those of an engine without the cache."""
    cfg, _model, params = tiny
    head = _ids((64,), seed=50).tolist()
    prompts = [head + _ids((9,), seed=51).tolist(),
               head + _ids((20,), seed=52).tolist(),
               head + _ids((9,), seed=51).tolist(),
               head[:48], head[:48]]
    plain, want = _engine(tiny), []
    for p in prompts:
        h = plain.submit(p, max_new_tokens=8)
        _drive(plain)
        want.append(h.result())
    eng, got = _engine(tiny, prefix_cache=True), []
    for p in prompts:
        h = eng.submit(p, max_new_tokens=8)
        _drive(eng)
        got.append(h.result())
    assert got == want
    assert eng.prefix_stats()["hit_tokens"] >= 64 + 64 + 40 + 40
    eng.prefix_cache.check_invariants()
    assert eng.alloc.occupancy() == eng.prefix_stats()["cached_pages"]
    for p, out in zip(prompts, got):
        _held_to_the_reference(params, cfg, p, out)


class _Scripted:
    """A proposer that drafts from a script keyed on the tokens
    generated so far (tests/test_spec_decode.py's seam)."""

    def __init__(self, prompt_len, script):
        self.prompt_len, self.script, self._done = prompt_len, script, 0

    def sync(self, context):
        self._done = len(context) - self.prompt_len

    def propose(self, k):
        return self.script[self._done:self._done + k]


def test_speculative_decoding_rolls_latent_pages_back(tiny):
    """Speculation deals in a page offset only: drafts are verified by
    the prefill program at decode offsets and rejected ones rolled back
    by clamping the offset. A script that is right at two places in
    three and wrong at the third has drafts accepted AND rejected; the
    tokens are plain greedy decoding's."""
    cfg, _model, params = tiny
    prompt = _ids((70,), seed=60).tolist()
    plain = _engine(tiny)
    h = plain.submit(prompt, max_new_tokens=24)
    _drive(plain)
    truth = h.result()
    script = [t if i % 3 else (t + 1) % 255 + 1
              for i, t in enumerate(truth)]
    eng = _engine(tiny, spec_len=3,
                  spec_proposer=lambda: _Scripted(len(prompt), script))
    g = eng.submit(prompt, max_new_tokens=24)
    _drive(eng)
    assert g.result() == truth
    st = eng.spec_stats()
    assert st["rounds"] > 0
    assert st["accepted_tokens"] > 0 and st["rejected_tokens"] > 0
    _held_to_the_reference(params, cfg, prompt, truth)
    assert eng.alloc.occupancy() == 0


def test_load_report_counts_latent_bytes(tiny):
    cfg, _model, _params = tiny
    eng = _engine(tiny)
    report = eng.load_report()
    width = latent_page_width(cfg)
    assert report["kv_bytes_per_token"] == 3 * width * 4
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8)
    assert report["kv_bytes_total"] == 160 * report["kv_page_bytes"]
    assert report["state_bytes_total"] == 0


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option,match", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'.*latent pages"),
    (dict(sharding=object()), "sharding.*latent pages")],
    ids=["int8", "sharding"])
def test_the_engine_refuses_what_latent_pages_cannot_do(tiny, option,
                                                        match):
    with pytest.raises(ValueError, match=match):
        _engine(tiny, **option)


@pytest.mark.parametrize("option,match", [
    (dict(disaggregate=True, prefix_cache=True), "disaggregate"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tensor_parallel=2), "sharding")],
    ids=["disaggregate", "int8", "tensor_parallel"])
def test_the_deployment_refuses_at_construction(tiny, option, match):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=match + ".*latent pages"):
        LlamaDeployment(config=cfg, params=params, **option)


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny, prefix_cache=True)
    with pytest.raises(ValueError, match="kv_migration.*latent pages"):
        eng.kv_export_pages([1])


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    caches = [(jnp.zeros((1, 16, 1, 8)),) * 2] * cfg.n_layers
    with pytest.raises(TypeError, match="latent pages"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    holder = {}

    @serve.deployment
    class LatentLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=32)
            holder["dep"] = self

    try:
        handle = serve.run(LatentLLM.bind(), timeout_s=300)
        prompt = _ids((83,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:83] == prompt and len(out) == 93
        _held_to_the_reference(params, cfg, prompt, out[83:])
        report = holder["dep"].engine().load_report()
        assert report["kv_bytes_per_token"] == \
            3 * latent_page_width(cfg) * 4
        assert len(report["moe_expert_share"]) == 16
    finally:
        serve.shutdown()
