"""Kimi-Linear on the normal path (ray_tpu.models.kimi_linear through
LLMEngine and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/kimi_linear.py: the delta rule scanned token by
token, K and V EXPANDED a head, never the absorbed form), on the CPU at
``kimi_linear_tiny``: two periods of (KDA, KDA, KDA, MLA), the first
opening with the dense layer, 16 experts of which 4 a token and one
shared. The first model whose pool holds NO K/V layer: a recurrent
state a slot in six layers, latent pages in two.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums and in the FORM of both kinds of
token mixing (the program solves a chunk of the delta rule at once and
reads the latent pool a block at a time in the absorbed form; the
reference scans positions and expands K and V a head): logits of the
order of 1 agree to rtol 1e-4 / atol 2e-5, as the other families' do.
Each wrong rule below moves logits by a hundred times that or more. The
engine's tokens are held to the reference's full forward pass
teacher-forced: at every generated position where the reference's
top-2 margin exceeds ten times the rtol of the logits, the engine's
token is the reference's argmax.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.axk1 import MLAttention, mla_param_count
from ray_tpu.models.kimi_linear import (KimiLinear, kda_param_count,
                                        kimi_linear_48b,
                                        kimi_linear_param_count,
                                        kimi_linear_tiny)
from ray_tpu.models.kv_cache import (KIND_KV, KIND_LATENT, KIND_RECURRENT,
                                     RecurrentState, export_page_bytes,
                                     init_kv_pool, kv_layer_store,
                                     kv_layer_view, kv_pool_page_bytes,
                                     latent_page_width,
                                     page_cols_from_bytes,
                                     state_bytes_per_slot)
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.faults import FaultInjector

RTOL, ATOL = 1e-4, 2e-5


def _family():
    from benchmarks import common
    return common.load_family("kimi_linear", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights (not balanced: the tests
    want uneven loads too), then every norm's scale away from one so
    that a scale left out shows."""
    from benchmarks import weights
    model = KimiLinear(cfg)
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
    cfg = kimi_linear_tiny(dtype=jnp.float32)
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


def _rounds(eng):
    return [e[5] for e in eng.events.snapshot() if e[2] == "round"]


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass, 150 positions (the delta rule in
    chunks, the latent attention expanded)."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(kda_allow_neg_eigval=True),        # Solar-Open2's 2 sigmoid
    dict(norm_topk_prob=False),
    dict(routed_scaling_factor=1.0),
    dict(router="sigmoid")],                # no choice bias
    ids=["doubled_beta", "gates_not_renormalised", "no_scaling_factor",
         "no_choice_bias"])
def test_each_declared_rule_shows(tiny, wrong):
    """A program that read one declared rule differently is far outside
    the tolerance that holds the right one."""
    cfg, _model, params = tiny
    ids = _ids((1, 150), seed=3)
    want = _reference(params, ids, cfg)
    other = dataclasses.replace(cfg, **wrong)
    got, _ = jax.jit(KimiLinear(other).apply)(params,
                                              jnp.asarray(ids, jnp.int32))
    assert np.abs(np.asarray(got) - want).max() > 100 * (
        ATOL + RTOL * np.abs(want).max())


@pytest.mark.parametrize("control", ["doubled_beta", "unshared_key",
                                     "lower_precision"])
def test_the_reference_shows_its_controls(tiny, control):
    """The controls of the chip's comparison (PERF.md section 6, PR
    39): a reference that doubles beta, leaves the shared decoupled
    key out of the scores, or rounds every matrix to float8 e4m3 is far
    from the program."""
    cfg, model, params = tiny
    ids = _ids((1, 150), seed=4)
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    wrong = _reference(params, ids, cfg, **{control: True})
    assert np.abs(np.asarray(got) - wrong).max() > 100 * (
        ATOL + RTOL * np.abs(wrong).max())


def test_layer_kinds_and_the_published_count():
    cfg = kimi_linear_48b()
    kinds = cfg.layer_kinds
    assert KIND_KV not in kinds and len(kinds) == 27
    assert [i + 1 for i, k in enumerate(kinds) if k == KIND_LATENT] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert kinds.count(KIND_RECURRENT) == 20
    assert (cfg.latent_dim, cfg.qk_head_dim) == (576, 192)
    assert cfg.softmax_scale == 192 ** -0.5
    # ISSUE 39's arithmetic, re-derived by the program's own count
    assert round(kda_param_count(cfg) / 1e6, 2) == 39.52
    assert round(mla_param_count(cfg) / 1e6, 2) == 29.11
    assert round(kimi_linear_param_count(cfg) / 1e9, 2) == 49.12
    # the benchmark's cut: two periods, 64 of 256 experts, 1/4 vocabulary
    cut = kimi_linear_48b(n_layers=8, vocab_size=40960,
                          experts_held=(0, 64))
    assert cut.layer_kinds == (KIND_RECURRENT,) * 3 + (KIND_LATENT,) + \
        (KIND_RECURRENT,) * 3 + (KIND_LATENT,)
    assert round(kimi_linear_param_count(cut, 64) / 1e9, 3) == 3.772
    shapes = jax.eval_shape(KimiLinear(cut).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == kimi_linear_param_count(cut, 64)
    assert "feed_forward" in shapes["layers_0"]
    assert "conv" in shapes["layers_0"]["attention"]
    assert shapes["layers_1"]["moe"]["w1"].shape == (64, 2304, 1024)
    assert shapes["layers_1"]["moe"]["router"].shape == (2304, 256)
    assert shapes["layers_1"]["moe"]["router_bias"].shape == (256,)
    attn = shapes["layers_3"]["attention"]
    assert attn["wq"]["kernel"].shape == (2304, 32 * 192)
    assert attn["wkv_a"]["kernel"].shape == (2304, 576)
    assert attn["wkv_b"].shape == (512, 32 * 256)
    assert "wq_a" not in attn and "q_norm" not in attn
    # a slot's state: 6 x (2 MiB + 73,728 B); a token's pages: 2 x 1,280 B
    bf16 = dataclasses.replace(cut, dtype=jnp.bfloat16)
    assert state_bytes_per_slot(bf16) == 6 * 2_170_880
    assert kv_pool_page_bytes(bf16, 64) == 163_840


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
    one = dataclasses.replace(cfg, n_layers=1, full_attn_layers=(1,))
    (pool,) = init_kv_pool(one, n_pages, page_size)
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
    """One layer of NoPE latent attention with a direct query on 40
    positions: the expanded form (no cache) and the absorbed form over
    the latent pool, in one prefill call, in two chunks across a page
    boundary, and as a prefill followed by decode steps of one token."""
    cfg = kimi_linear_tiny(dtype=jnp.float32)
    attn, params, x = _attention(cfg)
    assert set(params["params"]) == {"wq", "wkv_a", "kv_norm", "wkv_b",
                                     "wo"}
    want, _ = jax.jit(attn.apply)(params, x, None, jnp.arange(40))
    got, pool = _paged(attn, params, x, cfg, chunks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    (pages,) = pool
    width = latent_page_width(cfg)
    assert pages.shape == (24, 8, width) and width % 128 == 0
    stored = np.asarray(pages[1:6]).reshape(40, width)
    assert np.abs(stored[:, :cfg.latent_dim]).min() > 0
    assert not stored[:, cfg.latent_dim:].any()
    # NoPE: what is stored does not depend on where it is stored
    shifted, pool2 = _paged(attn, params, x[:, 8:], cfg, (32,))
    np.testing.assert_allclose(
        np.asarray(pool2[0][1:5]).reshape(32, width), stored[8:],
        rtol=RTOL, atol=ATOL)


# ------------------------------------ the paged path against the reference

def test_paged_logits_match_the_reference(tiny):
    """Chunked prefill of 600 tokens in chunks of 64 (across chunk
    boundaries, pages of 8 and the 512-token edge of the window loop's
    first block), then six decode steps, through BOTH kinds of state
    (the recurrent state of slot 2 of 3, the latent pool), against the
    plain reference's full forward pass, ON LOGITS."""
    cfg, model, params = tiny
    P, G, C, page = 600, 6, 64, 8
    ids = _ids((1, P + G), seed=6)
    want = _reference(params, ids, cfg)[0]
    pool = init_kv_pool(cfg, 80, page, n_slots=3)
    table = jnp.asarray(1 + np.arange(76)[None], jnp.int32)
    slots = jnp.asarray([2], jnp.int32)

    @jax.jit
    def call(pool, chunk, pos, n_real):
        def valid():
            return jnp.arange(chunk.shape[1])[None] < n_real
        views = [kv_layer_view(layer, table, slots, valid)
                 for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    got = []
    for start in list(range(0, P, C)) + list(range(P, P + G)):
        n = min(C, P - start) if start < P else 1
        chunk = jnp.asarray(ids[:, start:start + n], jnp.int32)
        if n < C and start < P:
            chunk = jnp.pad(chunk, ((0, 0), (0, C - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32),
                            jnp.int32(n))
        got.append(np.asarray(logits[0, :n]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=RTOL,
                               atol=ATOL)
    # the other slots' state was never moved
    for entry in pool:
        if isinstance(entry, RecurrentState):
            assert not np.asarray(entry.state[:2]).any()
            assert np.abs(np.asarray(entry.state[2])).max() > 0


# ------------------------------------------------------------- the pool

def test_a_pool_with_no_kv_layer(tiny):
    """Six entries are a state a slot, two one pool of latent pages,
    none K and V: the page's bytes count the latent layers alone, the
    slot's the recurrent ones, a shipped page's frames skip the
    recurrent layers by kind, and int8 pages are refused by the latent
    layers."""
    cfg, _model, _params = tiny
    pool = init_kv_pool(cfg, 16, 8, n_slots=4)
    width = latent_page_width(cfg)
    assert len(pool) == 8
    for kind, entry in zip(cfg.layer_kinds, pool):
        if kind == KIND_RECURRENT:
            assert isinstance(entry, RecurrentState)
            assert entry.state.shape == (4, 4, 16, 16)
            assert entry.conv.shape == (4, 3, 3 * 64)
        else:
            assert len(entry) == 1 and entry[0].shape == (16, 8, width)
    assert kv_pool_page_bytes(cfg, 8) == 2 * 8 * width * 4
    assert state_bytes_per_slot(cfg) == 6 * (4 * 4 * 16 * 16
                                             + 4 * 3 * 192)
    with pytest.raises(ValueError, match="int8.*latent"):
        init_kv_pool(cfg, 16, 8, "int8", n_slots=4)
    paged = [e for e in pool if not isinstance(e, RecurrentState)]
    blobs = export_page_bytes(paged, 3)
    assert [len(layer) for layer in blobs] == [1, 1]
    cols = page_cols_from_bytes(cfg, 8, "fp", blobs)
    assert len(cols) == 2 and cols[0][0].shape == (8, width)
    with pytest.raises(ValueError, match="4 layers, pool has 2"):
        page_cols_from_bytes(cfg, 8, "fp", blobs + blobs)


def test_load_report_counts_both_kinds_and_no_kv(tiny):
    cfg, _model, _params = tiny
    eng = _engine(tiny)
    per_slot = state_bytes_per_slot(cfg)
    width = latent_page_width(cfg)
    eng.submit(_ids((20,), seed=60).tolist(), max_new_tokens=30)
    for _ in range(3):
        eng.step()
    report = eng.load_report()
    assert report["kv_bytes_per_token"] == 2 * width * 4
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8)
    assert report["kv_bytes_total"] == 160 * report["kv_page_bytes"]
    assert 0 < report["kv_bytes_in_use"] < report["kv_bytes_total"]
    assert report["state_bytes_total"] == 4 * per_slot
    assert report["state_bytes_in_use"] == per_slot
    _drive(eng)
    report = eng.load_report()
    assert report["state_bytes_in_use"] == report["kv_bytes_in_use"] == 0
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


# ------------------------------------------------------ the paged engine

def test_mixed_rows_through_both_kinds_of_state(tiny):
    """Prompts of 150, 7 and 70 tokens in a prefill call of four rows
    of chunks of 32: the longest crosses five rounds (its state and its
    latent pages cross rounds and page edges), rows carry padding
    inside, and 12 tokens each are decoded."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (150, 7, 70)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = _rounds(eng)
    assert max(r["prefill_rows"] for r in rounds) == 3
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        _held_to_the_reference(params, cfg, p, out)
    routed = sum(r["moe_pairs_routed"] for r in rounds)
    assert routed == sum(r["moe_pairs"] for r in rounds) > 0
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        eng.stats["state_slots"] > 0
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_a_reused_slot_starts_from_zeros(tiny):
    """One slot, two requests in turn: the second finds the first's
    state in its slot and the first's entries in its pages and must see
    neither."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1)
    first, second = _ids((30,), seed=20).tolist(), _ids((19,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_preemption_recomputes_both_kinds_of_state(tiny):
    """A pool too small for two growing requests: the younger is
    evicted, its pages freed, and requeued with prompt + generated,
    prefilled again from position 0 (its state rebuilt from zeros, its
    latent entries written again) and gives the tokens it would have
    given alone."""
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
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


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
    assert eng.alloc.occupancy() == 0


def test_more_clients_than_slots(tiny):
    """40 requests on 16 slots: a slot is the unit that bounds this
    model's concurrency, so the rest queue; every one ends as the
    reference has it, every slot was used, and nothing leaks."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=16, n_pages=16 * 6 + 1, chunk=4)
    prompts = [_ids((5 + (7 * i) % 30,), seed=100 + i).tolist()
               for i in range(40)]
    handles = [eng.submit(p, max_new_tokens=6 + i % 5)
               for i, p in enumerate(prompts)]
    peak = 0
    for _ in range(5000):
        if not eng.step():
            break
        peak = max(peak, 16 - eng.load_report()["free_slots"])
    assert peak == 16
    for i, (p, h) in enumerate(zip(prompts, handles)):
        out = h.result()
        assert len(out) == 6 + i % 5
        if i % 8 == 0:
            _held_to_the_reference(params, cfg, p, out, least=3)
    assert max(r["decode_riders"] for r in _rounds(eng)) > 8
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []
    assert eng.load_report()["state_bytes_in_use"] == 0


# -------------------------------------------- the riders' own contexts

def test_decode_context_tokens_by_hand(tiny):
    """The ``round`` event's ``decode_context_tokens`` is the sum of the
    riders' OWN context lengths after the dispatch, from the host's
    positions: one request of 20 tokens decoded in dispatches of 4
    steps reads 24, 28, ...; a second of 9 beside it adds its own; the
    longest rider's window, a whole block, is another number."""
    eng = _engine(tiny, chunk=4)
    eng.submit(_ids((20,), seed=70).tolist(), max_new_tokens=13)
    _drive(eng)
    dec = [r for r in _rounds(eng) if r["decode_steps"]]
    assert [r["decode_riders"] for r in dec] == [1] * len(dec)
    ctx, want = [r["decode_context_tokens"] for r in dec], []
    pos = 20
    for r in dec:
        pos += r["decode_steps"]
        want.append(pos)
    assert ctx == want and ctx[0] == 24
    assert all(r["decode_window_tokens"] == 512 for r in dec)
    assert eng.stats["decode_context_tokens"] == sum(ctx)
    # two riders: the sum of both, not riders x the longest
    eng = _engine(tiny, chunk=4)
    eng.submit(_ids((20,), seed=70).tolist(), max_new_tokens=13)
    eng.submit(_ids((9,), seed=71).tolist(), max_new_tokens=13)
    _drive(eng)
    both = [r for r in _rounds(eng) if r["decode_riders"] == 2]
    assert both
    first = both[0]
    assert first["decode_context_tokens"] == (
        20 + 9 + 2 * first["decode_steps"])
    for r in _rounds(eng):
        if not r["decode_steps"]:
            assert r["decode_context_tokens"] == 0
    # a model with pages only counts it too (a dense Llama)
    from ray_tpu.models.llama import Llama, llama_tiny
    lcfg = llama_tiny(dtype=jnp.float32)
    lm = Llama(lcfg)
    lp = jax.jit(lm.init)(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    dense = LLMEngine(lm, lp, max_slots=2, page_size=8, n_pages=32,
                      chunk=4)
    dense.submit([3, 4, 5, 6], max_new_tokens=6)
    _drive(dense)
    dec = [r for r in _rounds(dense) if r["decode_steps"]]
    assert dec[0]["decode_context_tokens"] == 4 + dec[0]["decode_steps"]


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=2), "spec_len.*recurrent state"),
    (dict(sharding=object()), "sharding.*recurrent state"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'.*latent pages")],
    ids=["prefix_cache", "spec_len", "sharding", "int8"])
def test_the_engine_refuses_by_both_lists_at_once(tiny, option, match):
    """One config stands on BOTH refusal lists: what shares, rewinds or
    ships a request's state is refused for the recurrent state, what
    interprets a page's payload for the latent pages, each naming the
    state it cannot handle."""
    with pytest.raises(ValueError, match=match):
        _engine(tiny, **option)


@pytest.mark.parametrize("option,match", [
    (dict(disaggregate=True, prefix_cache=True),
     "disaggregate.*recurrent state"),
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=3), "spec_len.*recurrent state"),
    (dict(tensor_parallel=2), "sharding.*recurrent state"),
    (dict(kv_dtype="int8"), "kv_dtype.*latent pages")],
    ids=["disaggregate", "prefix_cache", "spec_len", "tensor_parallel",
         "int8"])
def test_the_deployment_refuses_at_construction(tiny, option, match):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=match):
        LlamaDeployment(config=cfg, params=params, **option)


def test_each_list_alone_refuses_what_is_its_own(tiny, monkeypatch):
    """Were the recurrent layers' row lifted, the latent pages' row
    would still refuse KV export and sharding for this config, and the
    other way round: neither leans on the other."""
    from ray_tpu.models import kv_cache
    from ray_tpu.models.kv_cache import (KIND_LATENT, KIND_RECURRENT,
                                         refuse_unsupported)
    cfg, _model, _params = tiny
    for lifted, match in ((KIND_RECURRENT, "latent pages"),
                          (KIND_LATENT, "recurrent state")):
        with monkeypatch.context() as m:
            m.setitem(kv_cache.KIND_REFUSALS, lifted,
                      (kv_cache.KIND_REFUSALS[lifted][0], {}))
            for option in ("kv_migration", "sharding"):
                with pytest.raises(ValueError, match=match):
                    refuse_unsupported(cfg, **{option: True})
    refuse_unsupported(cfg, kv_dtype=False, sharding=False,
                       prefix_cache=False, spec_len=0)


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="kv_migration.*recurrent state"):
        eng.kv_export_pages([1])


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    caches = [(jnp.zeros((1, 16, 1, 8)),) * 2] * cfg.n_layers
    with pytest.raises(TypeError, match="recurrent state"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    assert cfg.model_class is KimiLinear
    assert not hasattr(cfg, "serving_rules")
    holder = {}

    @serve.deployment
    class HybridLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=32)
            holder["dep"] = self

    try:
        handle = serve.run(HybridLLM.bind(), timeout_s=300)
        prompt = _ids((83,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:83] == prompt and len(out) == 93
        _held_to_the_reference(params, cfg, prompt, out[83:])
        eng = holder["dep"].engine()
        assert KIND_KV not in eng.cfg.layer_kinds
        report = eng.load_report()
        assert report["kv_bytes_per_token"] == \
            2 * latent_page_width(cfg) * 4
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg)
        assert len(report["moe_expert_share"]) == 16
    finally:
        serve.shutdown()


def test_the_deployment_tunes_the_collector_once(tiny):
    """The first engine a process's deployment builds takes what is
    alive out of the cyclic collector's reach and spaces its young
    passes for a server (a full pass walked the whole heap with every
    thread stopped, one every 5.8 s at 128 slots: PERF.md section 6,
    PR 39); a later deployment changes nothing more."""
    import gc
    from ray_tpu.serve import obs
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    was, before = obs._COLLECTOR_TUNED, gc.get_freeze_count()
    thresholds = gc.get_threshold()
    obs._COLLECTOR_TUNED = False
    try:
        first = LlamaDeployment(config=cfg, params=params, max_slots=2,
                                page_size=8, n_pages=32)
        first.engine().shutdown()
        frozen = gc.get_freeze_count()
        assert obs._COLLECTOR_TUNED and frozen > before + 1000
        assert gc.get_threshold() == (obs.GC_YOUNG_THRESHOLD,
                                      *thresholds[1:])
        gc.set_threshold(*thresholds)
        second = LlamaDeployment(config=cfg, params=params, max_slots=2,
                                 page_size=8, n_pages=32)
        second.engine().shutdown()
        assert gc.get_freeze_count() <= frozen
        assert gc.get_threshold() == thresholds
    finally:
        obs._COLLECTOR_TUNED = was or obs._COLLECTOR_TUNED
        if obs._COLLECTOR_TUNED:
            gc.set_threshold(obs.GC_YOUNG_THRESHOLD, *thresholds[1:])
