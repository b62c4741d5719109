"""Granite-4.0-H on the normal path (ray_tpu.models.granite_hybrid
through LLMEngine and LlamaDeployment) against the plain float32
reference (benchmarks/reference/granite_hybrid.py), on the CPU at
``granite_hybrid_tiny``: one period of ten layers (Mamba-2 in nine, the
attention layer the sixth), 4 heads of 8 channels x 16 states solved in
chunks of 8, 4 query heads on 2 K/V heads of 16, a mixture of 8 experts
of which 3 a token and of which this share holds experts 0-3.

Tolerances. Both sides compute in float32 on the same weights and differ
in the order of their sums (the program solves a chunk of positions by
matrix products under a decay mask and hands the state from chunk to
chunk and from call to call; the reference scans positions one at a
time): logits of the order of 0.1 agree to rtol 1e-4 / atol 2e-6. Each
of the reference's controls moves logits by a thousand times that or
more. The engine's tokens are held to the reference's full forward pass
teacher-forced, and the captured log-probability of every generated
token (the whole row of logits behind it) to the reference's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kv_cache
from ray_tpu.models.granite_hybrid import (ATTENTION, MAMBA, GraniteHybrid,
                                           GraniteHybridConfig,
                                           Mamba2Mixer, NoPEAttention,
                                           granite_hybrid_param_count,
                                           granite_hybrid_tiny)
from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT,
                                     RecurrentState, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes, refuse_unsupported,
                                     state_bytes_per_slot)
from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.ssd import ssd_chunked, ssd_step
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-6
PAGE, CHUNK = 8, 16


def _family():
    from benchmarks import common
    return common.load_family("granite_hybrid", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale
    away from one so that a scale left out shows."""
    from benchmarks import weights
    model = GraniteHybrid(cfg)
    fam = _family()
    params = fam.init_params(weights.param_shapes(fam.model(cfg)), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        if "scale" in jax.tree_util.keystr(path):
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    """The chip's share: experts 0-3 of a router 8 wide."""
    cfg = granite_hybrid_tiny(dtype=jnp.float32, experts_held=(0, 4))
    model, params = _seeded(cfg)
    return cfg, model, params


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg, **control):
    fam = _family()
    return np.asarray(fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg, **control))


def _forward(model, params, ids):
    return np.asarray(jax.jit(model.apply)(
        params, jnp.asarray(ids, jnp.int32))[0])


def _held_to_the_reference(params, cfg, prompt, out):
    """The teacher-forced rule of the module docstring; returns the
    reference's logits of the generated positions."""
    P, G = len(prompt), len(out)
    logits = _reference(params, [list(prompt) + list(out)], cfg)[0]
    steps = logits[P - 1:P - 1 + G]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= G * 2 // 3
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]).all()
    return steps


def _drive(eng, max_rounds=5000):
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not quiesce")


def _engine(tiny, **kw):
    _cfg, model, params = tiny
    opts = dict(max_slots=4, page_size=PAGE, n_pages=64, chunk=4,
                prefill_chunk=CHUNK, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


# ----------------------------------------------------- the model itself

@pytest.mark.parametrize("seed", [2, 5, 6])
def test_forward_matches_the_reference(tiny, seed):
    """The whole forward pass without a cache: 37 positions are four
    chunks of 8 and one of 5 in every Mamba-2 layer."""
    cfg, model, params = tiny
    ids = _ids((2, 37), seed)
    np.testing.assert_allclose(_forward(model, params, ids),
                               _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)


def test_layer_kinds_and_the_published_counts():
    """The published model: 36 Mamba-2 layers and 4 attention layers at
    indices 5, 15, 25, 35; 32.2 B parameters whole and 4.76 B as the
    benchmark cuts it (one period, 36 of 72 experts, half the
    vocabulary); a slot's state by hand."""
    cfg = GraniteHybridConfig()
    kinds = cfg.layer_kinds
    assert len(kinds) == 40 and kinds.count(KIND_KV) == 4
    assert [i for i, k in enumerate(kinds) if k == KIND_KV] == [5, 15, 25,
                                                                35]
    assert cfg.layer_types[:10].count(MAMBA) == 9
    assert cfg.layer_types[5] == ATTENTION
    assert cfg.recurrent_state_shape == (128, 64, 128)
    assert cfg.recurrent_conv_shape == (3, 8448)
    assert cfg.head_dim == 128 and cfg.d_inner == 8192
    assert round(granite_hybrid_param_count(cfg) / 1e9, 1) == 32.2
    cut = GraniteHybridConfig(n_layers=10, vocab_size=50176,
                              experts_held=(0, 36))
    assert round(granite_hybrid_param_count(cut, 36) / 1e9, 2) == 4.76
    # 4 MiB of float32 state and 50,688 B of bfloat16 tail a layer
    assert state_bytes_per_slot(cut) == 9 * (4 * 2 ** 20 + 50688)
    assert kv_pool_page_bytes(cut, 64) == 64 * 4096
    with pytest.raises(ValueError, match="ONE group"):
        GraniteHybridConfig(mamba_groups=8)
    with pytest.raises(ValueError, match="layer_types names 10 of 11"):
        granite_hybrid_tiny(n_layers=11)


@pytest.mark.parametrize("layer_type", [MAMBA, ATTENTION])
def test_the_four_multipliers_by_hand_on_one_layer(layer_type):
    """One layer: the stream is ``12 E[id]``, each branch joins it times
    0.22, both feed-forwards read ONE norm, the logits are over 16; and
    the attention's scores are times ``attention_multiplier`` (a query
    against its keys by hand)."""
    cfg = granite_hybrid_tiny(dtype=jnp.float32, n_layers=1,
                              layer_types=(layer_type,),
                              embedding_multiplier=12.0,
                              residual_multiplier=0.22,
                              attention_multiplier=0.37,
                              logits_scaling=16.0)
    model, params = _seeded(cfg, seed=3)
    p = params["params"]
    lp = p["layers_0"]
    ids = jnp.asarray(_ids((2, 11), 4), jnp.int32)

    def norm(x, scale):
        return RMSNorm(cfg.norm_eps).apply({"params": {"scale": scale}}, x)
    x0 = 12.0 * p["tok_embeddings"][ids]
    h = norm(x0, lp["attention_norm"]["scale"])
    mixer = (NoPEAttention if layer_type == ATTENTION else Mamba2Mixer)(cfg)
    mixed, _ = mixer.apply({"params": lp["attention"]}, h, None, None)
    x1 = x0 + 0.22 * mixed
    n = norm(x1, lp["ffn_norm"]["scale"])
    x2 = x1 + 0.22 * MoEFeedForward(cfg).apply({"params": lp["moe"]}, n)
    want = norm(x2, p["norm"]["scale"]) @ p["tok_embeddings"].T / 16.0
    got = model.apply(params, ids)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(_reference(params, ids, cfg),
                               np.asarray(want), rtol=RTOL, atol=ATOL)
    if layer_type == ATTENTION:
        a = lp["attention"]
        q = (h @ a["wq"]["kernel"]).reshape(2, 11, 4, 16)
        k = (h @ a["wk"]["kernel"]).reshape(2, 11, 2, 16)
        v = (h @ a["wv"]["kernel"]).reshape(2, 11, 2, 16)
        # the last position's head 3 reads K/V head 1
        s = jnp.einsum("bd,bsd->bs", q[:, -1, 3], k[:, :, 1]) * 0.37
        o = jnp.einsum("bs,bsd->bd", jax.nn.softmax(s, -1), v[:, :, 1])
        heads = jnp.linalg.lstsq(a["wo"]["kernel"].T, mixed[:, -1].T)[0].T
        np.testing.assert_allclose(np.asarray(heads[:, 48:]),
                                   np.asarray(o), rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("what", _family().CONTROLS)
def test_the_comparison_fails_whatever_is_changed(tiny, what):
    """Each of the reference's eight controls (the last a state handed
    on in bfloat16) moves the logits by more than a hundred times the
    tolerance the program is held to."""
    assert len(_family().CONTROLS) == 8
    cfg, model, params = tiny
    ids = _ids((2, 37), 9)
    got = _forward(model, params, ids)
    wrong = _reference(params, ids, cfg, **{what: True})
    err = np.abs(got - wrong).max()
    assert err > 100 * (ATOL + RTOL * np.abs(got).max()), (what, err)


# ------------------------------------------------ the rule: ops/ssd.py

def _scan_inputs(B, T, H, P, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    dt = jax.nn.softplus(f(B, T, H) - 2.0)
    A = -jnp.exp(f(H))
    return f(B, T, H, P), dt, A, f(B, T, N), f(B, T, N), 1 + 0.5 * f(H)


@pytest.mark.parametrize("chunk", [1, 8, 256])
def test_step_chunked_and_the_reference_loop_agree(chunk):
    """``ssd_chunked`` at chunk lengths 1, 8 and 256 (T = 21: twenty-one
    chunks, three with a padded tail, one), ``ssd_step`` token by token
    and the reference's per-token loop give the same read-outs and the
    same final state."""
    from benchmarks.reference import granite_hybrid as ref
    B, T, H, P, N = 3, 21, 4, 8, 16
    x, dt, A, Bm, Cm, D = _scan_inputs(B, T, H, P, N)
    want = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    zeros = jnp.zeros((B, H, P, N), jnp.float32)
    valid = jnp.ones((B, T), bool)
    y, S = jax.jit(ssd_chunked, static_argnums=8)(
        x, dt, A, Bm, Cm, D, zeros, valid, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    s, ys = zeros, []
    for t in range(T):
        yt, s = ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, s,
                         valid[:, t])
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s),
                               rtol=1e-4, atol=1e-5)


def test_ragged_rows_cross_calls_and_leave_free_rows_alone():
    """Rows of 21, 13 and 0 real positions in calls of 8 (the state
    crossing three calls, a call's chunk of 4): each row's read-outs are
    the reference's over its own positions, a row that carries nothing
    keeps what it held bit for bit, and a ``fresh`` row of ``ssd_step``
    starts from zeros whatever its slot held."""
    from benchmarks.reference import granite_hybrid as ref
    B, T, H, P, N = 3, 24, 4, 8, 16
    x, dt, A, Bm, Cm, D = _scan_inputs(B, T, H, P, N, seed=1)
    lens = np.asarray([21, 13, 0])
    want = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    held = jnp.full((H, P, N), 7.0)
    S = jnp.zeros((B, H, P, N)).at[2].set(held)
    got = []
    for start in (0, 8, 16):
        valid = jnp.asarray(start + np.arange(8)[None] < lens[:, None])
        sl = slice(start, start + 8)
        y, S = ssd_chunked(x[:, sl], dt[:, sl], A, Bm[:, sl], Cm[:, sl], D,
                           S, valid, 4)
        got.append(y)
    got = np.asarray(jnp.concatenate(got, 1))
    for r, n in enumerate(lens[:2]):
        np.testing.assert_allclose(got[r, :n], np.asarray(want[r, :n]),
                                   rtol=1e-4, atol=1e-5)
    assert (np.asarray(S[2]) == 7.0).all()
    # the state after a row's LAST REAL position: row 1's after 13
    _, S13 = ssd_chunked(x[1:2, :13], dt[1:2, :13], A, Bm[1:2, :13],
                         Cm[1:2, :13], D, jnp.zeros((1, H, P, N)),
                         jnp.ones((1, 13), bool))
    np.testing.assert_allclose(np.asarray(S[1]), np.asarray(S13[0]),
                               rtol=1e-4, atol=1e-5)
    # one token: a fresh row starts from zeros, a free row stays
    dirty = jnp.full((B, H, P, N), 3.0)
    y, new = ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, dirty,
                      jnp.asarray([True, True, False]),
                      jnp.asarray([True, False, False]))
    y0, clean = ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D,
                         jnp.zeros_like(dirty), jnp.ones((B,), bool))
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(clean[0]))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y0[0]))
    assert np.abs(np.asarray(new[1] - clean[1])).max() > 1.0
    assert (np.asarray(new[2]) == 3.0).all()


# ----------------------------------------- the mixture behind the layers

def test_the_shares_add_up_to_the_whole_layer():
    """THE SHARE TEST (model-configs section 4): two chips hold four
    experts each of a router 8 wide. What each computes for the same
    tokens (its own experts' part, the router at its full width, the
    gates a softmax over all three chosen) plus the shared SwiGLU, which
    every chip computes alike, counted ONCE, is what the uncut reference
    gives for the whole layer."""
    from benchmarks.reference import granite_hybrid as ref
    cfg = granite_hybrid_tiny(dtype=jnp.float32)
    _model, params = _seeded(cfg, seed=1)
    layer = 3
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 31, cfg.dim))
    whole = params["params"][f"layers_{layer}"]["moe"]
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         _family().reference_weights(params, cfg)["layers"][layer].items()}
    with jax.default_matmul_precision("highest"):
        shared = ref.shared(x, w)
        want = ref.routed(x, w, top_k=cfg.num_experts_per_tok,
                          lo=0) + shared
    total, landed = jnp.zeros_like(x), 0
    for lo in (0, 4):
        share_cfg = dataclasses.replace(cfg, experts_held=(lo, 4))
        share = {k: (v[lo:lo + 4] if k in ("w1", "w2", "w3") else v)
                 for k, v in whole.items()}
        part = MoEFeedForward(share_cfg).apply({"params": share}, x)
        total = total + (part - shared)
        landed += float(jnp.abs(part - shared).max() > 1e-3)
    assert landed == 2                      # every share does some work
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=RTOL, atol=2e-5)
    # and the whole layer through the module that holds every expert
    np.testing.assert_allclose(
        np.asarray(MoEFeedForward(cfg).apply({"params": whole}, x)),
        np.asarray(want), rtol=RTOL, atol=2e-5)


# ------------------------------------ the paged path against the reference

def _call(model, params, table, slots):
    @jax.jit
    def call(pool, chunk, pos, n_real):
        valid = jnp.arange(chunk.shape[1])[None] < n_real[:, None]
        views = [kv_layer_view(layer, table, slots, lambda: valid)
                 for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    return call


def _pool(cfg, n_pages=40, n_slots=4):
    return init_kv_pool(cfg, n_pages, PAGE, n_slots=n_slots)


def test_the_pool_holds_each_layer_by_its_kind(tiny):
    """A state of RANK 3 a slot (``init_kv_pool`` takes the tuple as it
    is, ``state_bytes_per_slot`` multiplies it out) in nine layers, K/V
    pages in the sixth."""
    cfg, _model, _params = tiny
    pool = _pool(cfg)
    assert len(pool) == 10
    for i, (kind, entry) in enumerate(zip(cfg.layer_kinds, pool)):
        if i == 5:
            assert kind == KIND_KV
            assert [t.shape for t in entry] == [(40, PAGE, 2, 16)] * 2
        else:
            assert kind == KIND_RECURRENT
            assert isinstance(entry, RecurrentState)
            assert entry.state.shape == (4, 4, 8, 16)      # [slots, H, P, N]
            assert entry.state.dtype == jnp.float32
            assert entry.conv.shape == (4, 3, 4 * 8 + 2 * 16)
    assert kv_pool_page_bytes(cfg, PAGE) == 2 * PAGE * 2 * 16 * 4
    assert state_bytes_per_slot(cfg) == 9 * (4 * 4 * 8 * 16 + 4 * 3 * 64)


def test_paged_logits_match_the_reference(tiny):
    """Two rows of a prefill call of three (the third carries no
    request), 45 and 20 tokens in chunks of 16 (three and two calls: the
    state, the tail and the pages cross calls, each call two chunks of
    the recurrence; the last calls padded inside, so a prompt is no
    multiple of either chunk), then five decode steps through state,
    tail and pages, against the plain reference's full forward pass, ON
    LOGITS at every position."""
    cfg, model, params = tiny
    lens, G = (45, 20), 5
    ids = [_ids((n + G,), seed=30 + n) for n in lens]
    want = [_reference(params, [row], cfg)[0] for row in ids]
    pool = _pool(cfg)
    table = np.zeros((3, 8), np.int32)
    table[0, :7] = 1 + np.arange(7)
    table[1, :4] = 10 + np.arange(4)
    got = [[], []]
    for start in (0, CHUNK, 2 * CHUNK):
        chunk = np.zeros((3, CHUNK), np.int32)
        n_real = [max(0, min(CHUNK, n - start)) for n in lens] + [0]
        for r, n in enumerate(n_real[:2]):
            chunk[r, :n] = ids[r][start:start + n]
        # a row whose prompt is done rides the call as a dummy; rows 0
        # and 1 carry slots 2 and 0, a dummy names no slot (4)
        live_table = table.copy()
        for r, n in enumerate(n_real[:2]):
            if not n:
                live_table[r] = 0
        call = _call(model, params, jnp.asarray(live_table), jnp.asarray(
            [2 if n_real[0] else 4, 0 if n_real[1] else 4, 4], jnp.int32))
        logits, pool = call(
            pool, jnp.asarray(chunk),
            jnp.asarray([start, start if n_real[1] else 977, 977],
                        jnp.int32),
            jnp.asarray(n_real, jnp.int32))
        for r, n in enumerate(n_real[:2]):
            got[r].append(np.asarray(logits[r, :n]))
    # decode: row i IS slot i (slots None), every slot rides
    dtable = np.zeros((4, 8), np.int32)
    dtable[2], dtable[0] = table[0], table[1]
    decode = _call(model, params, jnp.asarray(dtable), None)
    row_of = {0: 2, 1: 0}
    for step in range(G):
        tok = np.zeros((4, 1), np.int32)
        pos = np.zeros((4,), np.int32)
        for r, n in enumerate(lens):
            tok[row_of[r], 0] = ids[r][n + step]
            pos[row_of[r]] = n + step
        live = np.asarray([1, 0, 1, 0], np.int32)
        logits, pool = decode(pool, jnp.asarray(tok), jnp.asarray(pos),
                              jnp.asarray(live))
        for r in range(2):
            got[r].append(np.asarray(logits[row_of[r], :1]))
    for r in range(2):
        np.testing.assert_allclose(np.concatenate(got[r]), want[r],
                                   rtol=RTOL, atol=ATOL)
    # the slots that carried nothing hold nothing: state and tail
    for entry in pool:
        if isinstance(entry, RecurrentState):
            assert not np.asarray(entry.state[jnp.asarray([1, 3])]).any()
            assert not np.asarray(entry.conv[jnp.asarray([1, 3])]).any()
            assert np.abs(np.asarray(entry.state[2])).max() > 0


def test_the_engine_matches_the_reference(tiny):
    """The real engine: three prompts of 52, 7 and 21 tokens in a
    prefill call of four rows of chunks of 16 (the longest crosses four
    rounds with its state and its tail handed over), then decoding in
    dispatches of four steps through state and pages. The tokens are
    the reference's teacher-forced, the captured log-probability of
    every generated token is the reference's, and the mixture's counters
    are live for this family: a share's pairs and the pairs routed."""
    cfg, _model, params = tiny
    eng = _engine(tiny, capture_logprobs=True)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (52, 7, 21)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    eng.accounts.take()
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert max(r["prefill_rows"] for r in rounds) == 3
    assert eng.stats["decode_kernel_pages"] == 0      # the CPU: the loop
    assert eng.stats["prefill_scan_kernel_positions"] == 0
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        steps = _held_to_the_reference(params, cfg, p, out)
        want = np.asarray(jax.nn.log_softmax(steps))[
            np.arange(len(out)), out]
        np.testing.assert_allclose(h.logprobs, want, rtol=1e-3, atol=2e-5)
    # ten mixture layers a forward, three experts a live token, a share
    # of them held; a decode step's own counters beside them
    stats = eng.stats
    assert stats["moe_layer_steps"] % 10 == 0
    assert stats["moe_decode_layer_steps"] % 10 == 0
    assert 0 < stats["moe_decode_layer_steps"] < stats["moe_layer_steps"]
    live_tokens = stats["moe_pairs_routed"] // (3 * 10)
    assert stats["moe_pairs_routed"] == 3 * 10 * live_tokens
    assert live_tokens >= sum(len(p) for p in prompts) + 3 * 11
    assert 0 < stats["moe_pairs"] < stats["moe_pairs_routed"]
    assert 0 < stats["moe_decode_experts_touched"] <= \
        4 * stats["moe_decode_layer_steps"]
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        stats["state_slots"] > 0
    report = eng.load_report()
    assert report["state_bytes_in_use"] == 0
    assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg)
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, PAGE)
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_a_reused_slot_starts_from_zeros(tiny):
    """One slot, two requests in turn: the second finds the first's
    state, tail and (behind re-allocated page ids) pages in its slot and
    must not see them."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1, n_pages=9)        # 8 usable pages
    first, second = _ids((40,), seed=20).tolist(), _ids((19,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    state = [np.asarray(e.state) for e in eng.pages
             if isinstance(e, RecurrentState)]
    assert len(state) == 9 and all(np.abs(s).max() > 0 for s in state)
    assert eng.alloc.occupancy() == 0
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_free_slots_ride_without_moving_what_they_hold(tiny):
    """One request in an engine of four slots: the other three ride
    every decode call, and their state and tail stay what they were, bit
    for bit; the pages no request holds stay too."""
    cfg, _model, params = tiny
    eng = _engine(tiny)

    def marked(e):
        if isinstance(e, RecurrentState):
            return RecurrentState(e.state.at[1:].set(7.0),
                                  e.conv.at[1:].set(3.0))
        return tuple(t.at[40:].set(9.0) for t in e)
    eng.pages = [marked(e) for e in eng.pages]
    prompt = _ids((37,), seed=30).tolist()
    h = eng.submit(prompt, max_new_tokens=9)
    _drive(eng)
    _held_to_the_reference(params, cfg, prompt, h.result())
    for entry in eng.pages:
        if isinstance(entry, RecurrentState):
            assert (np.asarray(entry.state[1:]) == 7.0).all()
            assert (np.asarray(entry.conv[1:]) == 3.0).all()
            assert np.abs(np.asarray(entry.state[0])).max() > 0
        else:
            assert all((np.asarray(t[40:]) == 9.0).all() for t in entry)


def test_more_requests_than_slots(tiny):
    """Seven requests on two slots and a pool that holds two requests'
    pages: every slot and every page id is reused, and each request
    gives the tokens the reference gives."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2, n_pages=17)
    prompts = [_ids((9 + 7 * i,), seed=60 + i).tolist() for i in range(7)]
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng)
    for p, h in zip(prompts, handles):
        _held_to_the_reference(params, cfg, p, h.result())
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option", sorted(
    kv_cache.KIND_REFUSALS[KIND_RECURRENT][1]))
def test_every_refusal_of_its_kinds_is_reached(tiny, option):
    """The model answers through the rows of the kinds it has: every
    option of the recurrent row is refused by its own words, and the K/V
    row refuses nothing (``kv_dtype`` is served: below)."""
    cfg, _model, _params = tiny
    keeps, why = kv_cache.KIND_REFUSALS[KIND_RECURRENT]
    with pytest.raises(ValueError) as refused:
        refuse_unsupported(cfg, **{option: "asked"})
    assert str(refused.value) == (
        f"{option}='asked' is not supported for GraniteHybridConfig: it "
        f"has layers that keep {keeps}; {why[option]}")
    assert kv_cache.KIND_REFUSALS[KIND_KV][1] == {}
    refuse_unsupported(cfg, kv_dtype="int8")


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=2), "spec_len.*recurrent state"),
    (dict(sharding=object()), "sharding.*recurrent state")],
    ids=["prefix_cache", "spec_len", "sharding"])
def test_the_engine_refuses_what_the_state_cannot_do(tiny, option, match):
    with pytest.raises(ValueError, match=match) as refused:
        _engine(tiny, **option)
    assert "GraniteHybridConfig" in str(refused.value)


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="kv_migration.*recurrent state"):
        eng.kv_export_pages([1])


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


def test_int8_pages_beside_the_float32_state(tiny):
    """``kv_dtype="int8"`` quantizes the attention layer's pages and
    leaves the recurrent state float32; the engine serves it."""
    cfg, _model, _params = tiny
    eng = _engine(tiny, kv_dtype="int8")
    h = eng.submit(_ids((21,), seed=70).tolist(), max_new_tokens=6)
    _drive(eng)
    assert len(h.result()) == 6
    kinds = ["state" if isinstance(e, RecurrentState) else str(e[0].dtype)
             for e in eng.pages]
    assert kinds == ["state"] * 5 + ["int8"] + ["state"] * 4
    report = eng.load_report()
    assert report["kv_dtype"] == "int8"
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, PAGE, "int8")


def test_the_static_cache_path_refuses_it(tiny):
    from ray_tpu.models.llama import generate
    _cfg, model, params = tiny
    with pytest.raises(TypeError, match="recurrent state"):
        generate(model, params, jnp.asarray(_ids((1, 8))), 4)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    holder = {}

    @serve.deployment
    class GraniteLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=PAGE, n_pages=64,
                             prefill_chunk=CHUNK)
            holder["dep"] = self

    try:
        handle = serve.run(GraniteLLM.bind(), timeout_s=300)
        prompt = _ids((41,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:41] == prompt and len(out) == 51
        _held_to_the_reference(params, cfg, prompt, out[41:])
        report = holder["dep"].engine().load_report()
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg)
        assert report["moe_pairs_total"] > 0
    finally:
        serve.shutdown()
