"""Olmo-Hybrid on the normal path (ray_tpu.models.olmo_hybrid through
LLMEngine and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/olmo_hybrid.py), on the CPU at
``olmo_hybrid_tiny``: two periods of (linear, linear, linear, full), 6
heads (no multiple of 16) of 12 x 64 in the linear layers (stored two
side by side on the lanes, as the served 96 x 192 are) and of 8 in the
full ones, a dense SwiGLU in every layer.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums (the program solves a chunk of the
delta rule at once, through matmuls under the gate's [C, C] mask; the
reference scans positions): logits of the order of 1 agree to rtol
1e-4 / atol 2e-5, as the other families' do. Each thing ``config.json``
leaves open and this family assumes (the q/k norm, the norm on each
branch's OUTPUT, the factor 2 on beta, ONE gate a head), dropped from
the reference or from the program, moves logits by a hundred times
that or more, and a state carried in bfloat16 misses it too. The
engine's tokens are held to the reference's full forward pass
teacher-forced, and the captured log-probability of every generated
token (the whole row of logits behind it) to the reference's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import olmo_hybrid as olmo_mod
from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT,
                                     RecurrentState, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes,
                                     state_bytes_per_slot)
from ray_tpu.models.olmo_hybrid import (OlmoHybrid, linear_param_count,
                                        olmo_hybrid_7b,
                                        olmo_hybrid_param_count,
                                        olmo_hybrid_tiny)
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5
PAGE, CHUNK = 8, 16


def _family():
    from benchmarks import common
    return common.load_family("olmo_hybrid", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights (decays from 0.999 down to
    hard ones), then every norm's scale away from one so that a scale
    left out shows."""
    from benchmarks import weights
    model = OlmoHybrid(cfg)
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
    cfg = olmo_hybrid_tiny(dtype=jnp.float32)
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
    """The cache-less forward pass, 150 positions (the delta rule in two
    chunks of 64 and one of 22), ON LOGITS, at the file's tolerance. The
    ids are those of twelve seeds at which every way of solving a chunk
    reads under half of it (XLA's triangular solve, PR 49's, and
    ``unit_lower_inverse``: 0.36, 0.23, 0.22 and 0.24, 0.30, 0.29 of
    it): at ONE position in 300 this tiny model amplifies float32
    rounding twenty-fold, whatever solves the chunk and beside a
    float64 reference too (seed 1: 0.29 and 1.72; seed 9: 2.47 and
    1.83), so that other ids measure that position and not the program
    (PERF.md section 6, PR 50; tests/test_linear_attention.py holds the
    chunk form to a float64 recurrence)."""
    cfg, model, params = tiny
    ids = _ids((2, 150), seed=seed)
    got = _forward(model, params, ids)
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_layer_kinds_and_the_published_counts():
    """Olmo-Hybrid-7B by the equations: a linear layer 215.6 M (88.75 M
    of mixing + 126.81 M of SwiGLU), a full one 185.8 M, 32 layers and
    an untied vocabulary of 100,352 7.43 B; a slot's state 96 x 192
    float32 a head, 2,211,840 B a layer, beside a tail of 69,120 B; a
    token's K/V 15,360 B a full layer; the 3 : 1 pattern."""
    cfg = olmo_hybrid_7b()
    assert cfg.head_dim == 128 and cfg.qk_norm
    assert cfg.layer_kinds == (KIND_RECURRENT,) * 3 + (KIND_KV,) \
        + cfg.layer_kinds[4:]
    assert cfg.layer_kinds.count(KIND_KV) == 8
    # a head's 192 values are one and a half lane tiles: two heads
    # side by side are three, and so the state is stored
    assert cfg.state_pack == 2
    assert cfg.recurrent_state_shape == (15, 96, 384)
    assert cfg.recurrent_conv_shape == (3, 11520)
    mixing = linear_param_count(cfg)
    assert mixing == (3840 * (2 * 2880 + 3 * 5760) + 2 * 3840 * 30
                      + 4 * 11520 + 2 * 30 + 192)
    assert round(mixing / 1e4) == 8875
    assert round((mixing + 3 * 3840 * 11008) / 1e5) == 2156
    assert round((4 * 3840 * 3840 + 3 * 3840 * 11008) / 1e5) == 1858
    assert round(olmo_hybrid_param_count(cfg) / 1e7) == 743
    d16 = olmo_hybrid_7b(n_layers=16)
    assert d16.layer_kinds.count(KIND_RECURRENT) == 12
    assert round(olmo_hybrid_param_count(d16) / 1e6) == 4101
    assert state_bytes_per_slot(d16) == 12 * (2_211_840 + 69_120)
    # 30 K/V heads a token, stored as 32 (whole 16-row tiles)
    assert d16.kv_page_heads == 32
    assert kv_pool_page_bytes(d16, 64) == 64 * 65_536
    # the tiny model keeps what makes the shapes awkward
    tiny = olmo_hybrid_tiny()
    assert tiny.linear_heads % 16 and tiny.linear_key_head_dim % 8
    assert tiny.linear_value_head_dim != tiny.linear_key_head_dim
    assert tiny.state_pack == 2
    assert tiny.recurrent_state_shape == (3, 12, 128)
    assert olmo_hybrid_tiny(linear_value_head_dim=24).state_pack == 1
    assert tiny.layer_kinds == ((KIND_RECURRENT,) * 3 + (KIND_KV,)) * 2


CONTROLS = ["no_qk_norm", "pre_norm", "beta_one", "mean_gate",
            "bf16_state"]


def _without(monkeypatch, cfg, what):
    """The PROGRAM with one thing dropped (the model class to build)."""
    if what == "no_qk_norm":
        return OlmoHybrid(dataclasses.replace(cfg, qk_norm=False))
    if what == "beta_one":
        return OlmoHybrid(dataclasses.replace(
            cfg, linear_allow_neg_eigval=False))
    if what == "pre_norm":          # no norm on a branch's output
        real = olmo_mod.RMSNorm
        monkeypatch.setattr(
            olmo_mod, "RMSNorm", lambda eps, name: (lambda x: x)
            if name.endswith("post_norm") else real(eps, name=name))
    elif what == "mean_gate":       # every head decays as the mean head
        def mean(g):
            return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        chunked, step = olmo_mod.kda_chunked, olmo_mod.kda_step
        monkeypatch.setattr(
            olmo_mod, "kda_chunked",
            lambda q, k, v, g, *rest: chunked(q, k, v, mean(g), *rest))
        monkeypatch.setattr(
            olmo_mod, "kda_step",
            lambda q, k, v, g, *rest: step(q, k, v, mean(g), *rest))
    elif what == "bf16_state":      # the state rounded as it is handed on
        chunked = olmo_mod.kda_chunked

        def rounded(q, k, v, g, beta, state, valid):
            outs, T = [], q.shape[1]
            for t in range(0, T, 8):
                o, state = chunked(*(a[:, t:t + 8] for a in
                                     (q, k, v, g, beta)), state,
                                   valid[:, t:t + 8])
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
                outs.append(o)
            return jnp.concatenate(outs, axis=1), state
        monkeypatch.setattr(olmo_mod, "kda_chunked", rounded)
    return OlmoHybrid(cfg)


@pytest.mark.parametrize("side", ["reference", "program"])
@pytest.mark.parametrize("what", CONTROLS)
def test_the_comparison_fails_whatever_is_dropped(tiny, monkeypatch, what,
                                                  side):
    """The q/k norm, the norm on each branch's output, the factor 2 on
    beta, the gate a head, the float32 state: the program passes the
    comparison, and with any one of them dropped from the REFERENCE or
    from the PROGRAM it fails it."""
    cfg, model, params = tiny
    ids = _ids((1, 50), seed=4)
    np.testing.assert_allclose(_forward(model, params, ids),
                               _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    if side == "reference":
        got = _forward(model, params, ids)
        want = _reference(params, ids, cfg, **{what: True})
    else:
        got = _forward(_without(monkeypatch, cfg, what), params, ids)
        want = _reference(params, ids, cfg)
    # (without its output norms the program's stream overflows: no
    # number is no agreement either)
    gap = float(np.abs(got - want).max())
    assert not gap <= 100 * RTOL * float(np.abs(want).max()), gap


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


def test_the_pool_holds_each_layer_by_its_kind(tiny):
    cfg, _model, _params = tiny
    pool = init_kv_pool(cfg, 16, PAGE, n_slots=4)
    assert len(pool) == 8
    for kind, entry in zip(cfg.layer_kinds, pool):
        if kind == KIND_RECURRENT:
            assert isinstance(entry, RecurrentState)
            assert entry.state.shape == (4, 3, 12, 128)
            assert entry.state.dtype == jnp.float32
            assert entry.conv.shape == (4, 3, 6 * (12 + 12 + 64))
        else:
            assert entry[0].shape == (16, PAGE, 16, 8)   # 6 heads as 16
    assert kv_pool_page_bytes(cfg, PAGE) == 2 * 2 * PAGE * 16 * 8 * 4
    assert state_bytes_per_slot(cfg) == 6 * (4 * 6 * 12 * 64 + 4 * 3 * 528)


def test_paged_logits_match_the_reference(tiny):
    """Two rows of a prefill call of three (the third carries no
    request), 29 and 20 tokens in chunks of 16 (two chunks each: the
    state and the tail cross a call, the second chunk padded inside),
    then five decode steps through the slots' state and the full
    layers' pages, against the plain reference's full forward pass, ON
    LOGITS at every position."""
    cfg, model, params = tiny
    lens, G = (29, 20), 5
    ids = [_ids((n + G,), seed=30 + n) for n in lens]
    want = [_reference(params, [row], cfg)[0] for row in ids]
    pool = init_kv_pool(cfg, 40, PAGE, n_slots=4)
    table = np.zeros((3, 8), np.int32)
    table[0, :5] = 1 + np.arange(5)
    table[1, :4] = 10 + np.arange(4)
    # rows 0 and 1 carry slots 2 and 0; row 2 names no slot (4)
    prefill = _call(model, params, jnp.asarray(table),
                    jnp.asarray([2, 0, 4], jnp.int32))
    got = [[], []]
    for start in (0, CHUNK):
        chunk = np.zeros((3, CHUNK), np.int32)
        n_real = [max(0, min(CHUNK, n - start)) for n in lens] + [0]
        for r, n in enumerate(n_real[:2]):
            chunk[r, :n] = ids[r][start:start + n]
        logits, pool = prefill(
            pool, jnp.asarray(chunk),
            jnp.asarray([start, start, 977], jnp.int32),
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
    # the slots that carried nothing hold nothing
    for entry in pool:
        if isinstance(entry, RecurrentState):
            assert not np.asarray(entry.state[jnp.asarray([1, 3])]).any()
            assert np.abs(np.asarray(entry.state[2])).max() > 0


# ------------------------------------------------------------ the engine

def test_the_engine_matches_the_reference(tiny):
    """The real engine: three prompts of 40, 7 and 21 tokens in a
    prefill call of four rows of chunks of 16 (the longest crosses three
    rounds with its state handed over), then decoding in dispatches of
    four steps. The tokens are the reference's teacher-forced, and the
    captured log-probability of every generated token is the
    reference's."""
    cfg, _model, params = tiny
    eng = _engine(tiny, capture_logprobs=True)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (40, 7, 21)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert max(r["prefill_rows"] for r in rounds) == 3
    assert eng.stats["decode_kernel_pages"] == 0      # the CPU: the loop
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        steps = _held_to_the_reference(params, cfg, p, out)
        want = np.asarray(jax.nn.log_softmax(steps))[
            np.arange(len(out)), out]
        np.testing.assert_allclose(h.logprobs, want, rtol=RTOL, atol=ATOL)
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        eng.stats["state_slots"] > 0
    assert eng.load_report()["state_bytes_in_use"] == 0
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


@pytest.mark.parametrize("one_tpu", [False, True])
def test_kernel_pages_are_counted_exactly_where_decode_holds_the_kernel(
        monkeypatch, one_tpu):
    """The full layers pad their 6 heads to the 16 rows a page stores
    and hand the kernel's rule those; the engine's counter asks the
    same rows (with ``cfg.n_heads`` it read 0 beside a program that
    held the kernel). At the tiny model with heads of 128 in bfloat16,
    the shapes the rule serves: ``decode_kernel_pages`` moves exactly
    where the decode program, lowered for a TPU, holds the call."""
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    cfg = olmo_hybrid_tiny(dtype=jnp.bfloat16, dim=768, n_layers=4)
    assert (cfg.head_dim, cfg.n_heads, cfg.kv_page_heads) == (128, 6, 16)
    model, params = _seeded(cfg)
    eng = _engine((cfg, model, params))
    prompt = _ids((20,), seed=3).tolist()
    eng.submit(prompt, max_new_tokens=6)
    _drive(eng)                     # the CPU's program: the loop
    assert eng.stats["decode_steps"] and not eng.stats[
        "decode_kernel_pages"]
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: one_tpu)
    fresh = step_programs._jit_decode.__wrapped__(
        model, 0.0, eng.KMAX, eng.S, False, None)
    i32 = jnp.int32
    text = fresh.trace(
        params, eng.pages, jnp.zeros((eng.S, eng.max_pages), i32),
        jnp.zeros((eng.S,), i32), jnp.zeros((eng.S,), i32),
        jax.random.PRNGKey(0), i32(1)).lower(
            lowering_platforms=("tpu",)).as_text()
    holds = "tpu_custom_call" in text and "paged_decode" in text
    assert holds is one_tpu
    assert eng.accounts.decode_kernel_serves() is holds
    # the engine's own program is built (no retrace): only the counter
    # reads the rule again
    eng.submit(prompt, max_new_tokens=6)
    _drive(eng)
    assert (eng.stats["decode_kernel_pages"] > 0) is holds
    assert any(e[5]["decode_kernel_pages"] for e in eng.events.snapshot()
               if e[2] == "round") is holds


def test_a_reused_slot_starts_from_zeros(tiny):
    """One slot, two requests in turn: the second finds the first's
    state and convolution tail in its slot and must not see them."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1)
    first, second = _ids((30,), seed=20).tolist(), _ids((19,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    state = [np.asarray(e.state) for e in eng.pages
             if isinstance(e, RecurrentState)]
    assert len(state) == 6 and all(np.abs(s).max() > 0 for s in state)
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_free_slots_ride_without_moving_their_state(tiny):
    """One request in an engine of four slots: the other three ride
    every decode call, and their state stays what it was, bit for
    bit."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    eng.pages = [
        RecurrentState(e.state.at[1:].set(7.0), e.conv.at[1:].set(3.0))
        if isinstance(e, RecurrentState) else e for e in eng.pages]
    prompt = _ids((25,), seed=30).tolist()
    h = eng.submit(prompt, max_new_tokens=9)
    _drive(eng)
    _held_to_the_reference(params, cfg, prompt, h.result())
    for entry in eng.pages:
        if isinstance(entry, RecurrentState):
            assert (np.asarray(entry.state[1:]) == 7.0).all()
            assert (np.asarray(entry.conv[1:]) == 3.0).all()
            assert np.abs(np.asarray(entry.state[0])).max() > 0


def test_more_requests_than_slots(tiny):
    """Seven requests on two slots: every slot is reused, and each
    request gives the tokens it gives alone."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2)
    prompts = [_ids((9 + 5 * i,), seed=60 + i).tolist() for i in range(7)]
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng)
    for p, h in zip(prompts, handles):
        _held_to_the_reference(params, cfg, p, h.result())
    report = eng.load_report()
    assert report["state_bytes_total"] == 2 * state_bytes_per_slot(cfg)
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, PAGE)


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=2), "spec_len.*recurrent state"),
    (dict(sharding=object()), "sharding.*recurrent state")],
    ids=["prefix_cache", "spec_len", "sharding"])
def test_the_engine_refuses_what_the_state_cannot_do(tiny, option, match):
    with pytest.raises(ValueError, match=match) as refused:
        _engine(tiny, **option)
    assert "OlmoHybridConfig" in str(refused.value)


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
    class OlmoLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=PAGE, n_pages=64,
                             prefill_chunk=CHUNK)
            holder["dep"] = self

    try:
        handle = serve.run(OlmoLLM.bind(), timeout_s=300)
        prompt = _ids((33,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:33] == prompt and len(out) == 43
        _held_to_the_reference(params, cfg, prompt, out[33:])
        report = holder["dep"].engine().load_report()
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg)
    finally:
        serve.shutdown()
