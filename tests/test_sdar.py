"""SDAR (models/sdar.py): a model that DECODES BY BLOCKS, through the
normal serving path. Float32 parameters at toy sizes, so that orders
cannot flip: the cache-less forward against the plain reference
(benchmarks/reference/sdar.py) and its controls; the engine
(prefill in chunks + the block program, several slots, staggered
admissions, every prompt remainder, budgets that end inside a block, an
eos inside a block, a preemption inside a block) against
``reference.generate`` token for token AND reveal for reveal under the
three strategies; what a finished slot may not do; the planner's and
the accounts' properties in forwards and blocks; the one table's
refusals; and the block mask's argument at 1, which must leave every
other model's attention as it was.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, weights
from ray_tpu.models import kv_cache
from ray_tpu.models.kv_cache import (DECODES_BY_BLOCKS, BlockDecode,
                                     block_decode, refuse_unsupported)
from ray_tpu.models.sdar import Sdar, SdarConfig, sdar_tiny
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.scheduler import SlotView, plan_step

PAGE, CHUNK, L = 8, 16, 4
STRATEGIES = ("sequential", "low_confidence_static",
              "low_confidence_dynamic")


def _family():
    return common.load_family("sdar", "serve")


def _ids(shape, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         1, 250), np.int32)


@pytest.fixture(scope="module")
def seeded():
    """The toy's seeded float32 weights (every config of this file
    shares their shapes) under the program's and the reference's
    names."""
    cfg = sdar_tiny(dtype=jnp.float32)
    params = _family().init_params(weights.param_shapes(Sdar(cfg)), 7)
    return params, _family().reference_weights(params, cfg)


def _tiny(**overrides):
    cfg = sdar_tiny(dtype=jnp.float32, **overrides)
    return cfg, Sdar(cfg)


def _engine(model, params, **kw):
    opts = dict(max_slots=3, page_size=PAGE, n_pages=64, chunk=3,
                prefill_chunk=CHUNK, temperature=0.0, seed=0)
    opts.update(kw)
    eng = LLMEngine(model, params, **opts)
    eng.record_reveals = True
    return eng


def _drive(eng, max_rounds=5000):
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not quiesce")


# every prompt remainder 0..L-1, budgets that end inside a block, and
# more requests than slots (admissions stagger as slots retire); the
# totals round to few lengths, so the reference compiles few shapes
REQUESTS = ((8, 8), (9, 7), (10, 6), (11, 5), (3, 9), (16, 4), (2, 2))


def _requests():
    return [(_ids((p,), seed=40 + i).tolist(), n)
            for i, (p, n) in enumerate(REQUESTS)]


# ----------------------------------------------------- the model itself

def test_the_config_says_how_it_decodes_and_no_other_does():
    from ray_tpu.models.llama import llama_tiny
    from ray_tpu.models.mixtral import olmoe_tiny
    cfg = sdar_tiny()
    assert block_decode(cfg) == BlockDecode(4, 255, 4,
                                            "low_confidence_dynamic", 0.9)
    assert block_decode(llama_tiny()) is None
    assert block_decode(olmoe_tiny()) is None
    assert cfg.model_class is Sdar and cfg.head_dim != cfg.dim // cfg.n_heads
    full = SdarConfig()
    assert (full.dim, full.n_heads * full.head_dim) == (2048, 4096)
    with pytest.raises(ValueError, match="remasking"):
        sdar_tiny(remasking="confident")
    with pytest.raises(ValueError, match="mask_token_id"):
        sdar_tiny(mask_token_id=256)


@pytest.mark.parametrize("steps,counts,masked,forwards", [
    (4, (1, 1, 1, 1), 4, 5), (4, (1, 1, 1, 1), 2, 3),
    (2, (2, 2), 4, 3), (2, (2, 2), 1, 2), (3, (2, 1, 1), 4, 4),
    (3, (2, 1, 1), 3, 3)])
def test_the_schedule_is_the_sources(steps, counts, masked, forwards):
    bd = BlockDecode(4, 0, steps, "sequential", 0.9)
    assert bd.transfer_counts() == counts
    assert tuple(_family().ref.transfer_counts(4, steps)) == counts
    assert bd.forwards(masked) == forwards


def _model_logits(model, params, ids):
    return np.asarray(jax.jit(lambda p, i: model.apply(p, i)[0])(
        params, jnp.asarray(ids)))


@pytest.mark.parametrize("control", [
    None, {"block_length": 1}, {"whole_width_norm": True}, "shifted"])
def test_forward_matches_the_reference_and_not_its_controls(seeded,
                                                            control):
    """The cache-less forward is the reference's; a causal mask, a
    whole-width query/key norm or a read shifted by one each is not."""
    params, rw = seeded
    cfg, model = _tiny()
    ids = _ids((2, 24), seed=3)
    masked = np.zeros(ids.shape, bool)
    masked[:, 21:] = masked[0, 13] = True
    fed = np.where(masked, cfg.mask_token_id, ids)
    got = _model_logits(model, params, fed)
    kw = control if isinstance(control, dict) else {}
    want = np.asarray(_family().reference_forward(rw, ids, cfg, masked,
                                                  **kw))
    if control == "shifted":
        got, want = got[:, 1:], want[:, :-1]
    err = np.abs(got - want).max() / np.abs(want).max()
    if control is None:
        assert err < 2e-5, err
    else:
        assert err > 1e-2, (control, err)


def test_the_static_cache_path_refuses_it(seeded):
    params, _rw = seeded
    cfg, model = _tiny()
    caches = [(jnp.zeros((1, 8, cfg.n_kv_heads, cfg.head_dim)),) * 2
              for _ in range(cfg.n_layers)]
    with pytest.raises(TypeError, match="decodes by blocks"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


# ------------------------------------- the engine against the reference

def _mid_threshold(rw, cfg):
    """A threshold by a rule about the toy's confidences alone: the
    middle of the widest gap among the middle half of the confidences
    at which the static schedule chose its tokens (so that some
    positions clear it and some do not, none of them nearly)."""
    fam = _family()
    static = sdar_tiny(dtype=jnp.float32,
                       remasking="low_confidence_static")
    conf = []
    for prompt, n in _requests():
        _t, _s, logits = fam.reference_generate(rw, prompt, n, static)
        conf += np.exp(fam.ref.confidences(logits)[1]).tolist()
    conf = np.sort(conf)[len(conf) // 4:3 * len(conf) // 4]
    at = int(np.argmax(np.diff(conf)))
    return float(conf[at] + conf[at + 1]) / 2.0


CASES = [(s, 0.9) for s in STRATEGIES] + [
    ("low_confidence_dynamic", t) for t in (0.0, "mid", 1.0)]


@pytest.mark.parametrize("strategy,threshold", CASES)
def test_the_engine_is_the_reference_token_for_token_and_reveal_for_reveal(
        seeded, strategy, threshold):
    """Prefill in chunks + block decoding through ``LLMEngine.submit``
    equal ``reference.generate``: the tokens AND the forward of its
    block at which each was revealed; the block program's counters add
    up; every page comes back."""
    params, rw = seeded
    if threshold == "mid":
        threshold = _mid_threshold(rw, sdar_tiny(dtype=jnp.float32))
    cfg, model = _tiny(remasking=strategy, confidence_threshold=threshold)
    eng = _engine(model, params)
    reqs = _requests()
    handles = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
    _drive(eng)
    fam = _family()
    spread = set()
    for (prompt, n), h in zip(reqs, handles):
        toks, steps, _logits = fam.reference_generate(rw, prompt, n, cfg)
        assert h.result() == toks.tolist()
        assert h._req.reveal_steps == steps.tolist()
        spread |= set(steps.tolist())
    if strategy != "low_confidence_dynamic" or threshold >= 0.9:
        assert spread == {0, 1, 2, 3}      # one position a step
    elif threshold == 0.0:
        assert spread == {0}               # a whole block a forward
    else:
        assert len(spread) > 1             # blocks of different lengths
    eng.accounts.take()                    # the last dispatches' counters
    s = eng.stats
    emitted = sum(n for _p, n in reqs)
    assert s["denoise_emitted"] == emitted
    assert s["denoise_revealed"] >= emitted
    assert s["denoise_rider_forwards"] + s["denoise_idle_forwards"] <= \
        s["decode_steps"] * eng.S
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_the_counters_add_up_to_blocks_times_forwards(seeded):
    """Under a schedule that reveals a fixed count a step the bound is
    exact: every block of four masks costs 4 + 1 forwards, nothing
    idles, 0.8 tokens a rider-forward, one forward in five a commit."""
    params, _rw = seeded
    cfg, model = _tiny(remasking="low_confidence_static")
    eng = _engine(model, params, chunk=10)
    handles = [eng.submit(_ids((8,), seed=i).tolist(), max_new_tokens=16)
               for i in range(3)]
    _drive(eng)
    assert all(len(h.result()) == 16 for h in handles)
    eng.accounts.take()
    s = eng.stats
    assert s["denoise_commits"] == 3 * 4
    assert s["denoise_rider_forwards"] == s["denoise_commits"] * 5
    assert s["denoise_idle_forwards"] == 0
    assert s["denoise_emitted"] == 0.8 * s["denoise_rider_forwards"]
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert sum(r.get("denoise_rider_forwards", 0) for r in rounds) <= 60
    assert eng.load_report()["block_length"] == 4


def test_an_eos_inside_a_block_cuts_the_stream_there(seeded):
    params, rw = seeded
    cfg, model = _tiny()
    prompt = _ids((9,), seed=60).tolist()
    toks, _steps, _lg = _family().reference_generate(rw, prompt, 14, cfg)
    toks = toks.tolist()
    eos = toks[5]                  # the second block's third position
    cut = toks.index(eos) + 1
    for overlap in (True, False):
        eng = _engine(model, params, eos_id=eos, overlap=overlap)
        h = eng.submit(prompt, max_new_tokens=14)
        _drive(eng)
        assert h.result() == toks[:cut]
        assert eng.alloc.occupancy() == 0


def test_a_preemption_inside_a_block_gives_the_uninterrupted_stream(seeded):
    """Preempted between two forwards of a block, a request re-prefills
    ``prompt + emitted tokens`` (a prompt with a remainder before its
    first commit, whole blocks after) and its stream is the
    undisturbed engine's: the commits' K/V are the re-prefill's."""
    params, _rw = seeded
    cfg, model = _tiny(remasking="low_confidence_static")
    prompts = [_ids((10,), seed=70).tolist(), _ids((8,), seed=71).tolist()]
    calm = _engine(model, params)
    want = [calm.submit(p, max_new_tokens=18) for p in prompts]
    _drive(calm)
    eng = _engine(model, params)         # 3 forwards a dispatch: a block
    got = [eng.submit(p, max_new_tokens=18) for p in prompts]  # of 5 straddles
    done = []
    for _ in range(200):
        eng.step()
        slot = eng.slots[0]
        if slot is not None and slot.cur is not None and \
                slot.decoded % 5 and len(slot.req.generated) >= 4 \
                and not done:
            with eng._lock:
                eng._preempt_locked(0)
            done.append(len(slot.req.generated))
    _drive(eng)
    assert done and eng.stats["preemptions"] == 1
    for w, g in zip(want, got):
        assert w.result() == g.result()
        assert w._req.reveal_steps == g._req.reveal_steps
    assert eng.alloc.occupancy() == 0


def test_a_finished_slot_moves_nothing_and_no_page_lies_past_the_end(seeded):
    """A slot that is owed nothing rides as a free one does: the block
    program leaves the pool and the slot's state bit for bit, counts
    its forwards as idle and routes none of its rows; and a request
    never holds a page past its last block's end."""
    params, _rw = seeded
    cfg, model = _tiny(confidence_threshold=0.0)   # 2 forwards a block
    eng = _engine(model, params, chunk=4, max_slots=2)
    h = eng.submit(_ids((10,), seed=80).tolist(), max_new_tokens=7)
    held = []
    for _ in range(200):
        live = eng.step()
        for slot in eng.slots:
            if slot is not None:
                assert len(slot.pages) <= -(-slot.end // PAGE)
                held.append(list(slot.pages))
        if not live:
            break
    assert len(h.result()) == 7 and held
    before = jax.device_get(eng.pages)
    state = jax.device_get(eng._dev_blocks)
    assert state[1][0] == 0                        # owed nothing
    table = np.zeros((eng.S, eng.max_pages), np.int32)
    table[0, :len(held[-1])] = held[-1]            # its pages, still named
    out, eng.pages, eng._rng, eng._dev_blocks, moe = eng._decode_fn(
        eng.params, eng.pages, jnp.asarray(table), eng._dev_blocks,
        eng._rng, jnp.int32(7))
    after = jax.device_get(eng.pages)
    for b, a in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        # (but for the null page, where every dead write lands)
        assert np.array_equal(b[1:], a[1:])
    for b, a in zip(state, jax.device_get(eng._dev_blocks)):
        assert np.array_equal(b, a)
    _buf, cnt, rev, tally = jax.device_get(out)
    assert not cnt.any() and not rev.any()
    assert tally.tolist() == [0, 0, 0, 0, 7]
    # no row routed: every expert's pairs, the touched, the fullest and
    # the tiles are 0 (the vector's layer-steps count the forwards)
    routed = np.asarray(moe)
    assert routed[cfg.num_experts + 2] == 7 * cfg.n_layers
    assert not np.delete(routed, cfg.num_experts + 2).any()


# --------------------------------- the planner and the books in forwards

def test_owed_is_a_bound_in_forwards_and_the_planner_plans_steps(seeded):
    params, _rw = seeded
    cfg, model = _tiny()
    eng = _engine(model, params, chunk=10)
    h = eng.submit(_ids((10,), seed=90).tolist(), max_new_tokens=9)
    eng.step()                                      # admit + prefill
    slot = eng.slots[0]
    # remainder 2: the first block has 2 masks (3 forwards), then
    # ceil((2 + 9) / 4) - 1 = 2 whole blocks of 5
    assert (slot.forwards, slot.end, slot.tail) == (13, 20, slot.tail)
    assert len(slot.tail) == 2 and eng._owed(slot) == 13 - slot.decoded
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0, owed=13,
                      seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0, owed=40,
                      seeded=True)]
    # a full, seeded batch runs ahead to the next completion BY THE BOUND
    plan = plan_step(views, total_slots=2, prefill_chunk=CHUNK,
                     decode_chunk=10, max_run_ahead=128, prefill_batch=4,
                     eos_bounded=False)
    assert plan.decode_steps == 13
    # admission work pending: the cadence is the chunk, whole blocks
    plan = plan_step(views, total_slots=3, prefill_chunk=CHUNK,
                     decode_chunk=10, max_run_ahead=128, prefill_batch=4,
                     eos_bounded=False)
    assert plan.decode_steps == 10
    _drive(eng)
    assert len(h.result()) == 9


def test_a_slot_that_finishes_early_retires_at_the_readback(seeded):
    """``low_confidence_dynamic`` at a threshold everything clears: two
    forwards a block against a bound of five; the readback that shows
    the last block frees the slot, the bound is never consumed, and the
    forwards ridden meanwhile are counted idle."""
    params, _rw = seeded
    cfg, model = _tiny(confidence_threshold=0.0)
    eng = _engine(model, params, chunk=6)
    h = eng.submit(_ids((8,), seed=91).tolist(), max_new_tokens=12)
    _drive(eng)
    assert len(h.result()) == 12
    eng.accounts.take()
    s = eng.stats
    assert s["denoise_commits"] == 3 and s["denoise_rider_forwards"] == 6
    assert s["decode_steps"] < 15                   # the bound: 3 x 5
    assert s["denoise_idle_forwards"] == s["decode_steps"] - 6


def test_sizes_that_split_a_block_are_refused(seeded):
    params, _rw = seeded
    _cfg, model = _tiny()
    with pytest.raises(ValueError, match="block_length"):
        LLMEngine(model, params, page_size=6, n_pages=16)
    with pytest.raises(ValueError, match="block_length"):
        LLMEngine(model, params, page_size=8, n_pages=16, prefill_chunk=10)


# ------------------------------------------------ the table's refusals

_OPTIONS = tuple(kv_cache.KIND_REFUSALS[DECODES_BY_BLOCKS][1])


def test_the_row_has_the_issues_six():
    assert set(_OPTIONS) == {"spec_len", "capture_logprobs",
                             "kv_migration", "prefix_cache", "kv_dtype",
                             "sharding"}


@pytest.mark.parametrize("option", _OPTIONS)
def test_the_tables_words_reach_the_refusal(option):
    how, why = kv_cache.KIND_REFUSALS[DECODES_BY_BLOCKS]
    cfg = sdar_tiny()
    with pytest.raises(ValueError) as refused:
        refuse_unsupported(cfg, **{option: "asked"})
    assert str(refused.value) == (
        f"{option}='asked' is not supported for SdarConfig: {how}; "
        f"{why[option]}")
    refuse_unsupported(cfg, **dict.fromkeys(_OPTIONS, False))
    # how a model decodes is asked of the config, whatever its type
    other = types.SimpleNamespace(n_layers=1, block_decode=cfg.block_decode)
    with pytest.raises(ValueError, match="decodes by blocks"):
        refuse_unsupported(other, **{option: True})


@pytest.mark.parametrize("option,kw", [
    ("spec_len", dict(spec_len=2)),
    ("capture_logprobs", dict(capture_logprobs=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_dtype", dict(kv_dtype="int8"))])
def test_the_engine_refuses_at_construction(seeded, option, kw):
    params, _rw = seeded
    _cfg, model = _tiny()
    with pytest.raises(ValueError, match=f"^{option}="):
        LLMEngine(model, params, page_size=PAGE, n_pages=16, **kw)


def test_the_deployment_and_the_export_refuse_too(seeded):
    from ray_tpu.serve.llm import LlamaDeployment
    params, _rw = seeded
    cfg, model = _tiny()
    with pytest.raises(ValueError, match="^kv_migration='disaggregate'"):
        LlamaDeployment(config=cfg, params=params, disaggregate=True,
                        prefix_cache=False)
    with pytest.raises(ValueError, match="^sharding="):
        LlamaDeployment(config=cfg, params=params, tensor_parallel=2)
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="^kv_migration='export'"):
        eng.kv_export_pages([1])


# ----------------------------- the block mask's argument, at 1 and past

def _pool_and_chunk(KH, hd, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    B, T, H, n_pages = 3, 8, 2 * KH, 12
    pk = jax.random.normal(ks[0], (n_pages, PAGE, KH, hd), dtype)
    pv = jax.random.normal(ks[1], (n_pages, PAGE, KH, hd), dtype)
    q = jax.random.normal(ks[2], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[3], (B, T, KH, hd), dtype)
    v = jax.random.normal(ks[4], (B, T, KH, hd), dtype)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0], [0, 0, 0, 0]],
                        jnp.int32)
    pos = jnp.asarray([12, 8, 40], jnp.int32)
    return q, k, v, pk, pv, table, pos


@pytest.mark.parametrize("KH,hd,dtype", [
    (2, 16, jnp.float32), (4, 16, jnp.bfloat16), (1, 32, jnp.float32)])
def test_block_len_1_is_the_program_every_other_model_has(KH, hd, dtype):
    """At ``block_len`` 1 the traced function is, equation for
    equation, the one without the argument, and its results are bit
    for bit the same (Mistral's and OLMoE's hot loop)."""
    q, k, v, pk, pv, table, pos = _pool_and_chunk(KH, hd, dtype)
    pk, pv = paged_append(pk, pv, table, pos, k, v)

    def plain(q, pk, pv, table, pos):
        return _paged_window_attention(q, pk, pv, None, None, table, pos)

    def at_one(q, pk, pv, table, pos):
        return _paged_window_attention(q, pk, pv, None, None, table, pos,
                                       block_len=1)
    args = (q, pk, pv, table, pos)
    assert str(jax.make_jaxpr(plain)(*args)) == \
        str(jax.make_jaxpr(at_one)(*args))
    assert np.array_equal(np.asarray(jax.jit(plain)(*args), np.float32),
                          np.asarray(jax.jit(at_one)(*args), np.float32))


@pytest.mark.parametrize("block_len", [2, 4, 8])
def test_the_block_mask_is_the_dense_one(block_len):
    """Query i sees the keys below (i // L + 1) * L: the paged loop
    against one dense softmax over the gathered window, rows that carry
    no request aside."""
    q, k, v, pk, pv, table, pos = _pool_and_chunk(2, 16, jnp.float32, 1)
    pos = pos // block_len * block_len
    pk, pv = paged_append(pk, pv, table, pos, k, v)
    got = np.asarray(_paged_window_attention(
        q, pk, pv, None, None, table, pos, block_len=block_len))
    B, T, H, hd = q.shape
    for b in range(2):
        keys = np.asarray(pk[table[b]]).reshape(-1, 2, hd)
        vals = np.asarray(pv[table[b]]).reshape(-1, 2, hd)
        for t in range(T):
            end = ((int(pos[b]) + t) // block_len + 1) * block_len
            for h in range(H):
                s = keys[:end, h // 2] @ np.asarray(q[b, t, h]) / np.sqrt(hd)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vals[:end, h // 2]
                assert np.allclose(got[b, t, h], want, atol=1e-5)


def test_the_decode_kernel_takes_a_block_under_the_block_mask(monkeypatch):
    """Steered onto a TPU, a decode step at ``block_len`` 1 goes to the
    decode kernel, and so does a block of four queries a row at
    ``block_len`` 4, with the mask passed on: what the kernel (here in
    interpret mode) reads out is the loop's. The engine asks the rule
    with a whole block a row and is told yes; off the chip, no."""
    from ray_tpu.ops import paged_decode_attention as paged_decode
    kernel = paged_decode.paged_decode_attention
    called = []
    monkeypatch.setattr(
        paged_decode, "paged_decode_attention",
        lambda q, *a, **kw: called.append((q.shape, kw["block_len"]))
        or kernel(q, *a, interpret=True, **kw))
    rng = np.random.default_rng(0)
    pk, pv = (jnp.asarray(rng.standard_normal((8, 16, 1, 128)),
                          jnp.bfloat16) for _ in range(2))
    table = jnp.asarray([[3, 5, 1, 0], [0, 0, 0, 0], [2, 4, 6, 7]],
                        jnp.int32)
    pos = jnp.asarray([28, 4000, 44], jnp.int32)
    q1, q4 = (jnp.asarray(rng.standard_normal((3, T, 16, 128)),
                          jnp.bfloat16) for T in (1, 4))
    loop = [np.asarray(_paged_window_attention(
        q, pk, pv, None, None, table, pos, block_len=L), np.float32)
        for q, L in ((q1, 1), (q4, 4))]
    assert not called                                    # the CPU
    monkeypatch.setattr(paged_decode, "_on_one_tpu", lambda: True)
    for (q, L), want in zip(((q1, 1), (q4, 4)), loop):
        got = np.asarray(_paged_window_attention(
            q, pk, pv, None, None, table, pos, block_len=L), np.float32)
        assert np.allclose(got[[0, 2]], want[[0, 2]], atol=2e-2)
        assert not got[1].any()
    assert called == [(q1.shape, 1), (q4.shape, 4)]
    cfg = sdar_tiny(dim=256, n_heads=16, n_kv_heads=1, head_dim=128,
                    dtype=jnp.bfloat16)
    from ray_tpu.serve.round_accounts import RoundAccounts
    pool = kv_cache.init_kv_pool(cfg, 4, 16)
    acc = RoundAccounts(cfg, {}, pool, slots=2, page_size=16, max_pages=4,
                        kv_dtype="fp", mesh=None)
    assert acc.block == cfg.block_decode and acc.decode_kernel_serves()
    monkeypatch.setattr(paged_decode, "_on_one_tpu", lambda: False)
    assert not acc.decode_kernel_serves()


def test_the_round_event_counts_a_block_s_pages_to_its_last_query(
        seeded, monkeypatch):
    """``decode_kernel_pages`` of a model that decodes by blocks: 0
    where the block program holds the loop (the CPU); where the rule
    says the kernel serves, each rider's pages to the end of the block
    the dispatch closes on (what the block's LAST query sees: the
    bound ``decode_context_tokens`` sums), and the rule was asked with
    a whole block a row."""
    from ray_tpu.ops import paged_decode_attention as paged_decode
    params, _rw = seeded
    cfg, model = _tiny(remasking="low_confidence_static")
    eng = _engine(model, params)

    def rounds():
        return [(e[5]["decode_kernel_pages"],
                 e[5]["decode_context_tokens"])
                for e in eng.events.snapshot()
                if e[2] == "round" and e[5]["decode_steps"]]

    eng.submit(_ids((6,), seed=1).tolist(), max_new_tokens=22)
    _drive(eng)
    before = rounds()
    assert before and not any(k for k, _c in before)
    asked = []
    monkeypatch.setattr(paged_decode, "applies",
                        lambda *a: asked.append(a) or True)
    eng.submit(_ids((6,), seed=2).tolist(), max_new_tokens=22)
    _drive(eng)
    after = rounds()[len(before):]
    # one rider, pages of 8: a block's end is a whole number of blocks
    assert after and all(c % L == 0 and k == -(-c // PAGE)
                         for k, c in after)
    assert len({c for _k, c in after}) > 1
    q, k, v, sk, table, value_dim, block_len = asked[0]
    assert q.shape == (3, L, cfg.n_heads, cfg.head_dim) and block_len == L
    assert k.shape == v.shape == (1, PAGE, cfg.n_kv_heads, cfg.head_dim)
    assert sk is None and value_dim is None
