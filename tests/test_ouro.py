"""Ouro on the normal path (ray_tpu.models.ouro through LLMEngine and
LlamaDeployment) against the plain float32 reference
(benchmarks/reference/ouro.py: no cache, no batching, the passes a
Python loop), on the CPU at ``ouro_tiny``: three layers run four times,
so a token keeps TWELVE cache entries behind three layers of weights.
The first model whose cache entries outnumber its layers: a page holds
every pass of a layer (a pass axis inside the page), one page id names
one page of all twelve.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums and in the FORM of the attention (the
program reads pages a block at a time through a pass's page table; the
reference masks one row of scores a query): logits of the order of 1
agree to rtol 1e-4 / atol 2e-5, as the other families' do. Each control
below moves logits by a thousand times that or more. The engine's
tokens are held to the reference's full forward pass teacher-forced,
and its captured log-probabilities (a function of the whole row of
logits) to the reference's at the same tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ouro as ouro_mod
from ray_tpu.models.kv_cache import (KIND_KV, export_page_bytes,
                                     init_kv_pool, kv_layer_store,
                                     kv_layer_view, kv_pool_page_bytes,
                                     layer_kinds, page_cols_from_bytes,
                                     refuse_unsupported)
from ray_tpu.models.ouro import (Ouro, exit_pass, ouro_2_6b,
                                 ouro_param_count, ouro_tiny)
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5
PAGE, CHUNK = 4, 16


def _family():
    from benchmarks import common
    return common.load_family("ouro", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale
    away from one so that a scale left out shows, and the exit gate
    three times its scale over a bias, so that the gates spread and a
    threshold under 1 chooses different passes at different positions
    (and none saturates: at a gate of exactly 1.0 in float32 the
    cumulative probability reaches a threshold of 1 before the last
    pass, by the rule as written)."""
    from benchmarks import weights
    model = Ouro(cfg)
    params = _family().init_params(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        if "exit_gate" in name:
            return leaf * 3.0 if leaf.ndim == 2 else leaf - 1.0
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = ouro_tiny(dtype=jnp.float32)
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


def _held_to_the_reference(params, cfg, prompt, out, least=None):
    """The teacher-forced rule of the module docstring."""
    P, G = len(prompt), len(out)
    logits = _reference(params, [list(prompt) + list(out)], cfg)[0]
    steps = logits[P - 1:P - 1 + G]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= (G * 2 // 3 if least is None else least)
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]).all()
    return steps


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


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass, 150 positions, four passes of three
    layers, ON LOGITS."""
    cfg, model, params = tiny
    ids = _ids((2, 150), seed=1)
    np.testing.assert_allclose(_forward(model, params, ids),
                               _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)


def test_one_pass_is_one_pass_and_four_differ(tiny):
    """T = 1 is one pass of the stack, final norm and head (the same
    weights: the parameters do not depend on T), and T = 4 differs from
    it by far more than the tolerance."""
    cfg, _model, params = tiny
    import dataclasses
    one = dataclasses.replace(cfg, total_ut_steps=1)
    ids = _ids((1, 40), seed=2)
    got_one = _forward(Ouro(one), params, ids)
    np.testing.assert_allclose(got_one, _reference(params, ids, one),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(
        got_one, _reference(params, ids, cfg, passes=1), rtol=RTOL,
        atol=ATOL)
    got_four = _forward(Ouro(cfg), params, ids)
    assert np.abs(got_four - got_one).max() > 1e3 * ATOL


@pytest.mark.parametrize("threshold", [0.5, 1.0])
def test_the_exit_rule(tiny, threshold):
    """The logits are those of the first pass whose cumulative exit
    probability reaches the threshold: at 1.0 the last pass's at every
    position, at 0.5 (with these gates) different passes at different
    positions; the program's choice and its logits are the
    reference's."""
    import dataclasses
    from benchmarks.reference import ouro as ref
    cfg, _model, params = tiny
    cfg = dataclasses.replace(cfg, early_exit_threshold=threshold)
    fam = _family()
    ids = _ids((2, 60), seed=3)
    rw = fam.reference_weights(params, cfg)
    _, chosen = ref.chosen_state(rw, jnp.asarray(ids, jnp.int32),
                                 **fam._sizes(cfg))
    if threshold == 1.0:
        assert (chosen == cfg.total_ut_steps - 1).all()
    else:
        assert len(set(chosen.ravel().tolist())) >= 3
    np.testing.assert_allclose(_forward(Ouro(cfg), params, ids),
                               _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    # the rule by hand on one column of gates
    lam = jnp.asarray([[0.2], [0.5], [0.9], [0.3]], jnp.float32)
    # p = .2, .4, .36, .04; cumulative .2, .6, .96, 1.0
    assert int(exit_pass(lam, 0.5)[0]) == 1
    assert int(exit_pass(lam, 0.9)[0]) == 2
    assert int(exit_pass(lam, 1.0)[0]) == 3
    assert int(exit_pass(lam, 0.1)[0]) == 0


@pytest.mark.parametrize("control", [
    dict(passes=3), dict(sandwich=False), dict(norm_every_pass=False)],
    ids=["three-passes", "no-sandwich-norms", "final-norm-once"])
def test_the_comparison_fails_each_control(tiny, control):
    """A model of three passes, one without the sandwich norms and one
    whose final norm is applied once after the last pass each FAIL the
    comparison the program passes."""
    cfg, model, params = tiny
    ids = _ids((1, 50), seed=4)
    got = _forward(model, params, ids)
    np.testing.assert_allclose(got, _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            got, _reference(params, ids, cfg, **control), rtol=RTOL,
            atol=ATOL)


def test_the_published_counts():
    """Ouro-2.6B by the equations: 2,668 M parameters in ONE stack of
    48 layers; 192 cache entries a token, 1,572,864 B; a 64-token page
    of all of them 100.66 MB; ``layer_kinds`` as long as the weights'
    layers."""
    cfg = ouro_2_6b()
    assert cfg.head_dim == 128 and cfg.kv_entries_per_layer == 4
    n = ouro_param_count(cfg)
    assert n == 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048) \
        + 2 * 49152 * 2048 + 2048 + 2049
    assert round(n / 1e6) == 2668
    assert layer_kinds(cfg) == (KIND_KV,) * 48
    page = kv_pool_page_bytes(cfg, 64)
    assert page == 192 * 64 * 8192 == 100_663_296
    fam = _family()
    from benchmarks import common
    file_cfg = common.load_json("configs", "ouro-2.6b.json")
    assert fam.kv_bytes_per_token(file_cfg) == 1_572_864 == page // 64
    assert fam.n_cache_entries(file_cfg) == 192
    # a step streams the stack four times and the head once
    stack = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2
    assert fam.decode_step_bytes(file_cfg, 0, 0) == \
        4 * stack + 49152 * 2048 * 2
    assert fam.decode_step_bytes(file_cfg, 304 * 16, 16) == \
        4 * stack + 49152 * 2048 * 2 + 16 * 2048 * 2 \
        + (304 * 16 + 16) * 1_572_864
    pcfg = fam.program_config(file_cfg)
    assert (pcfg.n_layers, pcfg.total_ut_steps, pcfg.vocab_size,
            pcfg.max_seq_len, pcfg.early_exit_threshold) == (
        48, 4, 49152, 4096, 1.0)


# ------------------------------------ the paged path against the reference

def _call(model, params, table):
    @jax.jit
    def call(pool, chunk, pos):
        views = [kv_layer_view(layer, table) for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    return call


def _paged_logits(cfg, model, params, ids, P, G):
    pool = init_kv_pool(cfg, 160, PAGE)
    table = jnp.asarray(1 + np.arange(155)[None], jnp.int32)
    call = _call(model, params, table)
    got = []
    for start in list(range(0, P, CHUNK)) + list(range(P, P + G)):
        n = min(CHUNK, P - start) if start < P else 1
        chunk = jnp.asarray(ids[:, start:start + n], jnp.int32)
        if n < CHUNK and start < P:
            chunk = jnp.pad(chunk, ((0, 0), (0, CHUNK - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32))
        got.append(np.asarray(logits[0, :n]))
    return np.concatenate(got), pool


def test_paged_logits_match_the_reference(tiny):
    """Chunked prefill of 530 tokens in chunks of 16 (34 chunks, 133
    pages of 4, across the 512-token edge of the page loop's first
    block), then six decode steps, through all twelve cache entries
    under ONE page table, against the plain reference's full forward
    pass, ON LOGITS."""
    cfg, model, params = tiny
    P, G = 530, 6
    ids = _ids((1, P + G), seed=6)
    got, pool = _paged_logits(cfg, model, params, ids, P, G)
    np.testing.assert_allclose(got, _reference(params, ids, cfg)[0],
                               rtol=RTOL, atol=ATOL)
    # every pass of every layer wrote its own entry of the same pages,
    # and no two passes hold the same keys
    for k, _v in pool:
        assert k.shape == (160, 4, PAGE, 4, 16)
        page = np.asarray(k[5])
        assert all(np.abs(page[t]).max() > 0 for t in range(4))
        assert all(np.abs(page[t] - page[0]).max() > 1e-3
                   for t in range(1, 4))
        assert not np.asarray(k[157:]).any()      # pages never handed out


def test_passes_sharing_one_entry_fail_the_comparison(tiny, monkeypatch):
    """The control of the cache's shape: every pass reading and writing
    pass 1's entry (the page table without the pass) gives logits the
    comparison refuses, though the cache-less forward pass of the same
    weights is right."""
    cfg, model, params = tiny
    P, G = 40, 4
    ids = _ids((1, P + G), seed=7)
    want = _reference(params, ids, cfg)[0]
    view = ouro_mod._pass_view
    monkeypatch.setattr(
        ouro_mod, "_pass_view",
        lambda entry, table, t, passes: view(entry, table, 0 * t, passes))
    got, _pool = _paged_logits(cfg, Ouro(cfg), params, ids, P, G)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_rows_of_different_lengths_and_a_dead_row(tiny):
    """Two rows of one prefill call at different offsets beside a row
    that carries no request (a null page-table row and a stale, large
    position): each live row's logits are the reference's of its own
    sequence, and the dead row neither widens the attended window nor
    writes outside the null page."""
    cfg, model, params = tiny
    lens = (41, 29)
    ids = [_ids((n,), seed=30 + n) for n in lens]
    want = [_reference(params, [row], cfg)[0] for row in ids]
    pool = init_kv_pool(cfg, 60, PAGE)
    table = np.zeros((3, 16), np.int32)
    table[0, :11] = 1 + np.arange(11)
    table[1, :8] = 20 + np.arange(8)
    call = _call(model, params, jnp.asarray(table))
    done, got = [0, 0], [[], []]
    while min(d - n for d, n in zip(done, lens)) < 0:
        chunk = np.zeros((3, CHUNK), np.int32)
        n_real = [0, 0]
        for r in range(2):
            n = min(CHUNK - 3 * r, lens[r] - done[r])   # rows out of step
            chunk[r, :n] = ids[r][done[r]:done[r] + n]
            n_real[r] = n
        logits, pool = call(pool, jnp.asarray(chunk),
                            jnp.asarray(done + [977], jnp.int32))
        for r in range(2):
            got[r].append(np.asarray(logits[r, :n_real[r]]))
            done[r] += n_real[r]
    for r in range(2):
        np.testing.assert_allclose(np.concatenate(got[r]), want[r],
                                   rtol=RTOL, atol=ATOL)
    for k, _v in pool:
        assert not np.asarray(k[28:]).any()     # nothing past row 1's pages
        assert not np.asarray(k[12:20]).any()


# ------------------------------------------------------------ the engine

def test_the_engine_matches_the_reference(tiny):
    """The real engine: a prompt of 530 tokens prefilled in 34 chunks
    beside two shorter requests, then decoding in dispatches of four
    steps across page and block edges. The tokens are the reference's
    teacher-forced, and the captured log-probability of every generated
    token (the whole row of logits behind it) is the reference's."""
    cfg, _model, params = tiny
    eng = _engine(tiny, capture_logprobs=True)
    prompts = [_ids((530,), seed=10).tolist(), _ids((37,), 11).tolist(),
               _ids((9,), 12).tolist()]
    handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
    _drive(eng)
    for p, h in zip(prompts, handles):
        out = h.result()
        steps = _held_to_the_reference(params, cfg, p, out)
        want = np.asarray(jax.nn.log_softmax(steps))[
            np.arange(len(out)), out]
        np.testing.assert_allclose(h.logprobs, want, rtol=RTOL, atol=ATOL)
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []
    report = eng.load_report()
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, PAGE) \
        == 12 * PAGE * 2 * 4 * 16 * 4


def test_more_clients_than_slots(tiny):
    """Twelve requests on four slots, every slot reused: each ends as
    the reference has it and nothing leaks."""
    cfg, _model, params = tiny
    eng = _engine(tiny, n_pages=4 * 20 + 1)
    prompts = [_ids((5 + (11 * i) % 50,), seed=100 + i).tolist()
               for i in range(12)]
    handles = [eng.submit(p, max_new_tokens=6 + i % 5)
               for i, p in enumerate(prompts)]
    _drive(eng)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        out = h.result()
        assert len(out) == 6 + i % 5
        if i % 3 == 0:
            _held_to_the_reference(params, cfg, p, out, least=3)
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


SHARED = _ids((24,), seed=50).tolist()              # six whole pages
TAILS = [_ids((7,), seed=51).tolist(), _ids((13,), seed=52).tolist()]


@pytest.fixture(scope="module")
def plain_tokens(tiny):
    """The plain engine's tokens of the two requests, once a module."""
    eng = _engine(tiny)
    handles = [eng.submit(SHARED + t, max_new_tokens=10) for t in TAILS]
    _drive(eng)
    return [h.result() for h in handles]


def _with_prefix_cache(tiny, _want):
    """The second request finds the first's six pages in the tree: each
    page id names all twelve entries' pages, shared and never copied but
    for the one copy-on-write page."""
    eng = _engine(tiny, prefix_cache=True)
    out = []
    for t in TAILS + TAILS[:1]:
        h = eng.submit(SHARED + t, max_new_tokens=10)
        _drive(eng)
        out.append(h.result())
    assert eng.prefix_stats()["hit_tokens"] >= 2 * len(SHARED)
    assert out[2] == out[0]
    # a fully cached prompt: the last page is copied (all passes of it)
    h = eng.submit(SHARED, max_new_tokens=4)
    _drive(eng)
    alone = _engine(tiny)
    ha = alone.submit(SHARED, max_new_tokens=4)
    _drive(alone)
    assert h.result() == ha.result()
    return out[:2]


class _Scripted:
    """A proposer that drafts a fixed continuation by how many tokens
    the slot has generated (tests/test_spec_decode.py's seam)."""

    def __init__(self, prompt_len, script):
        self.prompt_len, self.script, self._done = prompt_len, script, 0

    def sync(self, context):
        self._done = len(context) - self.prompt_len

    def propose(self, k):
        return self.script[self._done:self._done + k]


def _with_speculation(tiny, want):
    """Drafts verified through the prefill path at decode offsets: two
    in three are the plain engine's tokens (accepted), every third is
    wrong (rejected, and rolled back by the page offset alone, in every
    pass's entry)."""
    out = []
    for tail, tokens in zip(TAILS, want):
        script = [t if i % 3 != 2 else (t + 1) % 256
                  for i, t in enumerate(tokens)]
        eng = _engine(tiny, spec_len=2, spec_proposer=lambda: _Scripted(
            len(SHARED + tail), script))
        h = eng.submit(SHARED + tail, max_new_tokens=10)
        _drive(eng)
        out.append(h.result())
        st = eng.spec_stats()
        assert st["accepted_tokens"] > 0 and st["rejected_tokens"] > 0, st
    return out


def _with_int8_pages(tiny, _want):
    eng = _engine(tiny, kv_dtype="int8")
    # k, v and a scale a (page, pass, KV head) for each
    (k, _v, sk, _sv) = eng.pages[0]
    assert k.dtype == jnp.int8 and k.shape[1:] == (4, PAGE, 4, 16)
    assert sk.shape == (200, 4, 4)
    assert eng.page_bytes == 3 * 2 * 4 * (PAGE * 4 * 16 + 4 * 4)
    handles = [eng.submit(SHARED + t, max_new_tokens=10) for t in TAILS]
    _drive(eng)
    return [h.result() for h in handles]


def _with_a_kv_pull(tiny, _want):
    """A donor engine computes the shared prefix; the requester pulls
    its six pages (each page's frames carry all twelve entries) and
    decodes from them."""
    from ray_tpu.serve import kv_migration
    from ray_tpu.serve.prefix_cache import path_hashes
    donor_eng = _engine(tiny, prefix_cache=True)
    h = donor_eng.submit(SHARED + [7, 7, 7], max_new_tokens=2)
    _drive(donor_eng)
    h.result()
    donor = kv_migration.KVDonor(donor_eng)
    out = []
    for t in TAILS:
        eng = _engine(tiny, prefix_cache=True)
        eng.kv_fetcher = lambda pull, eng=eng: kv_migration.pull_prefix(
            kv_migration.loopback_call(donor), pull["hashes"],
            stats=eng.kv_migration_stats)
        h = eng.submit(SHARED + t, max_new_tokens=10,
                       pull={"hashes": path_hashes(SHARED, PAGE)})
        _drive(eng)
        out.append(h.result())
        st = eng.kv_migration_stats
        assert st["pulls"] == 1 and st["pulled_pages"] == 6, st
        assert st["fallbacks"] == 0 and st["aborts"] == 0, st
        assert eng.stats["kv_pull_landed"] == 1
    assert donor.open_transfers() == 0
    return out


@pytest.mark.parametrize("serve_with", [
    _with_prefix_cache, _with_speculation, _with_int8_pages,
    _with_a_kv_pull],
    ids=["prefix_cache", "spec_len", "int8", "kv_pull"])
def test_a_model_of_kv_pages_is_refused_nothing(tiny, plain_tokens,
                                                serve_with):
    """Every option that shares, rewinds, re-codes or ships K/V pages
    serves the looped model as it serves Llama, because each deals in
    page ids and a page holds every pass: the tokens are the plain
    engine's (the int8 pool's at the repo's floor of agreement for
    re-coded pages), and the reference's."""
    cfg, _model, params = tiny
    refuse_unsupported(cfg, prefix_cache=True, spec_len=2, kv_dtype="int8",
                       kv_migration="disaggregate")
    want = plain_tokens
    got = serve_with(tiny, want)
    for tail, w, g in zip(TAILS, want, got):
        _held_to_the_reference(params, cfg, SHARED + tail, w)
        if serve_with is _with_int8_pages:
            continue
        assert g == w
    if serve_with is _with_int8_pages:
        # the repo's floor for int8 pages against the model's type
        # (tests/test_kv_quant.py: a random 256-token vocabulary is the
        # worst case, near-uniform logits whose flips compound)
        agree = sum(x == y for w, g in zip(want, got) for x, y in zip(w, g))
        assert agree / sum(len(w) for w in want) >= 0.8, (want, got)


def test_a_page_ships_with_every_pass(tiny):
    """``export_page_bytes`` of one page id is the page of all twelve
    entries, and ``page_cols_from_bytes`` lands it whole; a payload of
    the one-entry-a-layer shape is refused by its byte count."""
    cfg, model, params = tiny
    eng = _engine(tiny, prefix_cache=True)
    h = eng.submit(SHARED + TAILS[0], max_new_tokens=2)
    _drive(eng)
    h.result()
    blobs = export_page_bytes(eng.pages, 3)
    assert len(blobs) == cfg.n_layers
    assert [len(b) for b in blobs[0]] == [4 * PAGE * 4 * 16 * 4] * 2
    cols = page_cols_from_bytes(cfg, PAGE, "fp", blobs)
    for (k, v), (pk, pv) in zip(cols, eng.pages):
        assert k.shape == (4, PAGE, 4, 16)
        np.testing.assert_array_equal(k, np.asarray(pk[3]))
        np.testing.assert_array_equal(v, np.asarray(pv[3]))
    with pytest.raises(ValueError, match="expected"):
        page_cols_from_bytes(cfg, PAGE, "fp",
                             [[b[:len(b) // 4] for b in layer]
                              for layer in blobs])


# --------------------------------------------------- the step programs

def _lowered(tiny, name):
    from ray_tpu.serve import step_programs
    cfg, model, params = tiny
    S = 4
    pool = init_kv_pool(cfg, 20, PAGE)
    table = jnp.zeros((S, 8), jnp.int32)
    key = jax.random.PRNGKey(0)
    zeros = jnp.zeros((S,), jnp.int32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 8, S, False, None)
        return fn.lower(params, pool, table, zeros, zeros, key,
                        jnp.int32(2))
    fn = step_programs._jit_prefill(model, 0.0, S, False, None)
    return fn.lower(params, pool, jnp.zeros((S, CHUNK), jnp.int32), zeros,
                    zeros, table, key)


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_a_step_program_holds_one_copy_of_the_stack(tiny, name):
    """The passes are a loop on the device: layer 0's query projection
    appears ONCE in the program (unrolled it would appear four times),
    inside a loop, under the scopes the benchmark's readers sum."""
    text = _lowered(tiny, name).as_text(debug_info=True)
    projections = [line for line in text.splitlines()
                   if "dot_general" in line
                   and "layers_0/attention/wq" in line]
    assert len(projections) == 1, len(projections)
    assert "stablehlo.while" in text
    for scope in ("ut_pass/", "exit_gate/", "/head/", "kv_append",
                  "kv_gather", "attn_scores", "attn_pv"):
        assert scope in text, scope


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    caches = [(jnp.zeros((1, 16, 4, 16)),) * 2] * cfg.n_layers
    with pytest.raises(TypeError, match="pass axis"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


def test_sharding_is_refused_by_name(tiny):
    """The config declares no partition rules: the pool's sharding knows
    no page with a pass axis yet (ROADMAP.md)."""
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.serve.sharding import ShardingConfigError
    cfg, _model, params = tiny
    assert not hasattr(cfg, "serving_rules")
    with pytest.raises(ShardingConfigError, match="OuroConfig"):
        LlamaDeployment(config=cfg, params=params, tensor_parallel=2)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    assert cfg.model_class is Ouro
    holder = {}

    @serve.deployment
    class LoopedLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=32)
            holder["dep"] = self

    try:
        handle = serve.run(LoopedLLM.bind(), timeout_s=300)
        prompt = _ids((83,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:83] == prompt and len(out) == 93
        _held_to_the_reference(params, cfg, prompt, out[83:])
        report = holder["dep"].engine().load_report()
        assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8)
        assert report["kv_bytes_total"] == 64 * report["kv_page_bytes"]
    finally:
        serve.shutdown()
