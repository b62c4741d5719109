"""A model arrives as one module (PR 45): what serving needs to know of
a family it asks the family's CONFIG (the class to build, the
partition rules where any exist), what a kind of request state cannot
do it reads from one table beside ``page_layout``, and the host loop's
file holds no device program. A new model that composes kinds that
exist touches nothing under ``ray_tpu/serve/``.
"""
import ast
import pathlib
import types

import pytest

from ray_tpu.models import kv_cache
from ray_tpu.models.kv_cache import (KIND_BORROWED, KIND_INDEXED, KIND_KV,
                                     KIND_LATENT, KIND_RECURRENT,
                                     KIND_SLIDING, KIND_STATELESS,
                                     refuse_unsupported)

SERVE = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu" / "serve"
# the family modules serve/ may import: sampling and the tiny default
# (llama), the mixture's counters every mixture family shares (mixtral)
_FAMILY_MODULES = {"axk1", "granite_hybrid", "kimi_linear", "laguna",
                   "mellum", "olmo_hybrid", "ouro", "phi4flash", "sdar",
                   "solar_open2"}


def _trees():
    for path in sorted(SERVE.rglob("*.py")):
        yield path, ast.parse(path.read_text(), filename=str(path))


def test_serve_asks_no_config_its_type():
    hits = []
    for path, tree in _trees():
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", "") == "isinstance" and \
                    "Config" in ast.unparse(node.args[1]):
                hits.append((path.name, node.lineno, ast.unparse(node)))
    assert not hits, hits


def test_serve_imports_no_family_but_llama_and_mixtral():
    hits = []
    for path, tree in _trees():
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            hits += [(path.name, node.lineno, n) for n in names
                     if n.startswith("ray_tpu.models.")
                     and n.split(".")[2] in _FAMILY_MODULES]
    assert not hits, hits


def test_the_host_loop_holds_no_device_program():
    """``serve/engine.py`` builds no program: the step programs live in
    ``serve/step_programs.py``, and what is left of ``jax.jit`` in the
    scheduler's file is nothing."""
    engine = ast.parse((SERVE / "engine.py").read_text())
    jits = [node.lineno for node in ast.walk(engine)
            if isinstance(node, ast.Attribute) and node.attr == "jit"]
    assert not jits, jits
    programs = (SERVE / "step_programs.py").read_text()
    for name in ("prefill", "decode", "verify", "write", "copy", "seed"):
        assert f"    def {name}(" in programs, name


# ----------------------------------------------- the family's facts

def _families():
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    from ray_tpu.models.granite_hybrid import (GraniteHybrid,
                                               granite_hybrid_tiny)
    from ray_tpu.models.kimi_linear import KimiLinear, kimi_linear_tiny
    from ray_tpu.models.laguna import Laguna, laguna_tiny
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.models.mellum import Mellum, mellum_tiny
    from ray_tpu.models.mixtral import Mixtral, mixtral_tiny
    from ray_tpu.models.olmo_hybrid import OlmoHybrid, olmo_hybrid_tiny
    from ray_tpu.models.ouro import Ouro, ouro_tiny
    from ray_tpu.models.phi4flash import Phi4Flash, phi4flash_tiny
    from ray_tpu.models.sdar import Sdar, sdar_tiny
    from ray_tpu.models.solar_open2 import SolarOpen2, solar_open2_tiny
    return {"llama": (llama_tiny, Llama, "feed_forward"),
            "mixtral": (mixtral_tiny, Mixtral, "moe/w2"),
            "axk1": (axk1_tiny, AXK1, None),
            "granite_hybrid": (granite_hybrid_tiny, GraniteHybrid, None),
            "kimi_linear": (kimi_linear_tiny, KimiLinear, None),
            "laguna": (laguna_tiny, Laguna, None),
            "mellum": (mellum_tiny, Mellum, None),
            "olmo_hybrid": (olmo_hybrid_tiny, OlmoHybrid, None),
            "ouro": (ouro_tiny, Ouro, None),
            "phi4flash": (phi4flash_tiny, Phi4Flash, None),
            "sdar": (sdar_tiny, Sdar, None),
            "solar_open2": (solar_open2_tiny, SolarOpen2, None)}


FAMILIES = ("llama", "mixtral", "axk1", "granite_hybrid", "kimi_linear",
            "laguna", "mellum", "olmo_hybrid", "ouro", "phi4flash", "sdar",
            "solar_open2")


@pytest.mark.parametrize("family", FAMILIES)
def test_the_deployment_builds_the_class_the_config_names(family):
    from ray_tpu.serve.llm import LlamaDeployment
    tiny, cls, _rule = _families()[family]
    cfg = tiny()
    assert cfg.model_class is cls
    # the engine is lazy: nothing is initialised or compiled here
    dep = LlamaDeployment(config=cfg, params={})
    assert type(dep.model) is cls and dep.model.config is cfg


@pytest.mark.parametrize("family", FAMILIES)
def test_partition_rules_come_from_the_config_or_not_at_all(family):
    """A family with rules gets ITS rules and its own divisibility
    check; one that declares none is refused by name, by
    ``family_sharding_rules`` itself (it handed Llama's rules to all
    four before PR 45, and only the kinds' refusals kept them unused)."""
    from ray_tpu.serve.sharding import (EngineSharding,
                                        ShardingConfigError,
                                        family_sharding_rules, validate_tp)
    tiny, _cls, rule = _families()[family]
    cfg = tiny()
    if rule is None:
        for ask in (lambda: family_sharding_rules(cfg),
                    lambda: validate_tp(cfg, 2),
                    lambda: EngineSharding.build(cfg, tp=1)):
            with pytest.raises(ShardingConfigError,
                               match=type(cfg).__name__):
                ask()
        return
    got = family_sharding_rules(cfg)
    assert got._rules == cfg.serving_rules._rules
    assert any(rule in pat.pattern for pat, _spec in got._rules)
    validate_tp(cfg, 2)
    with pytest.raises(ShardingConfigError, match="tp=3"):
        validate_tp(cfg, 3)


def test_the_readers_hold_no_list_of_families():
    """Whatever declares the facts is served by them: no type is looked
    up on the way."""
    from ray_tpu.serve.sharding import (ShardingConfigError,
                                        family_sharding_rules, validate_tp)
    asked = []
    cfg = types.SimpleNamespace(
        serving_rules="these", tp_validate=lambda tp, ep: asked.append(
            (tp, ep)))
    assert family_sharding_rules(cfg) == "these"
    validate_tp(cfg, 4, 2)
    assert asked == [(4, 2)]
    with pytest.raises(ShardingConfigError, match="SimpleNamespace"):
        family_sharding_rules(types.SimpleNamespace())


# ------------------------------------- what a kind of state cannot do

def _config_of(kind):
    from ray_tpu.models.axk1 import axk1_tiny
    from ray_tpu.models.deepseek_v32 import deepseek_v32_tiny
    from ray_tpu.models.mellum import mellum_tiny
    from ray_tpu.models.solar_open2 import solar_open2_tiny

    def pages_and(kind):
        # no model is made of these alone: pages, and a layer of the kind
        return lambda: types.SimpleNamespace(
            layer_kinds=(KIND_KV, kind), n_layers=2)
    return {KIND_RECURRENT: solar_open2_tiny, KIND_LATENT: axk1_tiny,
            KIND_INDEXED: deepseek_v32_tiny,
            KIND_SLIDING: mellum_tiny,
            KIND_BORROWED: pages_and(KIND_BORROWED),
            KIND_STATELESS: pages_and(KIND_STATELESS)}[kind]()


@pytest.mark.parametrize("kind,option", [
    (kind, option) for kind in (KIND_RECURRENT, KIND_LATENT, KIND_INDEXED,
                                KIND_SLIDING, KIND_BORROWED,
                                KIND_STATELESS)
    for option in kv_cache.KIND_REFUSALS[kind][1]])
def test_the_tables_words_reach_the_refusal(kind, option):
    keeps, why = kv_cache.KIND_REFUSALS[kind]
    cfg = _config_of(kind)
    with pytest.raises(ValueError) as refused:
        refuse_unsupported(cfg, **{option: "asked"})
    assert str(refused.value) == (
        f"{option}='asked' is not supported for {type(cfg).__name__}: "
        f"it has layers that keep {keeps}; {why[option]}")
    refuse_unsupported(cfg, **{option: False})


def test_the_table_is_nineteen_refusals_over_seven_kinds():
    """And, since PR 63, one row more that is no kind of layer: what a
    model that decodes by blocks cannot do yet (six refusals)."""
    kinds = {getattr(kv_cache, name) for name in dir(kv_cache)
             if name.startswith("KIND_") and name != "KIND_REFUSALS"}
    assert kinds | {kv_cache.DECODES_BY_BLOCKS} == set(
        kv_cache.KIND_REFUSALS)
    assert {kind: len(why) for kind, (_keeps, why)
            in kv_cache.KIND_REFUSALS.items()} == {
        KIND_KV: 0, KIND_RECURRENT: 4, KIND_LATENT: 3, KIND_INDEXED: 3,
        KIND_SLIDING: 5, KIND_BORROWED: 3, KIND_STATELESS: 1,
        kv_cache.DECODES_BY_BLOCKS: 6}


def test_pages_of_keys_and_values_are_refused_nothing():
    from ray_tpu.models.llama import llama_tiny
    from ray_tpu.models.mixtral import olmoe_tiny
    everything = dict(prefix_cache=True, spec_len=4, kv_dtype="int8",
                      kv_migration="disaggregate", sharding=True)
    refuse_unsupported(llama_tiny(), **everything)
    refuse_unsupported(olmoe_tiny(), **everything)


def test_a_page_of_latent_entries_takes_its_int8_refusal_from_the_table():
    cfg = _config_of(KIND_LATENT)
    with pytest.raises(ValueError) as refused:
        kv_cache.page_layout(cfg, KIND_LATENT, 8, "int8")
    assert kv_cache.KIND_REFUSALS[KIND_LATENT][1]["kv_dtype"] in str(
        refused.value)


# --------------------------- the cache's entries are the cache's to count

def test_cache_entries_that_outnumber_the_layers_are_the_caches_to_count():
    """A config whose layers run several times a token declares
    ``kv_entries_per_layer``: its pool, its page bytes and its exported
    page come from the cache's own count (a pass axis inside the page),
    while ``layer_kinds`` stays as long as the weights' layers, so a
    page id still names ONE page of every entry; a config that declares
    nothing keeps the pool it always had."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.llama import llama_tiny
    once = llama_tiny(n_kv_heads=4)
    thrice = types.SimpleNamespace(
        n_layers=once.n_layers, n_kv_heads=4, head_dim=once.head_dim,
        dtype=jnp.bfloat16, kv_entries_per_layer=3)
    assert kv_cache.kv_entries_per_layer(once) == ()
    assert kv_cache.kv_entries_per_layer(thrice) == (3,)
    assert kv_cache.layer_kinds(thrice) == (KIND_KV,) * once.n_layers
    Pg, KH, D = 8, 4, once.head_dim
    for dtype, tensors in (("fp", 2), ("int8", 4)):
        pool = kv_cache.init_kv_pool(thrice, 5, Pg, dtype)
        plain = kv_cache.init_kv_pool(once, 5, Pg, dtype)
        assert len(pool) == len(plain) == once.n_layers
        assert len(pool[0]) == len(plain[0]) == tensors
        assert pool[0][0].shape == (5, 3, Pg, KH, D)
        assert plain[0][0].shape == (5, Pg, KH, D)
        if dtype == "int8":
            assert pool[0][2].shape == (5, 3, KH)
            assert plain[0][2].shape == (5, KH)
        # three entries a layer: three times the bytes a page
        scale = 1 if dtype == "fp" else 2
        one = kv_cache.kv_pool_page_bytes(
            types.SimpleNamespace(**{**vars(thrice),
                                     "kv_entries_per_layer": 1}), Pg, dtype)
        assert kv_cache.kv_pool_page_bytes(thrice, Pg, dtype) == 3 * one
        assert one == once.n_layers * (
            2 * Pg * KH * D * (2 // scale) + (2 * KH * 4 if scale == 2
                                             else 0))
        # one page id: the page of all three entries of every layer
        blobs = kv_cache.export_page_bytes(pool, 2)
        assert sum(len(b) for layer in blobs for b in layer) == \
            kv_cache.kv_pool_page_bytes(thrice, Pg, dtype)
        cols = kv_cache.page_cols_from_bytes(thrice, Pg, dtype, blobs)
        assert cols[0][0].shape == (3, Pg, KH, D)
        with pytest.raises(ValueError, match="expected"):
            kv_cache.page_cols_from_bytes(once, Pg, dtype, blobs)
        assert np.asarray(cols[0][0]).dtype == np.asarray(
            pool[0][0]).dtype


# ------------------------ one position's logits a row (``logits_at``)

_PAGE, _T = 8, 16


@pytest.fixture(scope="module")
def toy():
    """get(family): (config, model, params) of the family's float32
    toy, initialised once a module in one jitted call."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.lru_cache(maxsize=None)
    def get(family):
        tiny, cls, _rule = _families()[family]
        cfg = tiny(dtype=jnp.float32)
        model = cls(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(3),
                                     jnp.zeros((1, 8), jnp.int32))
        return cfg, model, {"params": params["params"]}
    return get


def _ids(cfg, shape, seed):
    import jax
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1,
                              cfg.vocab_size)


def _pool(cfg, rows):
    """A pool of the family's own entries for ``rows`` slots (pages,
    rings, recurrent states: whatever its layers keep) and a page
    table giving each row four pages of its own."""
    import jax.numpy as jnp
    import numpy as np
    pool = kv_cache.init_kv_pool(
        cfg, 1 + 4 * rows, _PAGE, n_slots=rows,
        ring_len=kv_cache.sliding_ring_len(cfg, _PAGE, _T))
    table = jnp.asarray(1 + np.arange(4 * rows).reshape(rows, 4),
                        jnp.int32)
    return pool, table


@pytest.mark.parametrize("paged", [False, True], ids=["no_cache", "paged"])
@pytest.mark.parametrize("family", FAMILIES)
def test_logits_at_is_the_rows_of_the_full_logits(toy, family, paged):
    """``logits_at`` [B] gives ``[B, V]``: row i of it is position
    ``logits_at[i]`` of the ``[B, T, V]`` the same call gives without
    (the final norm, Ouro's gate and choice among passes, and the head
    are each a position's own), for ragged indices (a row's first
    position, its last, one inside), with and without a cache; the
    default keeps every position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg, model, params = toy(family)
    B = 3
    ids = _ids(cfg, (B, _T), 11)
    at = jnp.asarray([0, _T - 1, 7], jnp.int32)
    if paged:
        pool, table = _pool(cfg, B)

        @jax.jit
        def call(ids, at):
            def valid():
                return jnp.ones(ids.shape, bool)
            views = [kv_cache.kv_layer_view(layer, table, jnp.arange(B),
                                            valid) for layer in pool]
            start = jnp.zeros((B,), jnp.int32)
            return (model.apply(params, ids, kv_caches=views,
                                cache_len=start)[0],
                    model.apply(params, ids, kv_caches=views,
                                cache_len=start, logits_at=at)[0])
    else:
        @jax.jit
        def call(ids, at):
            return (model.apply(params, ids)[0],
                    model.apply(params, ids, logits_at=at)[0])
    full, rows = call(ids, at)
    assert full.shape == (B, _T, cfg.vocab_size)
    assert rows.shape == (B, cfg.vocab_size)
    assert full.dtype == rows.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(rows), np.asarray(full)[np.arange(B), np.asarray(at)],
        rtol=1e-5, atol=1e-6)


def _kinds(*kinds):
    """A config of nothing but its layers' kinds."""
    import types
    return lambda: types.SimpleNamespace(layer_kinds=kinds,
                                         n_layers=len(kinds))


@pytest.mark.parametrize("family", FAMILIES + (
    "deepseek_v32", "nothing_kept_anywhere", "an_entry_after_the_run"))
def test_where_a_call_narrows_is_read_from_the_kinds(family):
    """``sampled_only_from``: the first layer of the TRAILING run of
    layers that keep no entry, so ``n_layers`` (the gather stands before
    the final norm, as it did) for every family whose last layer keeps
    one: all but Phi-4-mini-flash, whose cross-decoder is 14 of the
    published 32 layers and 2 of the toy's 8. A layer without an entry
    BEFORE one that keeps its own does not count (its positions'
    results are read by that layer, which keeps every position's)."""
    from ray_tpu.models.deepseek_v32 import deepseek_v32_tiny
    from ray_tpu.models.kv_cache import (KIND_BORROWED, KIND_KV,
                                         KIND_STATELESS, layer_kinds,
                                         sampled_only_from)
    from ray_tpu.models.phi4flash import phi4_mini_flash
    tiny = {"deepseek_v32": deepseek_v32_tiny,
            "nothing_kept_anywhere": _kinds(KIND_STATELESS, KIND_BORROWED),
            "an_entry_after_the_run": _kinds(
                KIND_KV, KIND_BORROWED, KIND_STATELESS, KIND_KV)}.get(
                    family) or _families()[family][0]
    cfg = tiny()
    n = len(layer_kinds(cfg))
    assert n == cfg.n_layers
    want = {"phi4flash": 6, "nothing_kept_anywhere": 0}.get(family, n)
    assert sampled_only_from(cfg) == want
    if family == "phi4flash":
        assert sampled_only_from(phi4_mini_flash()) == 18
        assert sampled_only_from(phi4_mini_flash(n_layers=8)) == 6
        kinds = layer_kinds(phi4_mini_flash())
        assert set(kinds[18:]) == {KIND_BORROWED, KIND_STATELESS}
        assert kinds[17] == KIND_KV


@pytest.mark.parametrize("capture", [False, True],
                         ids=["tokens", "logprobs"])
@pytest.mark.parametrize("family", ["llama", "mixtral", "kimi_linear",
                                    "ouro"])
def test_the_prefill_program_samples_what_the_full_logits_would(
        toy, family, capture):
    """``jit_prefill`` asks the model for one position a row; its first
    tokens (temperature 0) and, under ``capture``, their log-probabilities
    equal the rule the program had until PR 54 (the full ``[B, T, V]``
    logits, row ``last_idx`` of each, argmax, ``log_softmax``) for a call
    that holds a row ending its prompt, a row mid-prompt at an offset, a
    short row and a dummy row: a dense model, a mixture, one with
    recurrent state, the looped one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.serve import step_programs
    cfg, model, params = toy(family)
    B = 4
    ids = _ids(cfg, (B, _T), 12).at[2, 5:].set(0).at[3].set(0)
    start = jnp.asarray([0, 8, 0, 0], jnp.int32)
    last_idx = jnp.asarray([_T - 1, _T - 1, 4, 0], jnp.int32)
    slots = jnp.asarray([0, 1, 2, B], jnp.int32)      # the dummy: none
    pool, table = _pool(cfg, B)
    table = table.at[3].set(0)                        # the dummy: null
    key = jax.random.PRNGKey(5)
    recurrent = bool(kv_cache.state_bytes_per_slot(
        cfg, kv_cache.sliding_ring_len(cfg, _PAGE, _T)))
    rest = (slots,) if recurrent else ()

    @jax.jit
    def until_pr54(pool):
        def live():
            return (table[:, :1] != 0) & (
                jnp.arange(_T)[None] <= last_idx[:, None])
        logits, _kv, _moe = step_programs._moe_apply(model, None)(
            params, ids, step_programs._views(pool, table, live, *rest),
            start, live)
        assert logits.shape == (B, _T, cfg.vocab_size)
        last = logits[jnp.arange(B), last_idx]
        firsts = jnp.argmax(last, axis=-1).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(last),
                                 firsts[:, None], axis=-1)[:, 0]
        return firsts, lp

    want_firsts, want_lp = until_pr54(pool)
    fn = step_programs._jit_prefill(model, 0.0, B, capture, None)
    out = fn(params, _pool(cfg, B)[0], ids, start, last_idx, table, key,
             *rest)[0]
    firsts, lp = out if capture else (out, None)
    np.testing.assert_array_equal(np.asarray(firsts),
                                  np.asarray(want_firsts))
    if capture:
        np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                                   rtol=1e-5, atol=1e-6)


# -------------- a dense family's programs hold nothing of the mixture's

@pytest.mark.parametrize("family", ["llama", "olmo_hybrid", "ouro",
                                    "phi4flash"])
def test_a_dense_familys_programs_never_reach_the_grouped_matmul(
        family, monkeypatch):
    """The decode (plain and capturing), verify, prefill and cache-less
    programs of the dense toys are traced with ``ops/grouped_matmul.py``
    (the op, its kernel, ``visits``) made to raise: a change to that
    module cannot move one byte of them (their sha256 against the
    parent's tree is a scratch script's, CHANGES.md PR 57), and they
    return no routing vector."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import mixtral
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.serve import step_programs

    def reached(*_a, **_k):
        raise AssertionError("a dense program reached the grouped matmul")
    for module, name in ((gm, "grouped_matmul"), (gm, "visits"),
                         (gm, "grouped_matmul_kernel"),
                         (mixtral, "grouped_matmul"), (mixtral, "visits")):
        monkeypatch.setattr(module, name, reached)
    tiny, cls, _rule = _families()[family]
    cfg = tiny(dtype=jnp.float32, vocab_size=229)   # no other test's
    model = cls(cfg)
    arr, i32, S = jax.ShapeDtypeStruct, jnp.int32, 6
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), i32))["params"]}
    pages = jax.eval_shape(lambda: kv_cache.init_kv_pool(
        cfg, 17, _PAGE, n_slots=S,
        ring_len=kv_cache.sliding_ring_len(cfg, _PAGE, _T)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    recurrent = bool(kv_cache.state_bytes_per_slot(
        cfg, kv_cache.sliding_ring_len(cfg, _PAGE, _T)))
    texts = [step_programs._jit_decode(model, 0.0, 8, S, cap, None).lower(
        params, pages, arr((S, 8), i32), arr((S,), i32), arr((S,), i32),
        key, arr((), i32)).as_text(debug_info=True) for cap in (False, True)]
    if not recurrent:       # a recurrent state has no rewind: no verify
        texts.append(step_programs._jit_verify(model, None).lower(
            params, pages, arr((S, 5), i32), arr((S,), i32),
            arr((S, 8), i32)).as_text(debug_info=True))
    texts.append(step_programs._jit_prefill(model, 0.0, 4, False, None).lower(
        params, pages, arr((4, _T), i32), arr((4,), i32), arr((4,), i32),
        arr((4, 8), i32), key,
        *((arr((4,), i32),) if recurrent else ())).as_text(debug_info=True))
    texts.append(jax.jit(model.apply).lower(
        params, arr((2, _T), i32)).as_text(debug_info=True))
    assert len(texts) == 5 - recurrent
    # no scope of the mixture's (a scope reads "/moe_experts" in a
    # location), no grouped matmul in its XLA form
    assert not any("/moe_" in t or "ragged_dot" in t for t in texts)


# --------------- the chunk's scan kernel is one family's, and no other's

@pytest.mark.parametrize("family", [f for f in FAMILIES
                                    if f != "phi4flash"])
def test_no_other_familys_programs_reach_the_selective_scan(family,
                                                            monkeypatch):
    """The prefill and decode programs of every other toy, traced with
    ``ops/selective_scan.py`` made to raise (its rule, its kernel and
    both ``jax.numpy`` forms): none of them has a state-space layer, so
    a change to that module cannot move one byte of them (their lowered
    text against the parent's tree is a scratch script's, CHANGES.md
    PR 62), and none holds a ``selective_scan`` call or the scope."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import selective_scan as ss
    from ray_tpu.serve import step_programs

    # (every family's module is imported before the names are patched:
    # the one that binds them keeps the real ones)
    tiny, cls, _rule = _families()[family]

    def reached(*_a, **_k):
        raise AssertionError("another family reached the selective scan")
    for name in ("serves", "selective_scan", "ssm_chunked", "ssm_step"):
        monkeypatch.setattr(ss, name, reached)
    cfg = tiny(dtype=jnp.float32, vocab_size=227)   # no other test's
    model = cls(cfg)
    arr, i32, S = jax.ShapeDtypeStruct, jnp.int32, 6
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), i32))["params"]}
    ring = kv_cache.sliding_ring_len(cfg, _PAGE, _T)
    pages = jax.eval_shape(lambda: kv_cache.init_kv_pool(
        cfg, 17, _PAGE, n_slots=S, ring_len=ring))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    recurrent = bool(kv_cache.state_bytes_per_slot(cfg, ring))
    texts = [
        step_programs._jit_decode(model, 0.0, 8, S, False, None).lower(
            params, pages, arr((S, 8), i32), arr((S,), i32),
            arr((S,), i32), key, arr((), i32)).as_text(debug_info=True),
        step_programs._jit_prefill(model, 0.0, 4, False, None).lower(
            params, pages, arr((4, _T), i32), arr((4,), i32),
            arr((4,), i32), arr((4, 8), i32), key,
            *((arr((4,), i32),) if recurrent else ())).as_text(
                debug_info=True)]
    # (a scope, a kernel's name and the module's file all follow a "/");
    # Granite-4.0-H's Mamba-2 layers name the SAME scope over their own
    # rule (ops/ssd.py: the readers find either recurrence by it)
    assert not any("/selective_scan" in t for t in texts)
    assert all(("/ssm_scan" in t) == (family == "granite_hybrid")
               for t in texts)


# ------------- the round's accounts: one module, a counter's names in one

_PACKAGE = SERVE.parent
_KERNELS = ("latent_window_attention", "paged_decode_attention",
            "ring_window_attention", "sparse_latent_attention",
            "selective_scan")


def _code_words(tree):
    """(identifiers, strings) of a module outside its docstrings."""
    docs = {id(node.body[0].value) for node in ast.walk(tree)
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef))
            and node.body and isinstance(node.body[0], ast.Expr)
            and isinstance(node.body[0].value, ast.Constant)}
    names, strings = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.alias):
            names |= {node.name, node.asname or node.name}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
        elif isinstance(node, ast.Constant) and isinstance(
                node.value, str) and id(node) not in docs:
            strings.add(node.value)
    return names, strings


@pytest.mark.parametrize("file,words", [
    # the host loop imports no kernel's module and names no counter
    ("engine.py", _KERNELS + ("_MOE_", "_SELECTION", "experts_touched")),
    # the programs concatenate the sections they are given
    ("step_programs.py", ("sparse_latent_attention", "index_topk",
                          "selection_len", "_moe_vector_of", "MOE_STATS",
                          "moe_stats_vector")),
])
def test_the_loop_and_the_programs_know_no_counter(file, words):
    names, strings = _code_words(ast.parse((SERVE / file).read_text()))
    hits = [(w, n) for w in words for n in names | strings if w in n]
    assert not hits, hits


def test_a_counters_name_is_written_in_one_module():
    """Each name of a section (and so its place in the vector) stands
    where the vector is laid out and nowhere else in the package."""
    from ray_tpu.models.mixtral import MoEStats
    from ray_tpu.ops.sparse_latent_attention import SelectionStats
    want = {name: "mixtral.py" for name in MoEStats.names[1:]}
    want.update({name: "sparse_latent_attention.py"
                 for name in SelectionStats.names})
    found = {name: set() for name in want}
    for path in sorted(_PACKAGE.rglob("*.py")):
        _names, strings = _code_words(ast.parse(path.read_text()))
        for name in want:
            if any(name in s for s in strings):
                found[name].add(path.name)
    assert found == {name: {where} for name, where in want.items()}


def test_the_sections_are_asked_of_the_config():
    """A mixture's by ``num_experts``, a selection's beside the code
    that sows it, and a model that chooses WITHOUT a mixture is counted
    by construction: its one section is the whole vector."""
    from ray_tpu.models.deepseek_v32 import deepseek_v32_tiny
    from ray_tpu.models.llama import llama_tiny
    from ray_tpu.models.mixtral import MoEStats, olmoe_tiny, stats_sections
    from ray_tpu.ops.sparse_latent_attention import SelectionStats
    assert stats_sections(llama_tiny()) == ()
    cfg = olmoe_tiny()
    assert stats_sections(cfg) == (MoEStats(cfg.num_experts, None),)
    held = olmoe_tiny(experts_held=(2, 4))
    (share,) = stats_sections(held)
    assert (share.head, len(share)) == (4, 4 + 4 + 1)
    assert len(stats_sections(cfg)[0]) == cfg.num_experts + 4
    cfg = deepseek_v32_tiny()
    assert stats_sections(cfg) == (MoEStats(cfg.num_experts, None),
                                   SelectionStats())
    lone = types.SimpleNamespace(stats_sections=(SelectionStats(),))
    assert stats_sections(lone) == (SelectionStats(),)
    assert SelectionStats().read([5, 3, 4, 1]) == {
        "index_keys_scored": 5, "sparse_entries_chosen": 3,
        "sparse_entries_read": 4, "selection_kernel_rows": 1}
    import numpy as np
    vec = np.asarray([1, 0, 2, 0, 2, 2, 1, 3], np.int32)
    assert MoEStats(4).read(vec) == {
        "pairs": 3, "experts_touched": 2, "load_max": 2, "layer_steps": 1,
        "tile_visits": 3, "pairs_routed": 3}
    assert MoEStats(8, (2, 4)).read(np.append(vec, 9)) == {
        "pairs": 3, "experts_touched": 2, "load_max": 2, "layer_steps": 1,
        "tile_visits": 3, "pairs_routed": 9}


# Every key the benchmark's readers read by name, copied from the
# parent's run (PR 57's tree, the same requests): a refactor of the
# accounts cannot drop one unseen.
_ROUND = {
    "round", "overlap", "host_gap_s", "wall_s", "admit_s", "plan_s",
    "dispatch_s", "readback_s", "cpu_s", "readback_cpu_s", "backlog",
    "decode_riders", "decode_steps", "decode_window_tokens",
    "decode_context_tokens", "decode_kernel_pages", "prefill_tokens",
    "prefill_budget", "prefill_rows", "prefill_window_tokens",
    "prefill_kernel_blocks", "prefill_width", "prefill_head_rows",
    # PR 61: the layers of the call that ran on those positions alone
    "prefill_sampled_only_layers",
    # PR 62: the positions one state-space layer's scan kernel walked
    "prefill_scan_kernel_positions"}
_ROUND_MOE = {
    "moe_pairs", "moe_experts_touched", "moe_load_max", "moe_layer_steps",
    "moe_tile_visits", "moe_pairs_routed", "moe_decode_pairs",
    "moe_decode_experts_touched", "moe_decode_load_max",
    "moe_decode_layer_steps", "moe_decode_tile_visits",
    "moe_decode_pairs_routed"}
_ROUND_SELECTION = {
    "index_keys_scored", "sparse_entries_chosen", "sparse_entries_read",
    "decode_index_keys_scored", "decode_sparse_entries_chosen",
    "decode_sparse_entries_read",
    # PR 59: the queries whose choice the kernel made
    "selection_kernel_rows", "decode_selection_kernel_rows"}
_ROUND_SLIDING = {"decode_sliding_keys", "sliding_kernel_keys",
                  "state_slots"}
# PR 60: the riders' context entries x the layers that READ pages, of a
# model whose pages have readers beside their owner
_ROUND_SHARED = {"decode_shared_kv_reads"}
_LOAD = {
    "cold_builds", "draining", "fetchq_depth", "free_pages", "free_slots",
    "has_work", "heartbeat_age_s", "itl_ewma_s", "kv_bytes_in_use",
    "kv_bytes_per_token", "kv_bytes_total", "kv_dtype", "kv_page_bytes",
    "max_queued", "max_queued_batch", "outstanding_tokens", "overlap",
    "pending_prefills", "prefix_digest", "programs_built", "queue_depth",
    "queue_depth_batch", "queue_depth_online", "role",
    "shed_retry_after_s", "shed_total", "sliding_bytes_per_slot",
    "sliding_kernel_keys", "state_bytes_in_use", "state_bytes_total",
    "stopped", "total_slots", "tp", "ttft_ewma_s", "weight_generation",
    "weights_id"}
_LOAD_MOE = {"moe_expert_share", "moe_pairs_total"}


@pytest.mark.parametrize("module,tiny,round_keys,load_keys", [
    ("llama", "llama_tiny", _ROUND, _LOAD),
    ("mixtral", "olmoe_tiny", _ROUND | _ROUND_MOE, _LOAD | _LOAD_MOE),
    ("mellum", "mellum_tiny", _ROUND | _ROUND_MOE | _ROUND_SLIDING,
     _LOAD | _LOAD_MOE),
    ("deepseek_v32", "deepseek_v32_tiny",
     _ROUND | _ROUND_MOE | _ROUND_SELECTION, _LOAD | _LOAD_MOE),
    ("phi4flash", "phi4flash_tiny",
     _ROUND | _ROUND_SLIDING | _ROUND_SHARED, _LOAD),
], ids=["llama", "olmoe", "mellum", "deepseek_v32", "phi4flash"])
def test_the_round_event_and_the_load_report_keep_their_keys(
        module, tiny, round_keys, load_keys):
    import importlib

    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.engine import LLMEngine
    cfg = getattr(importlib.import_module(f"ray_tpu.models.{module}"),
                  tiny)(dtype=jnp.float32)
    model = cfg.model_class(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=2, page_size=8, n_pages=32,
                    chunk=4, prefill_chunk=16, temperature=0.0, seed=0)
    try:
        req = eng.submit(list(range(1, 22)), max_new_tokens=6)
        for _ in range(500):
            if not eng.step():
                break
        assert len(req.result()) == 6
        rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
        assert set().union(*rounds) == round_keys
        assert set(eng.load_report()) == load_keys
        # the stats sum what the rounds report
        for key in round_keys - _ROUND | {"decode_context_tokens"}:
            assert eng.stats[key] == sum(r.get(key, 0) for r in rounds)
    finally:
        eng.shutdown()
