"""DeepSeek-V3.2 on the normal path (ray_tpu.models.deepseek_v32 through
LLMEngine and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/deepseek_v32.py: the expanded form, the selection
an explicit sort and mask), on the CPU at ``deepseek_v32_tiny``: a dense
layer and two mixture layers of latent attention whose queries attend
the 24 entries a 4-head indexer chooses, 16 experts in 4 groups of
which 2 stay, 4 a token and one shared, YaRN over 64 original
positions. Contexts run to several ``index_topk``.

Tolerances. As tests/test_axk1.py's: both sides compute in float32 on
the same weights and differ in the order of their sums and the form of
the attention, so logits of the order of 1 agree to rtol 1e-4 / atol
2e-5. The CHOICE could differ where two index scores lie closer than
the sums' rounding moves them; at these seeds none does (the chosen
sets themselves are compared where the boundary is no near-tie). Each
control below moves logits by a thousand times the tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kv_cache
from ray_tpu.models.axk1 import MLAttention, mla_param_count
from ray_tpu.models.deepseek_v32 import (DeepSeekV32, LightningIndexer,
                                         deepseek_v32,
                                         deepseek_v32_param_count,
                                         deepseek_v32_tiny)
from ray_tpu.models.kv_cache import (KIND_INDEXED, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes, latent_page_width,
                                     refuse_unsupported)
from ray_tpu.models.mixtral import MoEFeedForward, group_limited
from ray_tpu.ops import latent_window_attention as latent_window
from ray_tpu.ops import sparse_latent_attention as sparse
from ray_tpu.ops.sparse_latent_attention import (SELECTION_STATS,
                                                 selection_stats_vector)
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5


def _family():
    from benchmarks import common
    return common.load_family("deepseek_v32", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale,
    the index key norm's bias and the routers' choice biases away from
    their start so that one left out shows."""
    from benchmarks import weights
    model = DeepSeekV32(cfg)
    params = _family().seeded(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        if name.endswith("['bias']"):
            return leaf + 0.3 * rng.standard_normal(leaf.shape).astype(
                np.float32)
        if "router_bias" in name:
            # (zeros as seeded: the family balances them at set-up)
            return leaf + 0.02 * rng.standard_normal(leaf.shape).astype(
                np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = deepseek_v32_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg, **control):
    fam = _family()
    return fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg, **control)


def _held_to_the_reference(params, cfg, prompt, out, least=None):
    """tests/test_axk1.py's teacher-forced rule."""
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

def test_forward_matches_the_reference_and_chooses_its_entries(tiny):
    """The cache-less forward pass, 150 positions (six ``index_topk``,
    past YaRN's 64 original positions): the logits, and the chosen sets
    themselves. The program's mask is read from its own ``topk_mask``
    over the indexer's scores of layer 0, the reference's from its
    sort."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want, chosen = _reference(params, ids, cfg, chosen=True)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL,
                               atol=ATOL)
    assert chosen.shape == (3, 2, 150, 150)
    sizes = chosen.sum(-1)
    assert (sizes == np.minimum(np.arange(150) + 1, 24)[None, None]).all()
    # past index_topk a query attends under a sixth of its context, and
    # not simply the most recent entries
    recent = np.tril(np.ones((150, 150), bool)) & ~np.tril(
        np.ones((150, 150), bool), -24)
    assert (chosen[:, :, 100:] != recent[100:]).any(axis=-1).mean() > 0.9

    # layer 0's selection as the program makes it
    lp = params["params"]["layers_0"]
    x = params["params"]["tok_embeddings"][jnp.asarray(ids)]
    from ray_tpu.models.axk1 import _rope, yarn_inv_freq
    from ray_tpu.models.llama import RMSNorm
    h = RMSNorm(cfg.norm_eps).apply({"params": lp["attention_norm"]}, x)
    c_q = RMSNorm(cfg.norm_eps).apply(
        {"params": lp["attention"]["q_norm"]},
        h @ lp["attention"]["wq_a"]["kernel"])
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                        cfg.rope_factor, cfg.rope_original_max_seq_len,
                        cfg.rope_beta_fast, cfg.rope_beta_slow)
    pos = jnp.arange(150)
    scores, _ = LightningIndexer(cfg).apply(
        {"params": lp["indexer"]}, c_q, h,
        lambda v: _rope(v, inv, pos, 1.0), pos)
    mine = np.asarray(sparse.topk_mask(scores, cfg.index_topk))
    # S_t itself, wherever the 24th and 25th best scores are no
    # near-tie (a ten-thousandth of the scores' spread apart)
    top = -np.sort(-np.asarray(scores), axis=-1)
    spread = np.std(top[np.isfinite(top)])
    with np.errstate(invalid="ignore"):
        clear = ~(np.abs(top[..., 23] - top[..., 24]) < 1e-4 * spread)
    assert clear.mean() > 0.95
    assert (mine == chosen[0]).all(-1)[clear].all()


@pytest.mark.parametrize("control", ["no_selection", "recent",
                                     "no_group_limit", "no_bias"])
def test_each_control_fails_the_comparison(tiny, control):
    """Controls (a), (b), (d), (e) of the chip run, at the tiny size:
    every entry attended, the most recent ``index_topk`` attended, the
    group limit left out, the choice bias left out. Each moves the
    reference's logits from the program's by a thousand tolerances."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    wrong = _reference(params, ids, cfg, **{control: True})
    assert np.abs(np.asarray(got) - wrong).max() > 1000 * ATOL
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(got), wrong, rtol=RTOL,
                                   atol=ATOL)


def test_half_as_many_entries_is_another_model(tiny):
    """Control (c): ``index_topk`` halved."""
    cfg, model, params = tiny
    ids = _ids((1, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    wrong = _reference(params, ids, cfg, index_topk=12)
    assert np.abs(np.asarray(got) - wrong).max() > 1000 * ATOL


def test_under_index_topk_the_layer_is_the_one_without_an_indexer():
    """Up to ``index_topk`` positions every entry is chosen: the
    cache-less layer's output is ``MLAttention``'s without an indexer
    BIT FOR BIT, and one position more it is not."""
    cfg = deepseek_v32_tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 25, cfg.dim))
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, n):
            attn = MLAttention(
                cfg, indexer=LightningIndexer(cfg, name="indexer"),
                name="attention")
            return attn(x[:, :n], None, jnp.arange(n))[0]
    layer = Layer()
    params = jax.jit(layer.init, static_argnums=2)(
        jax.random.PRNGKey(6), x, 25)
    plain = MLAttention(cfg)
    attention = {"params": params["params"]["attention"]}
    for n, same in ((24, True), (25, False)):
        got = jax.jit(layer.apply, static_argnums=2)(params, x, n)
        want, _ = jax.jit(plain.apply)(attention, x[:, :n], None,
                                       jnp.arange(n))
        assert (np.asarray(got) == np.asarray(want)).all() == same


def test_layer_kinds_and_the_published_count():
    cfg = deepseek_v32()
    assert cfg.layer_kinds == (KIND_INDEXED,) * 61
    assert cfg.latent_dim == 576 and latent_page_width(cfg) == 640
    assert abs(cfg.softmax_scale - 0.13523) < 5e-6
    # ISSUE 56's arithmetic: 187.11 M of latent attention and 13.96 M
    # of indexer a layer; 671.9 B without the MTP module
    assert mla_param_count(cfg) == 187_107_328 + 13_959_424
    whole = deepseek_v32_param_count(cfg)
    assert abs(whole / 671.9e9 - 1.0) < 1e-3, whole
    # the benchmark's cut: one chip of 32, five layers, 3.226 B
    cut = deepseek_v32(n_layers=5, first_k_dense=1, vocab_size=16160,
                       experts_held=(0, 8))
    assert abs(deepseek_v32_param_count(cut, experts=8) / 3.226e9
               - 1.0) < 1e-3
    # the flax tree holds what the count says
    tiny_cfg = deepseek_v32_tiny(dtype=jnp.float32)
    shapes = jax.eval_shape(DeepSeekV32(tiny_cfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == deepseek_v32_param_count(tiny_cfg)


# ------------------------------------------------------- the group limit

def test_the_group_limit_against_a_hand_worked_case():
    """Eight experts in four groups of two, two groups stay, three
    experts a token. Group scores are the sums of a group's two
    (largest) values: [0.9 + 0.1, 0.5 + 0.45, 0.6 + 0.3, 0.7 + 0.0] =
    [1.0, 0.95, 0.9, 0.7]: groups 0 and 1 stay, so expert 6 (0.7, the
    second largest of all) and expert 4 (0.6) may not be chosen, and
    the three chosen are 0 (0.9), 2 (0.5), 3 (0.45)."""
    cfg = deepseek_v32_tiny(num_experts=8, n_group=4, topk_group=2)
    choice = jnp.asarray([[0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.7, 0.0]])
    limited = np.asarray(group_limited(cfg, choice))
    assert (limited[0, :4] == np.asarray(choice)[0, :4]).all()
    assert np.isneginf(limited[0, 4:]).all()
    assert sorted(np.asarray(jax.lax.top_k(limited, 3)[1])[0]) == [0, 2, 3]
    # a tie between groups goes to the lower group
    tie = jnp.asarray([[0.5, 0.5, 0.9, 0.1, 0.6, 0.4, 0.2, 0.1]])
    assert np.isfinite(np.asarray(group_limited(cfg, tie))[0]).tolist() \
        == [True] * 4 + [False] * 4
    # no groups declared: the choice as it is
    assert group_limited(dataclasses.replace(cfg, n_group=1), choice) \
        is choice
    from ray_tpu.models.axk1 import axk1_tiny
    assert group_limited(axk1_tiny(), choice) is choice
    # the reference's own rule gives the same three
    from benchmarks.reference import deepseek_v32 as ref
    w = {"router": jnp.eye(8), "router_bias": jnp.zeros((8,))}
    logit = jnp.log(choice + 1e-9) - jnp.log1p(-choice - 1e-9)
    weight = ref.route(logit.at[0, 7].set(-30.0), w, top_k=3,
                       norm_topk=True, scaling=1.0, n_group=4,
                       topk_group=2)
    assert sorted(np.nonzero(np.asarray(weight)[0])[0]) == [0, 2, 3]


def test_the_shares_add_up_to_the_whole_layer():
    """THE SHARE TEST (model-configs section 4) at 32 shares: 64
    experts in 8 groups of which 4 stay, 8 a token, each chip holding 2
    (a quarter of a group's 8, as the cut's 8 of 32). What the 32
    shares compute for the same tokens (the router at its full width
    with its bias and its groups, the gates normalised over all
    chosen) plus the shared expert counted ONCE is the uncut
    reference's whole layer."""
    from benchmarks import weights
    from benchmarks.reference import deepseek_v32 as ref
    cfg = deepseek_v32_tiny(dtype=jnp.float32, num_experts=64, n_group=8,
                            topk_group=4, num_experts_per_tok=8)
    params = _family().seeded(weights.param_shapes(DeepSeekV32(cfg)), 0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 31, cfg.dim))
    whole = params["params"]["layers_1"]["moe"]
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         _family().reference_weights(params, cfg)["layers"][1].items()}
    with jax.default_matmul_precision("highest"):
        shared = ref.axk1.shared(x, w)
        want = ref.routed(x, w, lo=0, top_k=8, norm_topk=True,
                          scaling=2.5, n_group=8, topk_group=4)[0] + shared
    apply = jax.jit(lambda cfg_, p: MoEFeedForward(cfg_).apply(
        {"params": p}, x), static_argnums=0)
    total, landed = jnp.zeros_like(x), 0
    for lo in range(0, 64, 2):
        share = {k: (v[lo:lo + 2] if k in ("w1", "w2", "w3") else v)
                 for k, v in whole.items()}
        part = apply(dataclasses.replace(cfg, experts_held=(lo, 2)), share)
        total = total + (part - shared)
        landed += float(jnp.abs(part - shared).max() > 1e-3)
    assert landed == 32                      # every share does some work
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=RTOL, atol=ATOL)


def test_the_routers_rule_matches_the_reference(tiny):
    """What the program's mixture sows as its choice is the
    reference's: the groups, the bias and the tie rules alike."""
    from benchmarks.reference import deepseek_v32 as ref
    from ray_tpu.models.mixtral import MOE_STATS
    cfg, _model, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, cfg.dim))
    moe = params["params"]["layers_1"]["moe"]
    _, sown = jax.jit(lambda p: MoEFeedForward(cfg).apply(
        {"params": p}, x, mutable=[MOE_STATS]))(moe)
    got = np.sort(np.asarray(sown[MOE_STATS]["topk"]).reshape(80, 4), -1)
    w = {"router": moe["router"], "router_bias": moe["router_bias"]}
    with jax.default_matmul_precision("highest"):
        weight = ref.route(x.reshape(80, -1), w, top_k=4, norm_topk=True,
                           scaling=2.5, n_group=4, topk_group=2)
    want = np.sort(np.argsort(-np.asarray(weight), -1)[:, :4], -1)
    assert (got == want).all()
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-5)


# ------------------------------------------------ the exact choice itself

def test_topk_mask_is_the_sort_ties_and_all():
    """``topk_mask`` against a stable sort on rows with many equal
    scores (small integers), rows shorter than ``k``, a row that sees
    nothing, and signs and zeros of both kinds; and the decode step's
    form, a stable sort, chooses the same sets."""
    rng = np.random.default_rng(0)
    scores = rng.integers(-3, 4, size=(6, 5, 97)).astype(np.float32)
    scores[0, 0] = rng.standard_normal(97)
    scores[1, :, 40:] = -np.inf                  # 40 visible, k = 24
    scores[2, :, 10:] = -np.inf                  # 10 visible: all chosen
    scores[3, 0] = -np.inf                       # a row without a request
    scores[4, 0, ::2] = 0.0
    k = 24
    got = np.asarray(jax.jit(sparse.topk_mask, static_argnums=1)(
        jnp.asarray(scores), k))
    order = np.argsort(-scores, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    want = (rank < k) & np.isfinite(scores)
    assert (got == want).all()
    assert got[2].sum(-1).tolist() == [[10] * 5][0]
    assert got[3, 0].sum() == 0
    # the decode step's form: one stable sort that carries each
    # position's place along
    place = jnp.broadcast_to(jnp.arange(97, dtype=jnp.int32), scores.shape)
    worst_first, at = jax.lax.sort((-jnp.asarray(scores), place),
                                   dimension=2, num_keys=1, is_stable=True)
    by_sort = np.zeros_like(want)
    np.put_along_axis(by_sort, np.asarray(at)[..., :k],
                      np.asarray(worst_first)[..., :k] < np.inf, axis=-1)
    assert (by_sort == want).all()


def _walk_scores(B, T, S, start, seed=1):
    """Scores [B, T, S] of a call whose longest row starts at ``start``
    (another at 3, the last without a request) as ``index_scores``
    leaves them: ``-inf`` at every position a query cannot see."""
    rng = np.random.default_rng(seed)
    table = 1 + np.arange(B * (S // 8)).reshape(B, S // 8)
    table[-1] = 0
    pos = np.full((B,), 3)
    pos[0] = start
    seen = (np.arange(S)[None, None] <= (
        pos[:, None, None] + np.arange(T)[None, :, None])
        ) & (table[:, :1, None] != 0)
    scores = np.where(seen, rng.standard_normal((B, T, S)), -np.inf)
    return (jnp.asarray(scores, jnp.float32), jnp.asarray(table, jnp.int32),
            jnp.asarray(pos, jnp.int32))


def test_the_choice_is_made_over_the_walks_width():
    """A chunk's choice costs the WALK's width: a table of four blocks
    (4 x 64 pages of 8 = 2,048 positions), rows whose walk ends in the
    first, the second and the last quarter, choose what ``topk_mask``
    over the whole table chooses, and nothing past the walk."""
    B, T, S = 3, 16, 2048
    for start in (40, 600, 1990):
        scores, table, pos = _walk_scores(B, T, S, start)
        member, chosen, by_kernel = jax.jit(
            sparse._chosen_of_the_walk, static_argnums=(3, 4))(
            scores, table, pos, 8, 24)
        want = np.asarray(sparse.topk_mask(scores, 24))
        assert (np.asarray(member) == want).all()
        assert (np.asarray(chosen) == want.sum(-1)).all()
        assert member.shape == (B, T, S) and not by_kernel


def _rows(case):
    """(scores [rows, S], k, ends or None) of a named case."""
    rng = np.random.default_rng(7)
    scores = rng.integers(-3, 4, size=(32, 640)).astype(np.float32)
    k, ends = 24, None
    if case == "many_equal":
        scores[0] = rng.standard_normal(640)
    elif case == "fewer_than_k":
        scores[::2, 10:] = -np.inf               # 10 visible: all chosen
        scores[1::2, 40:] = -np.inf
    elif case == "sees_nothing":
        scores[3:9] = -np.inf
        scores[20:] = -np.inf
    elif case == "inf_tails":
        ends = rng.integers(0, 641, size=32)
        ends[:2] = 0, 640
        scores[np.arange(640)[None] >= ends[:, None]] = -np.inf
        scores[5, :7] = -np.inf                  # and one inside the sight
    elif case == "negatives_and_zeros":
        scores = -np.abs(rng.standard_normal((32, 640))).astype(np.float32)
        scores[:, ::3] = 0.0
        scores[:, 1::7] = -0.0
        scores[4] = 0.0
    elif case == "k_1":
        k = 1
    elif case == "k_over_width":
        k = 4096
    elif case == "sixteen_rows_a_tile":
        scores = scores[:16, :384]
    elif case == "the_top_bit_alone":
        # every key on one side of zero, so the first digit decides nothing
        scores = np.abs(rng.standard_normal((32, 640))).astype(np.float32)
        scores[1::2] *= -1
    return scores, k, ends


@pytest.mark.parametrize("case,digit_bits", [
    ("many_equal", 1), ("fewer_than_k", 1), ("sees_nothing", 1),
    ("inf_tails", 1), ("negatives_and_zeros", 1), ("k_1", 1),
    ("k_over_width", 1), ("sixteen_rows_a_tile", 1),
    ("the_top_bit_alone", 1), ("many_equal", 2), ("inf_tails", 4)])
def test_the_kernels_choice_is_topk_masks_bit_for_bit(case, digit_bits):
    """``topk_select`` in interpret mode against ``topk_mask``, the
    definition it is held to, on rows of many equal scores (small
    integers: the lowest positions among the k-th's equals), rows that
    see fewer than ``k`` or nothing, tails of ``-inf`` the kernel is
    told of, negative scores and zeros of both signs, ``k`` of 1 and
    over the width, and at the digit widths the timer tried."""
    from unittest import mock
    scores, k, ends = _rows(case)
    want = np.asarray(sparse.topk_mask(jnp.asarray(scores), k))
    with mock.patch.object(sparse, "_SELECT_DIGIT_BITS", digit_bits):
        member, chosen = sparse.topk_select(
            jnp.asarray(scores), k,
            None if ends is None else jnp.asarray(ends, jnp.int32),
            dtype=jnp.float32, interpret=True)
    assert member.dtype == jnp.float32
    assert (np.asarray(member) == want.astype(np.float32)).all()
    assert (np.asarray(chosen) == want.sum(-1)).all()
    assert want.sum() > 0


@pytest.fixture
def select_in_interpret(monkeypatch):
    """``topk_select`` in interpret mode wherever its rule, steered as
    the chip would answer it, sends a choice; the shapes it was given."""
    calls = []
    kernel = sparse.topk_select
    monkeypatch.setattr(sparse, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(
        sparse, "topk_select", lambda *a, **kw: calls.append(
            a[0].shape) or kernel(*a, interpret=True, **kw))
    return calls


@pytest.mark.parametrize("start", [40, 600, 1100, 1990])
@pytest.mark.parametrize("B,T", [(16, 1), (2, 16)],
                         ids=["decode_step", "chunk"])
def test_the_kernel_chooses_what_the_walk_saw(select_in_interpret, B, T,
                                              start):
    """``_chosen_of_the_walk`` through the kernel, a decode step
    ([B, 1, S]) and a chunk ([B, T, S]) whose walk ends in each quarter
    of a table of four blocks: ``topk_mask``'s choice over the whole
    table in the type asked for, and nothing past the walk."""
    S = 2048
    scores, table, pos = _walk_scores(B, T, S, start, seed=start)
    member, chosen, by_kernel = sparse._chosen_of_the_walk(
        scores, table, pos, 8, 24, jnp.bfloat16)
    want = np.asarray(sparse.topk_mask(scores, 24))
    assert by_kernel and select_in_interpret == [(B * T, S)]
    assert member.dtype == jnp.bfloat16 and member.shape == (B, T, S)
    assert (np.asarray(member, np.float32) == want).all()
    assert (np.asarray(chosen) == want.sum(-1)).all()
    assert not np.asarray(member)[..., start + T:].any()


def test_the_kernels_rule():
    """float32 scores in whole lane tiles, rows in whole tiles that fit
    beside their keys and their mask (of numbers), one TPU: off the
    chip nothing is the kernel's."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert not sparse.select_serves(32, 12288, f32, bf16)
    from unittest import mock
    with mock.patch.object(sparse, "_on_one_tpu", lambda: True):
        assert sparse.select_serves(32, 12288, f32, bf16)
        assert sparse.select_serves(1024, 16384, f32, bf16)
        assert not sparse.select_serves(4, 1024, f32, bf16)
        assert not sparse.select_serves(32, 1000, f32, bf16)
        assert not sparse.select_serves(32, 1024, bf16, bf16)
        assert not sparse.select_serves(32, 1024, f32, jnp.bool_)
        assert not sparse.select_serves(32, 1 << 20, f32, bf16)
    assert sparse._select_rows(1024, 16384, bf16) == 64
    assert sparse._select_rows(1024, 16384, f32) == 64
    assert sparse._select_rows(1024, 32768, f32) == 32
    assert sparse._select_rows(32, 16384, bf16) == 32
    assert sparse._select_rows(48, 1152, bf16) == 16


# -------------------------------------- both pools, against the reference

def _attention(cfg, seed=5, T=90):
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, positions, kv_cache=None, cache_len=None):
            return MLAttention(
                cfg, indexer=LightningIndexer(cfg, name="indexer"),
                name="attention")(x, None, positions, kv_cache, cache_len)
    layer = Layer()
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, cfg.dim))
    params = jax.jit(layer.init)(jax.random.PRNGKey(seed + 1), x,
                                 jnp.arange(T))
    return layer, params, x


def _paged(layer, params, x, cfg, chunks, page_size=8, n_pages=32,
           stats=False):
    """x [B, T, D] through a pool of latent pages and index-key pages
    in ``chunks`` calls, each row on its own pages."""
    B, T, _ = x.shape
    (pool,) = init_kv_pool(dataclasses.replace(cfg, n_layers=1), n_pages,
                           page_size)
    per_row = -(-T // page_size)
    table = jnp.asarray(1 + np.arange(B * per_row).reshape(B, per_row),
                        jnp.int32)
    apply = jax.jit(lambda p, xs, positions, view, pos: layer.apply(
        p, xs, positions, view, pos, mutable=[SELECTION_STATS]))
    outs, counts, start = [], [], 0
    for n in chunks:
        pos = jnp.full((B,), start, jnp.int32)
        positions = pos[:, None] + jnp.arange(n)[None]
        (out, view), sown = apply(params, x[:, start:start + n],
                                  positions, kv_layer_view(pool, table), pos)
        pool = kv_layer_store(view)
        outs.append(out)
        counts.append(np.asarray(
            sown[SELECTION_STATS]["attention"]["counts"]))
        start += n
    return jnp.concatenate(outs, axis=1), pool, counts


@pytest.mark.parametrize("chunks", [(90,), (56, 34), (50,) + (1,) * 40],
                         ids=["one_call", "two_chunks", "decode_steps"])
def test_chosen_entries_through_both_pools_equal_the_expanded_form(chunks):
    """One layer on 90 positions (almost four ``index_topk``): the
    cache-less masked expanded form, and the absorbed form over the
    latent pool with the index keys in pages of their own, in one
    prefill call (the masked walk), in two chunks across a page
    boundary, and as a prefill followed by decode steps of one token
    (the same walk, a query a row)."""
    cfg = deepseek_v32_tiny(dtype=jnp.float32)
    layer, params, x = _attention(cfg)
    want, _ = jax.jit(layer.apply)(params, x, jnp.arange(90))
    got, pool, _ = _paged(layer, params, x, cfg, chunks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    # two entries a token under ONE page id: [c | k_r] in whole tiles
    # and the index key beside it
    pages, index = pool
    assert pages.shape == (32, 8, latent_page_width(cfg))
    assert index.shape == (32, 8, cfg.index_head_dim)
    rows = -(-90 // 8)
    for held in (pages[1:1 + rows], index[1:1 + rows]):
        assert np.abs(np.asarray(held).reshape(rows * 8, -1)[:90]
                      ).max(-1).min() > 0
    assert not np.asarray(index[1 + 2 * rows:]).any()


def test_the_counters_against_hand_counts():
    """What a call says it scored, chose and read, a query: a prefill
    chunk of 40 at offset 0 scores t + 1 keys, chooses min(t + 1, 24)
    and READS THE WHOLE WALK (one block of the loop: the table's 96
    positions); a decode step at position 70 scores 71, chooses 24 and
    reads the walk's 96 too; on the CPU the kernel chose for none."""
    cfg = deepseek_v32_tiny(dtype=jnp.float32)
    layer, params, x = _attention(cfg)
    _, _, counts = _paged(layer, params, x, cfg, (40, 30, 1))
    first, _, step = counts
    t = np.arange(40)
    assert (first[0] == t + 1).all() and (first[1] == np.minimum(
        t + 1, 24)).all() and (first[2] == 96).all()
    assert step[:, :, 0].tolist() == [[71, 71], [24, 24], [96, 96], [0, 0]]
    live = jnp.asarray([[True], [False]])
    assert selection_stats_vector({"a": step, "b": step}, live).tolist() \
        == [142, 48, 192, 0]


@pytest.mark.parametrize("T,tokens,read_first,read_last", [
    (64, 8, [320, 64, 0], [384, 128, 0]),
    (1, 1, [320, 64, 0], [320, 64, 0])], ids=["chunk", "decode_step"])
def test_the_kernels_member_mask_equals_the_loop(T, tokens, read_first,
                                                 read_last):
    """ops/latent_window_attention.py with the choice as its mask, in
    interpret mode, against the XLA walk: 64 queries of 16 heads (a
    chunk, tiles of 8 tokens) or one (a decode step, a tile a row) over
    300-364 positions in blocks of 64 keys, a row without a request
    beside them, a random choice of 24 entries a query."""
    rng = np.random.default_rng(3)
    B, H, D, Dv, Pg = 3, 16, 256, 128, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    pages = jnp.asarray(rng.standard_normal((80, Pg, D)), jnp.bfloat16)
    table = np.zeros((B, 24), np.int32)
    table[0] = 1 + np.arange(24)
    table[1] = 30 + np.arange(24)
    pos = jnp.asarray([300, 17, 0], jnp.int32)
    scores = rng.standard_normal((B, T, 24 * Pg)).astype(np.float32)
    seen = np.arange(24 * Pg)[None, None] <= (
        np.asarray(pos)[:, None, None] + np.arange(T)[None, :, None])
    seen[2] = False
    member = sparse.topk_mask(jnp.asarray(np.where(seen, scores, -np.inf)),
                              24)
    want, _ = sparse._walked(q, pages, jnp.asarray(table), pos, member,
                             0.1, Dv)
    got = latent_window.latent_window_attention(
        q, pages, jnp.asarray(table), pos, softmax_scale=0.1,
        value_dim=Dv, block_pages=4, tokens=tokens, interpret=True,
        member=member)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    assert not np.asarray(got[2]).any()
    read = np.asarray(latent_window.entries_read(
        jnp.asarray(table), pos, T, H, 4, Pg, tokens=tokens))
    # a tile walks blocks of 64 keys to its last query's
    assert read[:, 0].tolist() == read_first
    assert read[:, -1].tolist() == read_last


def test_a_call_under_one_tile_is_one_tile_a_row():
    """The tile the walk's kernel is asked for: a chunk's own, the
    whole call where it holds less than one tile (a decode step, a
    verify), none where the heads fill no whole sublane tile."""
    assert sparse._tile_tokens(256, 128) == 16
    assert sparse._tile_tokens(1, 128) == 1
    assert sparse._tile_tokens(4, 128) == 4
    assert sparse._tile_tokens(1, 12) is None
    assert latent_window.serves(1, 128, 640, 512, 64, jnp.bfloat16) is False


def test_paged_logits_match_the_reference(tiny):
    """Chunked prefill of 600 tokens in chunks of 64 (25 ``index_topk``;
    across chunk boundaries, pages of 8 and the 512-token edge of the
    walk's first block), then six decode steps, through both pools,
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


# ------------------------- index-key pages follow their latent pages

def test_the_pool_holds_two_entries_a_token_under_one_page_id(tiny):
    cfg, _model, _params = tiny
    eng = _engine(tiny)
    for latent, index in eng.pages:
        assert latent.shape == (160, 8, latent_page_width(cfg))
        assert index.shape == (160, 8, cfg.index_head_dim)
    report = eng.load_report()
    per_token = 3 * (latent_page_width(cfg) + cfg.index_head_dim) * 4
    assert report["kv_bytes_per_token"] == per_token
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8) \
        == 8 * per_token
    assert report["kv_bytes_total"] == 160 * report["kv_page_bytes"]
    h = eng.submit(_ids((70,), seed=2).tolist(), max_new_tokens=4)
    eng.step()
    assert eng.load_report()["kv_bytes_in_use"] == \
        eng.alloc.occupancy() * report["kv_page_bytes"] > 0
    _drive(eng)
    assert len(h.result()) == 4 and eng.alloc.occupancy() == 0
    # a shipped page would carry both tensors of every layer
    blobs = kv_cache.export_page_bytes(eng.pages, 1)
    assert [len(layer) for layer in blobs] == [2, 2, 2]
    cols = kv_cache.page_cols_from_bytes(cfg, 8, "fp", blobs)
    assert cols[0][1].shape == (8, cfg.index_head_dim)


@pytest.mark.parametrize("chunk", [3, 8])
def test_the_served_tokens_do_not_depend_on_the_cadence(tiny, chunk):
    """``deployment.decode_chunk`` 3 of the cell's configuration against
    the engine's default 8: three requests of unequal lengths past
    ``index_topk`` that share the rounds (a prompt mid-prefill beside
    riders), the same greedy tokens as a request served alone at the
    helper's cadence of 4, and every page back in the pool."""
    prompts = [_ids((n,), seed=20 + n).tolist() for n in (70, 45, 100)]
    alone = []
    for p in prompts:
        eng = _engine(tiny)
        h = eng.submit(p, max_new_tokens=11)
        _drive(eng)
        alone.append(h.result())
    eng = _engine(tiny, chunk=chunk)
    handles = [eng.submit(p, max_new_tokens=11) for p in prompts]
    _drive(eng)
    assert [h.result() for h in handles] == alone
    assert eng.alloc.occupancy() == 0
    assert eng.stats["decode_steps"] >= 10


def test_preemption_frees_and_recomputes_both_pools(tiny):
    """A pool too small for two growing requests: the younger is
    evicted, its pages (latent entries and index keys alike, one id)
    freed and handed out again, and both requests end as the reference
    has them."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2, page_size=4, n_pages=30, chunk=2,
                  prefill_chunk=8)
    prompts = [_ids((40,), seed=40).tolist(), _ids((37,), 41).tolist()]
    handles = [eng.submit(p, max_new_tokens=22) for p in prompts]
    _drive(eng)
    assert eng.stats["preemptions"] > 0
    for p, h in zip(prompts, handles):
        _held_to_the_reference(params, cfg, p, h.result())
    assert eng.alloc.occupancy() == 0


def test_the_prefix_cache_shares_both_pools(tiny):
    """The prefix cache deals in page ids only: a second prompt that
    shares 64 tokens (past ``index_topk``: its queries choose among the
    SHARED pages' index keys) skips their prefill, a repeat of a whole
    prompt of full pages goes through the one copy-on-write page copy
    (both tensors of a layer), and the tokens are those of an engine
    without the cache."""
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
    """tests/test_axk1.py's scripted proposer."""

    def __init__(self, prompt_len, script):
        self.prompt_len, self.script, self._done = prompt_len, script, 0

    def sync(self, context):
        self._done = len(context) - self.prompt_len

    def propose(self, k):
        return self.script[self._done:self._done + k]


def test_speculative_decoding_rolls_both_pools_back(tiny):
    """Speculation deals in a page offset only: rejected drafts' latent
    entries AND index keys are overwritten by the next write at the
    clamped offset, and no query sees them meanwhile (a key past a
    query's position scores ``-inf``). The tokens are plain greedy
    decoding's."""
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
    assert st["accepted_tokens"] > 0 and st["rejected_tokens"] > 0
    _held_to_the_reference(params, cfg, prompt, truth)
    assert eng.alloc.occupancy() == 0


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_round_event_carries_the_selections_counters(tiny, form,
                                                         request):
    """One request of 70 + 10 through the engine: the ``round`` events
    and ``stats`` sum, over the three layers and the live tokens only,
    the keys scored (t + 1 a query), the entries chosen (min(t + 1,
    24)), the entries read and the queries whose choice the kernel
    made (none on the CPU; every live one where the rule is steered
    and the kernel interpreted: 16 slots, so that a decode step's rows
    fill a tile); the decode dispatches' part stands apart and reads
    its walk too."""
    from ray_tpu.serve import step_programs
    programs = (step_programs._jit_prefill, step_programs._jit_decode)
    if form == "kernel":
        calls = request.getfixturevalue("select_in_interpret")
        for program in programs:
            program.cache_clear()
            request.addfinalizer(program.cache_clear)
    eng = _engine(tiny, max_slots=16)
    h = eng.submit(_ids((70,), seed=1).tolist(), max_new_tokens=10)
    _drive(eng)
    assert len(h.result()) == 10
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    total = {k: sum(r.get(k, 0) for r in rounds) for k in (
        "index_keys_scored", "sparse_entries_chosen",
        "sparse_entries_read", "selection_kernel_rows",
        "decode_index_keys_scored", "decode_sparse_entries_chosen",
        "decode_sparse_entries_read", "decode_selection_kernel_rows")}
    assert all(eng.stats[k] == v for k, v in total.items())
    # (the vector of a dispatch is read back behind its tokens, never
    # waited for: the last one's may still be on the device)
    steps = total["decode_sparse_entries_chosen"] // (3 * 24)
    assert 0 < steps <= eng.stats["decode_steps"]
    t = np.arange(70)
    prefill_scored = 3 * int((t + 1).sum())
    prefill_chosen = 3 * int(np.minimum(t + 1, 24).sum())
    decode_scored = 3 * sum(70 + i + 1 for i in range(steps))
    assert total["decode_index_keys_scored"] == decode_scored
    assert total["index_keys_scored"] == prefill_scored + decode_scored
    assert total["decode_sparse_entries_chosen"] == 3 * 24 * steps
    assert total["sparse_entries_chosen"] == prefill_chosen + 3 * 24 * steps
    # the masked walk reads its whole window, a query: a step of the
    # one rider the table's only block, a chunk's query as much
    walk = total["decode_sparse_entries_read"] // (3 * steps)
    assert total["decode_sparse_entries_read"] == 3 * steps * walk
    assert 80 <= walk and walk % 8 == 0
    assert total["sparse_entries_read"] == 3 * (70 + steps) * walk
    if form == "kernel":
        # a prefill call's [1, 32] or [4, 32] queries, a step's [16, 1]
        assert (16, 1024) in calls and set(calls) <= {
            (16, 1024), (32, 1024), (128, 1024)}
        assert total["decode_selection_kernel_rows"] == 3 * steps
        assert total["selection_kernel_rows"] == 3 * (70 + steps)
    else:
        assert total["decode_selection_kernel_rows"] == 0
        assert total["selection_kernel_rows"] == 0
    assert "index_keys_scored" not in _plain_round_keys()


def _plain_round_keys():
    from benchmarks import common, weights
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    cfg = axk1_tiny(dtype=jnp.float32)
    params = common.load_family("axk1", "serve").seeded(
        weights.param_shapes(AXK1(cfg)), 0)
    eng = LLMEngine(AXK1(cfg), params, max_slots=2, page_size=8,
                    n_pages=40, chunk=4, prefill_chunk=32)
    eng.submit(_ids((20,), seed=3).tolist(), max_new_tokens=3)
    _drive(eng)
    return {k for e in eng.events.snapshot() if e[2] == "round"
            for k in e[5]}


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option", ["kv_dtype", "kv_migration",
                                    "sharding"])
def test_the_refusal_row(option):
    cfg = deepseek_v32_tiny()
    keeps, why = kv_cache.KIND_REFUSALS[KIND_INDEXED]
    assert list(why) == ["kv_dtype", "kv_migration", "sharding"]
    with pytest.raises(ValueError) as refused:
        refuse_unsupported(cfg, **{option: "asked"})
    assert str(refused.value) == (
        f"{option}='asked' is not supported for DeepSeekV32Config: it has "
        f"layers that keep {keeps}; {why[option]}")
    refuse_unsupported(cfg, prefix_cache=True, spec_len=3)


@pytest.mark.parametrize("option,match", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'.*pages of index keys"),
    (dict(sharding=object()), "sharding.*pages of index keys")],
    ids=["int8", "sharding"])
def test_the_engine_refuses_what_index_pages_cannot_do(tiny, option, match):
    with pytest.raises(ValueError, match=match):
        _engine(tiny, **option)


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    cache = [(jnp.zeros((1, 16, 4, 8)), jnp.zeros((1, 16, 4, 8)))] * 3
    with pytest.raises(TypeError, match="pages of index keys"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=cache,
                    cache_len=0)


def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """The normal path end to end: ray_tpu.init() -> serve.run() of a
    LlamaDeployment on this config -> the engine's two step programs."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny

    @serve.deployment
    class Llm:
        def __init__(self):
            self.inner = LlamaDeployment(
                config=cfg, params=params, max_slots=2, page_size=8,
                n_pages=64, prefix_cache=True)

        def __call__(self, payload):
            return self.inner(payload)
    handle = serve.run(Llm.bind())
    import ray_tpu
    prompt = _ids((50,), seed=70).tolist()
    out = ray_tpu.get(handle.remote({"prompt_ids": prompt,
                                     "max_new_tokens": 6}), timeout=300)
    assert out[:50] == prompt and len(out) == 56
    _held_to_the_reference(params, cfg, prompt, out[50:], least=3)
    serve.shutdown()
