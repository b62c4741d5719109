"""The main path's Pallas kernels compiled for a described (not
attached) TPU v5e, at the widths chip_smoke.py runs them, and the
engine's step programs at the serving cells' KV pool shapes (that no
program copies the pool can be read off the program, not counted at
run time).

Interpret mode accepts block shapes the chip's compiler refuses, so
these compiles are the only CPU-side guard against a kernel that
passes every other test and cannot lower. Nothing runs: shapes in,
executable out. The topology is described inside a fixture (never at
import — one process at a time may load the TPU library, and every
pytest worker imports every file), and the persistent compile cache
is off around the compiles (an entry written for a described chip
cannot be read back without one).
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import flash_attention as flash_mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (batch, seq, heads, head_dim, Pallas calls): GPT-2-124M train batch
# and TinyLlama-1.1B train batch (bench.py), both down the one-pass
# backward (forward + one backward call); a long sequence, which the
# tile plan sends down the two-pass route on a grid of blocks (forward,
# dq, dk/dv); heads of 128, one a program
@pytest.mark.parametrize("B,T,H,D,calls", [(24, 1024, 12, 64, 2),
                                           (8, 1024, 32, 64, 2),
                                           (1, 8192, 12, 64, 3),
                                           (8, 1024, 8, 128, 2)])
def test_flash_attention_fwd_bwd_compiles(one_chip, monkeypatch,
                                          B, T, H, D, calls):
    # the kernel picks interpret mode from the attached backend, which
    # is the CPU here; steer it to the compiled path for this compile
    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)
    assert flash_mod.tile_plan(T, T, D, True).one_pass == (calls == 2)

    def loss(q, k, v):
        return flash_mod.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = [((B, T, H, D), jnp.bfloat16)] * 3
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        one_chip, *qkv)
    assert compiled.as_text().count("tpu_custom_call") == calls


# (experts held, D, F, the sorted pairs of a decode call: 8 a token of
# 32 slots, Kimi-Linear's and Laguna's 128) of the seven mixtures the
# serving cells run; a 4 x 256 prefill call sorts 8,192; the three
# matmuls of the mixture, each under its own tile plan (one block a
# visit; Solar's two column tiles; A.X-K1's and DeepSeek-V3.2's seven
# contraction blocks up and seven column tiles down), none past the
# memory a kernel may use
@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("E,D,F,decode_pairs", [
    (64, 2048, 1024, 256), (40, 4096, 1280, 256), (12, 7168, 2048, 256),
    (64, 2304, 1024, 1024), (64, 2304, 896, 256), (256, 2048, 512, 1024),
    (8, 7168, 2048, 256)],
    ids=["olmoe", "solar-open2", "axk1", "kimi-linear", "mellum2",
         "laguna-xs2", "dsv32"])
def test_grouped_matmul_compiles(one_chip, monkeypatch, E, D, F,
                                 decode_pairs, kind):
    from ray_tpu.ops import grouped_matmul as gm
    # the op asks the attached backend, which is the CPU here
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    M = 8192 if kind == "prefill" else decode_pairs

    def experts(rows, w1, w3, w2, group_sizes):
        h = jax.nn.silu(gm.grouped_matmul(rows, w1, group_sizes)) * \
            gm.grouped_matmul(rows, w3, group_sizes)
        return gm.grouped_matmul(h, w2, group_sizes)

    compiled = _compile(
        experts, one_chip, ((M, D), jnp.bfloat16),
        ((E, D, F), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
        ((E, F, D), jnp.bfloat16), ((E,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 3


# shapes no cell has, which the same rule has to carry: float32
# operands, a matrix narrower than whole lanes (the shipped body's, as
# the fallback plan with a remainder is), a contraction not in whole
# sublanes, one group, rows short of a tile
@pytest.mark.parametrize("M,K,N,G,dtype", [
    (300, 2304, 256, 4, jnp.float32), (256, 2048, 1024, 8, jnp.float32),
    (256, 200, 72, 3, jnp.bfloat16), (256, 256, 200, 3, jnp.bfloat16),
    (256, 72, 256, 3, jnp.bfloat16), (256, 5000, 3000, 2, jnp.bfloat16),
    (16, 128, 128, 1, jnp.bfloat16)])
def test_grouped_matmul_compiles_off_the_cells_shapes(one_chip, M, K, N, G,
                                                      dtype):
    from ray_tpu.ops import grouped_matmul as gm
    compiled = _compile(gm.grouped_matmul_kernel, one_chip,
                        ((M, K), dtype), ((G, K, N), dtype),
                        ((G,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 1


# (slots, heads): the recurrent state of kimi-linear-d8.gen-sat and of
# solar-open2-d4.doc-sat, heads of 128 x 128 float32; the one-token
# delta-rule kernel updates it where it lies
@pytest.mark.parametrize("B,H", [(128, 32), (32, 64)],
                         ids=["gen-sat", "doc-sat"])
def test_kda_step_kernel_compiles(one_chip, B, H):
    from ray_tpu.ops import linear_attention as la
    f32, d = jnp.float32, 128
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in
            [((B, H, d), f32)] * 4 + [((B, H), f32), ((B, H, d, d), f32),
                                      ((B,), jnp.bool_), ((B,), jnp.bool_)]]
    compiled = jax.jit(la.kda_step_kernel, donate_argnums=5).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "output_to_operand_aliasing={{1}: (8, {})}" in text
    assert not _state_passes(text, (B, H, d, d))
    # the state is the program's argument and its result: nothing of
    # its size beside it
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# the recurrent state of olmo-hybrid-d16.sample-sat: 96 slots of 30
# heads of 96 x 192 float32 stored two side by side, [96, 15, 96, 384];
# the packed one-token kernel updates it where it lies
def test_kda_step_packed_kernel_compiles(one_chip):
    from ray_tpu.ops import linear_attention as la
    f32, (B, H, dk, dv, p) = jnp.float32, (96, 30, 96, 192, 2)
    shape = (B, H // p, dk, p * dv)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in
            [((B, H, dk), f32)] * 2 + [((B, H, dv), f32)]
            + [((B, H), f32)] * 2 + [(shape, f32), ((B,), jnp.bool_),
                                     ((B,), jnp.bool_)]]
    compiled = jax.jit(la.kda_step_packed_kernel, donate_argnums=5).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "output_to_operand_aliasing={{1}: (6, {})}" in text
    assert not _state_passes(text, shape)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def _state_passes(text, shape):
    """Lines of the compiled program, OUTSIDE the delta-rule kernel's
    custom call, that produce a float32 tensor of one layer's whole
    recurrent state: a copy, a select (a reset in front of the step),
    a convert, or a fusion of any of them."""
    dims = ",".join(str(d) for d in shape)
    pat = re.compile(
        r"^\s*(?:ROOT )?%?\S+ = \(?f32\[" + dims + r"\][^\n]*? "
        r"(copy|select|convert|fusion|multiply|add)\(", re.M)
    return [m.group(0).strip()[:120] for m in pat.finditer(text)]


def _assert_one_kernel_a_layer(text, shape, layers):
    """The decode program's delta-rule layers: one custom call each,
    under the ``kda_recurrence`` scope the benchmark's readers sum,
    its state operand aliased to its result, and no other operation
    over a whole state."""
    calls = re.findall(r"custom-call\([^\n]*kda_recurrence/kda_step[^\n]*",
                       text)
    assert len(calls) == layers, (len(calls), layers)
    for call in calls:
        assert "output_to_operand_aliasing={{1}: (8, {})}" in call, call[:200]
    passes = _state_passes(text, shape)
    assert not passes, (len(passes), passes[:4])


# ---------------------------------------------------------------
# The engine's step programs keep the KV pool as it is stored
# (models/kv_cache.py says what a pool declared otherwise cost).

N_PAGES, PAGE, SLOTS, KMAX = 513, 64, 32, 8


def _cell_cfg(kv_heads):
    """Two layers at a serving cell's attention and pool widths:
    Mistral-7B (32 heads over 8 KV heads, 4096 wide) or OLMoE-1B-7B's
    attention (16 over 16, 2048 wide; a dense MLP stands in for the
    mixture, which never touches the pool)."""
    from ray_tpu.models.llama import LlamaConfig
    heads, dim, hidden = {8: (32, 4096, 14336),
                          16: (16, 2048, 1024)}[kv_heads]
    return LlamaConfig(vocab_size=32768, max_seq_len=4096, dim=dim,
                       n_layers=2, n_heads=heads, n_kv_heads=kv_heads,
                       hidden_dim=hidden, rope_theta=1e6,
                       dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def _program(name, model, mesh):
    """(jitted program, its arguments after params and pages) as the
    engine builds and calls them."""
    from ray_tpu.serve import step_programs
    i32 = jnp.int32
    table = ((SLOTS, model.config.max_seq_len // PAGE), i32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = (key.shape, key.dtype)
    if name == "decode":
        return (step_programs._jit_decode(model, 0.0, KMAX, SLOTS, False,
                                       mesh),
                [table, ((SLOTS,), i32), ((SLOTS,), i32), key,
                 ((), i32)])
    if name == "prefill":
        B, T = 4, 256
        return (step_programs._jit_prefill(model, 0.0, B, False, mesh),
                [((B, T), i32), ((B,), i32), ((B,), i32),
                 ((B, table[0][1]), i32), key])
    T = 5                                   # spec_len 4
    return (step_programs._jit_verify(model, mesh),
            [((SLOTS, T), i32), ((SLOTS,), i32), table])


def _pool_copies(text, shard_shape):
    """Lines of the compiled program that COPY a tensor of one
    layer's pool shape (a plain copy or a fusion XLA named for one),
    whatever layout they write."""
    dims = ",".join(str(d) for d in shard_shape)
    pat = re.compile(
        r"^\s*(?:ROOT )?%?(\S+) = bf16\[" + dims
        + r"\](?:\{[^}]*\})? (copy|fusion)\(", re.M)
    return [m.group(0).strip() for m in pat.finditer(text)
            if m.group(2) == "copy" or m.group(1).startswith("copy")]


def _compile_step(name, cfg, pool_sharding, param_sharding, small,
                  mesh=None):
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.llama import Llama
    model = Llama(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda t, sh: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=sh),
        params, param_sharding(params))
    pages = jax.eval_shape(lambda: init_kv_pool(cfg, N_PAGES, PAGE))
    pages = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                       sharding=pool_sharding), pages)
    fn, rest = _program(name, model, mesh)
    rest = [jax.ShapeDtypeStruct(s, d, sharding=small)
            for s, d in rest]
    compiled = fn.lower(params, pages, *rest).compile()
    pool = pages[0][0]
    return compiled, pool


def _assert_pool_stays(compiled, pool, shard_shape):
    assert pool.shape == (N_PAGES, PAGE) + pool.shape[2:], (
        "the pool is stored page-major", pool.shape)
    copies = _pool_copies(compiled.as_text(), shard_shape)
    assert not copies, (
        f"{len(copies)} whole-pool copies in the program", copies[:4])
    # 2 layers x (K, V) x bf16
    one_pool = 2 * 2 * math.prod(shard_shape) * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < one_pool, (
        "the program's temporaries hold a second pool", temp, one_pool)
    # a dense model's step is XLA's from end to end: a custom call
    # between the scatter and the gather breaks the pool's loop-carry
    # aliasing and buys a pool copy a step (PERF.md section 6, PR 30)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.fixture(scope="module")
def step_program(one_chip):
    """get(name, kv_heads): that cell's step program compiled for one
    described chip, and one layer's pool; compiled once a module."""
    @functools.lru_cache(maxsize=None)
    def get(name, kv_heads):
        return _compile_step(
            name, _cell_cfg(kv_heads), one_chip,
            lambda params: jax.tree_util.tree_map(lambda _: one_chip,
                                                  params),
            one_chip)
    return get


@pytest.mark.parametrize("kv_heads", [8, 16], ids=["mistral", "olmoe"])
@pytest.mark.parametrize("name", ["decode", "prefill", "verify"])
def test_step_programs_copy_no_pool(step_program, name, kv_heads):
    compiled, pool = step_program(name, kv_heads)
    _assert_pool_stays(compiled, pool, pool.shape)


def _f32_blocks(text, kv_heads):
    """Float32 tensors of the compiled program as large as one gathered
    block of the window loop (32 rows x 512 tokens x KH x 128) that
    carry its KV-head and head dimensions, in whatever order (the
    verify call's logits, f32[32,5,32768], are large too and are no
    block; the prefill call's are [4, 32768] since PR 54)."""
    block = SLOTS * 512 * kv_heads * 128
    found = []
    for m in re.finditer(r"= f32\[([0-9,]+)\]", text):
        dims = [int(d) for d in m.group(1).split(",")]
        if math.prod(dims) >= block and {kv_heads, 128} <= set(dims):
            found.append(m.group(0))
    return found


# decode over 8 KV heads, and every prefill and verify call, presents a
# KV head's query group as rep x T >= 2 rows: a matrix product already.
# Decode over 16 KV heads (one query head each, T = 1) is the case
# ops/paged_attention.py pads to two rows: without that, both
# contractions fall to the vector unit behind a float32 copy of each
# gathered block (20 such tensors in this program; PERF.md section 6,
# PR 33)
@pytest.mark.parametrize("name,kv_heads", [
    ("decode", 8), ("decode", 16), ("prefill", 16), ("verify", 16)])
def test_block_loop_contracts_on_the_matrix_unit(step_program, name,
                                                 kv_heads):
    text = step_program(name, kv_heads)[0].as_text()
    blocks = _f32_blocks(text, kv_heads)
    assert not blocks, (
        f"{len(blocks)} float32 tensors of a gathered block's size",
        sorted(set(blocks)))
    for scope, spec in (("attn_scores", "btkrd,bskd->bkrts"),
                        ("attn_pv", "bkrts,bskd->bkrtd")):
        convs = re.findall(
            r" convolution\([^\n]*" + scope + "/" + spec, text)
        # one a layer
        assert len(convs) >= 2, (scope, len(convs))


# the chunked-prefill program samples ONE position a row, so it asks
# the model for that position's logits alone (``logits_at``): the head
# contracts [B, dim], and no [B, T, V] value exists in the program
# (1,024 positions went through the head of a [4, 256] call, 4 were
# read: PERF.md section 6, PR 54). The verify program reads every
# position's argmax and keeps them all.
_TINIES = {"llama": "llama_tiny", "mixtral": "mixtral_tiny",
           "axk1": "axk1_tiny", "kimi_linear": "kimi_linear_tiny",
           "laguna": "laguna_tiny", "mellum": "mellum_tiny",
           "olmo_hybrid": "olmo_hybrid_tiny", "ouro": "ouro_tiny",
           "solar_open2": "solar_open2_tiny"}


def _head_results(text):
    """Result shapes of the ``dot_general``s under the ``head`` scope of
    a lowered program (``as_text(debug_info=True)``: an operation names
    its location by reference)."""
    head = set(re.findall(
        r'^(#loc\d+) = loc\("[^"]*/head/dot_general"', text, re.M))
    found = []
    for m in re.finditer(r"stablehlo\.dot_general[^\n]*-> tensor<([0-9x]+)"
                         r"xf32>[^\n]*loc\((#loc\d+)\)", text):
        if m.group(2) in head:
            found.append(tuple(int(d) for d in m.group(1).split("x")))
    return found


@pytest.mark.parametrize("family", sorted(_TINIES))
def test_prefill_applies_the_head_to_one_position_a_row(family):
    import importlib
    from ray_tpu.models.kv_cache import (init_kv_pool, sliding_ring_len,
                                         state_bytes_per_slot)
    from ray_tpu.serve import step_programs
    tiny = getattr(importlib.import_module(f"ray_tpu.models.{family}"),
                   _TINIES[family])
    B, T, V, S = 4, 16, 424, 6          # no other extent of a toy is 424
    cfg = tiny(dtype=jnp.float32, vocab_size=V)
    model = cfg.model_class(cfg)
    params = {"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]}
    ring = sliding_ring_len(cfg, 8, T)
    pages = jax.eval_shape(
        lambda: init_kv_pool(cfg, 17, 8, n_slots=S, ring_len=ring))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    arr, i32 = jax.ShapeDtypeStruct, jnp.int32
    slots = (arr((B,), i32),) if state_bytes_per_slot(cfg, ring) else ()
    prefill = step_programs._jit_prefill(model, 0.0, B, False, None).lower(
        params, pages, arr((B, T), i32), arr((B,), i32), arr((B,), i32),
        arr((B, 8), i32), key, *slots).as_text(debug_info=True)
    assert _head_results(prefill) == [(B, V)]
    assert f"tensor<{B}x{T}x{V}xf32>" not in prefill
    assert f"tensor<{B}x{V}xf32>" in prefill
    verify = step_programs._jit_verify(model, None).lower(
        params, pages, arr((S, 5), i32), arr((S,), i32),
        arr((S, 8), i32)).as_text(debug_info=True)
    assert _head_results(verify) == [(S, 5, V)]


def test_the_cells_prefill_program_holds_no_logits_of_the_call(
        step_program):
    """At a serving cell's shape, compiled for the chip: the [4, 256]
    call's float32 logits (134 MB at Mistral's vocabulary, 403 MB at
    Mellum 2's) are gone from the program, the four rows' are there."""
    text = step_program("prefill", 8)[0].as_text()
    assert "f32[4,256,32768]" not in text
    assert "f32[4,32768]" in text
    assert "f32[32,5,32768]" in step_program("verify", 8)[0].as_text()


# a decode step over a bfloat16 K/V pool on one TPU attends through the
# Pallas kernel of ops/paged_decode_attention.py (the tests above run
# the rule on the CPU, where it keeps the loop): one call a layer with
# the pool going in as it is stored, the visits' schedule computed once
# a step and shared by the layers, and the chunked prefill (256 x H
# query rows against a page are no visit's scores) and the verify
# (several queries a row under the causal mask) left to the loop
# an operation of layer %d's ``paged_decode_attention`` call that is
# neither the kernel nor a reshape of its operands: the visits' schedule
# (whatever it is made of: a compiled fusion is named after ONE of its
# operations, and the schedule's cumulative sum no longer names any)
_SCHEDULE_OF_LAYER = (r"layers_%d/attention/attn_scores/"
                      r"jit\(paged_decode_attention\)/"
                      r"(?!paged_decode/|reshape\")")


@pytest.mark.parametrize("kv_heads", [8, 16], ids=["mistral", "olmoe"])
def test_decode_attends_in_one_kernel_a_layer(one_chip, monkeypatch,
                                              kv_heads):
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    # the programs are cached by (model, knobs): the cases above traced
    # them with the loop, and no later one may find the kernel's
    for name in ("_jit_decode", "_jit_prefill", "_jit_verify"):
        monkeypatch.setattr(step_programs, name,
                            getattr(step_programs, name).__wrapped__)
    cfg = _cell_cfg(kv_heads)

    def compiled(name):
        return _compile_step(
            name, cfg, one_chip,
            lambda params: jax.tree_util.tree_map(lambda _: one_chip,
                                                  params), one_chip)
    decode, pool = compiled("decode")
    text = decode.as_text()
    calls = re.findall(
        r"custom-call\([^\n]*/attn_scores/[^\n]*paged_decode[^\n]*", text)
    assert len(calls) == cfg.n_layers, len(calls)
    flat = "bf16[%d,%d,128]" % (N_PAGES, PAGE * kv_heads)
    pages = pd.pages_per_visit(cfg.n_heads, PAGE, kv_heads,
                               cfg.max_seq_len // PAGE)
    for call in calls:
        # K's pages and V's, each a free view of the pool as it lies
        assert call.count(flat) == 2 * pages, call[:400]
    assert not _pool_copies(text, pool.shape)
    assert not _pool_copies(text, (N_PAGES, PAGE * kv_heads, 128))
    assert "kv_gather" not in text and "attn_pv" not in text
    # every layer's pool is the program's argument and its result
    one_pool = 2 * 2 * math.prod(pool.shape) * 2
    assert decode.memory_analysis().alias_size_in_bytes >= one_pool
    assert decode.memory_analysis().temp_size_in_bytes < one_pool
    # the schedule is the first layer's alone: the others share it,
    # and hold nothing of the call but the kernel
    assert re.search(_SCHEDULE_OF_LAYER % 0, text)
    assert not re.search(_SCHEDULE_OF_LAYER % 1, text)
    for name in ("prefill", "verify"):
        assert "tpu_custom_call" not in compiled(name)[0].as_text()


# one layer-step at each serving cell's shape: (rows, heads, KV heads,
# table columns), KV heads None over LATENT pages [64, 640] whose value
# is their first 512 columns (A.X-K1: a table of 16,384 tokens, its
# riders at 8,192-8,704; Kimi-Linear at its 128 slots)
# and the widest table the rule hands the kernel (its schedule goes in
# by scalar prefetch: half of the chip's 1 MiB of scalar memory); T
# queries a row under a block mask of L (1: causal): SDAR's block of
# four at its 128 slots and a table as wide as its published context
# (394 KB of schedule), and a verify of four drafts at Mistral's shape
# (the rule leaves it to the loop; the kernel still builds for it)
@pytest.mark.parametrize("B,H,KH,max_pages,T,L", [
    (16, 16, 16, 64, 1, 1), (32, 32, 8, 64, 1, 1), (32, 32, 4, 256, 1, 1),
    (32, 64, 8, 64, 1, 1), (32, 32, 4, 3584, 1, 1),
    (32, 64, None, 256, 1, 1), (128, 32, None, 64, 1, 1),
    (128, 32, 4, 512, 4, 4), (32, 32, 8, 64, 5, 1)],
    ids=["ouro", "mistral", "mellum2", "solar_open2", "widest_table",
         "axk1_latent", "kimi_linear_latent", "sdar_block",
         "mistral_verify"])
def test_paged_decode_kernel_compiles(one_chip, B, H, KH, max_pages, T, L):
    from ray_tpu.ops import paged_decode_attention as pd
    assert pd.schedule_bytes(
        B, max_pages, pd.pages_per_visit(T * H, PAGE, KH or 1, max_pages)
    ) <= pd._SCHEDULE_BYTES
    table = [((B, max_pages), jnp.int32), ((B,), jnp.int32)]
    if KH is None:
        pool = ((1025, PAGE, 640), jnp.bfloat16)
        compiled = _compile(
            lambda q, pk, pt, pos: pd.paged_decode_attention(
                q, pk, None, pt, pos, softmax_scale=0.1309, value_dim=512),
            one_chip, ((B, 1, H, 640), jnp.bfloat16), pool, *table)
        assert "bf16[%d,1,%d,512]" % (B, H) in compiled.as_text()
    else:
        pool = ((1025, PAGE, KH, 128), jnp.bfloat16)
        compiled = _compile(
            lambda *a: pd.paged_decode_attention(*a, softmax_scale=0.088,
                                                 block_len=L),
            one_chip, ((B, T, H, 128), jnp.bfloat16), pool, pool, *table)
        assert "bf16[%d,1,%d,128]" % (B, T * H) in compiled.as_text()
        assert not _pool_copies(compiled.as_text(),
                                (1025, PAGE * KH, 128))
    assert not _pool_copies(compiled.as_text(), pool[0])


def test_decode_copies_no_pool_shard_under_tp4(topo, monkeypatch):
    """Tensor-parallel over the four described chips: each chip holds
    2 of Mistral's 8 KV heads of every page, and copies none of it.
    Traced as on a TPU (the rule's backend steered; its mesh is the
    program's own, serve/step_programs.py ``ambient_mesh``): GSPMD
    cannot partition a Mosaic kernel, so the program holds the loop
    and no custom call."""
    from ray_tpu.mesh.sharding import infer_sharding
    from ray_tpu.serve import step_programs
    from ray_tpu.serve.sharding import EngineSharding
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(step_programs, "_jit_decode",
                        step_programs._jit_decode.__wrapped__)
    cfg = _cell_cfg(8)
    try:
        sh = EngineSharding.build(cfg, tp=4, devices=topo.devices)
    except Exception as e:
        pytest.skip(f"no tensor mesh over the described chips: {e}")
    compiled, pool = _compile_step(
        "decode", cfg, sh.kv_sharding,
        lambda params: infer_sharding(params, sh.rules, sh.mesh),
        sh.replicated, mesh=sh.mesh)
    _assert_pool_stays(compiled, pool,
                       sh.kv_sharding.shard_shape(pool.shape))


# ---------------------------------------------------------------
# A hybrid's step programs at the cell's widths (Solar-Open2: one GQA
# layer and three delta-rule layers of 64 heads of 128, 32 slots, 1,025
# pages; 8 of 320 experts held keeps the compile short): they fit, keep
# the recurrent state where it lies as they keep the pool, and hold the
# chunked delta rule's [C, C, d] products in no temporary.

def _hybrid_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.solar_open2 import SolarOpen2, solar_open2_250b
    from ray_tpu.serve import step_programs
    cfg = solar_open2_250b(n_layers=4, vocab_size=24576, max_seq_len=4096,
                           experts_held=(0, 8), param_dtype=jnp.bfloat16)
    model = SolarOpen2(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, 1025, PAGE, n_slots=SLOTS)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, SLOTS, False, None)
        rest = [table, ((SLOTS,), i32), ((SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_hybrid_step_programs_keep_the_state_in_place(one_chip,
                                                      monkeypatch, name):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import linear_attention as la
    # both ops ask the attached backend, which is the CPU here
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    monkeypatch.setattr(la, "_on_one_tpu", lambda: True)
    compiled = _hybrid_step(name, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # the experts' grouped matmul
    state = r"f32\[32,64,128,128\]"
    copies = re.findall(r"= " + state + r"(?:\{[^}]*\})? copy\(", text)
    assert not copies, f"{len(copies)} whole-state copies in {name}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_state = 32 * 64 * 128 * 128 * 4       # a layer's, 128 MiB
    if name == "decode":
        # three delta-rule layers of four, each ONE kernel over its
        # state in place: the step's own temporaries and no state's
        # worth (two passes and a write of XLA's took up to one more)
        _assert_one_kernel_a_layer(text, (32, 64, 128, 128), 3)
        assert temp < one_state, (name, temp)
    else:
        # the chunk's [4, 64, 64, 64, 128] float32 products alone
        # would be 512 MiB each
        assert "kda_step" not in text
        assert temp < 4 * one_state, (name, temp)


# ---------------------------------------------------------------
# A latent-attention model's step programs at the cell's widths
# (A.X-K1: 64 heads over a 576-wide latent entry, 32 slots, 4,353 pages
# of 64, a page table 256 wide; the dense layer and one mixture layer
# with 2 of 192 experts held keep the compile short): the pool stays
# page-major where it lies, and the absorbed contractions are matrix
# products over the gathered block as it is stored.

def _latent_step(name, one_chip):
    from ray_tpu.models.axk1 import AXK1, axk1
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    cfg = axk1(n_layers=2, vocab_size=20480, max_seq_len=16384,
               experts_held=(0, 2), param_dtype=jnp.bfloat16)
    model = AXK1(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(lambda: init_kv_pool(cfg, 4353, PAGE)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, SLOTS, False, None)
        rest = [table, ((SLOTS,), i32), ((SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_latent_step_programs_keep_the_pool_page_major(one_chip,
                                                       monkeypatch, name):
    from ray_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    compiled = _latent_step(name, one_chip)
    text = compiled.as_text()
    # the entry is stored in whole 128-lane tiles (640 for 576): as
    # [n_pages, 64, 1, 576] or [n_pages, 64, 576] the compiler's
    # compact layout makes n_pages the minor axis and every program
    # copies each layer's pool in and out (PERF.md section 6, PR 34)
    pool = r"bf16\[4353,64,640\]"
    entry = re.search(pool + r"(\{[^}]*\}) parameter", text)
    assert entry and entry.group(1).startswith("{2,1,0"), entry
    copies = re.findall(r"= " + pool + r"(?:\{[^}]*\})? copy\(", text)
    assert not copies, f"{len(copies)} whole-pool copies in {name}"
    one_pool = 4353 * 64 * 640 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (one_pool if name == "decode" else 2 * one_pool), (
        name, temp)
    if name == "decode":
        # no float32 copy of a gathered block [32, 512, 640]
        big = [m.group(0) for m in re.finditer(r"= f32\[([0-9,]+)\]", text)
               if math.prod(int(d) for d in m.group(1).split(","))
               >= SLOTS * 512 * 640]
        assert not big, sorted(set(big))
    for scope, spec in (("attn_scores", "btkrd,bskd->bkrts"),
                        ("attn_pv", "bkrts,bskd->bkrtd")):
        convs = re.findall(
            r" convolution\([^\n]*" + scope + "/" + spec, text)
        assert len(convs) >= 2, (name, scope, len(convs))    # one a layer


# ---------------------------------------------------------------
# A model whose latent layers CHOOSE their entries, at its cell's widths
# (DeepSeek-V3.2: 128 heads over a 640-wide latent entry, 64 index heads
# over a 128-wide index key in pages of their own, 4,353 pages of 64, a
# page table 256 wide; the dense layer and one mixture layer with 8 of
# 256 experts held keep the compile short): both pools stay where they
# lie in both programs, and both attend through the latent kernel with
# the choice as its mask (a decode step's query a tile of one token).

def _indexed_step(name, one_chip):
    from ray_tpu.models.deepseek_v32 import DeepSeekV32, deepseek_v32
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    cfg = deepseek_v32(n_layers=2, first_k_dense=1, vocab_size=16160,
                       max_seq_len=16384, experts_held=(0, 8),
                       param_dtype=jnp.bfloat16)
    model = DeepSeekV32(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(lambda: init_kv_pool(cfg, 4353, PAGE)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode.__wrapped__(
            model, 0.0, 128, SLOTS, False, None)
        rest = [table, ((SLOTS,), i32), ((SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill.__wrapped__(
            model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_chosen_entries_programs_keep_both_pools_in_place(one_chip,
                                                          monkeypatch,
                                                          name):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import latent_window_attention as lw
    for mod, attr in ((gm, "_use_kernel"), (lw, "_on_one_tpu")):
        monkeypatch.setattr(mod, attr, lambda: True)
    compiled = _indexed_step(name, one_chip)
    text = compiled.as_text()
    # two pools a layer, each as it is declared: page-major, whole tiles
    for pool in ("4353,64,640", "4353,64,128"):
        entry = re.search(r"bf16\[%s\](\{[^}]*\}) parameter" % pool, text)
        assert entry and entry.group(1).startswith("{2,1,0"), (pool, entry)
        copies = re.findall(
            r"= bf16\[%s\](?:\{[^}]*\})? copy\(" % pool, text)
        assert not copies, f"{len(copies)} copies of a {pool} pool in {name}"
    assert text.count("may-alias") >= 4, name     # both pools, two layers
    kernels = re.findall(
        r"custom-call\([^\n]*/dsa_attn/latent_window[^\n]*", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    # the masked walk is the latent kernel, a call a layer, with the
    # choice ([4, 256, 16384] a chunk, [32, 1, 16384] a step) as one
    # more operand; nothing is sorted or gathered
    assert len(kernels) == 2, len(kernels)
    choice = "bf16[4,256,16384]" if name == "prefill" else "bf16[32,1,16384]"
    for call in kernels:
        assert call.count("bf16[4353,64,640]{2,1,0}") == 8, call[:300]
        assert choice in call, call[:300]
    assert not re.search(r" sort\([^\n]*/dsa_", text)
    assert "bf16[32,2048,640]" not in text
    if name == "prefill":
        # nothing of a block's float32 scores [4, 128, 256, 512] is left
        block = 4 * 128 * 256 * 512
        scores = sorted({m.group(0) for m in re.finditer(
            r"= f32\[([0-9,]+)\]", text)
            if math.prod(int(d) for d in m.group(1).split(",")) == block})
        assert not scores, scores
        assert temp < 600 << 20, temp
    else:
        assert temp < 400 << 20, temp


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_choice_is_one_kernel_a_layer(one_chip, monkeypatch, name):
    """Both step programs of the cell, with the selector's rule steered
    as the chip answers it: ONE ``topk_select`` call a layer under
    ``dsa_topk`` (a step's [32, 16384] scores, a call's [1024, 16384],
    the mask in bfloat16 as the walk's kernel takes it), and nothing
    under that scope loops: no ``while`` whose body counts, no
    conditional over four widths."""
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import latent_window_attention as lw
    from ray_tpu.ops import sparse_latent_attention as sp
    for mod, attr in ((gm, "_use_kernel"), (lw, "_on_one_tpu"),
                      (sp, "_on_one_tpu")):
        monkeypatch.setattr(mod, attr, lambda: True)
    text = _indexed_step(name, one_chip).as_text()
    chosen = re.findall(
        r"custom-call\([^\n]*/dsa_topk/topk_select[^\n]*", text)
    assert len(chosen) == 2, len(chosen)               # two layers
    rows = 1024 if name == "prefill" else 32
    for call in chosen:
        assert f"f32[{rows},16384]" in call, call[:300]
    assert re.search(r"bf16\[%d,16384\][^\n]*custom-call\([^\n]*"
                     r"/dsa_topk/topk_select" % rows, text)
    looped = re.findall(
        r"(?:while|conditional)\([^\n]*/dsa_topk/[^\n]*", text)
    assert not looped, looped[:2]
    # the walk's kernel takes the mask as it comes
    walks = re.findall(
        r"custom-call\([^\n]*/dsa_attn/latent_window[^\n]*", text)
    assert len(walks) == 2, len(walks)


@pytest.mark.parametrize("rows,width,k", [
    (32, 12288, 2048), (1024, 8192, 2048), (32, 16384, 2048),
    (1024, 16384, 2048), (48, 1152, 24)],
    ids=["step_walked", "call_walked", "step", "call", "off_the_cells"])
def test_topk_select_compiles(one_chip, rows, width, k):
    """The selection kernel alone for the described chip: the cell's
    two shapes at the widths its walks end in and at the table's, and
    one off them (tiles of 16 rows, chunks of 128 columns)."""
    from ray_tpu.ops import sparse_latent_attention as sp
    compiled = _compile(
        lambda scores, ends: sp.topk_select(scores, k, ends), one_chip,
        ((rows, width), jnp.float32), ((rows,), jnp.int32))
    assert "topk_select" in compiled.as_text()


# ---------------------------------------------------------------
# A model of recurrent and latent layers ONLY at its cell's widths and
# ITS slots (Kimi-Linear: 32 delta-rule heads of 128 over 128 SLOTS, a
# 576-wide latent entry, 4,609 pages of 64, a page table 64 wide; one
# period of four layers, the first dense, with 2 of 256 experts held
# keeps the compile short): both kinds of state stay where they lie in
# one program, at four times the slots of the other cells.

def _no_kv_step(name, one_chip):
    from ray_tpu.models.kimi_linear import KimiLinear, kimi_linear_48b
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    S = 128
    cfg = kimi_linear_48b(n_layers=4, vocab_size=40960, max_seq_len=4096,
                          experts_held=(0, 2), param_dtype=jnp.bfloat16)
    model = KimiLinear(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, 4609, PAGE, n_slots=S)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((S, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        rest = [table, ((S,), i32), ((S,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_a_model_with_no_kv_layer_keeps_both_kinds_in_place(
        one_chip, monkeypatch, name):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import linear_attention as la
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    monkeypatch.setattr(la, "_on_one_tpu", lambda: True)
    compiled = _no_kv_step(name, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # the experts' grouped matmul
    state = r"f32\[128,32,128,128\]"
    copies = re.findall(r"= " + state + r"(?:\{[^}]*\})? copy\(", text)
    assert not copies, f"{len(copies)} whole-state copies in {name}"
    pool = r"bf16\[4609,64,640\]"
    entry = re.search(pool + r"(\{[^}]*\}) parameter", text)
    assert entry and entry.group(1).startswith("{2,1,0"), entry
    copies = re.findall(r"= " + pool + r"(?:\{[^}]*\})? copy\(", text)
    assert not copies, f"{len(copies)} whole-pool copies in {name}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_state = 128 * 32 * 128 * 128 * 4      # a layer's, 256 MiB
    if name == "decode":
        # three delta-rule layers of four, each one kernel in place
        _assert_one_kernel_a_layer(text, (128, 32, 128, 128), 3)
        assert temp < one_state, (name, temp)
    else:
        # four rows' chunks, whatever the slots
        assert "kda_step" not in text
        assert temp < 2 * one_state, (name, temp)


# ---------------------------------------------------------------
# Both latent families' [4, 256] prefill program with the chunk's
# attention over latent pages as ONE kernel a latent layer
# (ops/latent_window_attention.py), which a TPU outside any mesh
# chooses: nothing of a block's float32 scores or accumulator
# ([4, 1, heads, 256, 512] in the loop's form: 134 MB at 64 heads) is
# among the program's temporaries or a loop's carry, and the pool is
# read by page id where it lies.

def _assert_prefill_attends_in_the_kernel(compiled, heads, layers, pool,
                                          temp_limit):
    text = compiled.as_text()
    calls = re.findall(
        r"custom-call\([^\n]*/attn_scores/latent_window[^\n]*", text)
    assert len(calls) == layers, (len(calls), layers)
    for call in calls:
        # the pool goes in as it is stored, a page an operand block
        assert call.count("bf16[%s]{2,1,0}" % pool) == 8, call[:400]
    block = 4 * heads * 256 * 512
    scores = sorted({m.group(0) for m in re.finditer(
        r"= f32\[([0-9,]+)\]", text)
        if math.prod(int(d) for d in m.group(1).split(",")) == block})
    assert not scores, scores
    assert "attn_pv" not in text and "bskd->bkrts" not in text
    entry = re.search(r"bf16\[%s\](\{[^}]*\}) parameter" % pool, text)
    assert entry and entry.group(1).startswith("{2,1,0"), entry
    copies = re.findall(
        r"= bf16\[%s\](?:\{[^}]*\})? copy\(" % pool, text)
    assert not copies, f"{len(copies)} whole-pool copies"
    # every layer's pool is the program's argument and its result
    assert text.count("may-alias") >= layers, text[:400]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_limit, temp


@pytest.mark.parametrize("family", ["axk1", "kimi_linear"])
def test_latent_prefill_attends_in_one_kernel_a_layer(one_chip,
                                                      monkeypatch, family):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import latent_window_attention as lw
    from ray_tpu.ops import linear_attention as la
    from ray_tpu.serve import step_programs
    for mod, name in ((gm, "_use_kernel"), (la, "_on_one_tpu"),
                      (lw, "_on_one_tpu")):
        monkeypatch.setattr(mod, name, lambda: True)
    # the programs are cached by (model, knobs): the cases above traced
    # these models' with the loop, and no later one may find the kernel's
    monkeypatch.setattr(step_programs, "_jit_prefill",
                        step_programs._jit_prefill.__wrapped__)
    if family == "axk1":
        # two latent layers; the loop's form takes 526 MB of
        # temporaries here, this one 188
        _assert_prefill_attends_in_the_kernel(
            _latent_step("prefill", one_chip), 64, 2, "4353,64,640",
            256 << 20)
    else:
        # one latent layer of four; the rest is the delta-rule layers'
        _assert_prefill_attends_in_the_kernel(
            _no_kv_step("prefill", one_chip), 32, 1, "4609,64,640",
            2 * 128 * 32 * 128 * 128 * 4)


# Both latent families' decode program with a latent layer's attention
# as ONE ``paged_decode`` call (ops/paged_decode_attention.py), which a
# TPU outside any mesh chooses: the pool goes in as it is stored, 16
# operand blocks of one page each, nothing of the loop's gathered block
# [rows, 512, 640] is left, and the prefill program keeps its own
# kernel.
@pytest.mark.parametrize("family", ["axk1", "kimi_linear"])
def test_latent_decode_attends_in_one_kernel_a_layer(one_chip,
                                                     monkeypatch, family):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import linear_attention as la
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    for mod, name in ((gm, "_use_kernel"), (la, "_on_one_tpu"),
                      (pd, "_on_one_tpu")):
        monkeypatch.setattr(mod, name, lambda: True)
    # the programs are cached by (model, knobs): the cases above traced
    # these models' with the loop, and no later one may find the kernel's
    monkeypatch.setattr(step_programs, "_jit_decode",
                        step_programs._jit_decode.__wrapped__)
    if family == "axk1":
        compiled, rows, heads, layers, pool = (
            _latent_step("decode", one_chip), SLOTS, 64, 2, "4353,64,640")
    else:
        compiled, rows, heads, layers, pool = (
            _no_kv_step("decode", one_chip), 128, 32, 1, "4609,64,640")
    text = compiled.as_text()
    calls = [line for line in re.findall(
        r"[^\n]*custom-call\([^\n]*/attn_scores/[^\n]*paged_decode[^\n]*",
        text) if "tpu_custom_call" in line]
    assert len(calls) == layers, (len(calls), layers)
    for call in calls:
        assert call.count("bf16[%s]{2,1,0}" % pool) == 16, call[:400]
        assert "= bf16[%d,1,%d,512]" % (rows, heads) in call, call[:400]
    assert "kv_gather" not in text and "attn_pv" not in text
    assert "bskd->bkrts" not in text
    block = [m.group(0) for m in re.finditer(
        r"= (?:bf16|f32)\[%d,512,640\]" % rows, text)]
    assert not block, sorted(set(block))
    entry = re.search(r"bf16\[%s\](\{[^}]*\}) parameter" % pool, text)
    assert entry and entry.group(1).startswith("{2,1,0"), entry
    copies = re.findall(
        r"= bf16\[%s\](?:\{[^}]*\})? copy\(" % pool, text)
    assert not copies, f"{len(copies)} whole-pool copies"
    assert text.count("may-alias") >= layers, text[:400]


# ---------------------------------------------------------------
# A model whose layers keep caches of two sizes at its cell's widths
# (Mellum 2: 32 heads over 4 KV heads of 128, a window of 1,024 in a
# ring of 1,344 positions a slot, 32 slots, 4,481 pages of 64, a page
# table 256 wide; one period of four layers with 2 of 64 experts held
# keeps the compile short): the rings stay where they lie, head-major
# inside a slot, in both programs, and the full layer's pool is the
# other K/V models'.

def _two_sizes_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool, sliding_ring_len
    from ray_tpu.models.mellum import Mellum, mellum2_12b
    from ray_tpu.serve import step_programs
    cfg = mellum2_12b(n_layers=4, max_seq_len=16384, experts_held=(0, 2),
                      param_dtype=jnp.bfloat16)
    model = Mellum(cfg)
    ring_len = sliding_ring_len(cfg, PAGE, 256)
    assert ring_len == 1344

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, 4481, PAGE, n_slots=SLOTS,
                             ring_len=ring_len)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, SLOTS, False, None)
        rest = [table, ((SLOTS,), i32), ((SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_sliding_rings_and_pages_stay_in_place(one_chip, monkeypatch,
                                               name):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import ring_window_attention as rw
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    monkeypatch.setattr(rw, "_on_one_tpu", lambda: True)
    # the programs are cached by (model, knobs): no later case may find
    # the ones traced under this rule
    for cached in ("_jit_decode", "_jit_prefill"):
        monkeypatch.setattr(step_programs, cached,
                            getattr(step_programs, cached).__wrapped__)
    compiled = _two_sizes_step(name, one_chip)
    text = compiled.as_text()
    # three sliding layers' append and attention: ONE Pallas call each,
    # under the layer type's scope (the benchmark's readers find it
    # there), the rings its operands and its results
    calls = re.findall(
        r"%ring_window[.\d]* = \([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*", text)
    assert len(calls) == 3, len(calls)
    ring = r"bf16\[32,4,1344,128\]"
    for call in calls:
        assert "attn_sliding/jit(ring_window_kernel)/ring_window" in call
        assert len(re.findall(ring, call.split("custom-call(")[0])) == 2
    # a ring is [slots, KV heads, positions, head], in HBM as declared,
    # a parameter and a result of the program in place
    entry = re.findall(ring + r"(\{[^}]*\}) parameter", text)
    assert len(entry) >= 6 and all(e.startswith("{3,2,1,0") for e in entry)
    assert not any("S(1)" in e for e in entry), entry
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliased and aliased.group(1).count("may-alias") >= 8
    # and nothing passes over a whole ring: no copy or transpose of one
    # (PR 42: a ring declared position-major bought two a layer-step),
    # no round trip through the fast memory around the call (left to
    # choose, the compiler moved a ring that fits there whole and back:
    # 44 MB each way a layer-call), no scatter flattened into a pass
    # over a ring's 172,032 rows, no gathered copy of the rows' rings
    pool = r"bf16\[4481,64,4,128\]"
    for what, shape in (("ring", ring), ("pool", pool)):
        copies = re.findall(
            r"= " + shape + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
        assert not copies, f"{len(copies)} whole-{what} copies in {name}"
    moved = _pool_copies(text, (32, 4, 1344, 128)) + _pool_copies(
        text, (4481, 64, 4, 128))
    assert not moved, moved[:4]
    for op in (r"copy-start\(", r"copy-done\(", r"slice-start\(",
               r"scatter\("):
        assert not re.findall(
            r"(?:" + ring + r"|bf16\[172032,128\]|bf16\[4,4,1344,128\])"
            r"[^\n]* " + op, text), op
    assert "ring_append" not in text and "ring_scores" not in text
    assert "attn_full/kv_gather" in text       # the full layer's loop
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_ring = 2 * 32 * 4 * 1344 * 128 * 2       # a layer's k and v, 88 MB
    if name == "decode":
        # the step's own temporaries (the mixture's rows, the head's
        # logits, a gathered block of pages): not a second ring a layer
        assert temp < 2 * one_ring, (name, temp)
    else:
        # four rows of 256 queries: the block loop's float32 scores
        # [4, 32, 256, 512] and the mixture's 8,192 routed rows. (The
        # sliding layers' float32 scores [4, 32, 256, 1344], 176 MB a
        # layer, no longer stand; the program's peak was and is the
        # mixture's, 818 MB at this depth with or without them.)
        assert temp < 900 << 20, (name, temp)
    assert "f32[4,32,256,1344]" not in text and "f32[4,4,8,256,1344]" \
        not in text


def test_off_the_chip_rule_the_sliding_layers_keep_the_form(one_chip,
                                                            monkeypatch):
    """The rule read on the CPU (as every other test's engine does):
    the decode program holds no ``ring_window`` call, and the form's
    scopes stand where the kernel's would."""
    from ray_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    text = _two_sizes_step("decode", one_chip).as_text()
    assert "%ring_window" not in text
    for scope in ("attn_sliding/ring_append", "attn_sliding/ring_scores",
                  "attn_sliding/ring_pv"):
        assert scope in text, scope


# The sliding layers' kernel alone at the cell's two shapes, as a step
# program has it: a decode step inside the loop over steps (32 rows of
# one token), a prefill call on donated rings ([4, 256]). Interpret
# mode takes block shapes the chip's compiler refuses.
@pytest.mark.parametrize("B,T", [(32, 1), (4, 256)],
                         ids=["decode", "prefill"])
def test_ring_window_kernel_compiles(one_chip, B, T):
    from ray_tpu.ops import ring_window_attention as rw
    bf, i32 = jnp.bfloat16, jnp.int32
    ring = ((32, 4, 1344, 128), bf)
    shapes = [((B, T, 32, 128), bf), ((B, T, 4, 128), bf),
              ((B, T, 4, 128), bf), ring, ring, ((B,), i32),
              ((B, T), jnp.bool_)] + ([((B,), i32)] if T > 1 else [])
    assert rw.write_rows(1344, T) == 64
    assert rw.key_spans(32, 1344) == [(0, 1344)]
    assert len(rw.key_spans(2048, 1344)) == 6

    def call(q, k, v, rk, rv, pos, valid, slots=None):
        return rw.ring_window_kernel(q, k, v, rk, rv, slots, pos, valid,
                                     window=1024)

    def steps(q, k, v, rk, rv, pos, valid):
        def body(_, c):
            y, rk, rv, pos = c
            y, rk, rv = call(q + y, k, v, rk, rv, pos, valid)
            return y, rk, rv, pos + 1
        return jax.lax.fori_loop(0, 8, body, (q, rk, rv, pos))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(steps if T == 1 else call,
                       donate_argnums=(3, 4)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _pool_copies(text, (32, 4, 1344, 128))
    assert not re.findall(r"bf16\[32,4,1344,128\][^\n]* copy-start\(", text)


# ---------------------------------------------------------------
# A model whose QUERY differs by layer type over one K/V pool at its
# cell's widths (Laguna-XS.2: 48 query heads in a full layer, 64 in a
# sliding one, over 8 KV heads of 128 in both; a window of 512 in a ring
# of 832 positions a slot, 128 slots, 4,609 pages of 64, a page table 64
# wide; the dense layer and the first sliding layer with 2 of 256
# experts held keep the compile short): both programs build, the ring
# kernel at L = 832 and H = 64 (a group of 8) and the paged decode
# kernel at H = 48 (a group of 6) as the step programs build them, and
# rings and pool stay where they lie.

LAGUNA_SLOTS, LAGUNA_PAGES = 128, 4609


def _query_widths_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool, sliding_ring_len
    from ray_tpu.models.laguna import Laguna, laguna_xs2
    from ray_tpu.serve import step_programs
    cfg = laguna_xs2(n_layers=2, max_seq_len=4096, experts_held=(0, 2),
                     param_dtype=jnp.bfloat16)
    model = Laguna(cfg)
    ring_len = sliding_ring_len(cfg, PAGE, 256)
    assert ring_len == 832

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, LAGUNA_PAGES, PAGE, n_slots=LAGUNA_SLOTS,
                             ring_len=ring_len)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((LAGUNA_SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, LAGUNA_SLOTS,
                                       False, None)
        rest = [table, ((LAGUNA_SLOTS,), i32), ((LAGUNA_SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_two_query_widths_over_one_pool_build_both_kernels(
        one_chip, monkeypatch, name):
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.ops import ring_window_attention as rw
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    monkeypatch.setattr(rw, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    for cached in ("_jit_decode", "_jit_prefill"):
        monkeypatch.setattr(step_programs, cached,
                            getattr(step_programs, cached).__wrapped__)
    assert rw.write_rows(832, 1) == rw.write_rows(832, 256) == 64
    assert pd.pages_per_visit(48, PAGE, 8, 64) == 4
    compiled = _query_widths_step(name, one_chip)
    text = compiled.as_text()
    ring = r"bf16\[128,8,832,128\]"
    pool = r"bf16\[4609,64,8,128\]"
    # the sliding layer: ONE ring call under its scope, 64 query heads
    # a token (a chunk's rows 4 x 256), the rings its operands and its
    # results
    calls = re.findall(
        r"%ring_window[.\d]* = \([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*", text)
    assert len(calls) == 1, len(calls)
    assert "attn_sliding/jit(ring_window_kernel)/ring_window" in calls[0]
    assert len(re.findall(ring, calls[0].split("custom-call(")[0])) == 2
    # (a chunk's result comes back as [rows, tokens, 64 x 128])
    heads = "bf16[128,1,64,128]" if name == "decode" else "bf16[4,256,8192]"
    assert heads in calls[0], calls[0][:300]
    # the full layer: the decode kernel at 48 heads in jit_decode, the
    # block loop in jit_prefill
    paged = re.findall(
        r"%paged_decode[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*", text)
    assert len(paged) == (1 if name == "decode" else 0), len(paged)
    if paged:
        assert "attn_full" in paged[0]
        assert "bf16[128,1,48,128]" in paged[0], paged[0][:300]
        assert "kv_gather" not in text
    else:
        assert "attn_full/kv_gather" in text
    # the gate inside either type's scope, the dense layer, the shared
    # expert
    for scope in ("attn_sliding/attn_gate", "attn_full/attn_gate",
                  "layers_0/feed_forward", "moe_shared"):
        assert scope in text, scope
    # nothing passes over a whole ring or the whole pool
    for what, shape in (("ring", ring), ("pool", pool)):
        copies = re.findall(
            r"= " + shape + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
        assert not copies, f"{len(copies)} whole-{what} copies in {name}"
    assert not _pool_copies(text, (128, 8, 832, 128)) + _pool_copies(
        text, (4609, 64, 8, 128))
    for op in (r"copy-start\(", r"copy-done\(", r"slice-start\("):
        assert not re.findall(ring + r"[^\n]* " + op, text), op
    entry = re.findall(ring + r"(\{[^}]*\}) parameter", text)
    assert len(entry) >= 2 and all(e.startswith("{3,2,1,0") for e in entry)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (300 << 20 if name == "decode" else 900 << 20), temp


# ---------------------------------------------------------------
# A model whose layers run several times at its cell's sizes, WHOLE
# (Ouro-2.6B: 48 layers of 16 heads over 16 KV heads of 128 run 4 times,
# the whole vocabulary, 16 slots, 97 pages of 64 tokens whose pages
# carry the pass axis: 192 cache entries a token behind 48 layers of
# weights): test_step_programs_copy_no_pool's rule on the pool inside a
# loop over passes inside the loop over steps. The passes are ONE loop
# on the device, so the programs hold one copy of the stack, the pool is
# each program's argument and its result in place, and weights, pool
# and temporaries fit the chip together.

OURO_PAGES, OURO_SLOTS = 97, 16


def _looped_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.ouro import Ouro, ouro_2_6b
    from ray_tpu.serve import step_programs
    cfg = ouro_2_6b(max_seq_len=4096, param_dtype=jnp.bfloat16)
    model = Ouro(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, OURO_PAGES, PAGE)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((OURO_SLOTS, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, OURO_SLOTS, False,
                                       None)
        rest = [table, ((OURO_SLOTS,), i32), ((OURO_SLOTS,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_looped_step_programs_copy_no_pool_and_hold_one_stack(one_chip,
                                                              name):
    compiled = _looped_step(name, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text        # XLA's from end to end
    # a layer's pool as it is stored, and as a pass's layer sees it
    stored, seen = (OURO_PAGES, 4, PAGE, 16, 128), (OURO_PAGES * 4, PAGE,
                                                    16, 128)
    entry = re.findall(r"bf16\[%s\](\{[^}]*\}) parameter" % ",".join(
        str(d) for d in stored), text)
    assert len(entry) >= 96 and all(e.startswith("{4,3,2,1,0")
                                    for e in entry), entry[:2]
    for shape in (stored, seen):
        dims = ",".join(str(d) for d in shape)
        moved = _pool_copies(text, shape) + re.findall(
            r"= bf16\[" + dims + r"\](?:\{[^}]*\})? transpose\(", text)
        assert not moved, (len(moved), moved[:2])
    # ONE copy of the stack: each of the 48 layers' block loop appears
    # once, not once a pass
    for scope, spec in (("attn_scores", "btkrd,bskd->bkrts"),
                        ("attn_pv", "bkrts,bskd->bkrtd")):
        convs = re.findall(
            r" convolution\([^\n]*ut_pass/layers_\d+/attention/[^\n\"]*"
            + scope + "/" + spec, text)
        assert len(convs) == 48, (scope, len(convs))
    mem = compiled.memory_analysis()
    pool = 48 * 2 * math.prod(stored) * 2               # 9.76 GB
    weights = 2 * (48 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
                   + 2 * 49152 * 2048)                  # 5.34 GB
    # the pool is the program's argument and its result, in place
    assert mem.alias_size_in_bytes >= pool
    assert mem.argument_size_in_bytes >= pool + weights
    # no temporary of a layer's pool of one pass's size a layer, let
    # alone of the pool's: the decode program's are the projections'
    # layout copies, hoisted out of both loops (8.4 MB each, three a
    # layer), the prefill call's its logits and the block loop's scores
    temp = mem.temp_size_in_bytes
    assert temp < (1400 << 20 if name == "decode" else 512 << 20), temp
    # weights, pool and temporaries together fit a chip of 15.75 GiB
    held = (mem.argument_size_in_bytes + temp + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    assert held < 15.75 * 2 ** 30, held


def test_looped_decode_attends_in_one_kernel_a_layer(one_chip,
                                                     monkeypatch):
    """On one TPU the looped model's decode step attends through the
    Pallas kernel: 48 calls (one a layer of the one stack, not one a
    pass), each reading a pass's view of the pool as it is stored, the
    pool still the program's argument and its result, and the whole no
    larger than the chip."""
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(step_programs, "_jit_decode",
                        step_programs._jit_decode.__wrapped__)
    compiled = _looped_step("decode", one_chip)
    text = compiled.as_text()
    calls = re.findall(
        r"custom-call\([^\n]*ut_pass/layers_\d+/attention/attn_scores/"
        r"[^\n]*paged_decode[^\n]*", text)
    assert len(calls) == 48, len(calls)
    seen = "bf16[%d,%d,128]" % (OURO_PAGES * 4, PAGE * 16)
    assert all(call.count(seen) == 16 for call in calls), calls[0][:400]
    assert "kv_gather" not in text and "attn_pv" not in text
    for shape in ((OURO_PAGES, 4, PAGE, 16, 128),
                  (OURO_PAGES * 4, PAGE, 16, 128),
                  (OURO_PAGES * 4, PAGE * 16, 128)):
        dims = ",".join(str(d) for d in shape)
        moved = _pool_copies(text, shape) + re.findall(
            r"= bf16\[" + dims + r"\](?:\{[^}]*\})? transpose\(", text)
        assert not moved, (len(moved), moved[:2])
    mem = compiled.memory_analysis()
    pool = 48 * 2 * OURO_PAGES * 4 * PAGE * 16 * 128 * 2
    assert mem.alias_size_in_bytes >= pool
    temp = mem.temp_size_in_bytes
    assert temp < 1400 << 20, temp
    held = (mem.argument_size_in_bytes + temp + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    assert held < 15.75 * 2 ** 30, held


# ---------------------------------------------------------------
# A dense hybrid whose shapes are no whole tiles at its cell's widths
# and ITS slots (Olmo-Hybrid: 30 delta-rule heads of 96 x 192 over 96
# SLOTS, 30 K/V heads of 128 in 769 pages of 64, a page table 16 wide;
# one period of four layers keeps the compile short). A page stores 32
# head rows and the state two heads side by side ([96, 15, 96, 384]):
# declared so, both stay where they lie, the chip keeps what
# ``state_bytes_per_slot`` and ``kv_pool_page_bytes`` count, and on one
# TPU the full layer decodes through the paged-decode kernel (30 heads
# alone fail its rule of whole sublane tiles) and each delta-rule layer
# steps through the packed kernel.

OLMO_SLOTS, OLMO_PAGES = 96, 769


def _dense_hybrid_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.olmo_hybrid import OlmoHybrid, olmo_hybrid_7b
    from ray_tpu.serve import step_programs
    S = OLMO_SLOTS
    cfg = olmo_hybrid_7b(n_layers=4, max_seq_len=1024,
                         param_dtype=jnp.bfloat16)
    model = OlmoHybrid(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, OLMO_PAGES, PAGE, n_slots=S)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((S, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        rest = [table, ((S,), i32), ((S,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return cfg, fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_a_dense_hybrid_keeps_its_state_and_pages_as_the_chip_tiles_them(
        one_chip, monkeypatch, name):
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         state_bytes_per_slot)
    from ray_tpu.ops import linear_attention as la
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(la, "_on_one_tpu", lambda: True)
    for builder in ("_jit_decode", "_jit_prefill"):
        monkeypatch.setattr(step_programs, builder,
                            getattr(step_programs, builder).__wrapped__)
    cfg, compiled = _dense_hybrid_step(name, one_chip)
    text = compiled.as_text()
    assert cfg.recurrent_state_shape == (15, 96, 384)
    state = r"f32\[96,15,96,384\]"
    entry = re.search(state + r"(\{[^}]*\}) parameter", text)
    assert entry and entry.group(1).startswith("{3,2,1,0"), entry
    copies = re.findall(r"= " + state + r"(?:\{[^}]*\})? copy\(", text)
    assert not copies, f"{len(copies)} whole-state copies in {name}"
    assert cfg.kv_page_heads == 32
    shape = (OLMO_PAGES, PAGE, 32, 128)
    pool = r"bf16\[%s\]" % ",".join(str(d) for d in shape)
    entry = re.search(pool + r"(\{[^}]*\}) parameter", text)
    assert entry and entry.group(1).startswith("{3,2,1,0"), entry
    assert not _pool_copies(text, shape)
    # what the chip keeps is what load_report() counts: weights apart,
    # the program's arguments are the pool and the slots' state
    mem = compiled.memory_analysis()
    kept = (OLMO_PAGES * kv_pool_page_bytes(cfg, PAGE)
            + OLMO_SLOTS * state_bytes_per_slot(cfg))
    assert mem.alias_size_in_bytes == pytest.approx(kept, rel=1e-3)
    one_state = OLMO_SLOTS * 15 * 96 * 384 * 4      # a layer's, 203 MiB
    calls = [c for c in re.findall(
        r"custom-call\([^\n]*attn_scores/[^\n]*paged_decode[^\n]*", text)
        if 'custom_call_target="tpu_custom_call"' in c]
    steps = re.findall(
        r"custom-call\([^\n]*kda_recurrence/kda_step_packed[^\n]*", text)
    if name == "decode":
        assert len(calls) == 1, len(calls)      # the one full layer
        assert "kv_gather" not in text
        # three delta-rule layers, each ONE kernel over its state in
        # place, and nothing else over a whole state
        assert len(steps) == 3, len(steps)
        assert all("output_to_operand_aliasing={{1}: (6, {})}" in c
                   for c in steps), steps[0][:300]
        passes = _state_passes(text, (OLMO_SLOTS, 15, 96, 384))
        assert not passes, (len(passes), passes[:4])
        assert mem.temp_size_in_bytes < one_state, mem.temp_size_in_bytes
    else:
        assert not calls and not steps
        assert mem.temp_size_in_bytes < 4 * one_state, mem.temp_size_in_bytes


# ---- a model whose pages have readers beside their owner, and layers
# that keep nothing (models/phi4flash.py): at the published widths and
# phi4-mini-flash.reason-sat's pool, eight layers that keep every kind
# (state-space 0, 2, 4; sliding 1, 3; full 5; memory unit 6; cross 7).
# Ten K/V pairs of 128 are stored as 16 head rows a page: declared as
# 10 (or 12) the compiler keeps the pool positions-minor and copies it
# around every program (2.2-2.4 GB of temporaries, PERF.md section 6,
# PR 60).

PHI_SLOTS, PHI_PAGES = 64, 3073


def _shared_pages_step(name, one_chip):
    from ray_tpu.models.kv_cache import init_kv_pool, sliding_ring_len
    from ray_tpu.models.phi4flash import Phi4Flash, phi4_mini_flash
    from ray_tpu.serve import step_programs
    S = PHI_SLOTS
    cfg = phi4_mini_flash(n_layers=8, max_seq_len=3072,
                          param_dtype=jnp.bfloat16)
    model = Phi4Flash(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(lambda: init_kv_pool(
        cfg, PHI_PAGES, PAGE, n_slots=S,
        ring_len=sliding_ring_len(cfg, PAGE, 256))))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((S, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 128, S, False, None)
        rest = [table, ((S,), i32), ((S,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return cfg, fn.lower(params, pages, *rest).compile()


_PHI_PROGRAMS = {}


def _shared_pages_program(name, one_chip, monkeypatch):
    """``_shared_pages_step`` with every rule of its kernels steered as
    the chip answers it (the pages', the rings', the scan's); compiled
    once a module."""
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.ops import ring_window_attention as rw
    from ray_tpu.ops import selective_scan as ss
    from ray_tpu.serve import step_programs
    if name not in _PHI_PROGRAMS:
        for mod in (pd, rw, ss):
            monkeypatch.setattr(mod, "_on_one_tpu", lambda: True)
        for builder in ("_jit_decode", "_jit_prefill"):
            monkeypatch.setattr(step_programs, builder,
                                getattr(step_programs, builder).__wrapped__)
        _PHI_PROGRAMS[name] = _shared_pages_step(name, one_chip)
    return _PHI_PROGRAMS[name]


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_shared_pages_rings_and_states_stay_in_place(one_chip, monkeypatch,
                                                     name):
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         sliding_ring_len,
                                         state_bytes_per_slot)
    cfg, compiled = _shared_pages_program(name, one_chip, monkeypatch)
    text = compiled.as_text()
    ring = sliding_ring_len(cfg, PAGE, 256)
    assert (cfg.n_kv_heads, cfg.kv_page_heads, ring) == (10, 16, 832)
    # every kind as declared, none copied whole: the one layer's pages
    # (16 head rows), the rings (10 heads, major), the float32 states
    shapes = {"pool": ("bf16", (PHI_PAGES, PAGE, 16, 128)),
              "ring": ("bf16", (PHI_SLOTS, 10, ring, 128)),
              "state": ("f32", (PHI_SLOTS, 16, 5120))}
    for what, (dtype, shape) in shapes.items():
        pat = r"%s\[%s\]" % (dtype, ",".join(str(d) for d in shape))
        entry = re.search(pat + r"(\{[^}]*\}) parameter", text)
        order = ",".join(str(d) for d in reversed(range(len(shape))))
        assert entry and entry.group(1).startswith("{" + order), (what,
                                                                  entry)
        copies = re.findall(
            r"= " + pat + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
        assert not copies, f"{len(copies)} whole-{what} copies in {name}"
    assert not _pool_copies(text, shapes["pool"][1])
    # what the chip keeps is what load_report() counts: ONE layer's
    # pages, and a slot's rings and states; a borrowed layer and a
    # stateless one add nothing
    mem = compiled.memory_analysis()
    kept = (PHI_PAGES * kv_pool_page_bytes(cfg, PAGE)
            + PHI_SLOTS * state_bytes_per_slot(cfg, ring))
    assert kept == (PHI_PAGES * PAGE * 2 * 16 * 128 * 2
                    + PHI_SLOTS * (2 * 2 * 10 * ring * 128 * 2
                                   + 3 * (16 * 5120 * 4 + 3 * 5120 * 2)))
    assert mem.alias_size_in_bytes == pytest.approx(kept, rel=1e-3)
    paged = [c for c in re.findall(
        r"custom-call\([^\n]*attn_shared/attn_scores/[^\n]*paged_decode"
        r"[^\n]*", text) if 'custom_call_target="tpu_custom_call"' in c]
    rings = [c for c in re.findall(
        r"custom-call\([^\n]*attn_sliding/[^\n]*ring_window[^\n]*", text)
        if 'custom_call_target="tpu_custom_call"' in c]
    assert len(rings) == 2, len(rings)          # both sliding layers
    one_state = PHI_SLOTS * 16 * 5120 * 4       # a layer's, 21 MB
    if name == "decode":
        # the owner and its ONE reader here, each one kernel over the
        # same pages; no block loop
        assert len(paged) == 2, len(paged)
        assert "kv_gather" not in text
        assert mem.temp_size_in_bytes < 4 * one_state, \
            mem.temp_size_in_bytes
    else:
        # the owner (layer 5) attends all 256 positions a row through
        # the block loop, the ONE loop over pages of the program; its
        # one reader here (layer 7) keeps nothing, so the call reaches
        # it narrowed to the sampled position a row (models/kv_cache.py
        # ``sampled_only_from``): a decode step's shape, the decode
        # kernel at four rows over the pages as the owner left them.
        # 159 MB of temporaries (224 MB with both layers in the loop)
        assert len(paged) == 1 and "/layers_7/" in paged[0], paged
        loops = re.findall(
            r' while\([^\n]*op_name="([^"]*/attn_shared/while)"', text)
        assert len(loops) == 1 and "/layers_5/" in loops[0], loops
        assert "layers_5/attention/attn_shared/kv_gather" in text
        assert "layers_7/attention/attn_shared/kv_gather" not in text
        assert mem.temp_size_in_bytes < 10 * one_state, \
            mem.temp_size_in_bytes


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_scan_is_one_kernel_a_state_space_layer(one_chip, monkeypatch,
                                                    name):
    """Phi-4-mini-flash's programs with the scan's rule steered as the
    chip answers it: the ``[4, 256]`` prefill call holds ONE
    ``selective_scan`` call a state-space layer (three of the eight
    layers here, nine of the cell's 32) under ``ssm_scan``, over the
    chunk as the model has it (``u`` in bfloat16, ``delta`` and ``y``
    in float32, nothing time-major), and nothing under that scope
    loops; a decode step is ``ssm_step``'s and holds none."""
    cfg, compiled = _shared_pages_program(name, one_chip, monkeypatch)
    text = compiled.as_text()
    scans = re.findall(
        r"custom-call\([^\n]*/ssm_scan/[^\n]*selective_scan[^\n]*", text)
    looped = re.findall(r" while\([^\n]*/ssm_scan/[^\n]*", text)
    assert not looped, looped[:2]
    if name == "decode":
        assert not scans
        assert not re.findall(r"custom-call\([^\n]*/ssm_scan/[^\n]*", text)
        return
    assert cfg.mixers.count("ssm") == 3
    assert len(scans) == 3, len(scans)
    for call in scans:
        assert 'custom_call_target="tpu_custom_call"' in call
        for operand in ("bf16[4,256,5120]", "f32[4,256,5120]",
                        "f32[4,16,40,128]"):
            assert operand in call, (operand, call[:400])
    assert "f32[256,4,5120]" not in text and "f32[256,4,16,5120]" not in text


@pytest.mark.parametrize("T", [64, 128, 256])
def test_selective_scan_kernel_compiles(one_chip, T):
    """The chunk's kernel alone for the described chip at the widths
    ``.reason-sat``'s engine builds: four rows of ``T`` positions over
    5,120 channels of 16 states, operands in the model's types."""
    from ray_tpu.ops import selective_scan as ss
    B, C, N = 4, 5120, 16
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert ss.serves.__wrapped__ if hasattr(ss.serves, "__wrapped__") \
        else True
    compiled = _compile(
        ss.selective_scan, one_chip, ((B, T, C), bf16), ((B, T, C), f32),
        ((N, C), f32), ((B, T, N), bf16), ((B, T, N), bf16), ((C,), f32),
        ((B, N, C), f32), ((B, T), jnp.bool_))
    assert "selective_scan" in compiled.as_text()


# -------------------------- a model that decodes by blocks (PR 63): SDAR

SDAR_PAGES, SDAR_SLOTS = 4609, 128


def _blocks_step(name, one_chip):
    """The block program and the prefill program of
    ``sdar-30b-d6.gen-sat`` (six layers of the published widths, all 128
    experts, 128 slots over 4,609 pages, a table as wide as the
    published context) for the described chip."""
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.models.sdar import Sdar, sdar_30b_a3b
    from ray_tpu.serve import step_programs
    S = SDAR_SLOTS
    cfg = sdar_30b_a3b(n_layers=6, param_dtype=jnp.bfloat16)
    model = Sdar(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(
        lambda: init_kv_pool(cfg, SDAR_PAGES, PAGE)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    width = cfg.max_seq_len // PAGE
    if name == "decode":
        fn = step_programs._jit_decode_blocks(model, 0.0, 128, S, None,
                                              None)
        state = placed(jax.eval_shape(
            lambda: step_programs.block_state(S, cfg.block_length)))
        rest = [jax.ShapeDtypeStruct((S, width), i32, sharding=one_chip),
                state, jax.ShapeDtypeStruct(key.shape, key.dtype,
                                            sharding=one_chip),
                jax.ShapeDtypeStruct((), i32, sharding=one_chip)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in (((4, 256), i32), ((4,), i32), ((4,), i32),
                             ((4, width), i32), (key.shape, key.dtype))]
    return cfg, fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_block_step_programs_copy_no_pool_and_fit_the_chip(one_chip, name,
                                                           monkeypatch):
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    # the rule as one TPU reads it; what is traced so is no other test's
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    for fn in ("_jit_decode_blocks", "_jit_prefill"):
        monkeypatch.setattr(step_programs, fn,
                            getattr(step_programs, fn).__wrapped__)
    cfg, compiled = _blocks_step(name, one_chip)
    text = compiled.as_text()
    stored = (SDAR_PAGES, PAGE, 4, 128)
    assert not _pool_copies(text, stored)
    calls = re.findall(
        r"custom-call\([^\n]*layers_\d+/attention/attn_scores/[^\n]*"
        r"paged_decode[^\n]*", text)
    convs = {scope: re.findall(
        r" convolution\([^\n]*layers_\d+/attention/[^\n\"]*"
        + scope + "/" + spec, text)
        for scope, spec in (("attn_scores", "btkrd,bskd->bkrts"),
                            ("attn_pv", "bkrts,bskd->bkrtd"))}
    # a gathered block of K or of V: rows x 8 pages as they are stored
    gathers = re.findall(r"bf16\[\d+,64,4,128\]\S* (?:gather|fusion)\(",
                         text.replace("bf16[%d,64,4,128]" % SDAR_PAGES, ""))
    if name == "decode":
        # a block's T = 4 queries a rider under the block mask are the
        # decode kernel's: one call a layer under the scope the
        # benchmark's reader sums, each rider's own pages fetched where
        # they lie (4 pages a visit of K and of V), and nothing of the
        # loop: no gathered block, neither of its contractions
        assert len(calls) == cfg.n_layers, len(calls)
        flat = "bf16[%d,%d,128]" % (SDAR_PAGES, PAGE * 4)
        assert pd.pages_per_visit(4 * 32, PAGE, 4,
                                  cfg.max_seq_len // PAGE) == 4
        assert all(call.count(flat) == 2 * 4 for call in calls), \
            calls[0][:400]
        assert not any(convs.values()) and not gathers, (convs, gathers)
        assert "kv_gather" not in text and "attn_pv" not in text
        # the visits' schedule is the first layer's alone
        assert re.search(_SCHEDULE_OF_LAYER % 0, text)
        assert not re.search(_SCHEDULE_OF_LAYER % 1, text)
    else:
        # T = 256 a row: 8,192 query rows against a page are sixteen
        # visits' scores, so the block loop serves the call
        assert not calls
        for scope, found in convs.items():
            assert len(found) == cfg.n_layers, (scope, len(found))
    mem = compiled.memory_analysis()
    pool = cfg.n_layers * 2 * math.prod(stored) * 2          # 3.62 GB
    weights = 2 * (cfg.n_layers * (
        128 * 3 * 2048 * 768 + 2 * 2048 * 4096 + 2 * 2048 * 512)
        + 2 * 151936 * 2048)                                 # 8.72 GB
    assert mem.alias_size_in_bytes >= pool
    assert mem.argument_size_in_bytes >= pool + weights
    # the largest temporaries: a forward's float32 logits [512, V]
    # (311 MB) and the gathered blocks; nothing of the pool's size
    temp = mem.temp_size_in_bytes
    assert temp < (2048 << 20), temp
    held = (mem.argument_size_in_bytes + temp + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    assert held < 15.75 * 2 ** 30, held
    if name == "decode":
        # the head over a block's rows, never over a chunk's
        assert "f32[128,4,151936]" in text
    else:
        assert "f32[4,256,151936]" not in text


# ---- a state-space rule with HEADS behind a mixture (models/
# granite_hybrid.py): at the published widths and
# granite4-h-small-d10.gen-sat's pool, three layers that keep both kinds
# (Mamba-2, attention, Mamba-2; 8 of 72 experts held keeps the compile
# short). A slot's state is [128, 64, 128] float32, the STATES minor:
# one lane tile, nothing padded, nothing copied.

GRANITE_SLOTS, GRANITE_PAGES = 112, 112 * 32 + 1


def _scalar_decay_step(name, one_chip):
    from ray_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                               GraniteHybrid,
                                               GraniteHybridConfig)
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    S = GRANITE_SLOTS
    cfg = GraniteHybridConfig(
        n_layers=3, layer_types=(MAMBA, ATTENTION, MAMBA), vocab_size=50176,
        max_seq_len=2304, experts_held=(0, 8), param_dtype=jnp.bfloat16)
    model = GraniteHybrid(cfg)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip), tree)
    params = placed({"params": jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]})
    pages = placed(jax.eval_shape(lambda: init_kv_pool(
        cfg, GRANITE_PAGES, PAGE, n_slots=S)))
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = ((S, cfg.max_seq_len // PAGE), i32)
    if name == "decode":
        fn = step_programs._jit_decode(model, 0.0, 8, S, False, None)
        rest = [table, ((S,), i32), ((S,), i32),
                (key.shape, key.dtype), ((), i32)]
    else:
        fn = step_programs._jit_prefill(model, 0.0, 4, False, None)
        rest = [((4, 256), i32), ((4,), i32), ((4,), i32),
                ((4, table[0][1]), i32), (key.shape, key.dtype),
                ((4,), i32)]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in rest]
    return cfg, fn.lower(params, pages, *rest).compile()


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_a_state_with_heads_stays_in_place_and_its_chunk_is_matmuls(
        one_chip, monkeypatch, name):
    """Granite-4.0-H's step programs at the published widths, the
    mixture's and the pages' rules steered as the chip answers them: the
    state pool ``[112, 128, 64, 128]`` float32 is kept as declared and
    updated in place (aliased bytes = pages + states + tails; nothing
    copied whole), a decode step passes over a layer's state in ONE
    fusion (the update and the read-out together: its temporaries hold
    no state's worth), and the ``[4, 256]`` prefill call solves its one
    chunk by matrix products (``convolution``s under ``ssd_intra`` and
    ``ssd_carry``) with no ``while`` over positions."""
    from ray_tpu.models.kv_cache import (kv_pool_page_bytes,
                                         state_bytes_per_slot)
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve import step_programs
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    for builder in ("_jit_decode", "_jit_prefill"):
        monkeypatch.setattr(step_programs, builder,
                            getattr(step_programs, builder).__wrapped__)
    cfg, compiled = _scalar_decay_step(name, one_chip)
    text = compiled.as_text()
    S = GRANITE_SLOTS
    # (the convolution's tails, 5.7 MB a layer, are small enough for the
    # compiler to move them to fast memory and back: not held here)
    shapes = {"pool": ("bf16", (GRANITE_PAGES, PAGE, 8, 128)),
              "state": ("f32", (S, 128, 64, 128))}
    for what, (dtype, shape) in shapes.items():
        pat = r"%s\[%s\]" % (dtype, ",".join(str(d) for d in shape))
        entry = re.search(pat + r"(\{[^}]*\}) parameter", text)
        order = ",".join(str(d) for d in reversed(range(len(shape))))
        assert entry and entry.group(1).startswith("{" + order), (what,
                                                                  entry)
        copies = re.findall(
            r"= " + pat + r"(?:\{[^}]*\})? (?:copy|transpose)\(", text)
        assert not copies, f"{len(copies)} whole-{what} copies in {name}"
    mem = compiled.memory_analysis()
    kept = (GRANITE_PAGES * kv_pool_page_bytes(cfg, PAGE)
            + S * state_bytes_per_slot(cfg))
    assert kept == (GRANITE_PAGES * PAGE * 2 * 8 * 128 * 2
                    + S * 2 * (128 * 64 * 128 * 4 + 3 * 8448 * 2))
    assert mem.alias_size_in_bytes == pytest.approx(kept, rel=1e-3)
    assert "tpu_custom_call" in text          # the experts' grouped matmul
    one_state = S * 128 * 64 * 128 * 4        # a layer's, 470 MB
    paged = [c for c in re.findall(
        r"custom-call\([^\n]*attn_scores/[^\n]*paged_decode[^\n]*", text)
        if 'custom_call_target="tpu_custom_call"' in c]
    loops = re.findall(r' while\([^\n]*op_name="([^"]*)"', text)
    if name == "decode":
        assert len(paged) == 1 and "/layers_1/" in paged[0], paged
        assert "ssd_intra" not in text
        # ONE fusion a layer reads the state, steps it and reads it out
        state = r"f32\[%d,128,64,128\]" % S
        passes = re.findall(
            r"^\s*(?:ROOT )?%\S+ = \(?[^\n]*" + state
            + r"[^\n]*? fusion\([^\n]*ssm_scan", text, re.M)
        assert len(passes) == 2, (len(passes), passes[:3])
        assert mem.temp_size_in_bytes < one_state // 2, \
            mem.temp_size_in_bytes
    else:
        assert not paged
        # the only loops under the recurrence's scope fetch the call's
        # four rows of the state (a gather); none walks positions
        scan_loops = [l for l in loops if "ssm_scan" in l or "ssd_" in l]
        assert all(l.endswith("ssm_scan/gather") for l in scan_loops), \
            scan_loops
        for scope, n in (("ssd_intra", 2), ("ssd_carry", 2)):
            products = re.findall(
                r"convolution\([^\n]*/" + scope + r"/[^\n]*", text)
            assert len(products) >= 2 * n, (scope, len(products))
        assert mem.temp_size_in_bytes < one_state, mem.temp_size_in_bytes
