"""The main path's Pallas kernels compiled for a described (not
attached) TPU v5e, at the widths chip_smoke.py runs them.

Interpret mode accepts block shapes the chip's compiler refuses, so
these compiles are the only CPU-side guard against a kernel that
passes every other test and cannot lower. Nothing runs: shapes in,
executable out. The topology is described inside a fixture (never at
import — one process at a time may load the TPU library, and every
pytest worker imports every file), and the persistent compile cache
is off around the compiles (an entry written for a described chip
cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import flash_attention as flash_mod
from ray_tpu.ops.paged_attention import paged_decode_attention


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (batch, seq, heads, head_dim): GPT-2-124M train batch and
# TinyLlama-1.1B train batch (bench.py)
@pytest.mark.parametrize("B,T,H,D", [(24, 1024, 12, 64),
                                     (8, 1024, 32, 64)])
def test_flash_attention_fwd_bwd_compiles(one_chip, monkeypatch,
                                          B, T, H, D):
    # the kernel picks interpret mode from the attached backend, which
    # is the CPU here; steer it to the compiled path for this compile
    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)

    def loss(q, k, v):
        return flash_mod.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = [((B, T, H, D), jnp.bfloat16)] * 3
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
             *qkv)


# (heads, kv_heads, head_dim): TinyLlama-1.1B GQA and an MHA D=128
# layout; 16 slots x 1024 tokens in 64-token pages, as chip_smoke
# serves them
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("H,KH,D", [(32, 4, 64), (16, 16, 128)])
def test_paged_decode_compiles(one_chip, H, KH, D, quantized):
    B, Pg, per_seq = 16, 64, 16
    n_pages = B * per_seq + 1
    pool = ((KH, n_pages, Pg, D),
            jnp.int8 if quantized else jnp.bfloat16)
    shapes = [((B, H, D), jnp.bfloat16), pool, pool,
              ((B, per_seq), jnp.int32), ((B,), jnp.int32)]
    if quantized:
        shapes += [((KH, n_pages, 1), jnp.float32)] * 2

    def step(*a):
        return paged_decode_attention(*a, interpret=False)

    _compile(step, one_chip, *shapes)


# (sorted pairs, what calls with them): OLMoE-1B-7B's experts (64 of
# 2048 x 1024, 8 a token) in a 32-slot decode call and in a 4 x 256
# prefill call, the three matmuls of the mixture
@pytest.mark.parametrize("M", [256, 8192], ids=["decode", "prefill"])
def test_grouped_matmul_compiles(one_chip, monkeypatch, M):
    from ray_tpu.ops import grouped_matmul as gm
    # the op asks the attached backend, which is the CPU here
    monkeypatch.setattr(gm, "_use_kernel", lambda: True)
    E, D, F = 64, 2048, 1024

    def experts(rows, w1, w3, w2, group_sizes):
        h = jax.nn.silu(gm.grouped_matmul(rows, w1, group_sizes)) * \
            gm.grouped_matmul(rows, w3, group_sizes)
        return gm.grouped_matmul(h, w2, group_sizes)

    compiled = _compile(
        experts, one_chip, ((M, D), jnp.bfloat16),
        ((E, D, F), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
        ((E, F, D), jnp.bfloat16), ((E,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") >= 3
