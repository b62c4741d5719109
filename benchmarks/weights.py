"""Random weights from ``--seed``, made ON the device in ONE jitted call,
in the type they are served in and already laid out as the engine will
shard them. The key is an ARGUMENT: a closed-over key lets XLA fold the
weights into the executable (a 133.6 MiB cache entry; PERF.md, PR 23).
``jit(model.init)`` is not used for the served model: it traces and
compiles the whole forward pass only to throw it away (63 s at 1.1B;
PERF.md, PR 23).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import jax_key


def param_shapes(model):
    """The model's own parameter tree as shapes and dtypes."""
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))


def llama_params(shapes, seed: int, shardings=None):
    """Llama-family weights for ``param_shapes(model)`` with the model's
    own scales: normal with std 1/sqrt(fan_in) for matrices, 0.02 for
    the embedding, ones for the norms (chip_smoke.init_llama's rule,
    copied)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            if leaf.ndim == 1:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            name = jax.tree_util.keystr(path)
            std = (0.02 if "tok_embeddings" in name
                   else leaf.shape[0] ** -0.5)
            x = jax.random.normal(jax.random.fold_in(key, i),
                                  leaf.shape, jnp.float32)
            out.append((std * x).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(make, out_shardings=shardings)
    return jax.block_until_ready(fn(jax_key(seed, 0)))


def gpt2_params(model, seed: int):
    """GPT-2 master weights from the model's own initialisers, as
    bench.py and the examples make them; key and ids are arguments.
    The ids are 1 x 8: no parameter's shape depends on them, and a
    24 x 1024 batch makes the init trace the flash kernel for 8 s."""
    ids = jnp.zeros((1, 8), jnp.int32)
    return jax.block_until_ready(
        jax.jit(model.init)(jax_key(seed, 0), ids))
