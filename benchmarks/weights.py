"""Random weights from ``--seed``, made ON the device in ONE jitted call,
in the type they are served in and already laid out as the engine will
shard them. The key is an ARGUMENT: a closed-over key lets XLA fold the
weights into the executable (a 133.6 MiB cache entry; PERF.md, PR 23).
``jit(model.init)`` is not used for a served model: it traces and
compiles the whole forward pass only to throw it away (63 s at 1.1B;
PERF.md, PR 23). Which leaf gets which scale is the family's to say
(benchmarks/families/<family>.py ``init_params``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import jax_key


def param_shapes(model):
    """The model's own parameter tree as shapes and dtypes."""
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))


def seeded_normal(shapes, seed: int, std_of, shardings=None):
    """Weights for ``param_shapes(model)``: leaf ``i`` of the flattened
    tree is ``std_of(path name, leaf)`` times a standard normal drawn
    from ``fold_in(key, i)``, or all ones where ``std_of`` gives None
    (a norm's scale)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            std = std_of(jax.tree_util.keystr(path), leaf)
            if std is None:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i),
                                  leaf.shape, jnp.float32)
            out.append((std * x).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(make, out_shardings=shardings)
    return jax.block_until_ready(fn(jax_key(seed, 0)))
