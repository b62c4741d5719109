"""Where JAX's persistent compilation cache lives.

A directory that moves never hits (the path is part of how a run finds
its entries), so there is exactly one rule: ``JAX_COMPILATION_CACHE_DIR``
if the environment sets it — JAX reads that variable itself, and this
module then sets nothing — else one fixed directory at the root of the
checkout. Never a temp dir, a pid or a timestamp.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache and return its
    directory. Idempotent; called wherever the program first makes JAX
    compile for a device (engine build, make_train_step, the bench and
    smoke scripts, the tests' conftest)."""
    import jax
    # keep every executable, not only those that took over a second:
    # a serving step at test size compiles in well under that, and
    # equal programs built by different jit objects (one per engine,
    # per worker process) then meet in the cache even inside one run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
