"""Where JAX's persistent compilation cache lives.

A directory that moves never hits (the path is part of how a run finds
its entries), so there is exactly one rule: ``JAX_COMPILATION_CACHE_DIR``
if the environment sets it — JAX reads that variable itself, and this
module then sets nothing — else one fixed directory at the root of the
checkout. Never a temp dir, a pid or a timestamp.
"""
from __future__ import annotations

import contextlib
import os
import re

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DEFAULT_DIR = os.path.join(_ROOT, ".jax_cache")
_ROOT_RE = "^" + re.escape(_ROOT + os.sep)


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


@contextlib.contextmanager
def metadata_keyed():
    """Programs first compiled inside this context are keyed in the
    persistent cache by their op metadata too (named scopes, module
    paths, source lines), with this checkout's root taken off the file
    names so that a copy elsewhere still hits.

    JAX strips debug information from the key by default, so an
    executable loaded from the cache names its operations as whoever
    compiled it did: a program whose ``jax.named_scope``s are read from
    a device trace (the serving engine's step programs, PERF.md section
    3) would otherwise show the scopes of an older checkout, or none.
    The price is a recompile when a file on the program's traceback
    shifts lines, which programs with Pallas kernels pay already (the
    serialized kernel carries its traceback). Thread-local; a no-op on
    a JAX without these options."""
    try:
        from jax._src import config as jcfg
        keyed = jcfg.compilation_cache_include_metadata_in_key(True)
        rooted = jcfg.hlo_source_file_canonicalization_regex(_ROOT_RE)
    except (ImportError, AttributeError):
        yield
        return
    with keyed, rooted:
        yield
