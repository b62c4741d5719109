"""Build-on-first-use for the C++ shared libraries under ``src/``.

``build/`` is not committed, so a fresh checkout (and the chip
machine's copy of one) compiles them the first time they are loaded.
A failure here must read as what it is — the compiler's own message,
or "no g++" — not as an ImportError three layers up.
"""
from __future__ import annotations

import os
import subprocess


class NativeBuildError(RuntimeError):
    pass


def ensure_built(src: str, lib: str) -> str:
    """Compile ``src`` into the shared library ``lib`` unless an
    up-to-date one is already there; returns ``lib``."""
    if os.path.exists(lib) and \
            os.path.getmtime(lib) >= os.path.getmtime(src):
        return lib
    os.makedirs(os.path.dirname(lib), exist_ok=True)
    cmd = ["g++", "-O2", "-Wall", "-fPIC", "-std=c++17", "-shared",
           "-o", lib, src, "-lpthread", "-lrt"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except FileNotFoundError:
        raise NativeBuildError(
            f"cannot build {os.path.basename(lib)}: no g++ on PATH "
            f"(needed once per checkout to compile {src})") from None
    if proc.returncode != 0:
        raise NativeBuildError(
            f"{' '.join(cmd)} failed ({proc.returncode}):\n"
            f"{proc.stderr.strip()}")
    return lib
