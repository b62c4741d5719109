"""pip/venv runtime environments.

Reference capability: python/ray/_private/runtime_env/pip.py — a venv
per requirements hash, built on the executing node, cached by URI, and
workers launched with its interpreter. This image has no network, so
the tests install a locally-built source package with --no-index.
"""
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.runtime_env import (pip_env_dir, stage_pip_env,
                                          validate_runtime_env)


def _make_pkg(tmp_path, name, version="1.0.0", magic=7):
    d = tmp_path / name
    (d / name).mkdir(parents=True)
    (d / name / "__init__.py").write_text(
        f"__version__ = '{version}'\nMAGIC = {magic}\n")
    (d / "setup.py").write_text(
        "from setuptools import setup, find_packages\n"
        f"setup(name='{name}', version='{version}', "
        "packages=find_packages())\n")
    return str(d)


def test_validation():
    validate_runtime_env({"pip": ["a", "b==1.0"]})
    validate_runtime_env({"pip": {"packages": ["a"],
                                  "local_index": "/tmp/x"}})
    with pytest.raises(TypeError):
        validate_runtime_env({"pip": "not-a-list"})
    with pytest.raises(TypeError):
        validate_runtime_env({"pip": [1, 2]})


def test_stage_and_cache(tmp_path):
    pkg = _make_pkg(tmp_path, "graft_stage_pkg", magic=11)
    env = {"pip": [pkg]}
    py = stage_pip_env(env)
    out = subprocess.run(
        [py, "-c", "import graft_stage_pkg as g; print(g.MAGIC)"],
        capture_output=True, text=True)
    assert out.stdout.strip() == "11", out.stderr
    # the driver interpreter must NOT see it (isolation)
    with pytest.raises(ImportError):
        import graft_stage_pkg  # noqa: F401
    # cache hit: second staging is instant (no pip invocation)
    t0 = time.perf_counter()
    assert stage_pip_env(env) == py
    assert time.perf_counter() - t0 < 0.1
    # framework stack visible inside the venv (layered base site)
    out = subprocess.run([py, "-c", "import numpy; print('np')"],
                         capture_output=True, text=True)
    assert out.stdout.strip() == "np"


@pytest.mark.slow      # 16 s: builds a virtualenv and boots a worker in it
def test_pip_task_runs_in_dedicated_venv_worker(tmp_path):
    """A task with a pip env runs on an env-keyed worker that
    re-exec'd into the venv interpreter and can import the package
    the driver lacks."""
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    pkg = _make_pkg(tmp_path, "graft_task_pkg", magic=23)
    c = Cluster(num_workers=2, resources_per_worker={"CPU": 2})
    try:
        @ray_tpu.remote(runtime_env={"pip": [pkg]})
        def probe():
            import graft_task_pkg
            return (graft_task_pkg.MAGIC, sys.executable)

        magic, exe = ray_tpu.get(probe.remote(), timeout=180)
        assert magic == 23
        # the worker's interpreter IS the venv's python
        assert pip_env_dir({"pip": [pkg]}) in exe

        @ray_tpu.remote
        def plain():
            try:
                import graft_task_pkg  # noqa: F401
                return "leaked"
            except ImportError:
                return "isolated"

        assert ray_tpu.get(plain.remote(), timeout=60) == "isolated"

        # same env again: reuses the cached venv (fast second call)
        t0 = time.perf_counter()
        magic2, exe2 = ray_tpu.get(probe.remote(), timeout=60)
        assert magic2 == 23 and exe2 == exe
        assert time.perf_counter() - t0 < 30
    finally:
        c.shutdown()


def test_pip_env_failure_fails_tasks_fast(tmp_path):
    """A broken pip env (unresolvable package offline) must FAIL the
    queued tasks with the pip error — not hang the caller in an
    endless respawn loop."""
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    c = Cluster(num_workers=1, resources_per_worker={"CPU": 2})
    try:
        @ray_tpu.remote(
            runtime_env={"pip": ["definitely-not-a-package-xyz42"]})
        def f():
            return 1

        with pytest.raises(Exception,
                           match="runtime_env setup failed"):
            ray_tpu.get(f.remote(), timeout=120)
    finally:
        c.shutdown()


def test_edited_local_pkg_invalidates_cache(tmp_path):
    """Editing a local source package in place must produce a NEW venv
    key (content fingerprint), not serve the stale cached venv."""
    import os
    pkg = _make_pkg(tmp_path, "graft_edit_pkg", magic=1)
    env = {"pip": [pkg]}
    d1 = pip_env_dir(env)
    init = os.path.join(pkg, "graft_edit_pkg", "__init__.py")
    with open(init, "a") as f:
        f.write("EXTRA = 1\n")
    os.utime(init, (time.time() + 2, time.time() + 2))
    d2 = pip_env_dir(env)
    assert d1 != d2


def test_pip_env_failure_fails_actor(tmp_path):
    """Actor creation with a broken pip env surfaces the REAL setup
    error instead of a placement timeout."""
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    c = Cluster(num_workers=1, resources_per_worker={"CPU": 2})
    try:
        @ray_tpu.remote(
            runtime_env={"pip": ["also-not-a-real-package-xyz42"]})
        class A:
            def ping(self):
                return 1

        with pytest.raises(Exception,
                           match="runtime_env setup failed"):
            a = A.remote()
            ray_tpu.get(a.ping.remote(), timeout=90)
    finally:
        c.shutdown()


def test_pip_env_in_local_runtime(tmp_path, rt):
    """The in-process runtime layers the venv's site-packages onto
    sys.path for the task's duration."""
    pkg = _make_pkg(tmp_path, "graft_local_pkg", magic=31)

    @rt.remote(runtime_env={"pip": [pkg]})
    def probe():
        import graft_local_pkg
        return graft_local_pkg.MAGIC

    assert rt.get(probe.remote(), timeout=180) == 31
    # in-process env: the module object stays cached in sys.modules
    # (documented env bleed), but the PATH layering is restored — a
    # fresh import attempt fails once the cache entry is gone
    sys.modules.pop("graft_local_pkg", None)
    with pytest.raises(ImportError):
        import graft_local_pkg  # noqa: F401
