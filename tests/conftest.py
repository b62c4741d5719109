"""Test fixtures.

Forces JAX onto a virtual 8-device CPU mesh (the reference tests multi-node
behavior with multiple raylets on one machine, python/ray/tests/conftest.py
``ray_start_cluster``; we test multi-chip behavior with a forced host-platform
device count) and provides a fresh runtime per test.
"""
import os

# Must be set before jax is imported anywhere. Worker processes the
# tests spawn inherit all of it.
#  - eight virtual CPU devices stand in for a multi-chip host;
#  - the tests check results, not speed, and on one core the suite's
#    time is XLA-CPU compile time: skipping LLVM's optimisation passes
#    takes about a third off it;
#  - one OpenMP thread per process: xgboost/torch/lightgbm workers
#    otherwise each spin up a thread per core and fight over them.
for _flag in ("--xla_force_host_platform_device_count=8",
              "--xla_backend_optimization_level=0",
              "--xla_llvm_disable_expensive_passes=true"):
    if _flag.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " " + _flag).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("OMP_NUM_THREADS", "1")

import pytest  # noqa: E402

from ray_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


@pytest.fixture
def rt():
    """A fresh local runtime per test."""
    import ray_tpu
    from ray_tpu._private.config import GlobalConfig
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    GlobalConfig.reset()
    ray_tpu.init(num_cpus=8, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()
    GlobalConfig.reset()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs
