"""CPU rehearsal of chip_smoke.py: its phase functions at toy size
(only the device check is skipped), and the device placement the
four-chip phase depends on."""
import os
import types

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.models.gpt2 import gpt2_tiny
from ray_tpu.models.llama import llama_tiny
from ray_tpu.serve.llm import LlamaDeployment
from ray_tpu.serve.sharding import (ShardingConfigError,
                                    replica_device_groups)

TINY = dict(prompt_len=12, new_tokens=6, max_slots=4, page_size=8)


def test_serving_phase_rehearsal(rt):
    out = chip_smoke.serving_phase(llama_tiny(dtype=jnp.float32),
                                   n_requests=3, **TINY)
    assert out["tokens"] == 18 and out["parity"]["decisive"] > 0


def test_kernel_phase_rehearsal():
    errs = chip_smoke.kernel_phase(flash_shapes=((1, 128, 2, 64),),
                                   kda_shapes=((3, 8, 128),),
                                   window_shapes=((32, 16, 256, 128,
                                                   (64, None, 600), 16),),
                                   gmm_shapes=((4, 384, 256, 200, 150),),
                                   decode_shapes=((16, 4,
                                                   (70, None, 1)),
                                                  (16, None,
                                                   (70, None, 129))),
                                   interpret=True)
    assert {n.split("_")[0] for n in errs} == {"flash", "kda", "latent",
                                               "grouped", "paged"}


def test_training_phase_rehearsal():
    out = chip_smoke.training_phase(gpt2_tiny(), batch=8, seq=32,
                                    steps=3, expect_flash=False)
    assert len(out["losses"]) == 3


def test_training_phase_fails_when_kernel_expected():
    # on the chip a step without the flash kernel must fail the smoke
    with pytest.raises(AssertionError, match="flash kernel missing"):
        chip_smoke.training_phase(gpt2_tiny(), batch=8, seq=32,
                                  steps=1, expect_flash=True)


# 17 s: six engines and two train steps. The placement it depends on
# stays in tier 1 (test_pool_replicas_land_on_distinct_devices).
@pytest.mark.slow
def test_multichip_phase_rehearsal():
    out = chip_smoke.multichip_phase(
        llama_tiny(dtype=jnp.float32, n_kv_heads=4), gpt2_tiny(),
        n_prompts=1, train_batch=8, train_seq=32, train_steps=2,
        expect_flash=False, **TINY)
    assert len(set(out["placed"])) == 4


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_pool_replicas_land_on_distinct_devices():
    """Regression: a one-chip replica used to get no sharding at all,
    so every pool member's params and KV pool sat on device 0."""
    dep = LlamaDeployment(config=llama_tiny(dtype=jnp.float32),
                          num_engine_replicas=4, max_slots=2,
                          page_size=8)
    pool = dep.engine()
    try:
        placed = []
        for eng in pool.engines():
            p = jax.tree_util.tree_leaves(eng.params)[0].devices()
            kv = eng.pages[0][0].devices()
            assert p == kv and len(p) == 1
            placed.append(next(iter(p)))
        assert len(set(placed)) == 4
    finally:
        pool.shutdown()


def test_replica_groups_wrap_on_cpu_only():
    cpu = jax.devices()
    assert len(replica_device_groups(10, 1, cpu)) == 10    # wraps
    tpu = [types.SimpleNamespace(platform="tpu", id=i)
           for i in range(4)]
    assert len(replica_device_groups(4, 1, tpu)) == 4
    assert len(replica_device_groups(2, 2, tpu)) == 2
    with pytest.raises(ShardingConfigError, match="do not fit"):
        replica_device_groups(5, 1, tpu)
    with pytest.raises(ShardingConfigError, match="do not fit"):
        replica_device_groups(3, 2, tpu)


# ------------------------------ what else the chip path depends on


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    import ray_tpu
    from ray_tpu.util import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        # the variable wins and the function sets no path of its own
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(ray_tpu.__file__))
        assert compile_cache.enable_compile_cache() == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_fleet_launchers_refuse_off_the_cpu_backend():
    from ray_tpu.serve.fleet.provider import (FleetNeedsCpuBackend,
                                              require_cpu_backend)
    require_cpu_backend({"JAX_PLATFORMS": "cpu"})
    for env in ({}, {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": ""}):
        with pytest.raises(FleetNeedsCpuBackend, match="CPU-tested"):
            require_cpu_backend(env)


def test_tpu_detection_lets_backend_errors_out(monkeypatch):
    """ray_tpu.init() on a host whose chip failed to open must fail,
    not come up with no TPU resource."""
    from ray_tpu._private import worker
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert worker._detect_tpu_chips() == 0
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        worker._detect_tpu_chips()


def test_native_build_failure_carries_the_compilers_message(tmp_path):
    from ray_tpu._private.native_build import (NativeBuildError,
                                               ensure_built)
    src = tmp_path / "bad.cc"
    src.write_text("int main( { return 0; }\n")
    with pytest.raises(NativeBuildError, match="bad.cc"):
        ensure_built(str(src), str(tmp_path / "build" / "libbad.so"))
