"""The plain references against the repo's models at toy size on the
CPU: float32 both sides, so they agree to rounding."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common, weights


def test_llama_reference_matches_the_model():
    cfg = common.load_json("rehearsal", "toy-llama.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 40)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    got = fam.reference_logits(fam.reference_weights(params, pcfg), ids,
                               pcfg)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_gpt2_reference_matches_the_model_loss_and_gradient():
    import dataclasses

    import optax
    cfg = common.load_json("rehearsal", "toy-gpt2.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    model = fam.model(dataclasses.replace(fam.program_config(cfg),
                                          dtype=jnp.float32))
    params = fam.init_params(model, 5)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, size=(4, 65)), jnp.int32)
    loss_fn = fam.loss_fn(model)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params,
                                                       {"ids": ids})
    gnorm = float(optax.global_norm(grads))
    ref_loss, ref_gnorm = fam.reference_loss_and_grad_norm(
        params, ids, cfg, micro_batch=2)
    assert abs(ref_loss - float(loss)) / float(loss) < 1e-5
    assert abs(ref_gnorm - gnorm) / gnorm < 1e-4


def test_references_import_nothing_of_the_models():
    ref_dir = os.path.join(common.HERE, "reference")
    names = [f for f in os.listdir(ref_dir) if f.endswith(".py")]
    assert {"llama.py", "gpt2.py"} <= set(names)
    for name in names:
        with open(os.path.join(ref_dir, name)) as f:
            code = f.read().replace("ray_tpu.models`", "")
        assert "ray_tpu" not in code.split('"""', 2)[2], name
