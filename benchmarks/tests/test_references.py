"""The plain references against the repo's models at toy size on the
CPU: float32 both sides, so they agree to rounding."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common, weights
from benchmarks.reference import gpt2 as gpt2_ref
from benchmarks.reference import llama as llama_ref
from benchmarks.serve_runner import llama_config, reference_weights
from benchmarks.train_runner import gpt2_config
from benchmarks.train_runner import reference_weights as gpt2_weights


def test_llama_reference_matches_the_model():
    from ray_tpu.models.llama import Llama
    cfg = common.load_json("rehearsal", "toy-llama.json")
    lcfg = llama_config(cfg)
    model = Llama(lcfg)
    params = weights.llama_params(weights.param_shapes(model), 2**31 + 7)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 40)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    got = llama_ref.forward(
        reference_weights(params, lcfg.n_layers), ids,
        n_heads=lcfg.n_heads, n_kv_heads=lcfg.n_kv_heads,
        eps=lcfg.norm_eps, theta=lcfg.rope_theta)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_gpt2_reference_matches_the_model_loss_and_gradient():
    import optax
    from ray_tpu.models import GPT2
    from ray_tpu.models.gpt2 import linear_cross_entropy
    cfg = common.load_json("rehearsal", "toy-gpt2.json")
    gcfg = gpt2_config(cfg)
    import dataclasses
    gcfg = dataclasses.replace(gcfg, dtype=jnp.float32)
    model = GPT2(gcfg)
    params = weights.gpt2_params(model, 5)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, size=(4, 65)), jnp.int32)

    def loss_fn(p):
        feats = model.apply(p, ids[:, :-1], return_features=True)
        return linear_cross_entropy(feats, p["params"]["wte"],
                                    ids[:, 1:])

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    gnorm = float(optax.global_norm(grads))
    ref_loss, ref_gnorm = gpt2_ref.loss_and_grad_norm(
        gpt2_weights(params, gcfg.n_layer), ids, n_head=gcfg.n_head,
        eps=cfg["layer_norm_epsilon"], micro_batch=2)
    assert abs(ref_loss - float(loss)) / float(loss) < 1e-5
    assert abs(ref_gnorm - gnorm) / gnorm < 1e-4


def test_references_import_nothing_of_the_models():
    import inspect
    for mod in (gpt2_ref, llama_ref):
        assert "ray_tpu" not in inspect.getsource(mod).replace(
            "ray_tpu.models`", "").split('"""', 2)[2]
