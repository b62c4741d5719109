"""BERT encoder family: forward semantics, masking, MLM training,
sharding (same test strategy as test_models.py for the decoders)."""
import numpy as np
import pytest


def test_forward_shapes_and_padding_mask():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Bert, bert_tiny
    cfg = bert_tiny()
    model = Bert(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(1, cfg.vocab_size, (2, 16)))
    # jitted: op-by-op dispatch compiles every primitive on its own
    params = jax.jit(model.init, static_argnames="return_mlm_logits")(
        jax.random.PRNGKey(0), ids, return_mlm_logits=True)
    apply = jax.jit(model.apply, static_argnames="return_mlm_logits")
    h = apply(params, ids)
    assert h.shape == (2, 16, cfg.dim)
    logits = apply(params, ids, return_mlm_logits=True)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # padding positions must not influence unpadded outputs
    mask = jnp.asarray([[1] * 16, [1] * 8 + [0] * 8])
    h_masked = apply(params, ids, attention_mask=mask)
    ids_trunc = ids[1:, :8]
    h_trunc = apply(params, ids_trunc,
                    attention_mask=jnp.ones((1, 8), jnp.int32))
    np.testing.assert_allclose(np.asarray(h_masked[1, :8]),
                               np.asarray(h_trunc[0]), atol=2e-4)


def test_mask_tokens_contract():
    from ray_tpu.models import mask_tokens
    rng = np.random.RandomState(0)
    ids = rng.randint(5, 1000, (8, 64))
    masked, labels = mask_tokens(rng, ids, vocab_size=1024,
                                 mask_token=3)
    picked = labels != -100
    frac = picked.mean()
    assert 0.08 < frac < 0.25                  # ~15% of positions
    # labels hold the ORIGINAL ids at picked positions
    assert (labels[picked] == ids[picked]).all()
    # most picked positions became [MASK]
    assert (masked[picked] == 3).mean() > 0.6
    # unpicked positions are untouched
    assert (masked[~picked] == ids[~picked]).all()


@pytest.mark.slow      # 23 s: trains 100 sharded steps to a loss threshold
def test_mlm_training_learns_and_shards():
    """MLM loss decreases on a learnable toy stream, with params
    sharded by bert_sharding_rules on the 8-device mesh (the spmd
    step builder — same path JaxTrainer uses)."""
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.mesh.device_mesh import create_mesh
    from ray_tpu.models import (Bert, bert_sharding_rules, bert_tiny,
                                mask_tokens, mlm_loss)
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)
    cfg = bert_tiny(vocab_size=64, dim=64, n_layers=2, n_heads=2,
                    hidden_dim=128)
    mesh = create_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    model = Bert(cfg)
    rng = np.random.RandomState(0)
    init_ids = jnp.asarray(rng.randint(4, cfg.vocab_size, (2, 16)))
    params = jax.jit(model.init, static_argnames="return_mlm_logits")(
        jax.random.PRNGKey(0), init_ids, return_mlm_logits=True)
    # structured data: token at t+1 == token at t (copy pattern), so
    # masked positions are predictable from neighbors
    def batch_ids(n=16):
        base = rng.randint(4, cfg.vocab_size, (n, 1))
        return np.repeat(base, 16, axis=1)

    optimizer = optax.adam(1e-2)
    rules = bert_sharding_rules()
    state = shard_state(TrainState.create(params, optimizer), rules,
                        mesh)

    def loss_fn(p, batch):
        logits = model.apply(p, batch["ids"],
                             return_mlm_logits=True)
        return mlm_loss(logits, batch["labels"])

    step = make_train_step(loss_fn, optimizer)
    losses = []
    with jax.set_mesh(mesh):
        for _ in range(100):
            ids = batch_ids()
            masked, labels = mask_tokens(rng, ids, cfg.vocab_size,
                                         mask_token=3)
            batch = put_batch({"ids": masked.astype(np.int32),
                               "labels": labels.astype(np.int32)},
                              mesh)
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    # sharded as declared: qkv kernels split over tensor
    qkv = state.params["params"]["layer_0"]["attn"]["qkv"]["kernel"]
    assert len(qkv.sharding.device_set) > 1


def test_pooled_output():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Bert, bert_tiny
    cfg = bert_tiny()
    model = Bert(cfg)
    ids = jnp.ones((2, 8), jnp.int32)
    params = jax.jit(model.init, static_argnames="return_pooled")(
        jax.random.PRNGKey(0), ids, return_pooled=True)
    hidden, pooled = jax.jit(
        model.apply, static_argnames="return_pooled")(
            params, ids, return_pooled=True)
    assert hidden.shape == (2, 8, cfg.dim)
    assert pooled.shape == (2, cfg.dim)
    assert float(abs(pooled).max()) <= 1.0      # tanh-bounded
