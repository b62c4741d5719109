"""Model correctness tests on the CPU mesh (tiny configs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.mesh import create_mesh, shard_params
from ray_tpu.models import GPT2, ResNet, gpt2_sharding_rules, resnet18
from ray_tpu.models.gpt2 import (cross_entropy_loss, count_params,
                                 gpt2_tiny, gpt2_124m)


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt2_tiny(dtype=jnp.float32, remat=False)
    model = GPT2(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jnp.zeros((2, 16), dtype=jnp.int32)
    params = model.init(rng, ids)
    return cfg, model, params


def test_gpt2_forward_shape(tiny_gpt):
    cfg, model, params = tiny_gpt
    ids = jnp.ones((2, 16), dtype=jnp.int32)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt2_causality(tiny_gpt):
    # Changing a future token must not change past logits.
    cfg, model, params = tiny_gpt
    rng = jax.random.PRNGKey(1)
    ids = jax.random.randint(rng, (1, 16), 0, cfg.vocab_size)
    logits_a = model.apply(params, ids)
    ids_b = ids.at[0, 10].set((ids[0, 10] + 1) % cfg.vocab_size)
    logits_b = model.apply(params, ids_b)
    np.testing.assert_allclose(np.asarray(logits_a[0, :10]),
                               np.asarray(logits_b[0, :10]),
                               rtol=2e-4, atol=2e-4)
    assert not np.allclose(np.asarray(logits_a[0, 10:]),
                           np.asarray(logits_b[0, 10:]))


def test_gpt2_loss_decreases_one_step(tiny_gpt):
    cfg, model, params = tiny_gpt
    rng = jax.random.PRNGKey(2)
    ids = jax.random.randint(rng, (4, 17), 0, cfg.vocab_size)
    x, y = ids[:, :-1], ids[:, 1:]

    def loss_fn(p):
        return cross_entropy_loss(model.apply(p, x), y)

    l0, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    params2 = jax.tree_util.tree_map(lambda p, g: p - 0.5 * g, params,
                                     grads)
    l1 = jax.jit(loss_fn)(params2)
    assert float(l1) < float(l0)


def test_gpt2_124m_param_count():
    cfg = gpt2_124m()
    model = GPT2(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), dtype=jnp.int32)))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params))
    # 124M with padded vocab (50304): ~124.4M
    assert 120e6 < n < 130e6, n


def test_gpt2_sharded_forward_matches_single(tiny_gpt, cpu_mesh_devices):
    cfg, model, params = tiny_gpt
    mesh = create_mesh({"data": 2, "tensor": 4})
    ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                             cfg.vocab_size)
    expected = model.apply(params, ids)
    sharded = shard_params(params, gpt2_sharding_rules(fsdp=False), mesh)
    out = jax.jit(model.apply)(sharded, ids)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=5e-4, atol=5e-4)


def test_cross_entropy_ignore_index():
    logits = jnp.zeros((1, 4, 10))
    targets = jnp.array([[1, 2, -100, -100]])
    loss = cross_entropy_loss(logits, targets)
    # Uniform logits: loss = log(10), averaged over 2 valid tokens.
    assert float(loss) == pytest.approx(np.log(10), rel=1e-5)


def test_resnet18_forward():
    cfg = resnet18(num_classes=10, dtype=jnp.float32,
                   small_inputs=True)
    model = ResNet(cfg)
    x = jnp.ones((2, 32, 32, 3))
    # jitted: op-by-op dispatch compiles every conv/bn on its own
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), x)
    logits = jax.jit(model.apply)(variables, x)
    assert logits.shape == (2, 10)

    # Train mode updates batch stats.
    logits, updates = jax.jit(
        lambda v, x: model.apply(v, x, train=True,
                                 mutable=["batch_stats"]))(variables, x)
    assert logits.shape == (2, 10)
    assert "batch_stats" in updates


# ---- Llama family --------------------------------------------------------

def test_llama_forward_shapes(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    logits, caches = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert caches is None
    assert logits.dtype == jnp.float32


def test_llama_gqa_param_shapes():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))
    wk = params["params"]["layers_0"]["attention"]["wk"]["kernel"]
    wq = params["params"]["layers_0"]["attention"]["wq"]["kernel"]
    # GQA: kv projection is n_kv_heads/n_heads the size of q.
    assert wk.shape[1] * 2 == wq.shape[1]


def test_llama_kv_cache_decode_matches_full_forward():
    """Decoding token-by-token with the KV cache must reproduce the
    full-sequence forward logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import Llama, llama_tiny
    from ray_tpu.models.llama import init_kv_caches

    cfg = llama_tiny()
    model = Llama(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0,
                             cfg.vocab_size)
    # jitted: op-by-op dispatch compiles every primitive on its own,
    # and a traced cache_len makes the six decode steps one program
    apply = jax.jit(model.apply)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    full_logits, _ = apply(params, ids)

    caches = init_kv_caches(cfg, 1, 12)
    # Prefill 6 tokens, then decode 6 single tokens.
    logits, caches = apply(params, ids[:, :6], kv_caches=caches,
                           cache_len=0)
    step_logits = [logits]
    for t in range(6, 12):
        lg, caches = apply(params, ids[:, t:t + 1],
                           kv_caches=caches, cache_len=t)
        step_logits.append(lg)
    stitched = jnp.concatenate(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(stitched),
                               np.asarray(full_logits),
                               rtol=2e-2, atol=2e-2)


def test_llama_generate_greedy_deterministic():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, generate, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    out1 = generate(model, params, prompt, max_new_tokens=8)
    out2 = generate(model, params, prompt, max_new_tokens=8)
    assert out1.shape == (1, 12)
    assert (out1 == out2).all()
    assert (out1[:, :4] == prompt).all()


def test_llama_sharded_on_mesh(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ray_tpu.models import Llama, llama_sharding_rules, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    ids = jnp.zeros((4, 16), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    mesh = Mesh(np.array(cpu_mesh_devices).reshape(2, 2, 2),
                ("data", "fsdp", "tensor"))
    from ray_tpu.mesh import shard_params
    sharded = shard_params(params, llama_sharding_rules(), mesh)

    @jax.jit
    def fwd(p, x):
        logits, _ = model.apply(p, x)
        return logits.sum()

    with mesh:
        val = fwd(sharded, jax.device_put(
            ids, NamedSharding(mesh, P("data", None))))
    assert np.isfinite(float(val))


def test_fused_linear_cross_entropy_matches_naive():
    """The chunked fused projection+loss must match the materialized
    logits path in value and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import GPT2
    from ray_tpu.models.gpt2 import (cross_entropy_loss,
                                     fused_linear_cross_entropy,
                                     gpt2_tiny)

    cfg = gpt2_tiny()
    model = GPT2(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                             cfg.vocab_size)
    x, y = ids[:, :-1], ids[:, 1:]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x)

    def naive_loss(p):
        return cross_entropy_loss(model.apply(p, x), y)

    def fused_loss(p):
        return fused_linear_cross_entropy(
            model.apply(p, x, return_features=True),
            p["params"]["wte"], y, chunk=8)

    # jitted: op-by-op dispatch compiles every primitive on its own
    naive, g1 = jax.jit(jax.value_and_grad(naive_loss))(params)
    fused, g2 = jax.jit(jax.value_and_grad(fused_loss))(params)
    np.testing.assert_allclose(float(naive), float(fused), rtol=1e-2)
    n1 = float(jnp.sqrt(sum(jnp.sum(a * a)
                            for a in jax.tree_util.tree_leaves(g1))))
    n2 = float(jnp.sqrt(sum(jnp.sum(a * a)
                            for a in jax.tree_util.tree_leaves(g2))))
    np.testing.assert_allclose(n1, n2, rtol=2e-2)


def test_llama_generate_eos_zero_not_instant_stop():
    """ADVICE r1: eos_id=0 must not read the zero-initialized tail of
    the token buffer as "eos already generated" and halt after one
    decode step."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, generate, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    ref = generate(model, params, prompt, max_new_tokens=8)
    out = generate(model, params, prompt, max_new_tokens=8, eos_id=0)
    # Greedy decode with eos_id=0 matches the no-eos decode until a real
    # 0 token is produced; if none was produced they must be identical.
    gen = ref[0, 4:]
    if not bool((gen == 0).any()):
        assert (out == ref).all()
    else:
        first0 = int((gen == 0).argmax())
        assert (out[0, 4:4 + first0 + 1] == gen[:first0 + 1]).all()


def test_llama_generate_stream_matches_generate():
    """Chunked streaming decode must emit exactly the fused
    while_loop decode's tokens (greedy), across chunk boundaries."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import (Llama, generate, generate_stream,
                                      llama_tiny)
    cfg = llama_tiny()
    m = Llama(cfg)
    p = jax.jit(m.init)(jax.random.PRNGKey(0),
                        jnp.zeros((2, 8), jnp.int32))
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(1, 200, (2, 16)), jnp.int32)
    full = np.asarray(generate(m, p, prompt, max_new_tokens=21))
    for chunk in (1, 4, 8):
        st = np.stack(list(generate_stream(
            m, p, prompt, max_new_tokens=21, chunk_size=chunk)), axis=1)
        assert st.shape[1] == 21
        assert (full[:, 16:37] == st).all(), f"chunk_size={chunk}"


def test_llama_generate_stream_eos_stops():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import (Llama, generate, generate_stream,
                                      llama_tiny)
    cfg = llama_tiny()
    m = Llama(cfg)
    p = jax.jit(m.init)(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    prompt = jnp.asarray(
        np.random.RandomState(5).randint(1, 200, (1, 16)), jnp.int32)
    full = np.asarray(generate(m, p, prompt, max_new_tokens=24))
    eos = int(full[0, 16 + 5])        # the 6th generated token
    toks = [int(t[0]) for t in generate_stream(
        m, p, prompt, max_new_tokens=24, eos_id=eos, chunk_size=4)]
    assert eos in toks
    assert len(toks) == toks.index(eos) + 1    # nothing after eos


def test_mixtral_forward_and_shared_decode_paths():
    """Mixtral (top-2 MoE Llama) reuses the KV-cache decode stack:
    generate and chunked generate_stream agree exactly."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Mixtral, mixtral_tiny, moe_aux_loss
    from ray_tpu.models.llama import generate, generate_stream
    cfg = mixtral_tiny()
    m = Mixtral(cfg)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 200, (2, 16)), jnp.int32)
    vs = jax.jit(m.init)(jax.random.PRNGKey(0), ids)
    logits, _ = jax.jit(m.apply)(vs, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    _, aux = jax.jit(
        lambda vs, ids: m.apply(vs, ids, mutable=["losses"]))(vs, ids)
    lb = float(moe_aux_loss(aux))
    assert 0.5 < lb < 4.0      # ~1.0 at balance, E at collapse
    full = np.asarray(generate(m, vs, ids, max_new_tokens=9))
    st = np.stack(list(generate_stream(m, vs, ids, max_new_tokens=9,
                                       chunk_size=4)), axis=1)
    assert (full[:, 16:25] == st).all()


def test_mixtral_expert_parallel_train_step(cpu_mesh_devices):
    """One jitted train step over an expert x data mesh with the
    family's EP+TP sharding rules: expert weights shard over the
    `expert` axis and the loss is finite."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.mesh import create_mesh
    from ray_tpu.models import (Mixtral, mixtral_sharding_rules,
                                mixtral_tiny)
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)

    mesh = create_mesh({"expert": 4, "data": 2})
    cfg = mixtral_tiny(dtype=jnp.float32)
    m = Mixtral(cfg)
    ids = jnp.zeros((4, 17), jnp.int32)
    params = jax.jit(lambda: m.init(jax.random.PRNGKey(0),
                                    ids[:, :-1]))()
    state = shard_state(TrainState.create(params, optax.adamw(1e-3)),
                        mixtral_sharding_rules(), mesh)
    # expert weights actually sharded over the expert axis
    w1 = state.params["params"]["layers_0"]["moe"]["w1"]
    assert "expert" in str(w1.sharding.spec)

    def loss_fn(p, batch):
        x, y = batch["ids"][:, :-1], batch["ids"][:, 1:]
        logits, _ = m.apply(p, x)
        oh = jax.nn.one_hot(y, cfg.vocab_size)
        return -jnp.mean(
            jnp.sum(oh * jax.nn.log_softmax(logits, axis=-1), -1))

    step = make_train_step(loss_fn, optax.adamw(1e-3))
    rng = np.random.RandomState(0)
    with jax.set_mesh(mesh):
        b = put_batch({"ids": rng.randint(
            0, 256, (4, 17)).astype(np.int32)}, mesh)
        state, metrics = step(state, b)
    assert 0.0 < float(metrics["loss"]) < 20.0


def test_vit_forward_and_learning():
    import numpy as np
    import optax
    from ray_tpu.models import (ViT, classification_loss, vit_tiny)

    cfg = vit_tiny()
    model = ViT(cfg)
    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, cfg.num_classes, 8))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), imgs)
    logits = jax.jit(model.apply)(params, imgs)
    assert logits.shape == (8, cfg.num_classes)
    assert logits.dtype == jnp.float32
    # mean pooling variant runs too
    vit_m = ViT(vit_tiny(pool="mean"))
    lm = jax.jit(vit_m.apply)(
        jax.jit(vit_m.init)(jax.random.PRNGKey(0), imgs), imgs)
    assert lm.shape == (8, cfg.num_classes)

    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, g = jax.value_and_grad(
            lambda p: classification_loss(model.apply(p, imgs),
                                          labels))(params)
        upd, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, upd), opt_state, loss

    params, opt_state, first = step(params, opt_state)
    for _ in range(15):
        params, opt_state, loss = step(params, opt_state)
    assert float(loss) < float(first), (first, loss)


def test_vit_sharded_train_step(cpu_mesh_devices):
    """One jitted train step over a data x tensor mesh with the ViT
    TP rules: qkv column-sharded over `tensor`, loss finite."""
    import numpy as np
    import optax
    from ray_tpu.mesh import create_mesh
    from ray_tpu.models import (ViT, classification_loss,
                                vit_sharding_rules, vit_tiny)
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)

    mesh = create_mesh({"data": 2, "tensor": 4})
    cfg = vit_tiny()
    model = ViT(cfg)
    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, cfg.num_classes, 8))
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(0),
                                        imgs[:1]))()
    state = shard_state(
        TrainState.create(params, optax.adamw(1e-3)),
        vit_sharding_rules(fsdp=False), mesh)
    qkv = state.params["params"]["block_0"]["qkv"]["kernel"]
    assert "tensor" in str(qkv.sharding.spec)

    def loss_fn(p, batch):
        return classification_loss(model.apply(p, batch["x"]),
                                   batch["y"])

    step = make_train_step(loss_fn, optax.adamw(1e-3))
    with jax.set_mesh(mesh):
        b = put_batch({"x": imgs, "y": labels}, mesh)
        state, metrics = step(state, b)
        assert np.isfinite(float(metrics["loss"]))
