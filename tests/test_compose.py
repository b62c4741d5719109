"""Composed-parallelism tests (SURVEY §7 step 7: PP/SP/EP/DP as
mesh-axis configs on JaxTrainer).

The single-process tests build {pipeline, sequence, data} meshes on the
8-device CPU fixture and check (a) the composed forward matches a
dense single-device reference, (b) training decreases the loss with
gradients flowing through the pipeline ppermutes AND the ring
attention rotation. The gang test runs the same composition across a
2-process jax.distributed gang with a dcn axis — the VERDICT r5 done
bar: a mixed {dcn, pipeline, data, sequence} mesh, loss decreasing,
via the public JaxTrainer API.
"""
import numpy as np
import pytest


def _mesh(axes):
    from ray_tpu.mesh.device_mesh import create_mesh
    return create_mesh(axes)


def _toy_stage_fn(with_ring=True):
    """One pipeline stage: linear mix + (optionally) ring attention
    over the sequence axis + residual."""
    import jax
    import jax.numpy as jnp

    def stage_fn(params, x):              # x: [B, T, D] local
        h = jnp.einsum("btd,de->bte", x, params["w"]) + params["b"]
        h = jax.nn.gelu(h)
        if with_ring:
            from ray_tpu.parallel.sequence import ring_attention
            B, T, D = h.shape
            qkv = h.reshape(B, T, 1, D)   # one head
            a = ring_attention(qkv, qkv, qkv, axis_name="sequence",
                               causal=True)
            h = h + a.reshape(B, T, D)
        return x + h

    return stage_fn


def _make_params(rng, S, D):
    import jax.numpy as jnp
    return {
        "w": jnp.asarray(rng.randn(S, D, D) * 0.05, jnp.float32),
        "b": jnp.zeros((S, D), jnp.float32),
    }


def _dense_reference(params, x, S):
    """Single-device replay of the composed program."""
    import jax
    import jax.numpy as jnp
    h = jnp.asarray(x)
    for s in range(S):
        p = {"w": params["w"][s], "b": params["b"][s]}
        z = jnp.einsum("btd,de->bte", h, p["w"]) + p["b"]
        z = jax.nn.gelu(z)
        B, T, D = z.shape
        q = z.reshape(B, T, 1, D)
        scale = 1.0 / (D ** 0.5)
        sco = jnp.einsum("bqhd,bkhd->bhqk", q, q) * scale
        mask = jnp.tril(jnp.ones((T, T), bool))
        sco = jnp.where(mask[None, None], sco, -1e30)
        a = jax.nn.softmax(sco, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", a, q).reshape(B, T, D)
        h = h + (z + att)
    return h


def test_composed_forward_matches_dense():
    import jax
    import jax.numpy as jnp
    from ray_tpu.train.compose import (make_composed_loss,
                                       put_composed_batch,
                                       shard_stage_params)
    mesh = _mesh({"pipeline": 2, "sequence": 2, "data": 2})
    S, B, T, D, M = 2, 4, 8, 16, 2
    rng = np.random.RandomState(0)
    params = _make_params(rng, S, D)
    x = np.asarray(rng.randn(B, T, D), np.float32)
    y = np.asarray(rng.randn(B, T, D), np.float32)

    def loss_fn(out, batch):
        d = (out - batch[1]) ** 2
        return jnp.sum(d), jnp.asarray(d.size, jnp.float32)

    loss = make_composed_loss(_toy_stage_fn(), loss_fn, mesh,
                              num_microbatches=M)
    got = float(loss(shard_stage_params(params, mesh),
                     put_composed_batch((x, y), mesh)))

    ref_out = _dense_reference(params, x, S)
    want = float(jnp.mean((ref_out - y) ** 2))
    assert got == pytest.approx(want, rel=2e-4), (got, want)


def test_composed_training_loss_decreases():
    import jax.numpy as jnp
    import optax
    from ray_tpu.train.compose import (make_composed_train_step,
                                       put_composed_batch)
    mesh = _mesh({"pipeline": 2, "sequence": 2, "data": 2})
    S, B, T, D, M = 2, 8, 8, 8, 2
    rng = np.random.RandomState(1)
    params = _make_params(rng, S, D)
    x = np.asarray(rng.randn(B, T, D), np.float32)
    y = x * 0.5 + 0.1

    def loss_fn(out, batch):
        d = (out - batch[1]) ** 2
        return jnp.sum(d), jnp.asarray(d.size, jnp.float32)

    step, state = make_composed_train_step(
        _toy_stage_fn(), loss_fn, optax.adam(3e-3), mesh, params,
        num_microbatches=M)
    batch = put_composed_batch((x, y), mesh)
    losses = []
    for _ in range(40):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.5, losses[::8]


@pytest.mark.slow      # 13 s: a two-process gang composing dcn x pipeline x sequence
def test_composed_gang_dcn_pipeline_sequence():
    """VERDICT r5 #5 done bar: JaxTrainer with a mixed
    {dcn, pipeline, data, sequence} mesh spanning a 2-process gang;
    the composed step trains and the loss decreases."""
    import ray_tpu._private.worker as worker_mod
    from ray_tpu.runtime import Cluster
    if worker_mod.is_initialized():
        worker_mod.shutdown()
    with Cluster(num_workers=2, resources_per_worker={"CPU": 2}):
        from ray_tpu.air import session
        from ray_tpu.train import JaxTrainer, ScalingConfig

        def loop(config):
            import jax
            import jax.numpy as jnp
            import numpy as onp
            import optax
            from ray_tpu.train.compose import (make_composed_train_step,
                                               put_composed_batch)
            mesh = session.get_mesh()
            rank = session.get_world_rank()
            S, D, M = int(mesh.shape["pipeline"]), 8, 2
            rng = onp.random.RandomState(7)
            params = {
                "w": jnp.asarray(rng.randn(S, D, D) * 0.05, jnp.float32),
                "b": jnp.zeros((S, D), jnp.float32),
            }

            def stage_fn(p, x):
                from ray_tpu.parallel.sequence import ring_attention
                h = jnp.einsum("btd,de->bte", x, p["w"]) + p["b"]
                h = jax.nn.gelu(h)
                B, T, Dm = h.shape
                qkv = h.reshape(B, T, 1, Dm)
                a = ring_attention(qkv, qkv, qkv,
                                   axis_name="sequence", causal=True)
                return x + h + a.reshape(B, T, Dm)

            def loss_fn(out, batch):
                d = (out - batch[1]) ** 2
                return jnp.sum(d), jnp.asarray(d.size, jnp.float32)

            step, state = make_composed_train_step(
                stage_fn, loss_fn, optax.adam(3e-3), mesh, params,
                num_microbatches=M)
            # per-host local batch shard (B_local x T_local layout)
            local = onp.random.RandomState(100 + rank)
            xl = onp.asarray(local.randn(8, 8, D), onp.float32)
            yl = xl * 0.5 + 0.1
            losses = []
            for _ in range(60):
                batch = put_composed_batch((xl, yl), mesh)
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            session.report({
                "first_loss": losses[0], "last_loss": losses[-1],
                "n_procs": jax.process_count(),
                "mesh": {k: int(v) for k, v in mesh.shape.items()
                         if v > 1},
            })

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(
                num_workers=2,
                mesh={"dcn": 2, "pipeline": 2, "data": 2,
                      "sequence": 2},
                jax_distributed=True,
                placement_strategy="STRICT_SPREAD")).fit()
        assert result.ok, result.error
        m = result.metrics
        assert m["n_procs"] == 2
        assert m["mesh"] == {"dcn": 2, "pipeline": 2, "data": 2,
                             "sequence": 2}
        assert m["last_loss"] < m["first_loss"] * 0.5, m


def test_composed_with_expert_all_to_all():
    """EP inside the composed step: the stage function routes tokens
    through experts sharded over the `expert` axis with a manual
    all_to_all — proving the fourth strategy composes in the same
    shard_map'd train step (PP x EP x DP here)."""
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.train.compose import (make_composed_train_step,
                                       put_composed_batch)
    mesh = _mesh({"pipeline": 2, "expert": 2, "data": 2})
    S, B, T, D, M, E = 2, 8, 4, 8, 2, 2
    rng = np.random.RandomState(3)
    params = {
        "w": jnp.asarray(rng.randn(S, D, D) * 0.05, jnp.float32),
        # per-stage, per-LOCAL-expert FFN weight [S, E_local=1, D, D]
        "we": jnp.asarray(rng.randn(S, 1, D, D) * 0.05, jnp.float32),
    }

    def stage_fn(p, x):
        # x: [b_local, T, D]; one expert per `expert`-axis member.
        h = jax.nn.gelu(jnp.einsum("btd,de->bte", x, p["w"]))
        b, t, d = h.shape
        # static round-robin routing: split local tokens in two, send
        # half to each expert via all_to_all (capacity-1 routing; the
        # collective plumbing + grads are what this test exercises)
        toks = h.reshape(b * t, d)
        half = toks.shape[0] // 2
        send = toks.reshape(2, half, d)
        recv = jax.lax.all_to_all(send, "expert", split_axis=0,
                                  concat_axis=0, tiled=False)
        # apply THIS member's expert FFN to everything it received
        out = jax.nn.gelu(
            jnp.einsum("shd,df->shf", recv, p["we"][0]))
        back = jax.lax.all_to_all(out, "expert", split_axis=0,
                                  concat_axis=0, tiled=False)
        return x + back.reshape(b, t, d)

    def loss_fn(out, batch):
        diff = (out - batch[1]) ** 2
        return jnp.sum(diff), jnp.asarray(diff.size, jnp.float32)

    x = np.asarray(rng.randn(B, T, D), np.float32)
    step, state = make_composed_train_step(
        stage_fn, loss_fn, optax.adam(5e-3), mesh, params,
        num_microbatches=M)
    batch = put_composed_batch((x, x * 0.5), mesh)
    losses = []
    for _ in range(40):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])
