"""The one runner for every configuration of ``kind: train``: the SPMD
train step exactly as bench.py and examples/02_train_gpt2.py build it
(shard_state / put_batch / make_train_step on a {"data": -1} mesh), fed
a fresh seeded batch through put_batch every step. The model, its loss,
its sharding rules and its plain reference are the configuration's
family's (benchmarks/families/, as ``ctx.family``). ``--trace 2`` runs
the same loop on, after the window's numbers are taken, under a trace
started through the program's control (ray_tpu._private.profiling).
"""
from __future__ import annotations

import time
import types

from benchmarks import parity, trafficgen
from benchmarks.common import (Timer, Tracer, cache_report, log,
                               prepare_trace)

TRACE_SECONDS = 3.0


def run(ctx) -> types.SimpleNamespace:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.mesh import create_mesh
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)
    from ray_tpu.util.compile_cache import enable_compile_cache

    cfg, tr, args, meter = ctx.cfg, ctx.traffic, ctx.args, ctx.meter
    fam = ctx.family
    cache_dir = enable_compile_cache()
    log(f"[cache] {cache_dir} before: {cache_report(cache_dir)}")
    model = fam.model(fam.program_config(cfg))
    batch, seq = int(tr["batch"]), int(tr["seq"])
    devices = jax.devices()[:ctx.chips]
    mesh = create_mesh({"data": -1}, devices=devices)

    with Timer("weights from the seed, one call", meter):
        with jax.default_device(devices[0]):
            params = fam.init_params(model, args.seed)
    batches = trafficgen.ZipfBatches(tr, args.seed, cfg["vocab_size"])

    # step 0 of the reference, on the weights and the batch of step 0,
    # before the train state (which donates them) exists
    with Timer("reference: loss and gradient norm of step 0", meter):
        ref_loss, ref_gnorm = fam.reference_loss_and_grad_norm(
            params, jnp.asarray(batches(0)), cfg)

    tcfg = cfg["train"]
    optimizer = optax.adamw(tcfg["lr"], weight_decay=tcfg["weight_decay"])
    state = shard_state(TrainState.create(params, optimizer),
                        fam.sharding_rules(), mesh)
    train_step = make_train_step(fam.loss_fn(model), optimizer)
    in_flight = int(tr.get("in_flight", 2))
    losses, gnorms = [], []

    with jax.set_mesh(mesh):
        with Timer("warm-up: the train step (step 0)", meter):
            b = put_batch({"ids": batches(0)}, mesh)
            state, m = train_step(state, b)
            loss0, gnorm0 = float(m["loss"]), float(m["grad_norm"])

        tracer = None
        t_open = time.monotonic()
        t_close = t_open + float(args.seconds)
        if args.trace == 1:
            tracer = Tracer(ctx.trace_dir, t_open, args.seconds,
                            TRACE_SECONDS)
            tracer.start()
        window_meter = meter.snapshot()
        step = 1
        pending = []
        while time.monotonic() < t_close:
            b = put_batch({"ids": batches(step)}, mesh)
            state, m = train_step(state, b)
            pending.append(m)
            losses.append(m["loss"])
            gnorms.append(m["grad_norm"])
            step += 1
            if len(pending) > in_flight:
                jax.block_until_ready(pending.pop(0)["loss"])
        jax.block_until_ready(state)
        t_end = time.monotonic()
        in_window = meter.since(window_meter)
    if tracer is not None:
        tracer.join(timeout=120)
    losses = [float(x) for x in losses]
    steps = len(losses)
    tokens = steps * batch * seq
    elapsed = t_end - t_open
    check = parity.train_rule(loss0, gnorm0, ref_loss, ref_gnorm,
                              [loss0] + losses)
    log(f"[correct] {check}")
    log(f"[window] {steps} steps of {batch} x {seq} in {elapsed:.3f} s "
        f"({1e3 * elapsed / max(steps, 1):.2f} ms a step); programs "
        f"built or loaded inside the window: "
        f"{in_window['programs']:.0f} (must be 0)")
    e2e = {"train_tokens_per_s": tokens / elapsed / ctx.chips,
           "setup_s": t_open - ctx.t_process}
    log(f"[cache] after: {cache_report(cache_dir, top=6)}")
    run_ = types.SimpleNamespace(
        kind="train", cfg=cfg, family=fam, traffic=tr, chips=ctx.chips,
        peaks=ctx.peaks, seconds=float(args.seconds),
        window=(t_open, t_end), e2e=e2e, attempted=steps, failed=0,
        correct=bool(check["ok"]),
        compiles_in_window=int(in_window["programs"]),
        steps=steps, batch=batch, seq=seq, losses=losses,
        trace_span=tracer.span if tracer else None,
        trace=None, shutdown=lambda: None)
    if args.trace == 2:
        # the window is closed and its numbers are taken: the same loop
        # runs on with the next batches under a trace. One start and
        # stop first, thrown away, so that the profiler's first start
        # falls into no number.
        from ray_tpu._private import profiling
        prepare_trace(ctx.trace_dir, profiling.start_device_trace,
                      profiling.stop_device_trace)
        with jax.set_mesh(mesh):
            profiling.start_device_trace(ctx.trace_dir)
            t_stop = time.monotonic() + TRACE_SECONDS
            first, pending = step, []
            while time.monotonic() < t_stop:
                b = put_batch({"ids": batches(step)}, mesh)
                state, m = train_step(state, b)
                pending.append(m)
                step += 1
                if len(pending) > in_flight:
                    jax.block_until_ready(pending.pop(0)["loss"])
            jax.block_until_ready(state)
            run_.trace_span = profiling.stop_device_trace()
        t0_, t1_ = run_.trace_span
        log(f"[traced phase] {step - first} steps in {t1_ - t0_:.3f} s "
            f"after the window = "
            f"{(step - first) * batch * seq / (t1_ - t0_) / ctx.chips:.0f}"
            f" tokens/s per chip with the profiler on")
        run_.trace_dir = ctx.trace_dir
    return run_
