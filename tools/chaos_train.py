"""Chaos harness: seeded faults against a real elastic training run.

Runs a deterministic DataParallelTrainer fit (durable async
checkpoints via air.CheckpointManager, heartbeat gang supervision,
elastic preemption resume) while a seeded ChaosInjector
(train/chaos.py) fires worker kills, hangs, slice preemptions with a
grace window, and torn-checkpoint litter at scheduled training steps.

After the run it PROVES the preemption-tolerance contract:

- loss-curve continuity: every reported loss equals the value a
  deterministic replay of the update rule produces for that step —
  resumed state is byte-equivalent to checkpointed state;
- exactly-once steps: no step appears in the final metrics history
  twice (restart rollback) and none is missing (step-aligned resume);
- bounded loss of progress: no restart lost more than one checkpoint
  interval of steps;
- the elastic path actually exercised: the gang shrank below its
  requested size after the preemption and grew back when capacity
  returned.

Writes a TRAIN_CHAOS json artifact gated by
tools/check_bench_schema.py (train_chaos family).

Run: python tools/chaos_train.py [--seed N] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ACCEL = "v5e-1"


def chaos_train_loop(config):
    """The workload under test: a deterministic recurrence whose loss
    at step k is a pure function of correct resume (w_k = 0.9*w_{k-1}
    + k), checkpointed asynchronously by rank 0 every
    ``checkpoint_interval`` steps. Reports a lightweight dict marker
    {"step": N} for each COMMITTED checkpoint so the trainer's
    restart rollback tracks durable progress; the real state lives in
    the CheckpointManager's step directories, and resume goes through
    ``latest_complete()`` — the deep-verifying resolver that skips
    torn directories."""
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.air.checkpoint_manager import (CheckpointManager,
                                                step_dir_name)
    from ray_tpu.train import chaos

    rank = session.get_world_rank()
    ctrl = config["control_dir"]
    root = config["ckpt_root"]
    interval = config["checkpoint_interval"]
    total = config["steps_total"]
    step_time = config.get("step_time_s", 0.02)
    # Fence: record this attempt as started. Any zombie loop from a
    # torn-down gang (an in-process kill cannot stop a thread) now
    # raises StaleGeneration at its next step / pre-commit check.
    att = session.get_attempt()
    chaos.fence(ctrl, att)

    manager = CheckpointManager(
        root, keep_last_k=config.get("keep_last_k"),
        pre_commit_hook=lambda s: chaos.check_generation(ctrl, att))
    try:
        # Resume AUTHORITY is the trainer-acknowledged marker: history
        # was rolled back to exactly its step, so resuming anywhere
        # else would duplicate or skip reported steps (a commit can
        # land durably a poll before its marker reaches the trainer).
        # The deep-verifying resolver is still consulted every
        # restart: it must skip torn litter and land on a commit at
        # least as new as the marker — resolver and marker disagreeing
        # would mean the durable tree lost acknowledged state.
        marker = session.get_checkpoint()
        start = 0
        w = np.zeros(4)
        if marker is not None:
            m = int(marker.to_dict()["step"])
            ck = manager.latest_complete()
            assert ck is not None, \
                "trainer holds marker %d but no complete checkpoint" % m
            state = Checkpoint.from_directory(
                os.path.join(root, step_dir_name(m))).to_dict()
            w = np.asarray(state["w"])
            start = m + 1
        if rank == 0:
            chaos.RESUMES.append(start)
        pending = []
        for k in range(start, total):
            chaos.check_generation(ctrl, att)
            chaos.hang_gate(ctrl, rank)
            w = 0.9 * w + k
            loss = float(np.sum(w))
            time.sleep(step_time)
            if rank == 0:
                marker = None
                for s, h in list(pending):
                    if h.done():
                        pending.remove((s, h))
                        if h.error is not None:
                            raise h.error
                        marker = s
                if k % interval == 0:
                    pending.append((k, manager.save_async(
                        {"w": np.array(w, copy=True), "step": k}, k)))
                session.report(
                    {"loss": loss, "step": k},
                    checkpoint=(Checkpoint.from_dict({"step": marker})
                                if marker is not None else None))
            else:
                session.heartbeat()
            if session.preempted():
                # Drain: flush state NOW (synchronously — the slice
                # dies when the grace window closes), hand the trainer
                # a marker for it, and return.
                if rank == 0:
                    manager.save({"w": np.array(w, copy=True),
                                  "step": k}, k)
                    session.report(
                        {"drained": True},
                        checkpoint=Checkpoint.from_dict({"step": k}))
                return
    finally:
        manager.close()


def expected_losses(total):
    """Replay the update rule: ground truth for loss continuity."""
    import numpy as np
    w = np.zeros(4)
    out = []
    for k in range(total):
        w = 0.9 * w + k
        out.append(float(np.sum(w)))
    return out


def run_chaos(seed=45, steps_total=120, checkpoint_interval=6,
              workers=2, min_workers=1, step_time_s=0.03,
              progress_deadline_s=0.6, keep_last_k=4,
              grace_s=2.0, stockout_s=0.35, workdir=None):
    """One seeded chaos run. Returns (artifact, hard-assertion list
    that all passed). Raises AssertionError when the run violates the
    preemption-tolerance contract."""
    import numpy as np

    import ray_tpu
    from ray_tpu.air import (FailureConfig, RunConfig, ScalingConfig)
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.autoscaler.node_provider import SimulatedTPUCloud
    from ray_tpu.train import chaos
    from ray_tpu.train.trainer import DataParallelTrainer

    owns_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_train_")
    ctrl = os.path.join(workdir, "control")
    root = os.path.join(workdir, "ckpts")
    os.makedirs(ctrl, exist_ok=True)
    os.makedirs(root, exist_ok=True)
    chaos.reset_measurements()
    # Warm the directory-commit path (orbax registry, jax dispatch):
    # the first commit in a process is orders slower than steady state,
    # which would starve the first checkpoint interval and turn the
    # first injected fault into an unbounded-progress-loss restart.
    Checkpoint.from_dict({"w": np.zeros(1), "step": 0}).to_directory(
        os.path.join(workdir, "warmup"))

    schedule = chaos.make_schedule(seed, steps_total,
                                   checkpoint_interval,
                                   grace_s=grace_s,
                                   stockout_s=stockout_s)
    # One simulated slice per gang member; capacity capped at the gang
    # size so a preempted slice's replacement only goes READY once the
    # victim is really gone AND the stockout window has passed.
    cloud = SimulatedTPUCloud(capacity={ACCEL: workers})
    slices = []
    for i in range(workers):
        name = f"chaos-slice-{i}"
        cloud.create_queued_resource(name, ACCEL)
        cloud.describe(name)            # promote to READY
        slices.append(name)

    trainer = DataParallelTrainer(
        chaos_train_loop,
        train_loop_config={
            "control_dir": ctrl, "ckpt_root": root,
            "checkpoint_interval": checkpoint_interval,
            "steps_total": steps_total, "step_time_s": step_time_s,
            "keep_last_k": keep_last_k,
        },
        scaling_config=ScalingConfig(num_workers=workers,
                                     min_workers=min_workers),
        run_config=RunConfig(failure_config=FailureConfig(
            max_failures=10,
            worker_progress_deadline_s=progress_deadline_s)),
        elastic_capacity_fn=lambda: cloud.ready_slice_count(ACCEL),
        elastic_wait_s=20.0)

    injector = chaos.ChaosInjector(
        trainer, schedule, ctrl, root, checkpoint_interval,
        cloud=cloud, slices=slices, accelerator_type=ACCEL).start()
    t0 = time.time()
    try:
        result = trainer.fit()
    finally:
        injector.stop()
    wall = time.time() - t0

    assert result.error is None, f"chaos run failed: {result.error}"
    history = result.metrics_history
    rows = [m for m in history
            if isinstance(m, dict) and isinstance(m.get("step"), int)
            and not isinstance(m.get("step"), bool)]
    steps_seen = [m["step"] for m in rows]
    duplicate_steps = len(steps_seen) - len(set(steps_seen))
    missing = sorted(set(range(steps_total)) - set(steps_seen))
    expected = expected_losses(steps_total)
    loss_err = max(abs(m["loss"] - expected[m["step"]])
                   for m in rows)
    # Lost progress per restart: the injector records the last
    # reported step at each gang teardown; rank 0 records every
    # attempt's resume step. Pairing them in order gives how much
    # reported-but-not-durable work each restart replayed.
    resumes = list(chaos.RESUMES)
    fails = list(injector.fail_steps)
    lost = [max(0, fails[i] - (resumes[i + 1] - 1))
            for i in range(min(len(fails), len(resumes) - 1))]
    max_lost = max(lost, default=0)
    counts = injector.injected_counts()

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001
        sha = None

    artifact = {
        "notes": (
            "Seeded chaos against a live elastic training fit: "
            "worker kill, heartbeat-detected hang, slice preemption "
            "with a grace-window drain + post-stockout regrow, and a "
            "torn checkpoint the resume resolver must skip. "
            "Invariants checked: exactly-once steps, loss-curve "
            "continuity under deterministic replay, <= one "
            "checkpoint interval of progress lost per restart."),
        "seed": seed,
        "steps_total": steps_total,
        "checkpoint_interval": checkpoint_interval,
        "workers": workers,
        "min_workers": min_workers,
        "step_time_s": step_time_s,
        "progress_deadline_s": progress_deadline_s,
        "schedule": [e.as_dict() for e in schedule],
        "injected": counts,
        "restarts": trainer.restarts,
        "preemptions": trainer.preemptions,
        "resizes": trainer.resizes,
        "world_sizes": trainer.world_sizes,
        "resume_steps": resumes,
        "fail_steps": fails,
        "lost_steps_per_restart": lost,
        "duplicate_steps": duplicate_steps,
        "missing_steps": len(missing),
        "max_lost_steps": max_lost,
        "loss_max_abs_err": loss_err,
        "final_step": max(steps_seen),
        "final_loss": rows[-1]["loss"],
        "elastic": {"min_world": min(trainer.world_sizes),
                    "max_world": max(trainer.world_sizes)},
        "cloud_preemptions": len(cloud.preemptions),
        "wall_s": round(wall, 2),
        "git_sha": sha,
    }

    # The contract, asserted at the source (the schema checker
    # re-refuses the same violations on the checked-in artifact).
    for kind in chaos.KINDS:
        assert counts[kind] >= 1, f"schedule never fired a {kind}"
    assert duplicate_steps == 0, \
        f"{duplicate_steps} duplicate steps: {sorted(steps_seen)}"
    assert not missing, f"missing steps {missing[:10]}"
    assert max_lost <= checkpoint_interval, \
        f"lost {max_lost} steps > interval {checkpoint_interval}"
    assert loss_err < 1e-6, f"loss diverged by {loss_err}"
    assert artifact["final_step"] == steps_total - 1
    assert trainer.preemptions >= 1, "preemption never drained"
    assert artifact["elastic"]["min_world"] < \
        artifact["elastic"]["max_world"], \
        "gang never ran below requested size (elastic shrink unseen)"
    assert trainer.resizes >= 1, \
        "gang never regrew after capacity returned"

    if owns_workdir:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    return artifact


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--interval", type=int, default=6)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--min-workers", type=int, default=1)
    ap.add_argument("--step-time", type=float, default=0.03)
    ap.add_argument("--deadline", type=float, default=0.6)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import ray_tpu
    ray_tpu.init()
    artifact = run_chaos(
        seed=args.seed, steps_total=args.steps,
        checkpoint_interval=args.interval, workers=args.workers,
        min_workers=args.min_workers, step_time_s=args.step_time,
        progress_deadline_s=args.deadline)
    print(json.dumps(artifact, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        # Self-gate: the artifact must pass its own schema family.
        from tools import check_bench_schema as cbs
        problems = []
        cbs.check_file(args.out, problems)
        for p in problems:
            print(f"SCHEMA FAIL {p}")
        if problems:
            sys.exit(1)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
