"""RL with SAC: continuous-control training on Pendulum swing-up.

Rollout workers are CPU actors sampling with the current stochastic
policy; the learner is one jitted update (twin soft-Q critics + actor
+ auto-tuned temperature, TPU when present).

Run:
  JAX_PLATFORMS=cpu python examples/11_rl_sac_pendulum.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))      # repo root (run from anywhere)

import ray_tpu
from ray_tpu.rllib import SACConfig

ray_tpu.init()

# SAC wants a high update-to-env-step ratio (~0.6 here): 400 env
# steps and 256 gradient updates per iteration.
algo = (SACConfig()
        .environment(env="Pendulum")
        .rollouts(num_rollout_workers=2, rollout_fragment_length=200)
        .training(lr=1e-3, learning_starts=500, train_batch_size=256,
                  num_sgd_iter_per_step=256, hidden_size=128)
        .debugging(seed=0)
        .build())

try:
    for i in range(40):
        result = algo.train()
        if (i + 1) % 5 == 0:
            print(f"iter {result['training_iteration']:2d}  "
                  f"reward_mean={result['episode_reward_mean']:8.1f}  "
                  f"alpha={result['alpha']:.3f}  "
                  f"buffer={result['buffer_size']}")

    # Deterministic eval with the learned mean policy: solved
    # swing-up scores around -100..-250; random is ~-1200.
    from ray_tpu.rllib.env import PendulumEnv

    env = PendulumEnv()
    returns = []
    for ep in range(5):
        obs, done, total = env.reset(seed=100 + ep), False, 0.0
        while not done:
            obs, rew, done, _ = env.step(algo.compute_action(obs))
            total += rew
        returns.append(round(total))
    print("deterministic eval returns:", returns)
finally:
    algo.stop()
    ray_tpu.shutdown()
