"""PPO on the Multitask environment (the paper's flagship Flash game, §IV-C).

Rollout collection runs as one compiled program per update (the `run()`
fast path); shows the learning signal well above the random baseline.

Run: PYTHONPATH=src python examples/ppo_multitask.py [--updates 40]
"""
import argparse
import time

import jax
import numpy as np

from repro.core import make
from repro.pool import make_vec
from repro.rl.ppo import PPOConfig, train
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--updates", type=int, default=40)
args = ap.parse_args()

env = make("Multitask-v0")

rew, eps, _ = make_vec(env, 16).rollout(2000, jax.random.PRNGKey(1))
random_return = float(rew.sum() / max(int(eps.sum()), 1))
print(f"random policy return: {random_return:.1f}")

cfg = PPOConfig(num_envs=16, rollout_len=128, epochs=3, minibatches=4, lr=3e-4)
t0 = time.time()
state, metrics = train(env, cfg, args.updates, jax.random.PRNGKey(0))
rets = np.asarray(metrics["return"])
print(f"PPO {args.updates} updates in {time.time()-t0:.1f}s "
      f"({args.updates * cfg.num_envs * cfg.rollout_len / (time.time()-t0):,.0f} steps/s)")
print(f"return trajectory: first {rets[0]:.1f} -> best {rets.max():.1f} "
      f"(alive-bonus env; higher = survives longer)")
