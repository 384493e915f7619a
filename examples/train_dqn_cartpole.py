"""End-to-end driver: DQN on CartPole-v1 with compiled environments.

Reproduces the paper's §V-B result shape on this host: the paper's Table I
hyperparameters train ~30 % faster on CaiRL envs than on interpreted envs;
the tuned config solves CartPole (500/500) in under a minute of wall-clock.

Run: PYTHONPATH=src python examples/train_dqn_cartpole.py [--steps 60000]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.cairl_dqn import TUNED
from repro.core import make
from repro.rl.dqn import greedy_returns, train_compiled
from repro.sustainability.impact import ImpactTracker
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=60000)
args = ap.parse_args()

env = make("CartPole-v1")
print(f"training DQN (tuned config) for {args.steps} compiled steps ...")
with ImpactTracker() as tracker:
    t0 = time.time()
    state, apply_fn, metrics = train_compiled(env, TUNED, args.steps,
                                              jax.random.PRNGKey(0), chunk=10000)
    train_s = time.time() - t0

rets = np.asarray(greedy_returns(env, apply_fn, state.params, jax.random.PRNGKey(7)))
print(f"wall-clock        : {train_s:.1f}s "
      f"({args.steps * TUNED.num_envs / train_s:,.0f} transitions/s incl. learning)")
print(f"train return (ema): {float(metrics['return'][-1]):.1f}")
print(f"greedy eval return: {rets.mean():.1f} ± {rets.std():.1f}  (solved = 500)")
print(f"energy            : {tracker.impact.energy_mwh:.3f} mWh, "
      f"CO2 {tracker.impact.co2_kg:.2e} kg (impact tracker, Table II method)")
