"""Quickstart: the paper's Listing 2, verbatim shape, plus the compiled fast path.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax

from repro import cairl  # <- the one-line migration the paper advertises
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# ---- Listing 2: classic Gym loop (drop-in) ---------------------------------
e = cairl.make("CartPole-v1")          # was: gym.make("CartPole-v1")
for ep in range(3):
    e.reset()
    term, steps, ret = False, 0, 0.0
    while not term and steps < 200:
        steps += 1
        s1, r, term, info = e.step(e.action_space.sample())
        obs = e.render()
        ret += r
    print(f"episode {ep}: {steps} steps, return {ret:.0f}, frame {obs.shape}")

# ---- make_vec: batched Gym-style stepping, state lives on device ------------
# The unified vector frontend: one constructor for every pool backend
# (backend="auto" picks the fused megastep engine when the id supports it).
pool = cairl.make_vec("CartPole-v1", 256, backend="vmap")
obs = pool.reset(seed=0)                       # (256, 4), device-resident
for i in range(100):
    obs, rew, done, info = pool.step(pool.sample_actions(i))
print(f"\nEnvPool: stepped {pool.num_envs} envs 100x; "
      f"mean reward {float(rew.mean()):.2f}, {int(done.sum())} resets this step")

# ---- the run() fast path: whole rollout as ONE device program ---------------
steps, batch = 2000, 256
rew, episodes, _ = pool.rollout(steps, jax.random.PRNGKey(0))  # compile
jax.block_until_ready(rew)
t0 = time.perf_counter()
rew, episodes, _ = pool.rollout(steps, jax.random.PRNGKey(1))
jax.block_until_ready(rew)
dt = time.perf_counter() - t0
print(f"compiled rollout: {steps * batch:,} env steps in {dt:.3f}s "
      f"= {steps * batch / dt:,.0f} steps/s across {batch} envs")
print(f"episodes completed on-device: {int(episodes.sum())}")
