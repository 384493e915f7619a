"""Fused on-device training (Anakin-style) + multi-seed fleets.

The pool/megastep layers removed the environment from the wall-clock
critical path; this module removes the *learner round-trip*. A train step
(`rl/dqn.make_train_step`, `rl/ppo.make_update_body`) is already a pure
carry → carry function whose env interaction runs through the XLA-resident
pool — so K of them scan into ONE compiled program whose carry (network
params, optimizer state, the replay ring, the pool state and the threefry
key chain) is **donated**: XLA writes each step's new carry into the old
carry's buffers, the 50k-transition replay ring included, and nothing
crosses the host boundary between chunk dispatches
(`analysis/audit.py` lowers this exact program and gates zero
host-transfer ops + full carry donation for the golden ids).

With env_backend="pallas"/"jnp" the env transition inside the scanned body
is the fused megastep kernel (kernels/envstep) — megastep rollout feeding
the learner in the same compiled program, the architecture Jumanji trains
with (PAPERS.md).

Key-chain pinning (the chunk seam): every RNG consumed by a fused chunk is
split from the key *inside the donated carry* — never re-derived host-side
per chunk (the `_rollout_fused` fold_in(key, step) trick would make the
trajectory a function of the chunk size). Consequently `run_fused(chunk=7)`
and `run_fused(chunk=64)` produce bit-identical trajectories, and both
match the undonated host-alternating dispatch loop bit for bit
(tests/test_train_fused.py pins this against committed goldens).

Fleets: because the whole training loop is one pure function of
(initial carry, lr), an entire seeds×lr sweep vmaps into a single compiled
batch — `fleet(env, Fleet(seed, lr), steps)` — whose wall-clock is
sublinear in fleet width (benchmarks/fig2) and whose row f is
bit-identical to the solo run with that row's seed and lr (the Adam update
threads lr as a traced scalar; float32(lr) == the solo path's weak-typed
python float).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.env import Env
from repro.core.registry import make as registry_make

#: the training configurations pinned by committed goldens
#: (tests/golden/train_<algo>_<env>.json), audited by analysis/audit.py and
#: benchmarked by benchmarks/fig2 — "<algo>/<env_id>"
GOLDEN_TRAIN_IDS = ("dqn/CartPole-v1", "dqn/FrozenLake-v0", "ppo/CartPole-v1")

_KEY_SDS = jax.ShapeDtypeStruct((2,), jnp.uint32)


# -- the fused chunk runner ---------------------------------------------------

def fused_train_chunk(step_fn: Callable) -> Callable:
    """Compile `n` train steps into ONE donated device program.

    `step_fn(carry, _) -> (carry, metrics)` is a scan body (the exact one
    the host-alternating path scans); the returned `run_chunk(carry, n)`
    jits `lax.scan(step_fn, carry, length=n)` with the carry donated, so
    the replay ring / optimizer state / pool state are updated in place
    instead of being re-materialized per dispatch. `n` is static — one
    compile per distinct chunk length, as in `dqn.train_compiled`.

    The input carry is consumed (donated): keep using the *returned* carry.
    """

    @functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
    def run_chunk(carry, n: int):
        return jax.lax.scan(step_fn, carry, None, length=n)

    return run_chunk


def _donate_safe(carry):
    """Copy carry leaves that alias one another. Init paths may
    legitimately reuse one array for several carry slots (ppo_init's
    shared zeros did, until fusion surfaced it), but donation requires
    distinct buffers — `f(donate(a), donate(a))` is a runtime error."""
    seen = set()

    def dedupe(x):
        if isinstance(x, jax.Array) and id(x) in seen:
            return jnp.array(x, copy=True)
        seen.add(id(x))
        return x

    return jax.tree.map(dedupe, carry)


def run_fused(step_fn: Callable, state, steps: int, chunk: int = 0):
    """Drive `steps` train steps through donated fused chunks.

    Full chunks plus one remainder chunk — exactly `steps` steps. The RNG
    chain lives in the carry (see module docstring), so the trajectory is
    invariant to `chunk`; metrics come back stacked (T, ...) like the
    host-alternating loop's.
    """
    chunk = min(chunk or steps, steps)
    run_chunk = fused_train_chunk(step_fn)
    state = _donate_safe(state)
    all_metrics = []
    done = 0
    while done < steps:
        n = min(chunk, steps - done)
        state, metrics = run_chunk(state, n)
        all_metrics.append(metrics)
        done += n
    metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs), *all_metrics)
    return state, metrics


# -- multi-seed / multi-hparam fleets -----------------------------------------

class Fleet(NamedTuple):
    """One row per experiment; arrays aligned on the fleet axis (F,)."""

    seed: jax.Array   # (F,) int32 — PRNGKey(seed[f]) seeds row f end to end
    lr: jax.Array     # (F,) float32 — row f's Adam learning rate

    @property
    def width(self) -> int:
        return self.seed.shape[0]


def fleet_grid(seeds, lrs) -> Fleet:
    """Cartesian product seeds × lrs as aligned Fleet rows (row-major)."""
    s = jnp.asarray(seeds, jnp.int32)
    l = jnp.asarray(lrs, jnp.float32)
    ss, ll = jnp.meshgrid(s, l, indexing="ij")
    return Fleet(ss.reshape(-1), ll.reshape(-1))


def _as_fleet(grid, default_lr: float) -> Fleet:
    """Normalize a grid spec: a Fleet, a {"seeds": .., "lrs": ..} dict
    (cartesian product; lrs defaults to the config's lr), or a seed list."""
    if isinstance(grid, Fleet):
        return Fleet(jnp.asarray(grid.seed, jnp.int32),
                     jnp.asarray(grid.lr, jnp.float32))
    if isinstance(grid, dict):
        unknown = set(grid) - {"seeds", "lrs"}
        if unknown:
            raise TypeError(f"unknown fleet grid keys {sorted(unknown)}; "
                            "expected 'seeds' and/or 'lrs'")
        return fleet_grid(grid.get("seeds", [0]), grid.get("lrs", [default_lr]))
    seeds = jnp.asarray(grid, jnp.int32)
    return Fleet(seeds, jnp.full(seeds.shape, default_lr, jnp.float32))


def _algo_parts(env: Env, algo: str, cfg):
    """(cfg, init_row(seed)->state, step_fn(state, _, lr=)->.., row_axes)

    `row_axes` is the fleet's vmap axis for each leaf of a row's state, as
    a pytree prefix: 0 where rows differ, None where every row holds the
    same value at every step.
    """
    if algo == "dqn":
        from repro.rl import dqn as _dqn
        from repro.rl.replay import ReplayState

        cfg = cfg or _dqn.DQNConfig()
        _, apply_fn = _dqn._build_net(env, cfg, jax.random.PRNGKey(0))
        step_fn = _dqn.make_train_step(env, apply_fn, cfg)
        init_row = lambda key: _dqn.dqn_init(env, cfg, key)[0]
        # Every row adds num_envs transitions a step, so the ring's cursor
        # is the same in all of them. Left unbatched, it keeps the ring's
        # write a block at one row: a batched start would make each
        # dynamic_update_slice a scatter.
        row_axes = _dqn.DQNState(*(0,) * len(_dqn.DQNState._fields))._replace(
            replay=ReplayState(0, 0, 0, 0, 0, ptr=None, size=None))
        return cfg, init_row, step_fn, row_axes
    if algo == "ppo":
        from repro.rl import ppo as _ppo

        cfg = cfg or _ppo.PPOConfig()
        body = _ppo.make_update_body(env, cfg)
        step_fn = lambda state, _, lr=None: body(state, lr=lr)
        init_row = lambda key: _ppo.ppo_init(env, cfg, key)
        return cfg, init_row, step_fn, 0
    raise ValueError(f"unknown fleet algo {algo!r}; expected 'dqn' or 'ppo'")


def _fleet_chunk(step_fn: Callable, row_axes) -> Callable:
    """`run_chunk((states, lr), n)`: n train steps of every fleet row, one
    donated program; `row_axes` as `_algo_parts` gives it."""

    def row_body(carry, _):
        state, lr = carry
        state, metrics = step_fn(state, None, lr=lr)
        return (state, lr), metrics

    @functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
    def run_chunk(carry, n: int):
        return jax.vmap(lambda c: jax.lax.scan(row_body, c, None, length=n),
                        in_axes=((row_axes, 0),),
                        out_axes=((row_axes, 0), 0))(carry)

    return run_chunk


def fleet(env: Union[Env, str], grid, steps: int, *, algo: str = "dqn",
          cfg=None, chunk: int = 0):
    """Train a whole seeds×lr fleet as ONE compiled, donated batch.

    `grid` is a `Fleet`, a `{"seeds": [...], "lrs": [...]}` dict (cartesian
    product) or a plain seed list. The entire training loop — init included
    — is vmapped over the fleet axis, so an F-row sweep is one device
    program per chunk rather than F sequential runs; wall-clock is
    sublinear in F (benchmarks/fig2 fleet-scaling rows).

    Determinism contract: row f is bit-identical to the solo
    `train_compiled(env, replace(cfg, lr=lr[f]), steps, PRNGKey(seed[f]))`
    run (tests/test_train_fused.py::test_fleet_rows_match_solo).

    Returns `(states, metrics)` pytrees with a leading (F,) fleet axis;
    DQN metrics are (F, steps), PPO metrics (F, updates).
    """
    if isinstance(env, str):
        env = registry_make(env)
    cfg, init_row, step_fn, row_axes = _algo_parts(env, algo, cfg)
    fl = _as_fleet(grid, cfg.lr)
    run_chunk = _fleet_chunk(step_fn, row_axes)
    states = jax.vmap(lambda s: init_row(jax.random.PRNGKey(s)),
                      out_axes=row_axes)(fl.seed)
    # copy lr into the carry: the chunk donates its whole carry, and the
    # caller's grid.lr must survive the call (states are freshly built here)
    carry = _donate_safe((states, jnp.array(fl.lr, copy=True)))
    chunk = min(chunk or steps, steps)
    all_metrics, done = [], 0
    while done < steps:
        n = min(chunk, steps - done)
        carry, metrics = run_chunk(carry, n)
        all_metrics.append(metrics)
        done += n
    states, _ = carry
    # leaves shared by every row get the (F,) fleet axis back
    states = jax.tree.map(
        lambda axis, sub: sub if axis == 0 else jax.tree.map(
            lambda x: jnp.broadcast_to(x, (fl.width,) + x.shape), sub),
        row_axes, states, is_leaf=lambda axis: axis is None)
    metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                           *all_metrics)
    return states, metrics


# -- golden training configurations (tests / audit / fig2 share these) --------

def golden_train_setup(gid: str):
    """(algo, env_id, cfg, steps) for a committed training-golden id.

    Small but adversarial configs: the DQN ring (96) wraps inside the
    64-step run (128 transitions), learning starts mid-run, epsilon decays
    across it and the target net re-syncs on a non-divisor period — so the
    goldens pin replay wrap-around, the warmup gate, the schedule and the
    sync boundary, not just the happy path.
    """
    if gid not in GOLDEN_TRAIN_IDS:
        raise KeyError(f"unknown golden train id {gid!r}; expected one of "
                       f"{GOLDEN_TRAIN_IDS}")
    algo, env_id = gid.split("/")
    if algo == "dqn":
        from repro.rl.dqn import DQNConfig

        cfg = DQNConfig(num_envs=2, memory_size=96, learn_start=16,
                        batch_size=8, exploration_steps=48,
                        target_update_freq=13)
        return algo, env_id, cfg, 64
    from repro.rl.ppo import PPOConfig

    # 4 updates × 16-step rollouts = 64 env steps per env.
    cfg = PPOConfig(num_envs=4, rollout_len=16, epochs=2, minibatches=2)
    return algo, env_id, cfg, 4


def lower_train_chunk(algo: str, env_id: str, cfg=None, chunk: int = 8):
    """Lower (don't run) the donated fused-train chunk for HLO inspection.

    The audit (`analysis/audit.py` train cells) gates this exact artifact —
    the program `run_fused` dispatches — for zero host-transfer ops and
    full carry donation (replay ring and optimizer state included). Carry
    shapes come from `jax.eval_shape` over the real init path, so nothing
    is allocated. Returns (lowered, abstract_carry).
    """
    env = registry_make(env_id)
    if cfg is None:
        _, _, cfg, _ = golden_train_setup(f"{algo}/{env_id}")
    _, init_row, step_fn, _ = _algo_parts(env, algo, cfg)
    carry = jax.eval_shape(init_row, _KEY_SDS)
    run_chunk = fused_train_chunk(step_fn)
    return run_chunk.lower(carry, chunk), carry
