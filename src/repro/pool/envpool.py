"""EnvPool-style batched environment execution engine.

EnvPool (Weng et al., 2022) showed that the multiplier after eliminating
interpreter overhead (the CaiRL claim) is a *pooled*, batched execution
engine behind one vectorized API. Here the pool is XLA-resident: the
batched env state is a device pytree that never crosses the host boundary,
`step` is a single compiled program with the previous state's buffers
donated, and the whole pool can be lowered *into* a training program via
`xla()` (the analogue of EnvPool's XLA API) so rollout collection and
learning fuse into one device program.

Two surfaces:

  - Gym-style stateful:  `obs = pool.reset(seed)`,
                         `obs, rew, done, info = pool.step(actions)`.
    State lives on device between calls; the step is jit-compiled with
    `donate_argnums` so XLA reuses the previous state's buffers in place.

  - XLA-resident pure:   `h = pool.xla()`, `carry = h.init(key)`,
                         `carry, out = h.step(carry, actions[, key])`.
    Pure functions of an explicit carry — scannable, vmappable, and the
    canonical batching layer the RL algorithms (rl/dqn.py, rl/ppo.py)
    are built on. Passing an explicit per-step `key` gives callers full
    control of the RNG stream (the carry key is used when omitted).

`EnvPool` is backed by `Vec(AutoReset(env))`: autoreset re-enters `reset`
inside the program on `done` (pre-reset obs surfaced as
`info["terminal_obs"]`), and `Vec` vmaps the whole stack across the batch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.env import Env, Timestep, supports_fused_step
from repro.core.registry import make as registry_make
from repro.core.spaces import sample_batch
from repro.core.wrappers import AutoReset, Vec

#: step-engine backends: "vmap" scans Vec(AutoReset(env)).step; the fused
#: family routes stepping through the megastep kernel (kernels/envstep) —
#: "pallas" auto-dispatches (Pallas on TPU, jnp rows elsewhere),
#: "pallas_interpret"/"jnp" force the interpreter / reference paths.
FUSED_BACKENDS = ("pallas", "pallas_interpret", "jnp")


class PoolState(NamedTuple):
    """XLA-resident pool carry. Everything stays on device across steps."""

    env_state: Any          # Vec(AutoReset(env)) state pytree, leading dim B
    obs: jax.Array          # (B, ...) current observation
    key: jax.Array          # fallback RNG stream for key-less stepping


class PoolStep(NamedTuple):
    """One batched transition (post-autoreset obs; terminal obs in info)."""

    obs: jax.Array          # (B, ...)
    reward: jax.Array       # (B,)
    done: jax.Array         # (B,)
    info: Dict[str, jax.Array]


class XlaPool(NamedTuple):
    """Pure-function handle for in-graph use (EnvPool's XLA API analogue)."""

    init: Callable[[jax.Array], PoolState]
    step: Callable[..., Tuple[PoolState, PoolStep]]
    step_many: Callable[..., Tuple[PoolState, PoolStep]]


class EnvPool:
    """Batched pool of one env type: `Vec(AutoReset(env), num_envs)` + jit.

    >>> pool = EnvPool("CartPole-v1", num_envs=256)
    >>> obs = pool.reset(seed=0)                  # (256, 4) on device
    >>> obs, rew, done, info = pool.step(actions) # one compiled dispatch

    backend="pallas" swaps the scan-of-vmap-step inner loop for the fused
    megastep kernel (kernels/envstep): `step` becomes one kernel launch, and
    `rollout`/`step_many` fuse `unroll` env steps per launch. Trajectories
    match the vmap backend (exact for int/bool fields, float rounding only
    where compilers reassociate). Requires fused-spec support
    (`core.env.supports_fused_step`); "pallas" resolves to the Pallas kernel
    on TPU and the row-major jnp reference elsewhere, "pallas_interpret" and
    "jnp" pin the interpreter / reference paths (tests, debugging).
    """

    def __init__(self, env: Union[Env, str], num_envs: int,
                 backend: str = "vmap", unroll: int = 1, **env_kwargs):
        if isinstance(env, str):
            env = registry_make(env, **env_kwargs)
        self.env = env
        self.num_envs = int(num_envs)
        self.backend = backend
        self.unroll = max(int(unroll), 1)
        if backend == "vmap":
            self._kernel_backend = None
        elif backend in FUSED_BACKENDS:
            self._kernel_backend = "auto" if backend == "pallas" else backend
            if not supports_fused_step(env):
                raise ValueError(
                    f"backend={backend!r} needs fused megastep support, but "
                    f"{env.name} has none (see repro.kernels.envstep); use "
                    "backend='vmap'")
        else:
            raise ValueError(f"unknown pool backend {backend!r}; expected "
                             f"'vmap' or one of {FUSED_BACKENDS}")
        self.venv = Vec(AutoReset(env), self.num_envs)
        self._carry: Optional[Tuple[Any, jax.Array]] = None  # (env_state, key)
        self._obs: Optional[jax.Array] = None
        # Stateful fast path: donate (env_state, key) so XLA writes the new
        # state into the old state's buffers. obs/reward/done outputs are NOT
        # part of the donated carry, so they stay valid across later steps.
        self._jit_reset = jax.jit(self._stateful_reset)
        self._jit_step = jax.jit(self._stateful_step, donate_argnums=(0,))
        self._jit_step_key = jax.jit(self._stateful_step_key,
                                     donate_argnums=(0,))
        self._rollout_cache: Dict[Tuple[int, bool], Callable] = {}

    # -- spaces / metadata ---------------------------------------------------
    @property
    def observation_space(self):
        return self.env.observation_space

    @property
    def action_space(self):
        return self.env.action_space

    def __len__(self) -> int:
        return self.num_envs

    def __repr__(self) -> str:  # pragma: no cover
        return f"{type(self).__name__}({self.env.name}, num_envs={self.num_envs})"

    @property
    def _fused(self) -> bool:
        return self._kernel_backend is not None

    # -- XLA-resident pure API ----------------------------------------------
    def _xla_init(self, key: jax.Array) -> PoolState:
        state, obs = self.venv.reset(key)
        return PoolState(state, obs, jax.random.fold_in(key, 0x57EB))

    def _step_many_core(self, env_state, actions: jax.Array, key: jax.Array,
                        venv: Optional[Vec] = None):
        """K batched env steps -> (env_state, (obs, reward, done, info)),
        outputs stacked with a leading (K, ...) axis. Fused backends run the
        whole block as one megastep kernel launch; vmap scans the step."""
        if self._fused:
            new_state, ts = self.env.fused_step(
                env_state, actions, num_steps=actions.shape[0],
                backend=self._kernel_backend)
            return new_state, (ts.obs, ts.reward, ts.done, ts.info)

        venv = venv if venv is not None else self.venv

        def body(state, xs):
            a, k = xs
            ts = venv.step(state, a, k)
            return ts.state, (ts.obs, ts.reward, ts.done, ts.info)

        k = actions.shape[0]
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(k))
        return jax.lax.scan(body, env_state, (actions, keys))

    def _xla_step(self, carry: PoolState, actions: jax.Array,
                  key: Optional[jax.Array] = None) -> Tuple[PoolState, PoolStep]:
        if self._fused:
            ps, out = self._xla_step_many(carry, actions[None], key)
            first = lambda x: x[0]
            return ps, PoolStep(out.obs[0], out.reward[0], out.done[0],
                                jax.tree.map(first, out.info))
        if key is None:
            next_key, key = jax.random.split(carry.key)
        else:
            next_key = carry.key
        ts = self.venv.step(carry.env_state, actions, key)
        return (PoolState(ts.state, ts.obs, next_key),
                PoolStep(ts.obs, ts.reward, ts.done, ts.info))

    def _xla_step_many(self, carry: PoolState, actions: jax.Array,
                       key: Optional[jax.Array] = None
                       ) -> Tuple[PoolState, PoolStep]:
        """Step the pool `actions.shape[0]` times in one fused block.

        `actions` is (K, B[, A]); outputs carry a leading (K, ...) axis.
        Equivalent to scanning `step` over the block (envs whose dynamics
        ignore the per-step key make the two paths bit-compatible)."""
        with jax.named_scope("cairl.layout"):
            if key is None:
                next_key, key = jax.random.split(carry.key)
            else:
                next_key = carry.key
        state, (obs, reward, done, info) = self._step_many_core(
            carry.env_state, actions, key)
        with jax.named_scope("cairl.layout"):
            last_obs = obs[-1]
        return (PoolState(state, last_obs, next_key),
                PoolStep(obs, reward, done, info))

    def xla(self) -> XlaPool:
        """Pure `(init, step, step_many)` for building into larger programs."""
        return XlaPool(self._xla_init, self._xla_step, self._xla_step_many)

    # -- Gym-style stateful API ----------------------------------------------
    def _stateful_reset(self, key):
        ps = self._xla_init(key)
        return (ps.env_state, ps.key), ps.obs

    def _stateful_step(self, carry, actions):
        env_state, key = carry
        ps, out = self._xla_step(PoolState(env_state, None, key), actions)
        return (ps.env_state, ps.key), out

    def _stateful_step_key(self, carry, actions, key):
        env_state, carry_key = carry
        ps, out = self._xla_step(PoolState(env_state, None, carry_key),
                                 actions, key)
        return (ps.env_state, ps.key), out

    def reset(self, seed: int = 0) -> jax.Array:
        """(Re)initialise all envs; returns the batched observation."""
        self._carry, self._obs = self._jit_reset(jax.random.PRNGKey(seed))
        return self._obs

    def step(self, actions,
             key: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict]:
        """Step every env once. Autoreset on done; state never leaves device.

        `key` pins the per-step RNG stream explicitly (the carry chain is
        left untouched) — `step(a, key=fold_in(k, t))` reproduces the raw
        `Vec.step(state, a, fold_in(k, t))` trace bit-for-bit, which is how
        the kill-and-resume tests replay the committed golden traces through
        a supervised pool (tests/test_supervisor.py).
        """
        if self._carry is None:
            raise RuntimeError("call reset() before step()")
        if key is None:
            self._carry, out = self._jit_step(self._carry, jnp.asarray(actions))
        else:
            self._carry, out = self._jit_step_key(
                self._carry, jnp.asarray(actions), key)
        self._obs = out.obs
        return out.obs, out.reward, out.done, out.info

    def sample_actions(self, seed: int = 0) -> jax.Array:
        return sample_batch(self.action_space, jax.random.PRNGKey(seed),
                            self.num_envs)

    def step_lowered(self):
        """Lower (don't run) the stateful step — for HLO inspection: the
        fault suite certifies the supervised steady-state step path still
        contains zero host-transfer instructions."""
        if self._carry is None:
            self.reset(seed=0)
        acts = jnp.zeros((self.num_envs,) + tuple(self.action_space.shape),
                         self.action_space.dtype)
        return jax.jit(self._stateful_step).lower(self._carry, acts)

    # -- snapshot / restore ----------------------------------------------------
    # The survivable-rollout contract (runtime/supervisor.py): `state_dict()`
    # is a HOST-materialized copy of the stateful carry — env state (with the
    # AutoReset key chain inside), the fallback carry key, and the current
    # obs — safe against XLA reusing the donated buffers on the next step.
    # `load_state_dict()` re-places it on device; ShardedEnvPool overrides
    # `_put_carry` so a gathered snapshot re-shards onto ANY mesh (the
    # elastic contract of checkpoint/manager.py).
    def state_dict(self) -> Dict[str, Any]:
        """Host snapshot of the stateful carry (numpy leaves, copied)."""
        if self._carry is None:
            raise RuntimeError("call reset() before snapshotting the pool")
        env_state, key = self._carry
        tree = {"env_state": env_state, "key": key, "obs": self._obs}
        return jax.tree.map(
            lambda x: np.array(jax.device_get(x), copy=True), tree)

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        """Restore a `state_dict()` snapshot (possibly from another pool
        instance — or, for sharded pools, another mesh)."""
        d = self._put_carry(d)
        self._carry = (d["env_state"], d["key"])
        self._obs = d["obs"]

    def _put_carry(self, d: Dict[str, Any]) -> Dict[str, Any]:
        return jax.tree.map(jnp.asarray, d)

    # -- compiled whole-rollout fast path -------------------------------------
    def rollout(self, num_steps: int, key: jax.Array, render: bool = False):
        """Random-policy rollout as ONE device program (Listing 1/2 loop).

        Returns (sum_reward (B,), episodes (B,), last_frame or zeros) —
        bit-identical to runner.rollout_random_fast for the unsharded pool.
        """
        fn = self._rollout_cache.get((num_steps, render))
        if fn is None:
            fn = jax.jit(lambda k: self._rollout(k, num_steps, render))
            self._rollout_cache[(num_steps, render)] = fn
        return fn(key)

    def rollout_lowered(self, num_steps: int, render: bool = False):
        """Lower (don't run) the rollout — for HLO inspection (fig4)."""
        return jax.jit(lambda k: self._rollout(k, num_steps, render)).lower(
            jax.random.PRNGKey(0))

    def _rollout(self, key: jax.Array, num_steps: int, render: bool):
        # Fused backends chunk the loop into `unroll`-step kernel launches
        # (render mode still needs per-step frames, so it keeps the vmap body).
        if self._fused and not render:
            return self._rollout_fused(key, num_steps)
        carry0 = self._xla_init(jax.random.fold_in(key, 0x5EED))
        frame0 = (self.venv.render(carry0.env_state) if render
                  else jnp.zeros((self.num_envs,), jnp.float32))

        def body(carry, i):
            ps, rew, eps, frame = carry
            k = jax.random.fold_in(key, i)
            actions = sample_batch(self.action_space, k, self.num_envs)
            # repro: allow[key-reuse] action-sample and step share the per-step key by design — the committed golden traces and the fused/vmap bit-parity proof pin this exact chain
            ps, out = self._xla_step(ps, actions, k)
            frame = self.venv.render(ps.env_state) if render else frame
            return (ps, rew + out.reward, eps + out.done.astype(jnp.int32), frame), None

        init = (carry0, jnp.zeros((self.num_envs,), jnp.float32),
                jnp.zeros((self.num_envs,), jnp.int32), frame0)
        (_, rew, eps, frame), _ = jax.lax.scan(body, init, jnp.arange(1, num_steps + 1))
        return rew, eps, frame

    def _rollout_fused(self, key: jax.Array, num_steps: int):
        """Same rollout, `unroll` steps per megastep launch. RNG mirrors the
        vmap body (actions from `fold_in(key, i)`, i in 1..num_steps), so
        trajectories match it step for step."""
        carry0 = self._xla_init(jax.random.fold_in(key, 0x5EED))
        kk = max(min(self.unroll, num_steps), 1)  # num_steps=0 -> no chunks
        n_chunks, rem = divmod(num_steps, kk)

        def chunk(n):
            def body(carry, c):
                ps, rew, eps = carry
                steps = c * kk + 1 + jnp.arange(n)
                ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(steps)
                acts = jax.vmap(
                    lambda s: sample_batch(self.action_space, s, self.num_envs)
                )(ks)
                ps, out = self._xla_step_many(ps, acts, key)
                return (ps, rew + out.reward.sum(0),
                        eps + out.done.astype(jnp.int32).sum(0)), None
            return body

        carry = (carry0, jnp.zeros((self.num_envs,), jnp.float32),
                 jnp.zeros((self.num_envs,), jnp.int32))
        if n_chunks:
            carry, _ = jax.lax.scan(chunk(kk), carry, jnp.arange(n_chunks))
        if rem:
            carry, _ = chunk(rem)(carry, jnp.asarray(n_chunks))
        _, rew, eps = carry
        return rew, eps, jnp.zeros((self.num_envs,), jnp.float32)
