"""DQN — the paper's evaluation algorithm (§V-B/§V-C, hyperparams Table I).

Two execution modes, matching the paper's comparison axis:
  - `train_compiled`: everything (env stepping via the XLA-resident EnvPool,
    replay, learning) inside one `lax.scan` device program — the CaiRL
    execution model.
  - `train_host`: identical learner, but the environment is an interpreted
    host object stepped one transition at a time — the AI-Gym execution
    model. Fig. 2 compares the wall-clock of the two.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.env import Env
from repro.pool import PoolState, make_vec
from repro.rl.networks import cnn_apply, cnn_init, mlp_apply, mlp_init
from repro.rl.replay import ReplayState, replay_add_batch, replay_init, replay_sample
from repro.train.optim import Adam, AdamState, huber_loss, linear_schedule


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    """Defaults = paper Table I."""

    discount: float = 0.99
    units: Tuple[int, ...] = (32, 32)
    activation: str = "elu"
    batch_size: int = 32
    lr: float = 3e-4
    target_update_freq: int = 150
    memory_size: int = 50_000
    exploration_start: float = 1.0
    exploration_final: float = 0.01
    exploration_steps: int = 5_000
    network: str = "mlp"           # "mlp" (memory obs) | "cnn" (pixel obs)
    num_envs: int = 1
    learn_start: int = 100
    env_backend: str = "vmap"      # pool step engine; "pallas" = fused megastep


class DQNState(NamedTuple):
    params: Any
    target: Any
    opt: AdamState
    replay: ReplayState
    pool: PoolState          # XLA-resident env pool carry (state + obs)
    key: jax.Array
    step: jax.Array
    ep_return: jax.Array     # (B,) running episodic return
    last_return: jax.Array   # (B,) most recent completed return


def _build_net(env: Env, cfg: DQNConfig, key):
    n_actions = env.action_space.n
    obs_shape = env.observation_space.shape
    if cfg.network == "cnn":
        params = cnn_init(key, obs_shape, out=n_actions)
        apply_fn = lambda p, x: cnn_apply(p, x, cfg.activation)
    else:
        sizes = (int(np.prod(obs_shape)),) + tuple(cfg.units) + (n_actions,)
        params = mlp_init(key, sizes)
        apply_fn = lambda p, x: mlp_apply(p, x.reshape(x.shape[: -len(obs_shape)] + (-1,)), cfg.activation)
    return params, apply_fn


def _make_pool(env: Env, cfg: DQNConfig):
    """The pool's pure xla() handle on the configured step engine.

    Built through the unified `make_vec` frontend. env_backend="pallas"
    routes every env transition through the fused megastep kernel (one
    launch per train step) instead of the chain of small vmap ops;
    trajectories — and therefore training — match "vmap" up to float
    rounding (tests/test_envstep_fused.py).
    """
    return make_vec(env, cfg.num_envs, backend=cfg.env_backend).xla()


def dqn_init(env: Env, cfg: DQNConfig, key: jax.Array) -> Tuple[DQNState, Callable]:
    key, knet, kenv = jax.random.split(key, 3)
    params, apply_fn = _build_net(env, cfg, knet)
    pool = _make_pool(env, cfg)
    opt = Adam(lr=cfg.lr).init(params)
    replay = replay_init(cfg.memory_size, env.observation_space.shape)
    state = DQNState(
        params=params, target=jax.tree.map(jnp.copy, params), opt=opt, replay=replay,
        pool=pool.init(kenv), key=key, step=jnp.asarray(0, jnp.int32),
        ep_return=jnp.zeros((cfg.num_envs,), jnp.float32),
        last_return=jnp.zeros((cfg.num_envs,), jnp.float32),
    )
    return state, apply_fn


def _epsilon(cfg: DQNConfig, step):
    return linear_schedule(cfg.exploration_start, cfg.exploration_final, cfg.exploration_steps)(step)


def _td_loss(apply_fn, params, target, batch, discount):
    """`terminal` is the stored env-termination flag — NOT folded `done`.

    A time-limit truncation is not a terminal state, so its transition is
    stored with terminal=0 and the target keeps bootstrapping from
    q(next_obs) (= q(terminal_obs), the pre-reset observation). Folding
    truncation into this flag zeroes the bootstrap at every time-limit cut
    and biases the values of any env that mostly ends by limit
    (Pendulum-v1, MountainCar-v0).
    """
    obs, action, reward, next_obs, terminal = batch
    q = apply_fn(params, obs)
    q_sa = jnp.take_along_axis(q, action[:, None], axis=-1)[:, 0]
    q_next = jnp.max(apply_fn(target, next_obs), axis=-1)
    tgt = reward + discount * (1.0 - terminal) * jax.lax.stop_gradient(q_next)
    return jnp.mean(huber_loss(q_sa, tgt))


def make_learn_step(apply_fn, cfg: DQNConfig):
    """The jitted learner update shared by both execution modes.

    `lr` (optional; traced values welcome) overrides cfg.lr at call time —
    the fleet trainer (repro.train.fused) vmaps one training loop over a
    whole learning-rate axis through this hook. float32(cfg.lr) and the
    python-float default produce bit-identical updates, so threading it
    does not perturb the solo path.
    """

    def learn(params, target, opt, batch, lr=None):
        optimizer = Adam(lr=cfg.lr if lr is None else lr)
        loss, grads = jax.value_and_grad(
            lambda p: _td_loss(apply_fn, p, target, batch, cfg.discount)
        )(params)
        params, opt = optimizer.update(grads, opt, params)
        return params, opt, loss

    return learn


def make_train_step(env: Env, apply_fn, cfg: DQNConfig):
    """One environment-interaction + learn step; scanned by train_compiled.

    The optional `lr` kwarg flows through to the learner (see
    make_learn_step) so `repro.train.fused.fleet` can thread a per-row
    learning rate through the otherwise-identical scan body.
    """
    pool = _make_pool(env, cfg)
    learn = make_learn_step(apply_fn, cfg)

    def step_fn(state: DQNState, _, lr=None):
        # Each sub-layer runs under one named scope (cairl.act, cairl.env,
        # cairl.replay, cairl.learn), which reaches every op's metadata in
        # the compiled program: a device profile attributes its time by it.
        with jax.named_scope("cairl.act"):
            key, k_eps, k_act, k_env, k_sample = jax.random.split(state.key, 5)
            eps = _epsilon(cfg, state.step)
            obs = state.pool.obs
            q = apply_fn(state.params, obs)
            greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
            randa = jax.random.randint(k_act, (cfg.num_envs,), 0, env.action_space.n)
            explore = jax.random.uniform(k_eps, (cfg.num_envs,)) < eps
            action = jnp.where(explore, randa, greedy)

        with jax.named_scope("cairl.env"):
            new_pool, ts = pool.step(state.pool, action, k_env)

        with jax.named_scope("cairl.replay"):
            terminal_obs = ts.info.get("terminal_obs", ts.obs)
            # Store the *termination* flag, not the folded done: truncated
            # episodes (info["truncated"], core/wrappers.TimeLimit) still
            # bootstrap through terminal_obs in _td_loss.
            truncated = ts.info.get("truncated", jnp.zeros_like(ts.done))
            terminal = ts.done & ~truncated
            replay = replay_add_batch(state.replay, obs, action, ts.reward, terminal_obs, terminal)
            batch = replay_sample(replay, k_sample, cfg.batch_size)

        # learn (skipped while the buffer warms up)
        with jax.named_scope("cairl.learn"):
            can_learn = replay.size >= cfg.learn_start
            new_params, new_opt, loss = learn(state.params, state.target,
                                              state.opt, batch, lr=lr)
            params = jax.tree.map(lambda n, o: jnp.where(can_learn, n, o), new_params, state.params)
            opt = jax.tree.map(lambda n, o: jnp.where(can_learn, n, o), new_opt, state.opt)

            # periodic hard target sync (Table I: every 150 steps)
            sync = (state.step % cfg.target_update_freq) == 0
            target = jax.tree.map(lambda t, p: jnp.where(sync, p, t), state.target, params)

        with jax.named_scope("cairl.act"):
            ep_return = state.ep_return + ts.reward
            last_return = jnp.where(ts.done, ep_return, state.last_return)
            ep_return = jnp.where(ts.done, 0.0, ep_return)
            step = state.step + 1
            mean_return = jnp.mean(last_return)

        new_state = DQNState(params, target, opt, replay, new_pool, key,
                             step, ep_return, last_return)
        metrics = {"loss": loss, "eps": eps, "return": mean_return}
        return new_state, metrics

    return step_fn


def train_compiled(env: Env, cfg: DQNConfig, steps: int, key: jax.Array,
                   chunk: int = 0, fused: bool = False):
    """Full DQN training as compiled scan(s).

    Returns (state, apply_fn, metrics dict of (T,)).

    fused=True dispatches the SAME scan body through
    `repro.train.fused.run_fused`: one donated jit per chunk, so the carry
    (replay ring, optimizer state, pool state, key chain) is updated in
    place on device instead of being re-materialized per dispatch.
    Trajectories are bit-identical to fused=False — the RNG chain lives in
    the carry either way, so neither `fused` nor `chunk` can shift it
    (tests/test_train_fused.py pins both against committed goldens).
    """
    state, apply_fn = dqn_init(env, cfg, key)
    step_fn = make_train_step(env, apply_fn, cfg)
    if fused:
        from repro.train.fused import run_fused

        state, metrics = run_fused(step_fn, state, steps, chunk)
        return state, apply_fn, metrics
    chunk = min(chunk or steps, steps)

    @functools.partial(jax.jit, static_argnums=(1,))
    def run_chunk(state, n):
        return jax.lax.scan(step_fn, state, None, length=n)

    all_metrics = []
    done = 0
    while done < steps:  # full chunks + one remainder chunk — exactly `steps`
        n = min(chunk, steps - done)
        state, metrics = run_chunk(state, n)
        all_metrics.append(metrics)
        done += n
    metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs), *all_metrics)
    return state, apply_fn, metrics


def train_host(make_env_host, env_spec_env: Env, cfg: DQNConfig, steps: int, key: jax.Array,
               seed: int = 0):
    """Same learner, interpreted host env (the AI-Gym execution model)."""
    host_env = make_env_host()
    host_env.seed(seed)
    key, knet = jax.random.split(key)
    params, apply_fn = _build_net(env_spec_env, cfg, knet)
    target = jax.tree.map(jnp.copy, params)
    opt = Adam(lr=cfg.lr).init(params)
    replay = replay_init(cfg.memory_size, env_spec_env.observation_space.shape)
    learn = jax.jit(make_learn_step(apply_fn, cfg))
    add = jax.jit(replay_add_batch)
    sample = jax.jit(replay_sample, static_argnums=2)
    act_jit = jax.jit(lambda p, o: jnp.argmax(apply_fn(p, o[None]), axis=-1)[0])

    rng = np.random.default_rng(seed)
    obs = np.asarray(host_env.reset(), np.float32)
    returns, ep_ret = [], 0.0
    for step in range(steps):
        eps = float(_epsilon(cfg, jnp.asarray(step)))
        if rng.random() < eps:
            action = host_env.action_space_sample()
        else:
            action = int(act_jit(params, jnp.asarray(obs)))
        next_obs, reward, done, info = host_env.step(action)
        next_obs = np.asarray(next_obs, np.float32)
        # Same termination/truncation split as the compiled path: the stored
        # flag blocks bootstrapping only at env-terminal states, so both
        # execution modes learn from identical TD targets.
        terminal = done and not info.get("truncated", False)
        replay = add(replay, jnp.asarray(obs)[None], jnp.asarray([action], jnp.int32),
                     jnp.asarray([reward], jnp.float32), jnp.asarray(next_obs)[None],
                     jnp.asarray([terminal], jnp.float32))
        ep_ret += reward
        if done:
            returns.append(ep_ret)
            ep_ret = 0.0
            next_obs = np.asarray(host_env.reset(), np.float32)
        obs = next_obs
        if int(replay.size) >= cfg.learn_start:
            key, k_s = jax.random.split(key)
            batch = sample(replay, k_s, cfg.batch_size)
            params, opt, _ = learn(params, target, opt, batch)
        if step % cfg.target_update_freq == 0:
            target = jax.tree.map(jnp.copy, params)
    return params, returns


def greedy_returns(env: Env, apply_fn, params, key: jax.Array, episodes: int = 8,
                   max_steps: int = 500) -> jax.Array:
    """Greedy evaluation over a batch of episodes (compiled, via the pool)."""
    pool = make_vec(env, episodes, backend="vmap").xla()

    @jax.jit
    def run(key):
        key, rkey = jax.random.split(key)
        ps = pool.init(rkey)
        finished = jnp.zeros((episodes,), bool)
        rets = jnp.zeros((episodes,), jnp.float32)

        def body(carry, _):
            ps, key, finished, rets = carry
            key, skey = jax.random.split(key)
            action = jnp.argmax(apply_fn(params, ps.obs), axis=-1).astype(jnp.int32)
            ps, ts = pool.step(ps, action, skey)
            rets = rets + ts.reward * (~finished)
            finished = finished | ts.done
            return (ps, key, finished, rets), None

        (_, _, _, rets), _ = jax.lax.scan(body, (ps, key, finished, rets), None, length=max_steps)
        return rets

    return run(key)
