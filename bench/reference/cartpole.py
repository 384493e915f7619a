"""Plain reference of the `cartpole-v1` deployment: CartPole-v1 under a
500-step time limit with auto-reset, one lane at a time, in plain `jax.numpy`.

Dynamics and constants are Gym's `CartPoleEnv` (Euler integrator, force
10 N, thresholds 2.4 m and 12 degrees); a reset draws the four state values
uniformly from [-0.05, 0.05) from its key. Auto-reset splits each lane's
key every step into (next key, reset key) and resets from the reset key.
A pool's init splits its key over the lanes (per shard, after folding in
the shard index, when the pool is sharded), splits each lane key once more
and resets from the second half. Nothing here imports the program.

`check` judges one chunk of a pool's transitions. CartPole is chaotic, so
each step is recomputed from the program's own state before it (its
carry-in for the first step, its returned observation, which is the whole
state, after that), and the key chain and time counter are followed from
the carry-in. A termination whose state lies within `TIE` of a threshold
is a tie: either decision passes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GRAVITY, MASSCART, MASSPOLE, LENGTH = 9.8, 1.0, 0.1, 0.5
FORCE_MAG, TAU = 10.0, 0.02
X_THRESHOLD = 2.4
THETA_THRESHOLD = 12 * 2 * math.pi / 360
MAX_STEPS = 500
#: distance to a termination threshold within which rounding may decide
TIE = 1e-5
#: the pool folds this into its key for the carry's own key
POOL_KEY_FOLD = 0x57EB


def reset(key, dtype=jnp.float32):
    vals = jax.random.uniform(key, (4,), minval=-0.05, maxval=0.05)
    return vals.astype(dtype)


def step(s, action, dtype=jnp.float32):
    """One Euler step of state (4,) -> (next state, terminated, margin)."""
    c = lambda v: jnp.asarray(v, dtype)
    x, x_dot, theta, theta_dot = s[0], s[1], s[2], s[3]
    total_mass = c(MASSPOLE + MASSCART)
    polemass_length = c(MASSPOLE * LENGTH)
    force = jnp.where(action == 1, c(FORCE_MAG), c(-FORCE_MAG))
    costheta, sintheta = jnp.cos(theta), jnp.sin(theta)
    temp = (force + polemass_length * theta_dot ** 2 * sintheta) / total_mass
    thetaacc = (c(GRAVITY) * sintheta - costheta * temp) / (
        c(LENGTH) * (c(4.0 / 3.0) - c(MASSPOLE) * costheta ** 2 / total_mass))
    xacc = temp - polemass_length * thetaacc * costheta / total_mass
    x = x + c(TAU) * x_dot
    x_dot = x_dot + c(TAU) * xacc
    theta = theta + c(TAU) * theta_dot
    theta_dot = theta_dot + c(TAU) * thetaacc
    ns = jnp.stack([x, x_dot, theta, theta_dot]).astype(dtype)
    margin = jnp.minimum(jnp.abs(jnp.abs(x) - X_THRESHOLD),
                         jnp.abs(jnp.abs(theta) - THETA_THRESHOLD))
    term = (jnp.abs(x) > X_THRESHOLD) | (jnp.abs(theta) > THETA_THRESHOLD)
    return ns, term, margin.astype(jnp.float32)


# -- reading the program's carry (by field name only) -------------------------
def lanes(carry):
    """(state (B, 4), t (B,), key (B, 2)) of a pool carry."""
    es = carry.env_state
    s = es.inner.inner
    state = jnp.stack([s.x, s.x_dot, s.theta, s.theta_dot], axis=-1)
    return state.astype(jnp.float32), es.inner.t, es.key


def with_time(carry, t):
    """`carry` with its lanes' time counters set to `t`."""
    es = carry.env_state
    return carry._replace(env_state=es._replace(inner=es.inner._replace(t=t)))


def init(key, num_envs: int, n_shards: int = 1):
    """What a pool's init makes from `key`: (state, t, lane keys, obs,
    carry key)."""
    per = num_envs // n_shards

    def shard(i):
        k = key if n_shards == 1 else jax.random.fold_in(key, i)
        return jax.random.split(k, per)

    lane_keys = jnp.concatenate([shard(i) for i in range(n_shards)])
    pair = jax.vmap(jax.random.split)(lane_keys)
    state = jax.vmap(reset)(pair[:, 1])
    t = jnp.zeros((num_envs,), jnp.int32)
    return state, t, pair[:, 0], state, jax.random.fold_in(key,
                                                           POOL_KEY_FOLD)


def check_init(carry, key, num_envs: int, n_shards: int = 1):
    """(largest float gap, count of exact mismatches) of an initial carry."""
    state, t, keys, obs, ckey = init(key, num_envs, n_shards)
    p_state, p_t, p_keys = lanes(carry)
    gap = jnp.maximum(jnp.max(jnp.abs(p_state - state)),
                      jnp.max(jnp.abs(carry.obs - obs)))
    bad = (jnp.sum(p_t != t) + jnp.sum(jnp.any(p_keys != keys, axis=-1))
           + jnp.sum(carry.key != ckey))
    return gap, bad


def run(carry, actions, dtype):
    """The reference put in the program's place: K free-running steps of
    every lane from `carry`, in `dtype`. Returns `(out, after)` shaped as
    `check` takes them."""
    state, t, key = lanes(carry)
    state = state.astype(dtype)

    def body(c, a):
        s, t, k = c
        ns, term, _ = jax.vmap(lambda s, a: step(s, a, dtype))(s, a)
        t1 = t + 1
        done = term | (t1 >= MAX_STEPS)
        pair = jax.vmap(jax.random.split)(k)
        fresh = jax.vmap(lambda k: reset(k, dtype))(pair[:, 1])
        post = jnp.where(done[:, None], fresh, ns)
        t = jnp.where(done, 0, t1)
        out = (post.astype(jnp.float32), jnp.ones(done.shape, jnp.float32),
               done, ns.astype(jnp.float32), (t1 >= MAX_STEPS) & ~term)
        return (post, t, pair[:, 0]), out

    (s, t, k), (obs, rew, done, tobs, trunc) = jax.lax.scan(
        body, (state, t, key), actions)
    info = {"terminal_obs": tobs, "truncated": trunc}
    return (obs, rew, done, info), (s.astype(jnp.float32), t, k)


def check(carry_in, actions, out, after):
    """Judge one chunk: (largest float gap, count of exact mismatches).

    `out` is the chunk's `(obs, reward, done, info)` with a leading K axis;
    `after` is `lanes()` of the carry after the chunk.
    """
    obs, reward, done, info = out
    tobs, trunc = info["terminal_obs"], info["truncated"]
    state, t, key = lanes(carry_in)
    # the state before each step, as the program had it
    prev = jnp.concatenate([state[None], obs[:-1]], axis=0)

    def body(c, xs):
        t, k = c
        s, a, p_obs, p_rew, p_done, p_tobs, p_trunc = xs
        ns, term, margin = jax.vmap(step)(s, a)
        t1 = t + 1
        limit = t1 >= MAX_STEPS
        r_done = term | limit
        tie = (margin < TIE) & ~limit
        used = jnp.where(tie, p_done, r_done)
        pair = jax.vmap(jax.random.split)(k)
        fresh = jax.vmap(reset)(pair[:, 1])
        post = jnp.where(used[:, None], fresh, ns)
        gap = jnp.maximum(jnp.max(jnp.abs(p_tobs - ns)),
                          jnp.max(jnp.abs(p_obs - post)))
        bad = (jnp.sum((p_done != r_done) & ~tie) + jnp.sum(p_rew != 1.0)
               + jnp.sum(p_trunc != (limit & ~term)))
        return (jnp.where(used, 0, t1), pair[:, 0]), (gap, bad)

    (t, key), (gaps, bads) = jax.lax.scan(
        body, (t, key), (prev, actions, obs, reward, done, tobs, trunc))
    o_state, o_t, o_key = after
    gap = jnp.maximum(jnp.max(gaps), jnp.max(jnp.abs(o_state - obs[-1])))
    bad = (jnp.sum(bads) + jnp.sum(o_t != t)
           + jnp.sum(jnp.any(o_key != key, axis=-1)))
    return gap, bad
