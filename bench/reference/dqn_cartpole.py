"""Plain reference of the `dqn-cartpole-v1` deployment: Table I's DQN
learner (a 32x32 elu MLP, Huber TD loss, Adam, a replay ring, a hard target
sync and an epsilon-greedy actor) over a pool of CartPole-v1 lanes, one
train step at a time, in plain `jax.numpy` in float32. Every matmul asks for
HIGHEST precision, so a TPU computes it in float32 too. The lanes are
`cartpole.py`'s; nothing here imports the program.

One train step, from the carry's key: split it into (next key, epsilon key,
action key, env key, sample key); epsilon falls linearly from its start to
its end over `exploration_steps`; each lane explores (a uniform draw below
epsilon) with a uniform random action, else takes the argmax of Q(params,
obs). Every lane steps once (auto-reset and time limit as in `cartpole.py`;
the env key is not used). The step's transitions (obs, action, reward,
terminal obs, terminal = termination, not truncation) go into the ring at
its pointer; a batch is drawn uniformly from the ring's filled part with
the sample key; once the ring holds `learn_start` transitions Adam takes
one step on the Huber TD loss against the target network, bootstrapping
unless the transition is terminal. The target copies the params on every
step whose count before it is a multiple of `target_update_freq`. The
carry keeps each lane's running return and the last one it finished.

`check` judges one chunk of the program from its carry in, its per-step
metrics and its carry out, which must hold all of the chunk's transitions
(a chunk of at most `memory_size // num_envs` steps). Each transition is
recomputed from the program's own obs before the step: CartPole is chaotic,
so the obs the program stored is where the reference steps from, while the
lane keys, time counters and the learner's key chain follow from the carry
in. The learner runs its own updates from the carry in's params, target and
Adam state, each on the batch its sample key draws from the ring as it
stood at that step. A greedy action that differs from the reference's is
a tie, and passes, where the reference's top two Q values lie within
`TIE_Q` of each other (relative to the lane's largest |Q|, at least 1); a
termination within `cartpole.TIE` of a threshold is a tie too.

Compared: `gap`, the largest difference of any float quantity over the
largest magnitude the reference gives it: the states before and after each
step, the terminal obs, the epsilon and mean return of each step, and the
final params and target. Each step's loss is compared as the TD error's
root mean square it stands for (`td_rms`), over the largest |Q| of the
chunk's batches: near convergence a TD error is a small difference of Q
values near 1 / (1 - discount), so two float32 computations of the loss
differ by up to 6e-4 of it, while rounding Q at its own size moves the
RMS by about float32's epsilon of |Q| at any stage of training. The Adam
moments are not compared themselves: mu averages gradients that cancel,
and two float32 runs of the same chunk read up to 3e-2 of its size apart
(1e-4 for nu); they act on the result through the params, which are
compared. `mismatches` counts differing actions, rewards, terminal flags,
time counters, keys, ring entries outside the chunk, the ring's cursor,
step counts (the Adam step's too) and returns (exact).
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import types
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference_cartpole_for_dqn",
    pathlib.Path(__file__).resolve().parent / "cartpole.py")
cartpole = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cartpole)

OBS_SIZE, N_ACTIONS = 4, 2
MAX_STEPS = cartpole.MAX_STEPS
#: relative distance of the top two Q values within which either greedy
#: action passes: float32 dots on the program's and the reference's params,
#: which differ by rounding, disagree on Q by about 1e-6 of its size; a
#: bfloat16 pass rounds each operand to 2**-9 of its size
TIE_Q = 1e-4
HIGHEST = jax.lax.Precision.HIGHEST


class HParams(NamedTuple):
    num_envs: int
    units: Tuple[int, ...]
    discount: float
    batch_size: int
    lr: float
    target_update_freq: int
    memory_size: int
    exploration_start: float
    exploration_final: float
    exploration_steps: int
    learn_start: int
    b1: float
    b2: float
    eps: float


def hparams(config, num_envs: int) -> HParams:
    """The learner's settings from a configuration file's `dqn` and
    `optimizer` groups."""
    d, o = config["dqn"], config["optimizer"]
    if d["activation"] != "elu" or o["name"] != "adam":
        raise ValueError("the reference is an elu MLP under Adam")
    return HParams(num_envs, tuple(d["units"]), float(d["discount"]),
                   int(d["batch_size"]), float(d["lr"]),
                   int(d["target_update_freq"]), int(d["memory_size"]),
                   float(d["exploration_start"]),
                   float(d["exploration_final"]),
                   int(d["exploration_steps"]), int(d["learn_start"]),
                   float(o["b1"]), float(o["b2"]), float(o["eps"]))


# -- the learner --------------------------------------------------------------
def dot(x, w, dtype):
    """x @ w: float32 at HIGHEST precision, or with both operands rounded
    to `dtype` and summed in float32, as one bfloat16 pass does."""
    if dtype == jnp.float32:
        return jnp.dot(x, w, precision=HIGHEST)
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def mlp(params, x, dtype=jnp.float32):
    for i, layer in enumerate(params):
        x = dot(x, layer["w"], dtype) + layer["b"]
        if i < len(params) - 1:
            # expm1 of the negative part only: its gradient at a large
            # positive x would be inf, and inf times the where's 0 is nan
            x = jnp.where(x > 0, x, jnp.expm1(jnp.minimum(x, 0.0)))
    return x


def mlp_init(key, sizes):
    """He-normal weights from one key split per layer, zero biases."""
    params = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        params.append({"w": w * jnp.sqrt(2.0 / fan_in),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def td_loss(params, target, batch, discount, dtype):
    """(mean Huber TD loss, largest |Q| of the batch)."""
    obs, action, reward, next_obs, terminal = batch
    q = mlp(params, obs, dtype)
    q_sa = jnp.take_along_axis(q, action[:, None], axis=1)[:, 0]
    q_next = jax.lax.stop_gradient(jnp.max(mlp(target, next_obs, dtype), 1))
    err = jnp.abs(q_sa - (reward + discount * (1.0 - terminal) * q_next))
    quad = jnp.minimum(err, 1.0)
    loss = jnp.mean(0.5 * quad * quad + (err - quad))
    return loss, jax.lax.stop_gradient(jnp.max(jnp.abs(q)))


def adam(hp: HParams, params, grads, step, mu, nu):
    """One Adam step (Kingma and Ba, Algorithm 1)."""
    step = step + 1
    t = step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: hp.b1 * m + (1.0 - hp.b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: hp.b2 * v + (1.0 - hp.b2) * g * g, nu,
                      grads)
    c1, c2 = 1.0 - hp.b1 ** t, 1.0 - hp.b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - hp.lr * (m / c1) / (jnp.sqrt(v / c2) + hp.eps),
        params, mu, nu)
    return params, step, mu, nu


def epsilon(hp: HParams, step):
    frac = jnp.clip(step.astype(jnp.float32) / hp.exploration_steps, 0.0,
                    1.0)
    return hp.exploration_start + frac * (hp.exploration_final -
                                          hp.exploration_start)


def keys(key):
    """(next, epsilon, action, env, sample) keys of one train step."""
    return jax.random.split(key, 5)


def draws(hp: HParams, k_eps, k_act, eps):
    """(explore, random action) of every lane."""
    explore = jax.random.uniform(k_eps, (hp.num_envs,)) < eps
    return explore, jax.random.randint(k_act, (hp.num_envs,), 0, N_ACTIONS)


def learn_step(hp: HParams, ln, batch, size, step, dtype):
    """One learner update of `ln` = (params, target, adam step, mu, nu) on
    `batch`; returns (ln, loss, largest |Q| of the batch)."""
    params, target, a_step, mu, nu = ln
    (loss, q_max), grads = jax.value_and_grad(td_loss, has_aux=True)(
        params, target, batch, hp.discount, dtype)
    new, n_step, n_mu, n_nu = adam(hp, params, grads, a_step, mu, nu)
    can = size >= hp.learn_start
    pick = lambda n, o: jax.tree.map(lambda a, b: jnp.where(can, a, b), n, o)
    params, a_step, mu, nu = pick((new, n_step, n_mu, n_nu),
                                  (params, a_step, mu, nu))
    sync = step % hp.target_update_freq == 0
    target = jax.tree.map(lambda p, t: jnp.where(sync, p, t), params, target)
    return (params, target, a_step, mu, nu), loss, q_max


def td_rms(loss):
    """The TD error's root mean square that a Huber loss stands for (exact
    for errors under 1)."""
    return jnp.sqrt(2.0 * loss)


def sample(hp: HParams, key, size):
    return jax.random.randint(key, (hp.batch_size,), 0,
                              jnp.maximum(size, 1))


# -- reading the program's carry (by field name only) -------------------------
RING = ("obs", "action", "reward", "next_obs", "done")


def lanes(carry):
    """What `check` takes of the carry after a chunk: all of it."""
    return carry


def learner(carry):
    return (carry.params, carry.target, carry.opt.step, carry.opt.mu,
            carry.opt.nu)


def ring(replay):
    return tuple(getattr(replay, f) for f in RING)


def with_lanes(pool, state, t, lane_keys, obs):
    """A pool carry with its lanes' state, time counters and keys set."""
    es = pool.env_state
    inner = es.inner
    x, x_dot, theta, theta_dot = (state[:, i] for i in range(4))
    cp = inner.inner._replace(x=x, x_dot=x_dot, theta=theta,
                              theta_dot=theta_dot)
    es = es._replace(inner=inner._replace(inner=cp, t=t), key=lane_keys)
    return pool._replace(env_state=es, obs=obs)


def time_counters(carry):
    return cartpole.lanes(carry.pool)[1]


def with_time(carry, t):
    """`carry` with its lanes' time counters set to `t`."""
    return carry._replace(pool=cartpole.with_time(carry.pool, t))


def written_at(hp: HParams, ptr, n: int):
    """The chunk step at which each ring slot is written, n for none."""
    off = (jnp.arange(hp.memory_size) - ptr) % hp.memory_size
    return jnp.where(off < n * hp.num_envs, off // hp.num_envs, n)


def slots(hp: HParams, ptr, i):
    return (ptr + i * hp.num_envs + jnp.arange(hp.num_envs)) % hp.memory_size


def _rel(diff_scale):
    """max |p - r| over max |r| of one quantity."""
    diff, scale = diff_scale
    return jnp.max(diff) / jnp.maximum(jnp.max(scale),
                                       jnp.finfo(jnp.float32).tiny)


def _ds(p, r):
    """(max |p - r|, max |r|) over the leaves of two trees."""
    pl, rl = jax.tree.leaves(p), jax.tree.leaves(r)
    return (jnp.max(jnp.stack([jnp.max(jnp.abs(a - b))
                               for a, b in zip(pl, rl)])),
            jnp.max(jnp.stack([jnp.max(jnp.abs(b)) for b in rl])))


def _leaf_gaps(p, r):
    return jnp.max(jnp.stack([_rel(_ds(a, b)) for a, b in
                              zip(jax.tree.leaves(p), jax.tree.leaves(r))]))


def _count(p, r):
    """Differing elements over the leaves of two trees."""
    return sum(jnp.sum(a != b) for a, b in zip(jax.tree.leaves(p),
                                                 jax.tree.leaves(r)))


def _greedy(q):
    """(argmax, top-two gap, lane scale) of each lane's Q values."""
    top = jax.lax.top_k(q, 2)[0]
    scale = jnp.maximum(1.0, jnp.max(jnp.abs(q), axis=-1))
    return jnp.argmax(q, axis=-1).astype(jnp.int32), top[:, 0] - top[:, 1], \
        scale


def check_init(carry, key, *, hp: HParams):
    """(gap, mismatches) of the carry the program builds from `key`."""
    key, knet, kenv = jax.random.split(key, 3)
    params = mlp_init(knet, (OBS_SIZE,) + tuple(hp.units) + (N_ACTIONS,))
    state, t, lane_keys, obs, pool_key = cartpole.init(kenv, hp.num_envs)
    p_state, p_t, p_keys = cartpole.lanes(carry.pool)
    gap = jnp.maximum(_leaf_gaps((carry.params, carry.target),
                                 (params, params)),
                      _leaf_gaps((p_state, carry.pool.obs), (state, obs)))
    zeros = jax.tree.map(jnp.zeros_like, (
        carry.opt, ring(carry.replay), carry.replay.ptr, carry.replay.size,
        carry.step, carry.ep_return, carry.last_return))
    bad = (_count((p_t, p_keys, carry.pool.key, carry.key),
                  (t, lane_keys, pool_key, key))
           + _count((carry.opt, ring(carry.replay), carry.replay.ptr,
                     carry.replay.size, carry.step, carry.ep_return,
                     carry.last_return), zeros))
    return gap, bad


def check(carry_in, steps, metrics, after, *, hp: HParams):
    """Judge one chunk of `len(steps)` train steps: (gap, mismatches).

    `metrics` holds the program's per-step loss, eps and return; `after`
    is the carry after the chunk."""
    n = steps.shape[0]
    if n * hp.num_envs > hp.memory_size:
        raise ValueError("a judged chunk must fit in the ring")
    ptr0, size0 = carry_in.replay.ptr, carry_in.replay.size
    ring_in, ring_out = ring(carry_in.replay), ring(after.replay)
    when = written_at(hp, ptr0, n)
    _, t0, keys0 = cartpole.lanes(carry_in.pool)

    def body(c, xs):
        i, p_eps, p_loss, p_ret = xs
        post, t, lane_keys, key, ln, ep, last, step = c
        s, a, r, tobs, term = (x[slots(hp, ptr0, i)] for x in ring_out)
        key, k_eps, k_act, _, k_sample = keys(key)
        # the actor, with the program's epsilon
        explore, randa = draws(hp, k_eps, k_act, p_eps)
        greedy, q_gap, q_scale = _greedy(mlp(ln[0], s))
        want = jnp.where(explore, randa, greedy)
        q_tie = ~explore & (q_gap <= TIE_Q * q_scale)
        # the env, from the program's obs before the step
        ns, r_term, margin = jax.vmap(cartpole.step)(s, a)
        t1 = t + 1
        limit = t1 >= cartpole.MAX_STEPS
        tie = (margin < cartpole.TIE) & ~limit
        used = jnp.where(tie, term > 0, r_term)
        done = used | limit
        pair = jax.vmap(jax.random.split)(lane_keys)
        fresh = jax.vmap(cartpole.reset)(pair[:, 1])
        bad = (jnp.sum((a != want) & ~q_tie) + jnp.sum(r != 1.0)
               + jnp.sum((term != r_term.astype(jnp.float32)) & ~tie))
        ep = ep + 1.0
        last = jnp.where(done, ep, last)
        ep = jnp.where(done, 0.0, ep)
        # the learner, on the ring as it stood after this step's writes
        size = jnp.minimum(size0 + (i + 1) * hp.num_envs, hp.memory_size)
        idx = sample(hp, k_sample, size)
        batch = tuple(jnp.where(
            (when[idx] <= i).reshape((-1,) + (1,) * (x_out.ndim - 1)),
            x_out[idx], x_in[idx]) for x_out, x_in in zip(ring_out, ring_in))
        ln, loss, q_max = learn_step(hp, ln, batch, size, step, jnp.float32)
        gaps = (_ds(s, post), _ds(tobs, ns), _ds(p_eps, epsilon(hp, step)),
                (jnp.abs(td_rms(p_loss) - td_rms(loss)), q_max),
                _ds(p_ret, jnp.mean(last)))
        post = jnp.where(done[:, None], fresh, ns)
        return ((post, jnp.where(done, 0, t1), pair[:, 0], key, ln, ep, last,
                 step + 1), (gaps, bad))

    c0 = (carry_in.pool.obs, t0, keys0, carry_in.key, learner(carry_in),
          carry_in.ep_return, carry_in.last_return, carry_in.step)
    (post, t, lane_keys, key, ln, ep, last, step), (gaps, bads) = \
        jax.lax.scan(body, c0, (steps, metrics["eps"], metrics["loss"],
                                metrics["return"]))
    p_state, p_t, p_keys = cartpole.lanes(after.pool)
    untouched = [(when == n).reshape((-1,) + (1,) * (x.ndim - 1))
                 for x in ring_in]
    gap = jnp.max(jnp.stack(
        [_rel(g) for g in gaps]
        + [_rel(_ds(p_state, post)), _rel(_ds(after.pool.obs, post)),
           _leaf_gaps(learner(after)[:2], ln[:2])]))
    bad = (jnp.sum(bads)
           + _count((p_t, p_keys, after.pool.key, after.key, after.step,
                     after.opt.step, after.ep_return, after.last_return),
                    (t, lane_keys, carry_in.pool.key, key, step, ln[2], ep,
                     last))
           + sum(jnp.sum((x_out != x_in) & u)
                 for x_out, x_in, u in zip(ring_out, ring_in, untouched))
           + jnp.sum(after.replay.ptr != (ptr0 + n * hp.num_envs)
                     % hp.memory_size)
           + jnp.sum(after.replay.size != jnp.minimum(
               size0 + n * hp.num_envs, hp.memory_size)))
    return gap, bad


def run(carry_in, steps, dtype, *, hp: HParams):
    """The reference put in the program's place: `len(steps)` train steps
    from `carry_in` with every matmul in `dtype`. Returns `(metrics,
    after)` shaped as `check` takes them."""
    n = steps.shape[0]
    ptr0, size0 = carry_in.replay.ptr, carry_in.replay.size
    state0, t0, keys0 = cartpole.lanes(carry_in.pool)

    def body(c, i):
        s, t, lane_keys, key, ln, ep, last, step, rng = c
        key, k_eps, k_act, _, k_sample = keys(key)
        eps = epsilon(hp, step)
        explore, randa = draws(hp, k_eps, k_act, eps)
        greedy = jnp.argmax(mlp(ln[0], s, dtype), axis=-1).astype(jnp.int32)
        a = jnp.where(explore, randa, greedy)
        ns, term, _ = jax.vmap(cartpole.step)(s, a)
        t1 = t + 1
        done = term | (t1 >= cartpole.MAX_STEPS)
        pair = jax.vmap(jax.random.split)(lane_keys)
        fresh = jax.vmap(cartpole.reset)(pair[:, 1])
        at = slots(hp, ptr0, i)
        rew = jnp.ones((hp.num_envs,), jnp.float32)
        rng = tuple(x.at[at].set(v.astype(x.dtype)) for x, v in
                    zip(rng, (s, a, rew, ns, term)))
        size = jnp.minimum(size0 + (i + 1) * hp.num_envs, hp.memory_size)
        batch = tuple(x[sample(hp, k_sample, size)] for x in rng)
        ln, loss, _ = learn_step(hp, ln, batch, size, step, dtype)
        ep = ep + rew
        last = jnp.where(done, ep, last)
        ep = jnp.where(done, 0.0, ep)
        post = jnp.where(done[:, None], fresh, ns)
        return ((post, jnp.where(done, 0, t1), pair[:, 0], key, ln, ep, last,
                 step + 1, rng),
                {"loss": loss, "eps": eps, "return": jnp.mean(last)})

    c0 = (state0, t0, keys0, carry_in.key, learner(carry_in),
          carry_in.ep_return, carry_in.last_return, carry_in.step,
          ring(carry_in.replay))
    (s, t, lane_keys, key, ln, ep, last, step, rng), metrics = jax.lax.scan(
        body, c0, steps)
    params, target, a_step, mu, nu = ln
    replay = carry_in.replay._replace(
        **dict(zip(RING, rng)),
        ptr=(ptr0 + n * hp.num_envs) % hp.memory_size,
        size=jnp.minimum(size0 + n * hp.num_envs, hp.memory_size))
    after = carry_in._replace(
        params=params, target=target,
        opt=carry_in.opt._replace(step=a_step, mu=mu, nu=nu), replay=replay,
        pool=with_lanes(carry_in.pool, s, t, lane_keys, s), key=key,
        step=step, ep_return=ep, last_return=last)
    return metrics, after


def truncations(carry_in, steps, after, *, hp: HParams):
    """Lanes cut by the time limit in a chunk, from its stored terminal
    flags and the time counters of its carry in."""
    _, t, _ = cartpole.lanes(carry_in.pool)
    ptr0, done = carry_in.replay.ptr, after.replay.done

    def body(t, i):
        t1 = t + 1
        term = done[slots(hp, ptr0, i)] > 0
        limit = t1 >= cartpole.MAX_STEPS
        return jnp.where(term | limit, 0, t1), jnp.sum(limit & ~term)

    return jnp.sum(jax.lax.scan(body, t, steps)[1])


def bind(hp: HParams):
    """The reference for one setting of the learner, with the interface
    `bench/control.py` and the consumer call."""
    return types.SimpleNamespace(
        hp=hp, MAX_STEPS=MAX_STEPS, lanes=lanes,
        time_counters=time_counters, with_time=with_time,
        check_init=functools.partial(check_init, hp=hp),
        check=functools.partial(check, hp=hp),
        run=functools.partial(run, hp=hp),
        truncations=functools.partial(truncations, hp=hp))
