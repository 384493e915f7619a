"""Plain reference of the `pong-v0` deployment: the arcade pipeline
TimeLimit(1000) -> ObsToPixels(84x84) -> FrameStack(4) over this toolkit's
one-rally Pong, with auto-reset, one lane at a time, in plain `jax.numpy`.

The game: the agent moves the right paddle (action 0 up, 1 stay, 2 down) by
0.05 within [0.12, 0.88]; the opponent tracks the ball at most 0.03 a step;
the ball moves by its velocity, reflects off the top and bottom walls, and
off a paddle plane (x 0.92 right, 0.08 left) that it crosses within the
paddle's half-height 0.12, gaining 0.25 of its offset from the paddle's
centre as vertical speed (capped at 0.05). Passing x < 0 scores +1 and
x > 1 scores -1; either ends the episode. A serve puts the ball at
(0.5, U[0.3, 0.7)) with horizontal speed +-0.035 and vertical U[-0.02, 0.02).

A frame draws four capsules (net, two paddles, ball) with a soft edge one
pixel wide: each pixel is the largest of coverage x intensity over them.
The frame stack holds the last four frames, oldest first; a reset fills it
with the first frame. Auto-reset splits each lane's key every step into
(next key, reset key), as `cartpole.py` describes. Nothing here imports
the program.

`check` judges one chunk: the reference runs every lane freely from the
chunk's carry-in (the game has no chaos to amplify rounding over one
chunk) and compares every frame, reward, done, truncation, the carry after
the chunk, key chain and time counter included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PADDLE_HALF, PADDLE_SPEED, OPP_SPEED = 0.12, 0.05, 0.03
BALL_SPEED_X, SPIN, MAX_VY = 0.035, 0.25, 0.05
PLAYER_X, OPP_X = 0.92, 0.08
H = W = 84
NUM_FRAMES = 4
MAX_STEPS = 1000
POOL_KEY_FOLD = 0x57EB
#: capsule radii and intensities: net, opponent, agent, ball
RADII = (0.004, 0.02, 0.02, 0.022)
INTENS = (0.25, 0.7, 1.0, 0.9)


def reset(key, dtype=jnp.float32):
    """State (6,): ball x, y, vx, vy, agent paddle y, opponent paddle y."""
    ky, kd, kv = jax.random.split(key, 3)
    serve = jnp.where(jax.random.bernoulli(kd), 1.0, -1.0)
    return jnp.stack([
        jnp.float32(0.5),
        jax.random.uniform(ky, (), minval=0.3, maxval=0.7),
        (BALL_SPEED_X * serve).astype(jnp.float32),
        jax.random.uniform(kv, (), minval=-0.02, maxval=0.02),
        jnp.float32(0.5), jnp.float32(0.5)]).astype(dtype)


def step(s, action, dtype=jnp.float32):
    """One game step -> (next state, reward, done)."""
    c = lambda v: jnp.asarray(v, dtype)
    bx, by, vx, vy, py, oy = (s[i] for i in range(6))
    move = (jnp.asarray(action) - 1).astype(dtype)
    py = jnp.clip(py + move * c(PADDLE_SPEED), c(PADDLE_HALF),
                  c(1.0 - PADDLE_HALF))
    oy = oy + jnp.clip(by - oy, c(-OPP_SPEED), c(OPP_SPEED))
    oy = jnp.clip(oy, c(PADDLE_HALF), c(1.0 - PADDLE_HALF))
    nx, ny = bx + vx, by + vy
    vy = jnp.where((ny < 0.0) | (ny > 1.0), -vy, vy)
    ny = jnp.where(ny < 0.0, -ny, ny)
    ny = jnp.where(ny > 1.0, c(2.0) - ny, ny)
    for plane, paddle, crossed in (
            (PLAYER_X, py, lambda nx: (bx < PLAYER_X) & (nx >= PLAYER_X)),
            (OPP_X, oy, lambda nx: (bx > OPP_X) & (nx <= OPP_X))):
        hit = crossed(nx) & (jnp.abs(ny - paddle) <= c(PADDLE_HALF))
        vy = jnp.where(hit, jnp.clip(vy + (ny - paddle) * c(SPIN),
                                     c(-MAX_VY), c(MAX_VY)), vy)
        vx = jnp.where(hit, -vx, vx)
        nx = jnp.where(hit, c(2.0 * plane) - nx, nx)
    won, lost = nx < 0.0, nx > 1.0
    reward = won.astype(jnp.float32) - lost.astype(jnp.float32)
    return jnp.stack([nx, ny, vx, vy, py, oy]).astype(dtype), reward, \
        won | lost


def render(s, dtype=jnp.float32):
    """State (6,) -> (H, W) frame."""
    c = lambda v: jnp.asarray(v, dtype)
    bx, by, py, oy = s[0], s[1], s[4], s[5]
    segs = ((c(0.5), c(0.02), c(0.5), c(0.98)),
            (c(OPP_X), oy - PADDLE_HALF, c(OPP_X), oy + PADDLE_HALF),
            (c(PLAYER_X), py - PADDLE_HALF, c(PLAYER_X), py + PADDLE_HALF),
            (bx, by, bx, by))
    px = (jnp.arange(W, dtype=dtype)[None, :] + c(0.5)) / c(W)
    pyy = (jnp.arange(H, dtype=dtype)[:, None] + c(0.5)) / c(H)
    frame = jnp.zeros((H, W), dtype)
    for (x0, y0, x1, y1), r, inten in zip(segs, RADII, INTENS):
        dx, dy = x1 - x0, y1 - y0
        l2 = jnp.maximum(dx * dx + dy * dy, c(1e-8))
        t = jnp.clip(((px - x0) * dx + (pyy - y0) * dy) / l2, 0.0, 1.0)
        d = jnp.sqrt((px - (x0 + t * dx)) ** 2 + (pyy - (y0 + t * dy)) ** 2)
        cov = jnp.clip((c(r) - d) / c(1.0 / H) + c(0.5), 0.0, 1.0)
        frame = jnp.maximum(frame, cov * c(inten))
    return frame


# -- reading the program's carry (by field name only) -------------------------
def lanes(carry):
    """(state (B, 6), t (B,), frames (B, 4, H, W), key (B, 2)) of a carry."""
    es = carry.env_state
    fs = es.inner
    s = fs.inner.inner
    state = jnp.stack([s.ball_x, s.ball_y, s.ball_vx, s.ball_vy, s.player_y,
                       s.opp_y], axis=-1)
    return state, fs.inner.t, fs.frames, es.key


def with_time(carry, t):
    """`carry` with its lanes' time counters set to `t`."""
    es = carry.env_state
    fs = es.inner
    return carry._replace(env_state=es._replace(
        inner=fs._replace(inner=fs.inner._replace(t=t))))


def _fresh(key, dtype):
    s = reset(key, dtype)
    return s, jnp.broadcast_to(render(s, dtype), (NUM_FRAMES, H, W))


def init(key, num_envs: int, n_shards: int = 1):
    """What a pool's init makes from `key`: (state, t, frames, lane keys,
    obs, carry key)."""
    per = num_envs // n_shards
    lane_keys = jnp.concatenate([
        jax.random.split(key if n_shards == 1 else jax.random.fold_in(key, i),
                         per) for i in range(n_shards)])
    pair = jax.vmap(jax.random.split)(lane_keys)
    state, frames = jax.lax.map(lambda k: _fresh(k, jnp.float32), pair[:, 1])
    return (state, jnp.zeros((num_envs,), jnp.int32), frames, pair[:, 0],
            frames, jax.random.fold_in(key, POOL_KEY_FOLD))


def check_init(carry, key, num_envs: int, n_shards: int = 1):
    """(largest float gap, count of exact mismatches) of an initial carry."""
    state, t, frames, keys, obs, ckey = init(key, num_envs, n_shards)
    p_state, p_t, p_frames, p_keys = lanes(carry)
    gap = jnp.max(jnp.stack([jnp.max(jnp.abs(p_state - state)),
                             jnp.max(jnp.abs(p_frames - frames)),
                             jnp.max(jnp.abs(carry.obs - obs))]))
    bad = (jnp.sum(p_t != t) + jnp.sum(jnp.any(p_keys != keys, axis=-1))
           + jnp.sum(carry.key != ckey))
    return gap, bad


def _lane_step(dtype):
    """One auto-reset step of one lane of the whole pipeline."""
    def f(s, t, frames, k, a):
        ns, rew, term = step(s, a, dtype)
        t1 = t + 1
        done = term | (t1 >= MAX_STEPS)
        pre = jnp.concatenate([frames[1:], render(ns, dtype)[None]])
        next_k, reset_k = jax.random.split(k)
        fs, fframes = _fresh(reset_k, dtype)
        post_s = jnp.where(done, fs, ns)
        post = jnp.where(done, fframes, pre)
        return (post_s, jnp.where(done, 0, t1), post, next_k), (
            post, rew, done, pre, (t1 >= MAX_STEPS) & ~term)
    return jax.vmap(f)


def run(carry, actions, dtype):
    """The reference put in the program's place: K free-running steps of
    every lane from `carry`, in `dtype`. Returns `(out, after)` shaped as
    `check` takes them."""
    state, t, frames, key = lanes(carry)
    lane = _lane_step(dtype)

    def body(c, a):
        c, (post, rew, done, pre, trunc) = lane(*c, a)
        return c, (post.astype(jnp.float32), rew, done,
                   pre.astype(jnp.float32), trunc)

    (s, t, fr, k), (obs, rew, done, tobs, trunc) = jax.lax.scan(
        body, (state.astype(dtype), t, frames.astype(dtype), key), actions)
    return ((obs, rew, done, {"terminal_obs": tobs, "truncated": trunc}),
            (s.astype(jnp.float32), t, fr.astype(jnp.float32), k))


def check(carry_in, actions, out, after):
    """Judge one chunk: (largest float gap, count of exact mismatches).

    `out` is the chunk's `(obs, reward, done, info)` with a leading K axis;
    `after` is `lanes()` of the carry after the chunk."""
    obs, reward, done, info = out
    lane = _lane_step(jnp.float32)

    def body(c, xs):
        a, p_obs, p_rew, p_done, p_tobs, p_trunc = xs
        c, (post, rew, r_done, pre, trunc) = lane(*c, a)
        gap = jnp.maximum(jnp.max(jnp.abs(p_obs - post)),
                          jnp.max(jnp.abs(p_tobs - pre)))
        bad = (jnp.sum(p_rew != rew) + jnp.sum(p_done != r_done)
               + jnp.sum(p_trunc != trunc))
        return c, (gap, bad)

    (s, t, frames, key), (gaps, bads) = jax.lax.scan(
        body, lanes(carry_in),
        (actions, obs, reward, done, info["terminal_obs"], info["truncated"]))
    o_state, o_t, o_frames, o_key = after
    gap = jnp.max(jnp.stack([jnp.max(gaps), jnp.max(jnp.abs(o_state - s)),
                             jnp.max(jnp.abs(o_frames - frames))]))
    bad = (jnp.sum(bads) + jnp.sum(o_t != t)
           + jnp.sum(jnp.any(o_key != key, axis=-1)))
    return gap, bad
