"""Per-env fused-step specs: row-major dynamics for the megastep kernel.

A `FusedSpec` describes one base environment's dynamics in *row-major* form:
the batched state is a single `(S, B)` float32 array (one row per state
component, batch along the 128-wide lane dimension) and `step_rows` advances
all B lanes with pure element-wise VPU ops. The same `step_rows` body runs
inside the Pallas megastep kernel (megastep.py) and the pure-jnp reference
(ref.py), so kernel and oracle share one dynamics implementation.

Only the *dynamics* (`step_rows`) is written by hand — every formula mirrors
the canonical env module (envs/classic/*, envs/grid/*, envs/arcade/*,
envs/puzzle.py) operation-for-operation; parity with the vmap path is a test
contract (tests/test_conformance.py), not an aspiration. The *layout*
(state/obs row counts, flatten/unflatten between the state pytree and the
row matrix) is derived automatically by `derive_layout` from a traced
`reset` of the env: field order, shapes and dtypes come from the state
NamedTuple itself, so a new env needs only its `step_rows` math, not a
hand-maintained field table. Integer state (boards, counters, cell indices)
rides in float32 rows; the values are small integers, so the round-trip
through f32 is exact. An env whose dynamics index rows in a different order
than its state fields declares a `field_order` override (Snake: the age
grid is field 0 but the dynamics put the scalars first).

Registry: `spec_for(core_env)` derives the spec for a supported base env;
`lookup(env)` additionally accepts a single declared `TimeLimit` over it
and returns `(spec, max_steps)`, else None.
"""
from __future__ import annotations

import functools
import weakref
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class FusedSpec(NamedTuple):
    """Row-major dynamics of one base env (state components × batch lanes)."""

    name: str
    state_size: int     # S: rows in the flattened base state
    obs_size: int       # O: rows in the observation
    # flatten: batched state pytree with (..., B) leaves -> (..., S, B) f32
    flatten: Callable[[Any], jax.Array]
    # unflatten: (S, B) f32 -> batched state pytree (inverse of flatten)
    unflatten: Callable[[jax.Array], Any]
    # step_rows: (rows (S, B), action (1, B) f32)
    #   -> (new_rows (S, B), obs (O, B), reward (1, B), done (1, B) f32)
    step_rows: Callable[[jax.Array, jax.Array], Tuple[jax.Array, ...]]
    # obs rows == state rows (obs = flattened base state). When True and the
    # base env has a capsule `scene()`, pixel wrapper stacks
    # (ObsToPixels / FrameStack) can run fused too: the kernel steps the
    # row-major game logic, and frames are rasterised per-chunk outside the
    # fused body (ops.fused_step).
    obs_is_state: bool = False


class FusedDynamics(NamedTuple):
    """What a fused env must declare by hand: the row math, and nothing else.

    `step_rows_factory(env)` closes over static config (board size etc.) and
    returns the `step_rows` body. Layout is derived; `field_order` overrides
    the row order only when the dynamics index rows in a different order
    than the state NamedTuple declares its fields.
    """

    step_rows_factory: Callable[[Any], Callable]
    obs_is_state: bool = False
    field_order: Optional[Tuple[str, ...]] = None


# -- derived layout ----------------------------------------------------------

def derive_layout(env, field_order: Optional[Tuple[str, ...]] = None):
    """Introspect a traced `reset`: (state_size, obs_size, flatten, unflatten).

    The state NamedTuple's fields — in declaration order, or `field_order` —
    become consecutive row blocks of `prod(field_shape)` rows each; the
    batch dimension stays on the trailing (lane) axis. `flatten` accepts any
    leading dims before the batch axis (the (K, B, ...) fresh-reset stacks
    `ops.fused_step` scans out), `unflatten` is its exact inverse on `(S, B)`
    rows, restoring per-field shapes and dtypes.
    """
    state_s, obs_s = jax.eval_shape(env.reset, jax.random.PRNGKey(0))
    cls = type(state_s)
    fields = tuple(state_s._fields)
    order = tuple(field_order) if field_order is not None else fields
    if sorted(order) != sorted(fields):
        raise ValueError(f"field_order {order} != state fields {fields}")
    shapes = {f: tuple(getattr(state_s, f).shape) for f in fields}
    dtypes = {f: getattr(state_s, f).dtype for f in fields}
    sizes = {f: int(np.prod(shapes[f], dtype=int)) for f in fields}
    state_size = sum(sizes.values())
    obs_size = int(np.prod(obs_s.shape, dtype=int))

    def flatten(state) -> jax.Array:
        rows = []
        for f in order:
            leaf = getattr(state, f)
            lead = leaf.shape[: leaf.ndim - len(shapes[f])]
            rows.append(jnp.swapaxes(
                jnp.reshape(leaf, lead + (sizes[f],)), -1, -2))
        return jnp.concatenate(rows, axis=-2).astype(jnp.float32)

    def unflatten(rows: jax.Array):
        parts, offset = {}, 0
        for f in order:
            block = jnp.swapaxes(rows[offset:offset + sizes[f]], -1, -2)
            offset += sizes[f]
            parts[f] = jnp.reshape(
                block, block.shape[:-1] + shapes[f]).astype(dtypes[f])
        return cls(**parts)

    return state_size, obs_size, flatten, unflatten


# -- CartPole ----------------------------------------------------------------

def _cartpole_rows(env) -> Callable:
    from repro.envs.classic.cartpole import (
        FORCE_MAG, GRAVITY, LENGTH, MASSPOLE, POLEMASS_LENGTH, TAU,
        THETA_THRESHOLD, TOTAL_MASS, X_THRESHOLD)

    def step_rows(rows, act):
        x, x_dot = rows[0:1], rows[1:2]
        theta, theta_dot = rows[2:3], rows[3:4]
        force = jnp.where(act == 1.0, FORCE_MAG, -FORCE_MAG)
        costheta, sintheta = jnp.cos(theta), jnp.sin(theta)
        temp = (force + POLEMASS_LENGTH * theta_dot**2 * sintheta) / TOTAL_MASS
        thetaacc = (GRAVITY * sintheta - costheta * temp) / (
            LENGTH * (4.0 / 3.0 - MASSPOLE * costheta**2 / TOTAL_MASS)
        )
        xacc = temp - POLEMASS_LENGTH * thetaacc * costheta / TOTAL_MASS
        nx = x + TAU * x_dot
        nxd = x_dot + TAU * xacc
        nth = theta + TAU * theta_dot
        nthd = theta_dot + TAU * thetaacc
        new = jnp.concatenate([nx, nxd, nth, nthd], axis=0)
        done = ((jnp.abs(nx) > X_THRESHOLD)
                | (jnp.abs(nth) > THETA_THRESHOLD)).astype(jnp.float32)
        return new, new, jnp.ones_like(done), done

    return step_rows


# -- MountainCar -------------------------------------------------------------

def _mountain_car_rows(env) -> Callable:
    from repro.envs.classic.mountain_car import (
        FORCE, GOAL_POS, GOAL_VEL, GRAVITY, MAX_POS, MAX_SPEED, MIN_POS)

    def step_rows(rows, act):
        pos, vel = rows[0:1], rows[1:2]
        nv = vel + (act - 1.0) * FORCE + jnp.cos(3 * pos) * (-GRAVITY)
        nv = jnp.clip(nv, -MAX_SPEED, MAX_SPEED)
        npos = jnp.clip(pos + nv, MIN_POS, MAX_POS)
        nv = jnp.where((npos <= MIN_POS) & (nv < 0), 0.0, nv)
        new = jnp.concatenate([npos, nv], axis=0)
        done = ((npos >= GOAL_POS) & (nv >= GOAL_VEL)).astype(jnp.float32)
        return new, new, -jnp.ones_like(done), done

    return step_rows


# -- Pendulum ----------------------------------------------------------------

def _pendulum_rows(env) -> Callable:
    from repro.envs.classic.pendulum import (
        DT, G, L, M, MAX_SPEED, MAX_TORQUE, _angle_normalize)

    def step_rows(rows, act):
        th, thdot = rows[0:1], rows[1:2]
        u = jnp.clip(act, -MAX_TORQUE, MAX_TORQUE)
        costs = _angle_normalize(th) ** 2 + 0.1 * thdot**2 + 0.001 * u**2
        nthdot = thdot + (3 * G / (2 * L) * jnp.sin(th) + 3.0 / (M * L**2) * u) * DT
        nthdot = jnp.clip(nthdot, -MAX_SPEED, MAX_SPEED)
        nth = th + nthdot * DT
        new = jnp.concatenate([nth, nthdot], axis=0)
        obs = jnp.concatenate([jnp.cos(nth), jnp.sin(nth), nthdot], axis=0)
        done = jnp.zeros_like(u)
        return new, obs, -costs, done

    return step_rows


# -- Acrobot -----------------------------------------------------------------

def _acrobot_rows(env) -> Callable:
    from repro.envs.classic.acrobot import (
        DT, G, I1, I2, L1, LC1, LC2, M1, M2, MAX_VEL_1, MAX_VEL_2)

    def dsdt(s, torque):
        theta1, theta2 = s[0:1], s[1:2]
        dtheta1, dtheta2 = s[2:3], s[3:4]
        d1 = (M1 * LC1**2
              + M2 * (L1**2 + LC2**2 + 2 * L1 * LC2 * jnp.cos(theta2))
              + I1 + I2)
        d2 = M2 * (LC2**2 + L1 * LC2 * jnp.cos(theta2)) + I2
        phi2 = M2 * LC2 * G * jnp.cos(theta1 + theta2 - jnp.pi / 2.0)
        phi1 = (-M2 * L1 * LC2 * dtheta2**2 * jnp.sin(theta2)
                - 2 * M2 * L1 * LC2 * dtheta2 * dtheta1 * jnp.sin(theta2)
                + (M1 * LC1 + M2 * L1) * G * jnp.cos(theta1 - jnp.pi / 2)
                + phi2)
        ddtheta2 = (torque + d2 / d1 * phi1
                    - M2 * L1 * LC2 * dtheta1**2 * jnp.sin(theta2) - phi2
                    ) / (M2 * LC2**2 + I2 - d2**2 / d1)
        ddtheta1 = -(d2 * ddtheta2 + phi1) / d1
        return jnp.concatenate([dtheta1, dtheta2, ddtheta1, ddtheta2], axis=0)

    def wrap(x, lo, hi):
        return lo + jnp.mod(x - lo, hi - lo)

    def step_rows(rows, act):
        torque = act - 1.0  # TORQUES = [-1, 0, 1]
        k1 = dsdt(rows, torque)
        k2 = dsdt(rows + DT / 2 * k1, torque)
        k3 = dsdt(rows + DT / 2 * k2, torque)
        k4 = dsdt(rows + DT * k3, torque)
        ns = rows + DT / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
        th1 = wrap(ns[0:1], -jnp.pi, jnp.pi)
        th2 = wrap(ns[1:2], -jnp.pi, jnp.pi)
        dth1 = jnp.clip(ns[2:3], -MAX_VEL_1, MAX_VEL_1)
        dth2 = jnp.clip(ns[3:4], -MAX_VEL_2, MAX_VEL_2)
        new = jnp.concatenate([th1, th2, dth1, dth2], axis=0)
        done = ((-jnp.cos(th1) - jnp.cos(th2 + th1)) > 1.0).astype(jnp.float32)
        reward = jnp.where(done > 0.0, 0.0, -1.0)
        obs = jnp.concatenate(
            [jnp.cos(th1), jnp.sin(th1), jnp.cos(th2), jnp.sin(th2),
             dth1, dth2], axis=0)
        return new, obs, reward, done

    return step_rows


# -- LightsOut ---------------------------------------------------------------

def _lightsout_rows(env) -> Callable:
    n = env.n
    m = n * n

    def step_rows(rows, act):
        board, t = rows[:m], rows[m:m + 1]
        # Per-cell (row, col) indices as (m, 1) planes; 2-D iota is TPU-native.
        idx = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        ri = (idx // n).astype(jnp.float32)
        ci = (idx % n).astype(jnp.float32)
        r = jnp.floor(act / n)
        c = act - r * n
        cross = (((ri == r) & (jnp.abs(ci - c) <= 1))
                 | ((ci == c) & (jnp.abs(ri - r) <= 1))).astype(jnp.float32)
        nb = board + cross - 2.0 * board * cross  # XOR on {0, 1} rows
        done = (jnp.sum(nb, axis=0, keepdims=True) == 0).astype(jnp.float32)
        reward = jnp.where(done > 0.0, 10.0, -1.0)
        new = jnp.concatenate([nb, t + 1.0], axis=0)
        return new, nb, reward, done

    return step_rows


# -- Grid suite (envs/grid) --------------------------------------------------
#
# The level layout (holes/cliff/walls, goal, food priorities) rides in the
# state rows, so the precomputed AutoReset fresh states regenerate it per
# episode *inside* the kernel's lane-select — on-device procedural
# generation on the same key chain that gives vmap/fused bit-parity.

def _grid_moves(act):
    """(1, B) f32 action -> (dr, dc) in the Gym FrozenLake order."""
    dr = jnp.where(act == 1.0, 1.0, 0.0) - jnp.where(act == 3.0, 1.0, 0.0)
    dc = jnp.where(act == 2.0, 1.0, 0.0) - jnp.where(act == 0.0, 1.0, 0.0)
    return dr, dc


def _grid_move(pos, act, n_rows, n_cols):
    """(1, B) f32 cell index + action -> edge-clipped new cell index.

    The f32 twin of `clip(r+dr) * n_cols + clip(c+dc)` in the env `step`s —
    exact for any board whose cell count fits f32 integers."""
    dr, dc = _grid_moves(act)
    r = jnp.floor(pos / n_cols)
    c = pos - r * n_cols
    nr = jnp.clip(r + dr, 0.0, n_rows - 1.0)
    nc = jnp.clip(c + dc, 0.0, n_cols - 1.0)
    return nr * n_cols + nc


def _cell_iota(m):
    """(m, 1) f32 per-cell index plane. 2-D iota is TPU-native, but Mosaic
    builds it only as an integer vector, so it is cast afterwards."""
    return jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0).astype(jnp.float32)


def _frozen_lake_rows(env) -> Callable:
    from repro.envs.grid.frozen_lake import GOAL_REWARD

    n, m = env.n, env.m

    def step_rows(rows, act):
        pos, holes = rows[0:1], rows[1:1 + m]
        npos = _grid_move(pos, act, n, n)
        idx = _cell_iota(m)
        at = (idx == npos).astype(jnp.float32)
        hole = jnp.sum(at * holes, axis=0, keepdims=True)
        goal = (npos == m - 1.0).astype(jnp.float32)
        done = jnp.maximum(hole, goal)
        reward = goal * GOAL_REWARD
        codes = jnp.where(at > 0.0, 3.0,
                          jnp.where(idx == m - 1.0, 2.0, holes))
        new = jnp.concatenate([npos, holes], axis=0)
        return new, codes, reward, done

    return step_rows


def _cliff_walk_rows(env) -> Callable:
    from repro.envs.grid.cliff_walk import CLIFF_REWARD, STEP_REWARD

    n_rows, n_cols, m = env.n_rows, env.n_cols, env.m
    start = float(env.start)

    def step_rows(rows, act):
        pos, cliff = rows[0:1], rows[1:1 + m]
        npos = _grid_move(pos, act, n_rows, n_cols)
        idx = _cell_iota(m)
        at = (idx == npos).astype(jnp.float32)
        fell = jnp.sum(at * cliff, axis=0, keepdims=True)
        goal = (npos == m - 1.0).astype(jnp.float32)
        new_pos = jnp.where(fell > 0.0, start, npos)
        reward = jnp.where(fell > 0.0, CLIFF_REWARD, STEP_REWARD)
        at2 = (idx == new_pos).astype(jnp.float32)
        codes = jnp.where(at2 > 0.0, 3.0,
                          jnp.where(idx == m - 1.0, 2.0, cliff))
        new = jnp.concatenate([new_pos, cliff], axis=0)
        return new, codes, reward, goal

    return step_rows


def _maze_rows(env) -> Callable:
    from repro.envs.grid.maze import GOAL_REWARD

    n, m = env.n, env.m

    def step_rows(rows, act):
        pos, goal, walls = rows[0:1], rows[1:2], rows[2:2 + m]
        cand = _grid_move(pos, act, n, n)
        idx = _cell_iota(m)
        at = (idx == cand).astype(jnp.float32)
        blocked = jnp.sum(at * walls, axis=0, keepdims=True)
        npos = jnp.where(blocked > 0.0, pos, cand)
        done = (npos == goal).astype(jnp.float32)
        reward = done * GOAL_REWARD
        at2 = (idx == npos).astype(jnp.float32)
        codes = jnp.where(at2 > 0.0, 3.0, jnp.where(idx == goal, 2.0, walls))
        new = jnp.concatenate([npos, goal, walls], axis=0)
        return new, codes, reward, done

    return step_rows


def _snake_rows(env) -> Callable:
    from repro.envs.grid.snake import DEATH_REWARD, EAT_REWARD, PHI

    n, m = env.n, env.m

    def step_rows(rows, act):
        head, food = rows[0:1], rows[1:2]
        length, eaten = rows[2:3], rows[3:4]
        ages, prio = rows[4:4 + m], rows[4 + m:4 + 2 * m]
        dr, dc = _grid_moves(act)
        r = jnp.floor(head / n)
        c = head - r * n
        nr, nc = r + dr, c + dc
        inb = ((nr >= 0.0) & (nr <= n - 1.0)
               & (nc >= 0.0) & (nc <= n - 1.0)).astype(jnp.float32)
        cand = (jnp.clip(nr, 0.0, n - 1.0) * n + jnp.clip(nc, 0.0, n - 1.0))
        eat = inb * (cand == food).astype(jnp.float32)
        ages2 = jnp.maximum(ages - jnp.where(eat > 0.0, 0.0, 1.0), 0.0)
        idx = _cell_iota(m)
        at = (idx == cand).astype(jnp.float32)
        hit = jnp.sum(at * (ages2 > 0.0).astype(jnp.float32), axis=0,
                      keepdims=True)
        die = jnp.maximum(1.0 - inb, hit)
        new_len = length + eat
        ages3 = jnp.where(at > 0.0, new_len, ages2)
        win = (new_len >= m).astype(jnp.float32)
        done = jnp.maximum(die, win)
        new_eaten = eaten + eat
        # Deterministic food chain (snake.place_food, same min-reductions):
        # k-th food = free cell minimising frac(prio + k·φ).
        vals = prio + new_eaten * PHI
        vals = vals - jnp.floor(vals)
        free = ((ages3 == 0.0) & (idx != cand)).astype(jnp.float32)
        v = jnp.where(free > 0.0, vals, 2.0)
        vmin = jnp.min(v, axis=0, keepdims=True)
        placed = jnp.min(jnp.where(v == vmin, idx, float(m)), axis=0,
                         keepdims=True)
        new_food = jnp.where(eat * (1.0 - done) > 0.0, placed, food)
        reward = eat * EAT_REWARD + die * DEATH_REWARD
        codes = jnp.where(at > 0.0, 2.0,
                          jnp.where(ages3 > 0.0, 1.0,
                                    jnp.where(idx == new_food, 3.0, 0.0)))
        new = jnp.concatenate([cand, new_food, new_len, new_eaten, ages3,
                               prio], axis=0)
        return new, codes, reward, done

    return step_rows


# -- Pong --------------------------------------------------------------------

def _pong_rows(env) -> Callable:
    from repro.envs.arcade.pong import (
        MAX_VY, OPP_SPEED, OPP_X, PADDLE_HALF, PADDLE_SPEED, PLAYER_X, SPIN)

    def step_rows(rows, act):
        x, y = rows[0:1], rows[1:2]
        vx, vy = rows[2:3], rows[3:4]
        py, oy = rows[4:5], rows[5:6]
        move = act - 1.0
        py = jnp.clip(py + move * PADDLE_SPEED, PADDLE_HALF, 1.0 - PADDLE_HALF)
        oy = oy + jnp.clip(y - oy, -OPP_SPEED, OPP_SPEED)
        oy = jnp.clip(oy, PADDLE_HALF, 1.0 - PADDLE_HALF)
        nx = x + vx
        ny = y + vy
        vy = jnp.where((ny < 0.0) | (ny > 1.0), -vy, vy)
        ny = jnp.where(ny < 0.0, -ny, ny)
        ny = jnp.where(ny > 1.0, 2.0 - ny, ny)
        hit_p = ((x < PLAYER_X) & (nx >= PLAYER_X)
                 & (jnp.abs(ny - py) <= PADDLE_HALF))
        vy = jnp.where(hit_p, jnp.clip(vy + (ny - py) * SPIN,
                                       -MAX_VY, MAX_VY), vy)
        vx = jnp.where(hit_p, -vx, vx)
        nx = jnp.where(hit_p, 2.0 * PLAYER_X - nx, nx)
        hit_o = ((x > OPP_X) & (nx <= OPP_X)
                 & (jnp.abs(ny - oy) <= PADDLE_HALF))
        vy = jnp.where(hit_o, jnp.clip(vy + (ny - oy) * SPIN,
                                       -MAX_VY, MAX_VY), vy)
        vx = jnp.where(hit_o, -vx, vx)
        nx = jnp.where(hit_o, 2.0 * OPP_X - nx, nx)
        new = jnp.concatenate([nx, ny, vx, vy, py, oy], axis=0)
        reward = (nx < 0.0).astype(jnp.float32) - (nx > 1.0).astype(jnp.float32)
        done = ((nx < 0.0) | (nx > 1.0)).astype(jnp.float32)
        return new, new, reward, done

    return step_rows


# -- Breakout ----------------------------------------------------------------

def _breakout_rows(env) -> Callable:
    from repro.envs.arcade.breakout import (
        BRICK_COLS, BRICK_H, BRICK_ROWS, BRICK_TOP, CLEAR_BONUS, MAX_VX,
        PADDLE_HALF, PADDLE_SPEED, PADDLE_Y, SPIN)

    m = BRICK_ROWS * BRICK_COLS

    def step_rows(rows, act):
        x, y = rows[0:1], rows[1:2]
        vx, vy = rows[2:3], rows[3:4]
        px = rows[4:5]
        board = rows[5:5 + m]
        move = act - 1.0
        px = jnp.clip(px + move * PADDLE_SPEED, PADDLE_HALF, 1.0 - PADDLE_HALF)
        nx = x + vx
        ny = y + vy
        vx = jnp.where((nx < 0.0) | (nx > 1.0), -vx, vx)
        nx = jnp.where(nx < 0.0, -nx, nx)
        nx = jnp.where(nx > 1.0, 2.0 - nx, nx)
        vy = jnp.where(ny < 0.0, -vy, vy)
        ny = jnp.where(ny < 0.0, -ny, ny)
        hit_pad = ((y < PADDLE_Y) & (ny >= PADDLE_Y)
                   & (jnp.abs(nx - px) <= PADDLE_HALF))
        vx = jnp.where(hit_pad, jnp.clip(vx + (nx - px) * SPIN,
                                         -MAX_VX, MAX_VX), vx)
        vy = jnp.where(hit_pad, -vy, vy)
        ny = jnp.where(hit_pad, 2.0 * PADDLE_Y - ny, ny)
        # Per-cell (row, col) planes; 2-D iota is TPU-native (LightsOut idiom).
        idx = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        rr = (idx // BRICK_COLS).astype(jnp.float32)
        cc = (idx % BRICK_COLS).astype(jnp.float32)
        cell_r = jnp.floor((ny - BRICK_TOP) / BRICK_H)
        cell_c = jnp.floor(nx * BRICK_COLS)
        in_region = ((ny >= BRICK_TOP)
                     & (ny < BRICK_TOP + BRICK_ROWS * BRICK_H))
        mask = ((rr == cell_r) & (cc == cell_c)).astype(jnp.float32) \
            * in_region.astype(jnp.float32) * board
        broke = jnp.sum(mask, axis=0, keepdims=True)
        new_board = board - mask
        vy = jnp.where(broke > 0.0, -vy, vy)
        cleared = jnp.sum(new_board, axis=0, keepdims=True) == 0.0
        lost = ny > 1.0
        done = (cleared | lost).astype(jnp.float32)
        reward = broke + jnp.where(cleared, CLEAR_BONUS, 0.0)
        new = jnp.concatenate([nx, ny, vx, vy, px, new_board], axis=0)
        return new, new, reward, done

    return step_rows


# -- registry ----------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dynamics():
    from repro.envs.arcade import Breakout, Pong
    from repro.envs.classic import Acrobot, CartPole, MountainCar, Pendulum
    from repro.envs.grid import CliffWalk, FrozenLake, Maze, Snake
    from repro.envs.puzzle import LightsOut

    return {
        CartPole: FusedDynamics(_cartpole_rows, obs_is_state=True),
        MountainCar: FusedDynamics(_mountain_car_rows, obs_is_state=True),
        Pendulum: FusedDynamics(_pendulum_rows),
        Acrobot: FusedDynamics(_acrobot_rows),
        LightsOut: FusedDynamics(_lightsout_rows),
        Pong: FusedDynamics(_pong_rows, obs_is_state=True),
        Breakout: FusedDynamics(_breakout_rows, obs_is_state=True),
        FrozenLake: FusedDynamics(_frozen_lake_rows),
        CliffWalk: FusedDynamics(_cliff_walk_rows),
        Maze: FusedDynamics(_maze_rows),
        # The dynamics put the scalar rows (head, food, length, eaten)
        # before the grids; the state NamedTuple declares `ages` first.
        Snake: FusedDynamics(_snake_rows, field_order=(
            "head", "food", "length", "eaten", "ages", "prio")),
    }


#: per-instance memo of derived specs: one env instance is probed/looked-up
#: repeatedly (pool construction, then every fused_step trace), and the
#: `jax.eval_shape` reset trace behind `derive_layout` is not free. Weak
#: keys so cached entries die with their env.
_SPEC_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def spec_for(env) -> Optional[FusedSpec]:
    """Derive the `FusedSpec` for a supported *base* env, else None."""
    try:
        return _SPEC_CACHE[env]
    except (KeyError, TypeError):  # miss, or an unhashable/unweakref env
        pass
    dyn = _dynamics().get(type(env))
    if dyn is None:
        spec = None
    else:
        state_size, obs_size, flatten, unflatten = derive_layout(
            env, dyn.field_order)
        spec = FusedSpec(type(env).__name__, state_size, obs_size, flatten,
                         unflatten, dyn.step_rows_factory(env),
                         dyn.obs_is_state)
    try:
        _SPEC_CACHE[env] = spec
    except TypeError:
        pass
    return spec


def lookup(env) -> Optional[Tuple[FusedSpec, Optional[int]]]:
    """(spec, max_steps) for `env` = base or TimeLimit(base), else None.

    The stack is read through its declared pipeline (core/pipeline.py) —
    only a bare base (the `-raw` ids) or a single TimeLimit over it (the
    `-v*` ids) is row-fusable; any other transform changes step semantics
    the kernel doesn't model (pixel stacks are planned one level up, in
    ops.fused_step).
    """
    from repro.core.pipeline import TimeLimit, declared_pipeline

    core, transforms = declared_pipeline(env)
    if core is None:
        return None
    max_steps = None
    if transforms:
        if len(transforms) != 1 or not isinstance(transforms[0], TimeLimit):
            return None
        max_steps = transforms[0].max_steps
    spec = spec_for(core)
    if spec is None:
        return None
    return spec, max_steps
