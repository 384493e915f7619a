"""Pallas TPU megastep — K fused environment steps per kernel launch.

The vmap execution path lowers each env step as a chain of many small XLA
ops, so a T-step rollout pays T× op dispatch and T× HBM round-trips for
state vectors of a few floats. This kernel keeps the whole batched state
resident in VMEM and advances it K steps per launch: physics update,
reward/done computation, time-limit truncation, auto-reset re-entry and the
observation write all happen inside one `pallas_call`.

Layout (see specs.py): state components are sublane rows, the env batch is
the 128-wide lane dimension. Per grid step one program instance owns a
(S', BB) state tile plus the (K, ·, BB) action/reset/output tiles for its
batch slice; the K-loop is a `fori_loop` carrying the state tile in
registers/VMEM, so HBM traffic per launch is O(K·(obs+reward+done)) writes
instead of O(K·everything) round-trips.

Randomness never enters the kernel: classic-control dynamics are
action-deterministic, and the auto-reset re-entry states (the only RNG
consumer) are precomputed outside with the exact `jax.random` call sequence
the vmap path makes (ops.py), then selected per lane with `jnp.where`. That
is what makes vmap/fused bit-parity a testable contract.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def fused_transition(step_rows: Callable, rows: jax.Array, act: jax.Array,
                     fresh: jax.Array, fresh_obs: jax.Array,
                     s_env: int, max_steps: Optional[int]):
    """One fused step on row-major state: dynamics + TimeLimit + AutoReset.

    All operands are 2-D `(rows, B)` float32. Mirrors, in order,
    `AutoReset(TimeLimit(env)).step` with the fresh reset state/obs already
    materialised. Shared by the Pallas kernel and the jnp reference (ref.py).

    Returns (new_rows, obs, terminal_obs, reward, done, truncated) —
    `terminal_obs` is the pre-reset observation AutoReset surfaces in
    `info["terminal_obs"]`; `truncated` is TimeLimit's distinct cut signal
    (1.0 only on a time-limit cut of a non-terminal state, all-zero when
    there is no time limit) surfaced in `info["truncated"]`.
    """
    stepped, obs, reward, done = step_rows(rows[:s_env], act)
    trunc = jnp.zeros_like(done)
    if max_steps is not None:
        tcnt = rows[s_env:s_env + 1] + 1.0
        trunc = (tcnt >= float(max_steps)).astype(jnp.float32) * (1.0 - done)
        done = jnp.maximum(done, (tcnt >= float(max_steps)).astype(jnp.float32))
        stepped = jnp.concatenate([stepped, tcnt], axis=0)
    new_rows = jnp.where(done > 0.0, fresh, stepped)
    obs_out = jnp.where(done > 0.0, fresh_obs, obs)
    return new_rows, obs_out, obs, reward, done, trunc


def _megastep_kernel(state_ref, act_ref, fresh_ref, fobs_ref,
                     out_state_ref, obs_ref, tobs_ref, rew_ref, done_ref,
                     trunc_ref, *, step_rows: Callable, k: int, s_env: int,
                     max_steps: Optional[int]):
    def body(t, rows):
        act = act_ref[pl.ds(t, 1), :]                    # (1, BB)
        fresh = fresh_ref[pl.ds(t, 1), :, :][0]          # (S', BB)
        fobs = fobs_ref[pl.ds(t, 1), :, :][0]            # (O, BB)
        new_rows, obs_out, tobs, reward, done, trunc = fused_transition(
            step_rows, rows, act, fresh, fobs, s_env, max_steps)
        obs_ref[pl.ds(t, 1), :, :] = obs_out[None]
        tobs_ref[pl.ds(t, 1), :, :] = tobs[None]
        rew_ref[pl.ds(t, 1), :] = reward
        done_ref[pl.ds(t, 1), :] = done
        trunc_ref[pl.ds(t, 1), :] = trunc
        return new_rows

    out_state_ref[...] = jax.lax.fori_loop(0, k, body, state_ref[...])


def megastep_pallas(step_rows: Callable, state: jax.Array, actions: jax.Array,
                    fresh: jax.Array, fresh_obs: jax.Array, *,
                    max_steps: Optional[int] = None, batch_block: int = 128,
                    interpret: bool = False):
    """Run K fused env steps over the batch as one `pallas_call`.

    state (S', B) f32; actions (K, B) f32; fresh (K, S', B) f32 precomputed
    auto-reset states; fresh_obs (K, O, B) f32. The batch is padded to the
    `batch_block` lane boundary (zero lanes compute inert garbage that is
    sliced off). Returns (new_state (S', B), obs (K, O, B),
    terminal_obs (K, O, B), reward (K, B), done (K, B),
    truncated (K, B)) — all f32.
    """
    sp, b = state.shape
    k = actions.shape[0]
    o = fresh_obs.shape[1]
    s_env = sp - (1 if max_steps is not None else 0)

    bb = batch_block
    bp = pl.cdiv(b, bb) * bb
    if bp != b:
        pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, bp - b)])
        state, actions, fresh, fresh_obs = map(pad, (state, actions, fresh,
                                                     fresh_obs))

    outs = pl.pallas_call(
        functools.partial(_megastep_kernel, step_rows=step_rows, k=k,
                          s_env=s_env, max_steps=max_steps),
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((sp, bb), lambda i: (0, i)),
            pl.BlockSpec((k, bb), lambda i: (0, i)),
            pl.BlockSpec((k, sp, bb), lambda i: (0, 0, i)),
            pl.BlockSpec((k, o, bb), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((sp, bb), lambda i: (0, i)),
            pl.BlockSpec((k, o, bb), lambda i: (0, 0, i)),
            pl.BlockSpec((k, o, bb), lambda i: (0, 0, i)),
            pl.BlockSpec((k, bb), lambda i: (0, i)),
            pl.BlockSpec((k, bb), lambda i: (0, i)),
            pl.BlockSpec((k, bb), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, bp), jnp.float32),
            jax.ShapeDtypeStruct((k, o, bp), jnp.float32),
            jax.ShapeDtypeStruct((k, o, bp), jnp.float32),
            jax.ShapeDtypeStruct((k, bp), jnp.float32),
            jax.ShapeDtypeStruct((k, bp), jnp.float32),
            jax.ShapeDtypeStruct((k, bp), jnp.float32),
        ],
        interpret=interpret,
        # fixed, not taken from the wrapped function: profiles name the
        # kernel by it
        name="_megastep_kernel",
    )(state.astype(jnp.float32), actions.astype(jnp.float32),
      fresh.astype(jnp.float32), fresh_obs.astype(jnp.float32))

    return tuple(x[..., :b] for x in outs)
