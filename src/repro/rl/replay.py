"""Device-resident ring replay buffer (pytree state, fully jittable).

The paper's DQN uses a 50 000-transition memory (Table I). Keeping it on
device means the sample→learn path never leaves the accelerator — the same
"stay in one memory space" principle as the renderer (§II-B).

Contract the fused trainer leans on (repro.train.fused): the ring is a
pure function of the transition STREAM, not of how the stream is chunked
into `replay_add_batch` calls — any regrouping of the same transitions
yields an identical ReplayState, so chunk boundaries in the donated train
scan can never lose or duplicate a transition. Pinned as a property in
tests/test_train_fused.py (`check_replay_chunking`) with hypothesis
drivers in tests/test_train_property.py.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint


class ReplayState(NamedTuple):
    obs: jax.Array        # (cap, *obs_shape)
    action: jax.Array     # (cap, *act_shape)
    reward: jax.Array     # (cap,)
    next_obs: jax.Array   # (cap, *obs_shape)
    done: jax.Array       # (cap,)
    ptr: jax.Array        # ()
    size: jax.Array       # ()


def replay_init(capacity: int, obs_shape: Tuple[int, ...], act_shape: Tuple[int, ...] = (),
                act_dtype=jnp.int32) -> ReplayState:
    return ReplayState(
        obs=jnp.zeros((capacity,) + obs_shape, jnp.float32),
        action=jnp.zeros((capacity,) + act_shape, act_dtype),
        reward=jnp.zeros((capacity,), jnp.float32),
        next_obs=jnp.zeros((capacity,) + obs_shape, jnp.float32),
        done=jnp.zeros((capacity,), jnp.float32),
        ptr=jnp.asarray(0, jnp.int32),
        size=jnp.asarray(0, jnp.int32),
    )


def replay_add_batch(state: ReplayState, obs, action, reward, next_obs, done) -> ReplayState:
    """Insert a batch of B transitions at the ring pointer (wrapping).

    Transition j lands in row (ptr + j) % cap of every field. Those rows are
    one contiguous block unless the batch wraps past the ring's end, so each
    field is written with `dynamic_update_slice` and never with a scatter
    over an index vector: the TPU runs a scatter row by row, and on a TPU
    v5e the fused DQN step's five 256-row scatters took nearly half its
    device time. Under `vmap` the pointer has to stay unbatched for that to
    hold (a batched start makes each `dynamic_update_slice` a scatter), so
    the fleet trainer keeps `ptr` and `size` shared by its rows.

    The write is branch-free and the same whether or not the batch wraps.
    Let `shift` be the count of rows that wrap to the front (0 unless it
    wraps) and `rolled` the batch rotated by `shift`, so its tail sits in
    rows 0..shift and its head after. Each field takes two B-row blocks:
    first at row 0, `rolled`'s tail over rows 0..shift with the old rows
    kept after; then at `ptr - shift` (= `min(ptr, cap - B)`), `rolled`'s
    head from row `ptr` to the ring's end, with the old rows kept before
    `ptr`. Without a wrap the first block writes rows 0..B back unchanged.
    The ring is held row-major: left free, the TPU compiler lays a narrow
    (cap, 4) field out column-major, and the row blocks then cost copies of
    the whole field each step.

    When B > capacity the ring lap would write the same slot from several
    batch elements, so the batch is truncated to its last `cap`
    transitions up front (ring semantics: later writes win; the dropped
    head would have been overwritten within this same call anyway). `ptr`
    still advances by the full B, as if every transition had been written.
    """
    cap = state.obs.shape[0]
    n = b = obs.shape[0]
    start = state.ptr
    if b > cap:
        drop = b - cap  # static (shape-derived), so plain-Python control flow
        obs, action, reward, next_obs, done = (
            x[drop:] for x in (obs, action, reward, next_obs, done))
        start = (state.ptr + drop) % cap
        b = cap
    shift = jnp.maximum(start + b - cap, 0)
    head_at = start - shift
    head = jnp.arange(b) >= shift  # rows of the block at head_at that take the batch

    def write(ring, x):
        x = x.astype(ring.dtype)
        rolled = lax.dynamic_slice_in_dim(jnp.concatenate([x, x]), b - shift, b)
        mask = head.reshape((b,) + (1,) * (x.ndim - 1))
        ring = lax.dynamic_update_slice_in_dim(
            ring, jnp.where(mask, ring[:b], rolled), 0, axis=0)
        old = lax.dynamic_slice_in_dim(ring, head_at, b)
        ring = lax.dynamic_update_slice_in_dim(
            ring, jnp.where(mask, rolled, old), head_at, axis=0)
        return with_layout_constraint(ring, Layout(tuple(range(ring.ndim))))

    return ReplayState(
        obs=write(state.obs, obs),
        action=write(state.action, action),
        reward=write(state.reward, reward),
        next_obs=write(state.next_obs, next_obs),
        done=write(state.done, done),
        ptr=(state.ptr + n) % cap,
        size=jnp.minimum(state.size + n, cap),
    )


def replay_sample(state: ReplayState, key: jax.Array, batch: int):
    """Uniform sample of `batch` transitions from the valid region."""
    idx = jax.random.randint(key, (batch,), 0, jnp.maximum(state.size, 1))
    return (
        state.obs[idx],
        state.action[idx],
        state.reward[idx],
        state.next_obs[idx],
        state.done[idx],
    )
