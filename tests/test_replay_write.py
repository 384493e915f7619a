"""The replay ring's block write equals the row scatter it replaced.

`replay_add_batch` writes each field as contiguous blocks with
`dynamic_update_slice`; the oracle here is the scatter over the index vector
`(ptr + arange(B)) % cap` it replaced, truncation of a batch wider than the
ring included. They must agree leaf for leaf and bit for bit at every start
pointer, with and without a wrap. The compiled fused DQN chunk, solo and
vmapped over a fleet, must hold no scatter under `cairl.replay`, only its
dynamic-update-slices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.registry import make
from repro.rl.replay import ReplayState, replay_add_batch, replay_init
from repro.train import fused as F

from test_trace_scopes import dqn_chunk_text, scoped_ops

CAP = 10


def scatter_add_batch(state: ReplayState, obs, action, reward, next_obs,
                      done) -> ReplayState:
    """The ring write as a scatter of each field over its row indices."""
    cap = state.obs.shape[0]
    b = obs.shape[0]
    start = state.ptr
    if b > cap:
        drop = b - cap
        obs, action, reward, next_obs, done = (
            x[drop:] for x in (obs, action, reward, next_obs, done))
        start = state.ptr + drop
    idx = (start + jnp.arange(min(b, cap))) % cap
    return ReplayState(
        obs=state.obs.at[idx].set(obs),
        action=state.action.at[idx].set(action),
        reward=state.reward.at[idx].set(reward.astype(jnp.float32)),
        next_obs=state.next_obs.at[idx].set(next_obs),
        done=state.done.at[idx].set(done.astype(jnp.float32)),
        ptr=(state.ptr + b) % cap,
        size=jnp.minimum(state.size + b, cap),
    )


def full_ring(obs_shape, ptr, size, seed=0) -> ReplayState:
    """A ring of CAP rows of distinct values, its cursor at `ptr`."""
    r = np.random.default_rng(seed)
    return ReplayState(
        obs=jnp.asarray(r.normal(size=(CAP,) + obs_shape), jnp.float32),
        action=jnp.asarray(r.integers(0, 1000, CAP), jnp.int32),
        reward=jnp.asarray(r.normal(size=CAP), jnp.float32),
        next_obs=jnp.asarray(r.normal(size=(CAP,) + obs_shape), jnp.float32),
        done=jnp.asarray(r.integers(0, 2, CAP), jnp.float32),
        ptr=jnp.asarray(ptr, jnp.int32),
        size=jnp.asarray(size, jnp.int32),
    )


def batch(b, obs_shape, seed=1):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(b,) + obs_shape), jnp.float32),
            jnp.asarray(r.integers(1000, 2000, b), jnp.int32),
            jnp.asarray(r.normal(size=b), jnp.float32),
            jnp.asarray(r.normal(size=(b,) + obs_shape), jnp.float32),
            jnp.asarray(r.integers(0, 2, b), bool))


@pytest.mark.parametrize("obs_shape", [(4,), (2, 3, 5)])
@pytest.mark.parametrize("b", [1, 3, 4, 10, 13])
def test_block_write_matches_scatter_at_every_pointer(b, obs_shape):
    """Below, at and above the ring's size, wrapping and not, from every
    start pointer of a full ring and of one filling up."""
    block = jax.jit(replay_add_batch)
    scatter = jax.jit(scatter_add_batch)
    xs = batch(b, obs_shape)
    for ptr in range(CAP):
        for size in (ptr, CAP):
            state = full_ring(obs_shape, ptr, size, seed=ptr)
            got, want = block(state, *xs), scatter(state, *xs)
            for name, g, w in zip(ReplayState._fields, got, want):
                assert g.dtype == w.dtype and g.shape == w.shape, name
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=f"{name} ptr={ptr}")


def test_block_write_from_an_empty_ring_in_sequence():
    """A stream of uneven batches into a fresh ring, as the learner adds
    them, reads the same after every add."""
    a = b = replay_init(CAP, (4,))
    for i, n in enumerate([3, 7, 1, 10, 4, 13, 9, 2]):
        xs = batch(n, (4,), seed=i)
        a, b = replay_add_batch(a, *xs), scatter_add_batch(b, *xs)
        jax.tree.map(np.testing.assert_array_equal, a, b)


def test_fused_dqn_chunk_writes_the_ring_without_a_scatter():
    """The ring's write compiles to dynamic-update-slices; the sample's
    gathers stay."""
    replay = [opcode for opcode, op_name in scoped_ops(dqn_chunk_text())
              if "cairl.replay" in op_name]
    assert "scatter" not in replay
    assert replay.count("dynamic-update-slice") >= 5, replay
    assert "gather" in replay


def test_fleet_chunk_writes_the_ring_without_a_scatter():
    """The fleet keeps the ring's cursor unbatched, so under its vmap the
    write stays dynamic-update-slices: a batched start would make each one
    a scatter."""
    _, init_row, step_fn, row_axes = F._algo_parts(
        make("CartPole-v1"), "dqn", F.golden_train_setup("dqn/CartPole-v1")[2])
    states = jax.eval_shape(jax.vmap(init_row, out_axes=row_axes),
                            jax.ShapeDtypeStruct((2, 2), jnp.uint32))
    lr = jax.ShapeDtypeStruct((2,), jnp.float32)
    text = F._fleet_chunk(step_fn, row_axes).lower((states, lr), 2).compile().as_text()
    replay = [opcode for opcode, op_name in scoped_ops(text)
              if "cairl.replay" in op_name]
    assert "scatter" not in replay
    assert replay.count("dynamic-update-slice") >= 5, replay
