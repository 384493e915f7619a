"""The Pallas frame-stack kernel equals the scan it replaces, bit for bit.

`frame_stack_pallas` (interpret mode here) against `frame_stack_ref`, the
K-step `lax.scan` it replaces: the obs stacks, the terminal-obs stacks and the
carried stack, over stack depths, chunk lengths (shorter than the stack too),
batches under, across and off the 128-lane tile, done patterns, and pixel
counts split into several blocks. The kernel only copies and selects, so any
difference is a fault.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.envstep import framestack
from repro.kernels.envstep.framestack import (frame_stack_pallas,
                                              frame_stack_ref)

H, W = 6, 10


def _inputs(b, n, k, done, h=H, w=W, seed=0):
    rng = np.random.default_rng(seed)
    frames = rng.random((b, n, h, w), np.float32)
    pre = rng.random((k, b, h, w), np.float32)
    fresh = rng.random((k, b, h, w), np.float32)
    return (jnp.asarray(frames), jnp.asarray(pre), jnp.asarray(fresh),
            jnp.asarray(done, bool))


def _random_done(k, b, share=0.3, seed=1):
    return np.random.default_rng(seed).random((k, b)) < share


def _assert_matches(frames, pre, fresh, done):
    want = frame_stack_ref(frames, pre, fresh, done)
    got = jax.jit(functools.partial(frame_stack_pallas, interpret=True))(
        frames, pre, fresh, done)
    for name, a, g in zip(("frames", "obs", "terminal_obs"), want, got):
        assert g.dtype == a.dtype and g.shape == a.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(a),
                                      err_msg=name)
    # the carried stack is the last step's obs
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1][-1]))


@pytest.mark.parametrize("k", (1, 3, 8))
@pytest.mark.parametrize("n", (1, 3, 4))
def test_kernel_matches_scan(n, k):
    b = 6
    _assert_matches(*_inputs(b, n, k, _random_done(k, b)))


def _done_pattern(name, k, b):
    done = np.zeros((k, b), bool)
    if name == "all_first":
        done[0] = True
    elif name == "consecutive":
        done[1:4, ::2] = True
    elif name == "last_only":
        done[-1, 1] = True
    elif name == "random":
        done = _random_done(k, b)
    return done


@pytest.mark.parametrize("pattern", ("none", "all_first", "consecutive",
                                     "last_only", "random"))
def test_done_patterns(pattern):
    k, b = 8, 5
    _assert_matches(*_inputs(b, 4, k, _done_pattern(pattern, k, b)))


@pytest.mark.parametrize("b", (3, 130, 256))
def test_batches_on_and_off_the_lane_tile(b):
    """Under one 128-lane tile, past one (padded to two) and two tiles."""
    k = 3
    _assert_matches(*_inputs(b, 4, k, _random_done(k, b)))


@pytest.mark.parametrize("budget", (1, 40_000))
def test_pixels_split_into_blocks(budget, monkeypatch):
    """A VMEM budget that holds one pixel, or a few, splits the frame into
    many blocks, each carrying its own part of the ring across the steps."""
    monkeypatch.setattr(framestack, "_VMEM_BUDGET", budget)
    k, b = 8, 4
    assert framestack._pixel_block(5 * 7, 4, 1, 4) < 5 * 7
    _assert_matches(*_inputs(b, 4, k, _random_done(k, b), h=5, w=7))


def test_cell_sized_block_fits_the_budget():
    """At 1024 envs, 4 x 84 x 84 f32 frames: 84 pixels a block, so 84 blocks
    and a grid of 672 steps at K = 8."""
    assert framestack._pixel_block(84 * 84, 32, 8, 4) == 84
