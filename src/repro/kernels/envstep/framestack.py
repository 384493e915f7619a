"""Pallas TPU frame stack — the pixel pipeline's ring and auto-reset select.

After the rasteriser has drawn a chunk's stepped (pre-reset) and fresh
frames, every env step t of the chunk needs

    terminal_obs[t] = concat(ring[1:], pre[t])
    obs[t]          = done[t] ? broadcast(fresh[t]) : terminal_obs[t]
    ring            = obs[t]

with the ring (the carried FrameStack) starting from the pool's state.
`frame_stack_ref` is that recurrence as a `lax.scan` — the reference and the
CPU path. Lowered by XLA it rebuilds the whole (B, N, H, W) stack in every
iteration and moves each frame through HBM several times.
`frame_stack_pallas` writes each output frame once: the ring stays in VMEM
across the K steps, and every step reads only its pre/fresh frames and done
flags and writes its two stacks.

Layout: the pool's frame arrays are laid out env-minor on the chip (the env
batch in lanes, the N stack slots in sublanes, pixels major), so the kernel
works on that view — rows of 128 envs, one row per (env tile, slot) and
pixel — and the reshapes to and from (…, B, N, H, W) cost nothing there
when B is a multiple of 128 (other batches are padded to one).
Pixels are independent, so the grid runs over pixel blocks, with the K steps
innermost and sequential; the ring lives in the carried-stack output block,
whose index does not change with the step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: envs a row of a block holds: one vector register's lanes
_LANES = 128
#: VMEM the double-buffered blocks may take: under v5e's default scoped
#: limit (16 MiB), with room for Mosaic's own scratch
_VMEM_BUDGET = 12 * 2**20


def frame_stack_ref(frames, pre, fresh, done):
    """The K-step ring as a scan.

    frames (B, N, H, W) carried stack; pre, fresh (K, B, H, W) stepped and
    fresh frames; done (K, B) bool. Returns (new_frames (B, N, H, W),
    obs (K, B, N, H, W), terminal_obs (K, B, N, H, W)).
    """
    def stack_body(frames, xs):
        pre_f, fresh_f, d = xs
        pre_stack = jnp.concatenate([frames[:, 1:], pre_f[:, None]], axis=1)
        post = jnp.where(d[:, None, None, None],
                         jnp.broadcast_to(fresh_f[:, None], pre_stack.shape),
                         pre_stack)
        return post, (post, pre_stack)

    frames_t, (obs, tobs) = jax.lax.scan(stack_body, frames,
                                         (pre, fresh, done))
    return frames_t, obs, tobs


def _frame_stack_kernel(frames_ref, pre_ref, fresh_ref, done_ref,
                        ring_ref, obs_ref, tobs_ref, *, n: int, bt: int):
    # Rows of every stack block are (env tile, slot): slot i of all the
    # block's env tiles is the row set i, i + n, i + 2n, ...
    @pl.when(pl.program_id(1) == 0)
    def _():
        ring_ref[...] = frames_ref[...]

    reset = done_ref[0] != 0                          # (1, bt, L)
    fresh = fresh_ref[0]                              # (pb, bt, L)
    for i in range(n):
        rows = pl.ds(i, bt, stride=n)
        # slot i takes slot i + 1 of the ring; the newest slot takes pre[t]
        src = (ring_ref[:, pl.ds(i + 1, bt, stride=n), :] if i < n - 1
               else pre_ref[0])
        post = jnp.where(reset, fresh, src)
        tobs_ref[0, :, rows, :] = src
        obs_ref[0, :, rows, :] = post
        ring_ref[:, rows, :] = post   # row i is read no more this step


def _pixel_block(p: int, m: int, bt: int, itemsize: int) -> int:
    """The largest divisor of the pixel count whose double-buffered blocks
    fit `_VMEM_BUDGET`: four stack blocks of m rows (carried stack in and
    out, obs, terminal obs) and two frame blocks of bt rows (pre, fresh),
    per pixel, in (8, 128) tiles."""
    rows = lambda r: -(-r // 8) * 8
    per_pixel = 2 * (4 * rows(m) + 2 * rows(bt)) * _LANES * itemsize
    most = max(1, _VMEM_BUDGET // per_pixel)
    return max(d for d in range(1, min(p, most) + 1) if p % d == 0)


def frame_stack_pallas(frames, pre, fresh, done, *, interpret: bool = False):
    """`frame_stack_ref` in one pass, as one `pallas_call`; same shapes,
    dtypes and bits."""
    b, n, h, w = frames.shape
    k = pre.shape[0]
    p = h * w
    bp = pl.cdiv(b, _LANES) * _LANES
    bt = bp // _LANES
    m = bt * n
    dt = frames.dtype
    if bp != b:
        # Mosaic's strided loads need whole 128-lane rows; the pad lanes are
        # inert and sliced off below
        pad = lambda x, axis: jnp.pad(
            x, [(0, bp - b) if i == axis else (0, 0) for i in range(x.ndim)])
        frames, pre, fresh, done = (pad(frames, 0), pad(pre, 1),
                                    pad(fresh, 1), pad(done, 1))

    # env-minor views: pixel, (env tile, slot) rows, env lanes
    ring0 = (frames.reshape(bt, _LANES, n, h, w).transpose(3, 4, 0, 2, 1)
             .reshape(p, m, _LANES))
    by_pixel = lambda x: (x.reshape(k, bt, _LANES, h, w)
                          .transpose(0, 3, 4, 1, 2).reshape(k, p, bt, _LANES))
    reset = done.astype(jnp.int32).reshape(k, 1, bt, _LANES)

    pb = _pixel_block(p, m, bt, dt.itemsize)
    stack_spec = pl.BlockSpec((1, pb, m, _LANES), lambda i, t: (t, i, 0, 0))
    frame_spec = pl.BlockSpec((1, pb, bt, _LANES), lambda i, t: (t, i, 0, 0))
    ring_spec = pl.BlockSpec((pb, m, _LANES), lambda i, t: (i, 0, 0))
    ring, obs, tobs = pl.pallas_call(
        functools.partial(_frame_stack_kernel, n=n, bt=bt),
        grid=(p // pb, k),
        in_specs=[ring_spec, frame_spec, frame_spec,
                  pl.BlockSpec((1, 1, bt, _LANES),
                               lambda i, t: (t, 0, 0, 0))],
        out_specs=[ring_spec, stack_spec, stack_spec],
        out_shape=[jax.ShapeDtypeStruct((p, m, _LANES), dt),
                   jax.ShapeDtypeStruct((k, p, m, _LANES), dt),
                   jax.ShapeDtypeStruct((k, p, m, _LANES), dt)],
        # the new stack overwrites the old one in place: a pixel block's
        # ring is read at its first step and written back after its last
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        # fixed, not taken from the wrapped function: profiles name the
        # kernel by it
        name="_frame_stack_kernel",
    )(ring0, by_pixel(pre), by_pixel(fresh), reset)

    stacks = lambda x: (x.reshape(k, h, w, bt, n, _LANES)
                        .transpose(0, 3, 5, 4, 1, 2)
                        .reshape(k, bp, n, h, w)[:, :b])
    new_frames = (ring.reshape(h, w, bt, n, _LANES).transpose(2, 4, 3, 0, 1)
                  .reshape(bp, n, h, w)[:b])
    return new_frames, stacks(obs), stacks(tobs)

