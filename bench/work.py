"""Work a step must do, counted from run-time shapes and dtypes.

The counts are fixed by the interface, not by the implementation: an env
step has to read its carry and actions and write its new carry and the
transition it returns, whatever kernel does it. A faster implementation
lowers the time, never these counts; a narrower dtype lowers them.
"""
from __future__ import annotations

import math

import jax


def tree_bytes(tree) -> int:
    """Bytes of every array leaf of `tree` (arrays or ShapeDtypeStructs)."""
    return sum(math.prod(x.shape) * jax.numpy.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def step_interface_bytes(carry_in, actions, carry_out, out) -> int:
    """HBM bytes one chunk of env steps must move: the carry read and
    written, the actions read, and the returned transitions written."""
    return (tree_bytes(carry_in) + tree_bytes(actions)
            + tree_bytes(carry_out) + tree_bytes(out))


def frame_bytes(n_frames: int, h: int, w: int, dtype) -> int:
    """Bytes a rasteriser must write for `n_frames` frames of (h, w)."""
    return n_frames * h * w * jax.numpy.dtype(dtype).itemsize


def roofline_seconds(n_bytes: float, n_flops: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the memory and the
    compute bound."""
    return max(n_bytes / peak["hbm_bw"], n_flops / peak["flops"])
