"""jit'd public wrapper for the rasteriser: picks Pallas on TPU, oracle on CPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.raster.raster import rasterize_pallas
from repro.kernels.raster.ref import rasterize_ref


@functools.partial(jax.jit, static_argnames=("h", "w", "backend"))
def rasterize(segs: jax.Array, intens: jax.Array, h: int, w: int, backend: str = "auto") -> jax.Array:
    """Render (B, S, 5) capsule scenes to (B, H, W) float32 framebuffers.

    backend: "auto" (pallas on TPU, jnp elsewhere) | "pallas" | "pallas_interpret" | "jnp".
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "jnp"
    if backend in ("pallas", "pallas_interpret"):
        return _frame_batched_pallas(h, w, backend == "pallas_interpret")(
            segs, intens)
    if backend == "jnp":
        return rasterize_ref(segs, intens, h, w)
    raise ValueError(f"unknown backend {backend!r}")


@functools.lru_cache(maxsize=None)
def _frame_batched_pallas(h: int, w: int, interpret: bool):
    """`rasterize_pallas` whose vmap is one call over more frames.

    Frames are independent, so a vmapped call (the vmap engine renders one
    env per lane) folds the vmapped axis into the frame batch. A vmapped
    `pallas_call` would instead gain a grid axis over blocks that Mosaic
    cannot tile.
    """

    @jax.custom_batching.custom_vmap
    def call(segs, intens):
        return rasterize_pallas(segs, intens, h, w, interpret=interpret)

    @call.def_vmap
    def _(axis_size, in_batched, segs, intens):
        segs, intens = (x if batched else
                        jnp.broadcast_to(x, (axis_size,) + x.shape)
                        for x, batched in zip((segs, intens), in_batched))
        out = call(segs.reshape((-1,) + segs.shape[2:]),
                   intens.reshape((-1,) + intens.shape[2:]))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


def rasterize_single(segs: jax.Array, intens: jax.Array, h: int, w: int) -> jax.Array:
    """Unbatched convenience: (S, 5), (S,) -> (H, W)."""
    return rasterize(segs[None], intens[None], h, w)[0]
