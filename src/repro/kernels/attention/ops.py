"""jit'd public wrapper for flash attention with backend dispatch."""
from __future__ import annotations

import functools

import jax

from repro.kernels import on_tpu
from repro.kernels.attention.flash import flash_attention
from repro.kernels.attention.ref import attention_ref


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "backend", "block_q", "block_k")
)
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    backend: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Multi-head GQA attention (B, Hq, Lq, D) × (B, Hkv, Lk, D) -> (B, Hq, Lq, D)."""
    if backend == "auto":
        backend = "pallas" if on_tpu() else "jnp"
    if backend == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k)
    if backend == "pallas_interpret":
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, interpret=True)
    if backend == "jnp":
        return attention_ref(q, k, v, causal=causal, window=window)
    raise ValueError(f"unknown backend {backend!r}")
