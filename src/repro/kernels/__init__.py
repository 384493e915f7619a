"""Pallas TPU kernels for the perf-critical hot spots.

- raster/    : the paper's SIMD software renderer, TPU-native (VMEM framebuffers)
- attention/ : flash GQA attention for the learner plane (train/prefill)
- envstep/   : fused multi-step environment kernels (megastep) behind the pool

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with backend dispatch) and ref.py (pure-jnp oracle used by tests).
"""
import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: "auto" dispatch then picks
    the Pallas kernel. Any failure to reach a backend propagates — it is
    never read as "not a TPU", which would quietly run the reference."""
    return jax.default_backend() == "tpu"
