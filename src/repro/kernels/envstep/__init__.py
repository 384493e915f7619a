"""repro.kernels.envstep — fused multi-step environment kernels (megastep).

K environment steps per `pallas_call`: physics, reward/done, time-limit
truncation, auto-reset re-entry and the observation write, fused over the
batch-lane dimension. `EnvPool(..., backend="pallas", unroll=K)` is the
consumer (docs/pool.md); `fused_step` is the `Env.fused_step` protocol
implementation for the registered classic-control + puzzle envs.

Structure mirrors kernels/raster and kernels/attention: megastep.py
(pl.pallas_call + BlockSpec), ref.py (pure-jnp oracle), ops.py (dispatching
wrapper with an interpret=True CPU mode), specs.py (per-env row dynamics;
the row *layout* is auto-derived from a traced reset — `derive_layout`).
framestack.py is the pixel pipeline's frame-stack kernel and its scan
reference, dispatched by `ops.frame_stack`.
"""
from repro.kernels.envstep.megastep import fused_transition, megastep_pallas
from repro.kernels.envstep.ops import env_megastep, fused_step, supports
from repro.kernels.envstep.ref import megastep_ref
from repro.kernels.envstep.specs import (FusedSpec, derive_layout, lookup,
                                         spec_for)

__all__ = [
    "FusedSpec", "derive_layout", "env_megastep", "fused_step",
    "fused_transition", "lookup", "megastep_pallas", "megastep_ref",
    "spec_for", "supports",
]
