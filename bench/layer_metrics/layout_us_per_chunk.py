"""Device microseconds per chunk, per chip, under `cairl.layout`: the
env state and fresh states to the kernel's rows and back, the output casts
and transposes (`kernels/envstep/ops.fused_step`), the pool's key split and
last observation (`pool/envpool.py`)."""
from scopes import scope_us_per_chunk


def read(ctx):
    return scope_us_per_chunk(ctx, "cairl.layout")
