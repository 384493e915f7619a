"""Device microseconds per chunk, per chip, under `cairl.frame_stack`:
the frame-stack ring and the auto-reset select of fresh frames
(`kernels/envstep/ops.fused_step`)."""
from scopes import scope_us_per_chunk


def read(ctx):
    return scope_us_per_chunk(ctx, "cairl.frame_stack")
