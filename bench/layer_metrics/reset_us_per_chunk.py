"""Device microseconds per chunk, per chip, under `cairl.reset`: the
auto-reset key chain and the fresh reset state of every lane for every step
of the chunk (`kernels/envstep/ops.fused_step`)."""
from scopes import scope_us_per_chunk


def read(ctx):
    return scope_us_per_chunk(ctx, "cairl.reset")
