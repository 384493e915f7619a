"""Device microseconds per chunk, per chip, under `cairl.megastep`: the
`_megastep_kernel` call with its padding, casts and slices, the K steps of
game logic (`kernels/envstep/megastep.py`)."""
from scopes import scope_us_per_chunk


def read(ctx):
    return scope_us_per_chunk(ctx, "cairl.megastep")
