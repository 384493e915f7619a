"""Device microseconds per chunk, per chip, under `cairl.render`: the
scenes of the stepped and the fresh states, the scene tables and both
`_raster_kernel` calls (`kernels/envstep/ops.py`, `kernels/raster/raster.py`)."""
from scopes import scope_us_per_chunk


def read(ctx):
    return scope_us_per_chunk(ctx, "cairl.render")
