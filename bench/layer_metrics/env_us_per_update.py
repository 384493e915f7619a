"""Device microseconds per train step, per chip, under `cairl.env`:
the pool's one env step, with the `cairl.reset`, `cairl.layout` and
`cairl.megastep` scopes nested in it (`pool/envpool.py`,
`kernels/envstep/ops.fused_step`)."""
from learner_scopes import us_per_update


def read(ctx):
    return us_per_update(ctx, "cairl.env")
