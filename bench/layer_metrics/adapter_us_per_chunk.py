"""Device microseconds per chunk under `bench.env_step` outside the Pallas
kernels: the megastep adapter's reset precompute, flatten/unflatten and
frame-stack scan (`kernels/envstep/ops.py`)."""


def read(ctx):
    tr = ctx["trace"]
    ops = [o for o in tr.ops(scope="bench.env_step") if not o.kernel]
    if not ops:
        return None
    return tr.seconds(ops) / ctx["stats"]["chunks"] * 1e6
