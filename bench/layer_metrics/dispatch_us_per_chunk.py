"""Host microseconds per call that enqueues a chunk, mean over the traced
window: JAX's dispatch of the compiled chunk program over the carry's
leaves and shardings, and the TPU runtime's launch (layer: host round
trip)."""


def read(ctx):
    d = ctx["stats"]["dispatch_s"]
    return sum(d) / len(d) * 1e6 if d else None
