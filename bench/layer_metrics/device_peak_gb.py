"""`memory_stats()["peak_bytes_in_use"]` after the window, the largest over
the cell's devices, in GB."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return None if peak is None else peak / 1e9
