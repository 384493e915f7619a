"""The 95th percentile of the device time of one execution of the window's
chunk program (the `XLA Modules` events whose name starts with
`jit_chunk`), in ms, over every chip. The host clock cannot time one chunk
of a few ms; the device's own clock can."""
import statistics


def read(ctx):
    times = [(m.end_ns - m.start_ns) * 1e-6 for m in ctx["trace"].modules
             if m.name.startswith("jit_chunk")]
    if len(times) < 20:
        return None
    return statistics.quantiles(times, n=20)[-1]
